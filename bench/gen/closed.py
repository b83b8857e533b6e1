"""A closed loop: ``clients`` callers, each sending its next request as
soon as the previous one is answered. Caller ``c`` starts after
``c * stagger_steps`` decode steps, so that requests neither finish nor
arrive in lock step; those the slots cannot hold wait in the queue. The
window opens after ``window_steps`` decode steps: once every slot is
busy and the first requests have been answered and replaced."""
from __future__ import annotations


def start_step(spec: dict, client: int) -> int:
    return client * spec["stagger_steps"]


def window_step(spec: dict) -> int:
    return spec["window_steps"]
