"""Tokens of every candidate decoded inside the window, over the window's seconds."""
from bench import readers

UNIT = "tokens/s"


def read(ctx):
    return readers.window_tokens(ctx) / ctx["window_s"]
