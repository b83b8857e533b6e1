"""A cell of BENCHMARK.json cut to a size the CPU runs in seconds: the
program's reduced float32 model, 8 slots, short prompts and answers,
four callers. Used by the tests only; the chip runs the cells as they
stand."""
from __future__ import annotations

import copy
import json
from typing import Union

from bench import harness

VISION = ("image_h", "image_w", "patch", "channels", "num_layers",
          "d_model", "num_heads", "d_ff")


def _file(group: str, name: Union[str, dict]) -> dict:
    if isinstance(name, dict):
        return copy.deepcopy(name)
    return json.loads((harness.BENCH / group / f"{name}.json").read_text())


def tiny_spec(config: Union[str, dict], traffic: Union[str, dict],
              limits: dict, max_new: int = 32, slots: int = 8,
              clients: int = 4, stagger: int = 2) -> dict:
    """The spec ``harness.load_cell`` would give a cell of ``config``
    under ``traffic`` (files under ``bench/``, or their contents),
    cut down: the sizes the reduced program runs, tower included."""
    import jax
    from repro.launch.serve import build_model, build_parser

    spec = {"cell": {"name": "tiny", "chips": 1},
            "config": _file("configs", config),
            "mix": _file("traffic", traffic),
            "limits": dict(limits),
            "end_to_end": [{"name": "setup_s"}], "per_layer": []}
    conf, mix = spec["config"], spec["mix"]
    flags = [f for f in conf["serve_flags"] if f != "--no-reduced"]
    flags[flags.index("--slots") + 1] = str(slots)
    conf["serve_flags"] = flags + ["--reduced"]
    mix["max_new"] = max_new
    mix["arrivals"].update(clients=clients, stagger_steps=stagger,
                           window_steps=2 * max_new)
    mix["prompt_len"].update(lo=8, hi=40, median=16)
    mix.update(warm_rows=4, warm_finish=8)
    args = build_parser().parse_args(harness.serve_flags(conf, mix, 0))
    box = {}

    def layout():
        cfg, _, params = build_model(args)
        box["cfg"] = cfg
        return params
    jax.eval_shape(layout)
    c = box["cfg"]
    s = conf["sizes"]
    s.update(num_layers=c.num_layers, d_model=c.d_model,
             num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
             head_dim=c.resolved_head_dim, d_ff=c.d_ff,
             vocab_size=c.vocab_size)
    if "vision" in s:
        s.update(num_evidence_tokens=c.num_evidence_tokens,
                 evidence_dim=c.evidence_dim)
        s["vision"] = {k: getattr(c.vision, k) for k in VISION}
        s["vision"]["projector"] = [[c.vision.d_model, c.evidence_dim]]
    return spec


def run(spec: dict, seed: int = 2 ** 31 + 7, seconds: float = 2.0,
        trace: bool = False, controls=()) -> dict:
    import time
    return harness.execute(spec, seed, seconds, trace, time.perf_counter(),
                           "TPU v5 lite", 1, controls=controls)
