"""A cell of BENCHMARK.json cut to a size the CPU runs in seconds: the
program's reduced float32 model, 8 slots, short prompts and answers,
four callers. Used by the tests only; the chip runs the cells as they
stand."""
from __future__ import annotations

import json

from bench import harness


def tiny_spec(config: str, traffic: str, limits: dict, max_new: int = 32,
              slots: int = 8, clients: int = 4, stagger: int = 2) -> dict:
    """The spec ``harness.load_cell`` would give a cell of ``config``
    under ``traffic`` (files under ``bench/``), cut down."""
    import jax
    from repro.launch.serve import build_model, build_parser

    spec = {"cell": {"name": f"{config}.{traffic}", "chips": 1},
            "config": json.loads((harness.BENCH / "configs" /
                                  f"{config}.json").read_text()),
            "mix": json.loads((harness.BENCH / "traffic" /
                               f"{traffic}.json").read_text()),
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
    return spec


def run(spec: dict, seed: int = 2 ** 31 + 7, seconds: float = 2.0,
        trace: bool = False, controls=()) -> dict:
    import time
    return harness.execute(spec, seed, seconds, trace, time.perf_counter(),
                           "TPU v5 lite", 1, controls=controls)
