#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers and the
controls' on the same sample, for each seed, in one process.

    python3 bench/control.py --workload <name> --seconds <s> [--quant int8 fp8] <seed> [<seed> ...]

Each seed builds the cell anew (weights, engine), serves a window at
the cell's own load, then compares a sample of what was served with the
float32 reference (the program's reading) and, for each precision in
``--quant``, with the reference rounded to it put in the program's place
(the control's reading). Prints one JSON line per seed. Needs the chip,
like ``run.py``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--quant", nargs="+", default=["int8"])
    ap.add_argument("seeds", type=int, nargs="+")
    a = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from bench.harness import execute, load_cell
    spec = load_cell(a.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("control: needs the chip", file=sys.stderr)
        return 1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in a.seeds:
        out = execute(spec, seed, a.seconds, False, time.perf_counter(),
                      devs[0].device_kind, spec["cell"]["chips"],
                      controls=a.quant)
        print(json.dumps({"seed": seed, "metrics": out["metrics"],
                          "program": out["check"]["numbers"],
                          "control": {q: c["numbers"]
                                      for q, c in out["control"].items()},
                          "sampled_tokens": out["check"]["tokens"]}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
