#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1`` runs
the same traffic with the profiler on and reports its per-layer metrics.
The cell (configuration, traffic mix, limits) comes from BENCHMARK.json
and the files it names. Exits non-zero, printing no result, unless JAX
finds a TPU with as many chips as the cell asks for. The comparison that
decides ``correct`` is printed as the last lines of standard error and,
under ``check``, as the last key of the result line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from bench.harness import load_cell
    spec = load_cell(a.workload)

    import jax
    devs = jax.devices()
    want = spec["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) < want:
        print(f"bench: needs {want} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from bench.harness import execute
    out = execute(spec, a.seed, a.seconds, bool(a.trace), T_START,
                  devs[0].device_kind, want)
    chk = out.pop("check")
    out.pop("control")
    line = {"correct": chk["correct"], **out,
            "check": {"sampled_requests": chk["sampled"],
                      "sampled_tokens": chk["tokens"],
                      **{k: {"value": chk["numbers"].get(k), "limit": v}
                         for k, v in spec["limits"].items()}}}
    for ln in chk["lines"]:
        print(f"check: {ln}", file=sys.stderr)
    print(f"check: correct {chk['correct']} over {chk['sampled']} sampled "
          f"requests, {chk['tokens']} served tokens", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
