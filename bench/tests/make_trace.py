"""Record the small chip trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/make_trace.py <out_dir>

On a TPU: a few calls of the paged decode and flash kernels and one XLA
matmul inside the harness's host spans (``window``, ``pump``), traced
with the harness's profiler options; copies the ``.xplane.pb`` to
``<out_dir>/small.xplane.pb``.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from bench.trace_reduce import profile_options
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        print("make_trace: needs a TPU", file=sys.stderr)
        return 1
    k = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    B, P, ps, H, Hkv, hd = 8, 65, 16, 16, 8, 128
    q = jax.random.normal(next(k), (B, 1, H, hd), jnp.bfloat16)
    kp = jax.random.normal(next(k), (P, ps, Hkv, hd), jnp.bfloat16)
    vp = jax.random.normal(next(k), (P, ps, Hkv, hd), jnp.bfloat16)
    bt = jnp.arange(1, 65, dtype=jnp.int32).reshape(B, 8)
    lengths = jnp.full((B,), 100, jnp.int32)
    qf = jax.random.normal(next(k), (1, 256, H, hd), jnp.bfloat16)
    x = jax.random.normal(next(k), (1024, 1024), jnp.bfloat16)
    decode = jax.jit(ops.paged_decode_attention)
    flash = jax.jit(lambda a: ops.flash_attention(a, a, a, causal=True))
    mm = jax.jit(lambda a: a @ a)
    for f, a in ((decode, (q, kp, vp, bt, lengths)), (flash, (qf,)),
                 (mm, (x,))):
        jax.block_until_ready(f(*a))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=profile_options())
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("pump"):
                jax.block_until_ready(decode(q, kp, vp, bt, lengths))
                jax.block_until_ready(flash(qf))
                jax.block_until_ready(mm(x))
            time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(src, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {os.path.join(out_dir, 'small.xplane.pb')} "
          f"({os.path.getsize(os.path.join(out_dir, 'small.xplane.pb'))} B)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
