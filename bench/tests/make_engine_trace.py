"""Record the small engine trace that ``test_engine_trace.py`` reads.

    python3 bench/tests/make_engine_trace.py <out_dir>

On a TPU: the serving engine over a 2-layer, 128-wide float32 model
(XLA paged decode, greedy, one slot, 4 decode steps a launch) serves two
requests, the second queued behind the first, through
``AsyncServeFrontend`` inside the harness's ``window`` span, each pump
wrapped in the harness's ``pump`` span, with the harness's profiler
options: the trace holds the engine's ``serve.*`` spans on the host
plane beside the device's operations. The same two requests are served
once before the trace starts, so nothing compiles inside it.

Writes ``<out_dir>/engine.xplane.pb``, holding only what
``trace_reduce`` reads: the device's ``XLA Ops`` and ``XLA Modules``
lines (event names and times) and the host's ``window``, ``pump``,
``submit``, ``stream`` and ``serve.*`` events. The trace as recorded
is ~100 times larger.
"""
from __future__ import annotations

import asyncio
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

MAX_NEW = 8
HOST_EVENTS = ("window", "pump", "submit", "stream")


def engine():
    import jax
    import jax.numpy as jnp

    from repro.config import (CAMDConfig, ModelConfig, PagedKVConfig,
                              SamplingConfig)
    from repro.models import build_model
    from repro.serving import ServeEngine
    cfg = ModelConfig(name="tiny-lm", family="dense", num_layers=2,
                      d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                      vocab_size=256, head_dim=32, tie_embeddings=True,
                      dtype="float32")
    model = build_model(cfg, jnp.float32)
    return ServeEngine(
        model, model.init(jax.random.PRNGKey(0)), slots=1, cache_len=64,
        sampling=SamplingConfig(max_new_tokens=MAX_NEW),
        camd=CAMDConfig(), mode="greedy", max_new_tokens=MAX_NEW,
        eos_id=cfg.vocab_size, impl="paged", macro_steps=4,
        paged_kv=PagedKVConfig(page_size=8), seed=0)


async def serve(fe, uids):
    import numpy as np

    from repro.serving import Request
    for uid in uids:
        await fe.submit(Request(uid=uid, prompt=np.random.default_rng(
            uid % 2).integers(2, 256, 12).astype(np.int32)))
    for uid in uids:
        await fe.result(uid)


def xplane_pb2():
    """XSpace's protobuf classes, loaded from the generated module that
    the TensorFlow installation carries, without importing TensorFlow."""
    import importlib.util
    tf = importlib.util.find_spec("tensorflow")
    path = os.path.join(os.path.dirname(tf.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def keep_read_lines(src: str, dst: str) -> None:
    """Copy the trace ``src`` to ``dst`` with only what ``trace_reduce``
    and ``test_engine_trace.py`` read (see the module's docstring)."""
    pb = xplane_pb2()
    space = pb.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = pb.XSpace()
    for plane in space.planes:
        meta = plane.event_metadata
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane.name.startswith("/host:")):
            continue
        lines = []
        for ln in plane.lines:
            if device and ln.name in ("XLA Ops", "XLA Modules"):
                events = list(ln.events)
                for e in events:
                    del e.stats[:]
            elif not device:
                events = [e for e in ln.events
                          if meta[e.metadata_id].name in HOST_EVENTS
                          or meta[e.metadata_id].name.startswith("serve.")]
            else:
                continue
            if events:
                del ln.events[:]
                ln.events.extend(events)
                lines.append(ln)
        if not lines:
            continue
        del plane.lines[:]
        plane.lines.extend(lines)
        used = {e.metadata_id for ln in lines for e in ln.events}
        for k in [k for k in meta if k not in used]:
            del meta[k]
        if device:
            for m in meta.values():
                del m.stats[:]
        out.planes.add().CopyFrom(plane)
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


def main(out_dir: str) -> int:
    import jax

    from bench.trace_reduce import profile_options
    from repro.serving import AsyncServeFrontend

    if jax.devices()[0].platform != "tpu":
        print("make_engine_trace: needs a TPU", file=sys.stderr)
        return 1
    eng = engine()
    pump = eng.pump

    def traced_pump():
        with jax.profiler.TraceAnnotation("pump"):
            return pump()
    eng.pump = traced_pump
    tmp = tempfile.mkdtemp()

    async def run():
        async with AsyncServeFrontend(eng, stream_tokens=False) as fe:
            await serve(fe, range(0, 2))             # compiles
            jax.profiler.start_trace(tmp, profiler_options=profile_options())
            with jax.profiler.TraceAnnotation("window"):
                await serve(fe, range(2, 4))
            jax.profiler.stop_trace()
    asyncio.run(run())
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(out_dir, "engine.xplane.pb")
    os.makedirs(out_dir, exist_ok=True)
    keep_read_lines(src, dst)
    print(f"wrote {dst} ({os.path.getsize(dst)} B of {os.path.getsize(src)} "
          f"B recorded); launches {eng.macro_launches}, spans "
          f"{sorted(eng.span_stats()['spans'])}")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
