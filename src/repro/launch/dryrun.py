import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes; record memory / cost / collective analysis.

This is how the distribution config is proven coherent without hardware:
512 placeholder host devices stand in for 2 pods × 256 v5e chips;
``.lower().compile()`` runs the full GSPMD partitioner, so sharding
mismatches, unsupported collectives, and compile-time OOMs surface as
hard failures here.

Cost methodology: XLA's cost_analysis counts a `lax.scan` body ONCE, and
production models scan over layers. The structural check therefore
compiles the FULL-depth scanned model (memory analysis is exact — scan
reuses buffers), while FLOPs / bytes / collective bytes are measured on
shallow UNROLLED variants at 1× and 2× the block pattern and extrapolated
linearly in depth (the per-layer delta is exact; embed/unembed/loss
overhead is captured by the 1× point).

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both
  python -m repro.launch.dryrun --arch kimi-k2-1t-a32b --shape train_4k
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import INPUT_SHAPES, ModelConfig, ShapeConfig, TrainConfig
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.training.optimizer import init_opt_state
from repro.training.train_loop import make_train_step
from repro.utils import hlo as hlo_utils

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s/link
HBM_BYTES = 16e9

# long-context variant: pure full-attention archs run long_500k with a
# sliding-window ring cache (DESIGN.md §5); sub-quadratic archs run native.
LONG_CONTEXT_WINDOW = 4096
# >100B-param models use bf16 optimizer state (DESIGN.md; kimi-k2)
BF16_OPT_THRESHOLD = 100e9
# >30B-param trainers use gradient accumulation to bound activations
MICROBATCH_THRESHOLD = 30e9
MICROBATCHES = 8


def resolve_config(arch: str, shape: ShapeConfig) -> ModelConfig:
    cfg = get_config(arch)
    if shape.name == "long_500k" and shape.mode == "decode":
        needs_window = cfg.attn_window == 0 and cfg.family in (
            "dense", "moe", "vlm", "audio")
        if needs_window:
            cfg = cfg.with_overrides(attn_window=LONG_CONTEXT_WINDOW)
    return cfg


def build_step(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
               unroll: bool = False, microbatches: int = None):
    """Returns (fn, arg_shapes tuple, in_sharding_specs tuple)."""
    model = build_model(cfg)
    specs = model.input_specs(shape)
    p_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_spec = shd.param_specs(cfg, p_shapes, mesh)

    if shape.mode == "train":
        opt_dtype = jnp.bfloat16 if cfg.num_params() > BF16_OPT_THRESHOLD \
            else jnp.float32
        mb = microbatches if microbatches is not None else (
            MICROBATCHES if cfg.num_params() > MICROBATCH_THRESHOLD else 1)
        tc = TrainConfig(remat=True, unroll=unroll, microbatches=mb)
        step = make_train_step(model, tc)
        o_shapes = jax.eval_shape(lambda p: init_opt_state(p, opt_dtype),
                                  p_shapes)
        o_spec = shd.opt_state_specs(cfg, o_shapes, mesh)
        batch = dict(specs)
        b_spec = shd.batch_specs(shape, batch, mesh)
        return step, (p_shapes, o_shapes, batch), (p_spec, o_spec, b_spec)

    if shape.mode == "prefill":
        cache_len = model.cache_len(shape.seq_len)
        c_shapes = jax.eval_shape(
            lambda: model.make_cache(shape.global_batch, cache_len))
        c_spec = shd.cache_specs(cfg, c_shapes, mesh)
        tok = specs["tokens"]
        t_spec = shd.batch_specs(shape, {"tokens": tok}, mesh)["tokens"]
        ev = specs.get("evidence")
        if ev is not None:
            e_spec = shd.batch_specs(shape, {"evidence": ev}, mesh)["evidence"]

            def fn(params, tokens, cache, evidence):
                return model.prefill(params, tokens, cache, evidence,
                                     unroll=unroll)

            return fn, (p_shapes, tok, c_shapes, ev), \
                (p_spec, t_spec, c_spec, e_spec)

        def fn(params, tokens, cache):
            return model.prefill(params, tokens, cache, unroll=unroll)

        return fn, (p_shapes, tok, c_shapes), (p_spec, t_spec, c_spec)

    # decode
    tok = specs["token"]
    c_shapes = specs["cache"]
    c_spec = shd.cache_specs(cfg, c_shapes, mesh)
    t_spec = shd.batch_specs(shape, {"token": tok}, mesh)["token"]

    def fn(params, token, cache):
        return model.decode_step(params, token, cache, unroll=unroll)

    return fn, (p_shapes, tok, c_shapes), (p_spec, t_spec, c_spec)


def _compile(cfg, shape, mesh, *, unroll=False, microbatches=None):
    fn, shapes, specs = build_step(cfg, shape, mesh, unroll=unroll,
                                   microbatches=microbatches)
    in_sh = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*shapes)
        compiled = lowered.compile()
    return compiled


def _extract(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    coll = hlo_utils.collective_bytes(text)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(coll.get("total", 0)),
        "coll_detail": {k: v for k, v in coll.items() if k != "total"},
    }


def measure_costs(arch: str, shape: ShapeConfig, mesh) -> Dict[str, Any]:
    """Per-layer cost extrapolation from 1× / 2×-pattern unrolled models.

    Microbatched trainers are measured at ONE microbatch (mb=1, batch/k)
    and scaled by k — per-step FLOPs/bytes are linear in tokens, and the
    ×k repeat of per-microbatch weight gathers is thereby counted
    honestly."""
    import dataclasses as _dc
    base = resolve_config(arch, shape)
    P = len(base.block_pattern)
    scale = 1
    if shape.mode == "train" and base.num_params() > MICROBATCH_THRESHOLD:
        scale = MICROBATCHES
        shape = _dc.replace(shape,
                            global_batch=shape.global_batch // MICROBATCHES)
    pts = []
    for mult in (1, 2):
        over = {"num_layers": P * mult}
        if base.is_encoder_decoder:
            over["num_encoder_layers"] = max(
                1, round(base.num_encoder_layers * P * mult / base.num_layers))
        cfg_small = base.with_overrides(**over)
        pts.append(_extract(_compile(cfg_small, shape, mesh, unroll=True,
                                     microbatches=1)))
    layers_equiv = base.num_layers / P
    out = {}
    for k in ("flops", "bytes", "coll"):
        delta = pts[1][k] - pts[0][k]
        out[k] = (pts[0][k] + max(delta, 0.0) * (layers_equiv - 1)) * scale
        out[f"{k}_per_layerblock"] = delta * scale
    out["coll_detail_1x"] = pts[0]["coll_detail"]
    out["coll_detail_2x"] = pts[1]["coll_detail"]
    return out


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D (fwd-only)."""
    n = cfg.active_params()
    if shape.mode == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.mode == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * shape.global_batch  # one token per sequence


def run_one(arch: str, shape_name: str, multi_pod: bool,
            out_dir: str = "benchmarks/results", verbose: bool = True,
            with_costs: bool = True) -> Dict[str, Any]:
    from repro.distributed.context import set_batch_axes
    set_batch_axes(("pod", "data") if multi_pod else ("data",))
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    n_chips = mesh.size
    cfg = resolve_config(arch, shape)

    # 1) structural check: FULL depth, scanned — must lower AND compile.
    t0 = time.time()
    compiled = _compile(cfg, shape, mesh)
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    raw = _extract(compiled)

    # 2) roofline costs: per-layer extrapolation from unrolled shallow runs.
    costs = measure_costs(arch, shape, mesh) if with_costs else raw

    flops_dev = costs["flops"]
    bytes_dev = costs["bytes"]
    coll_dev = costs["coll"]
    mf = model_flops(cfg, shape)
    terms = {"compute_s": flops_dev / PEAK_FLOPS,
             "memory_s": bytes_dev / HBM_BW,
             "collective_s": coll_dev / ICI_BW}
    bottleneck = max(terms, key=terms.get)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": n_chips, "mode": shape.mode, "status": "ok",
        "compile_s": round(t_compile, 2),
        "hlo_flops_per_dev": flops_dev,
        "hlo_bytes_per_dev": bytes_dev,
        "collective_bytes_per_dev": coll_dev,
        "scan_raw": raw,
        **{k: float(v) for k, v in terms.items()},
        "bottleneck": bottleneck,
        "model_flops_total": mf,
        "model_flops_per_dev": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / flops_dev if flops_dev else 0.0,
        "argument_bytes_per_dev": mem.argument_size_in_bytes,
        "output_bytes_per_dev": mem.output_size_in_bytes,
        "temp_bytes_per_dev": mem.temp_size_in_bytes,
        "fits_16gb_hbm": (mem.argument_size_in_bytes
                          + mem.temp_size_in_bytes) < HBM_BYTES,
        "params_total": cfg.num_params(),
        "params_active": cfg.active_params(),
        "window_variant": cfg.attn_window != get_config(arch).attn_window,
    }
    if with_costs:
        rec["cost_detail"] = {k: v for k, v in costs.items()
                              if k.startswith("coll_detail") or
                              k.endswith("per_layerblock")}
    if verbose:
        print(f"[{arch} × {shape_name} × {mesh_name}] "
              f"compile={t_compile:.1f}s flops/dev={flops_dev:.3e} "
              f"bytes/dev={bytes_dev:.3e} coll/dev={coll_dev:.3e} "
              f"bottleneck={bottleneck}")
        print(f"  memory_analysis: arg={mem.argument_size_in_bytes/1e9:.2f}GB "
              f"temp={mem.temp_size_in_bytes/1e9:.2f}GB "
              f"out={mem.output_size_in_bytes/1e9:.2f}GB "
              f"fits16GB={rec['fits_16gb_hbm']}")
        print(f"  roofline: compute={terms['compute_s']*1e3:.2f}ms "
              f"memory={terms['memory_s']*1e3:.2f}ms "
              f"collective={terms['collective_s']*1e3:.2f}ms "
              f"useful_flops_ratio={rec['useful_flops_ratio']:.3f}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{out_dir}/dryrun_{mesh_name}_{arch}_{shape_name}.json"
        with open(fname, "w") as f:
            json.dump(rec, f, indent=2, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="benchmarks/results")
    ap.add_argument("--no-costs", action="store_true",
                    help="structural compile only (skip cost extrapolation)")
    ap.add_argument("--continue-on-error", action="store_true")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for multi in meshes:
        for arch in archs:
            for shp in shapes:
                try:
                    run_one(arch, shp, multi, out_dir=args.out,
                            with_costs=not args.no_costs)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shp, multi, repr(e)))
                    print(f"FAILED [{arch} × {shp} × multi={multi}]: {e}")
                    traceback.print_exc()
                    if not args.continue_on_error:
                        raise
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nAll dry-run combinations lowered and compiled successfully.")


if __name__ == "__main__":
    main()
