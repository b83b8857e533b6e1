"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode never sees Mosaic's tiling rules or VMEM limits; these
tests hand each kernel to the TPU compiler (installed with jax, no chip
needed) at internvl2-2b's published widths — H=16, Hkv=8, hd=128,
d_model=2048, a 256-token image grid — and assert that the compiled
program holds the Pallas custom call. The paged decode kernel is also
compiled at the benchmark cell's shapes, where the compiled program
must keep the instruction name the benchmark's trace reduction looks
for. The topology is described inside a fixture, never at import, so
only the worker that runs this file loads the TPU library.
"""
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import PagedKVConfig
from repro.configs import get_config
from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import paged_decode_attention as pdec
from repro.kernels import xmodal_score as xm

CFG = get_config("internvl2-2b")
H, HKV, HD = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim
SLOTS, CACHE_LEN = 8, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache: keep it off while these tests run
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _paged_decode_text(spec, kv_dtype, B, n, P, ps=16, h=H, hkv=HKV, hd=HD):
    """Compiled HLO text of the paged decode kernel: B rows of n pages
    of ps tokens from a pool of P pages, bf16 or int8 with scales."""
    sdtype = {"bf16": jnp.bfloat16, "int8": jnp.int8}[kv_dtype]
    shapes = [spec((B, 1, h, hd), jnp.bfloat16),
              spec((P, ps, hkv, hd), sdtype), spec((P, ps, hkv, hd), sdtype),
              spec((B, n), jnp.int32), spec((B,), jnp.int32)]
    if kv_dtype == "int8":
        shapes += [spec((P, ps, hkv), jnp.float32)] * 2

        def fn(q, kp, vp, bt, ln, ks, vs):
            return pdec.paged_decode_attention(q, kp, vp, bt, ln,
                                               k_scale=ks, v_scale=vs)
    else:
        def fn(q, kp, vp, bt, ln):
            return pdec.paged_decode_attention(q, kp, vp, bt, ln)
    return _compiled_text(fn, *shapes)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles(spec, kv_dtype):
    ps = PagedKVConfig().page_size
    n = CACHE_LEN // ps
    text = _paged_decode_text(spec, kv_dtype, SLOTS, n, SLOTS * n + 1, ps)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles_at_cell_shapes(spec, kv_dtype):
    """qwen3-0.6b.reasoning-backlog: 24 slots, tables of 80 pages of 16,
    a pool of 1800 pages; the custom call keeps the name that
    ``bench/configs/qwen3-0.6b.json``'s kernel regex matches."""
    cell = json.loads((Path(__file__).parents[1] / "bench" / "configs"
                       / "qwen3-0.6b.json").read_text())
    pattern = re.compile(cell["kernels"]["paged_decode"])
    s = cell["sizes"]
    text = _paged_decode_text(spec, kv_dtype, 24, 80, 1800, 16,
                              s["num_heads"], s["num_kv_heads"],
                              s["head_dim"])
    assert "tpu_custom_call" in text
    kernel = [ln.strip() for ln in text.splitlines()
              if pattern.match(ln.strip())]
    assert kernel and all("tpu_custom_call" in ln for ln in kernel), kernel


def test_flash_prefill_2048_compiles(spec):
    qkv = spec((1, 2048, H, HD), jnp.bfloat16)
    text = _compiled_text(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_decode_attention_compiles(spec):
    q = spec((SLOTS, 1, H, HD), jnp.bfloat16)
    kv = spec((SLOTS, CACHE_LEN, HKV, HD), jnp.bfloat16)
    mask = spec((SLOTS, CACHE_LEN), jnp.bool_)
    text = _compiled_text(dec.decode_attention, q, kv, kv, mask)
    assert "tpu_custom_call" in text


def test_xmodal_score_compiles(spec):
    nv, d = CFG.num_evidence_tokens, CFG.d_model
    assert nv == 256 and d == 2048
    toks = spec((1, 64, d), jnp.float32)
    mask = spec((1, 64), jnp.float32)
    vis = spec((1, nv, d), jnp.float32)
    txt = spec((1, 24, d), jnp.float32)
    text = _compiled_text(xm.xmodal_score, toks, mask, vis, txt)
    assert "tpu_custom_call" in text
