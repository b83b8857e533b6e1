"""The serve entry point's construction path, kernel dispatch and
compile-cache placement — the pieces ``chip_smoke.py`` relies on."""
import types

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.launch import compile_cache
from repro.launch.serve import build_parser, cache_len_for


def test_reduced_is_default_and_switchable():
    parse = build_parser().parse_args
    assert parse([]).reduced is True
    assert parse(["--reduced"]).reduced is True
    assert parse(["--no-reduced"]).reduced is False


@pytest.mark.parametrize("flags,want", [
    # 12 prompt + 256 image tokens + 64 new = 332 -> 21 pages of 16
    (["--prompt-len", "12", "--max-new", "64"], 336),
    # exactly page-aligned stays put
    (["--prompt-len", "16", "--max-new", "48", "--page-size", "32"], 320),
])
def test_cache_len_holds_prompt_image_and_new_tokens(flags, want):
    args = build_parser().parse_args(["--no-reduced"] + flags)
    cfg = get_config("internvl2-2b")
    n = cache_len_for(cfg, args)
    assert n == want and n % args.page_size == 0
    assert n >= args.prompt_len + cfg.num_evidence_tokens + args.max_new


def test_build_model_reduced_float32_and_seeded():
    from repro.launch.serve import build_model
    parse = build_parser().parse_args
    cfg, model, p0 = build_model(parse(["--arch", "qwen3-0.6b", "--seed", "0"]))
    assert cfg.dtype == "float32" and model.param_dtype == jnp.float32
    _, _, p1 = build_model(parse(["--arch", "qwen3-0.6b", "--seed", "1"]))
    a, b = p0["embed"]["table"], p1["embed"]["table"]
    assert a.dtype == jnp.float32 and not bool(jnp.all(a == b))


def _fake_tpu(monkeypatch):
    dev = types.SimpleNamespace(platform="tpu")
    monkeypatch.setattr(ops.jax, "devices", lambda *a: [dev])


@pytest.mark.parametrize("env,want", [
    (None, "ref"), ("auto", "ref"), ("ref", "ref"),
    ("interpret", "interpret")])
def test_kernel_mode_off_tpu(monkeypatch, env, want):
    if env is None:
        monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    else:
        monkeypatch.setenv("REPRO_KERNEL_MODE", env)
    assert ops._mode() == want


@pytest.mark.parametrize("env", [None, "auto", "ref", "interpret", "bogus"])
def test_kernel_mode_on_tpu_is_always_the_kernel(monkeypatch, env):
    if env is None:
        monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    else:
        monkeypatch.setenv("REPRO_KERNEL_MODE", env)
    _fake_tpu(monkeypatch)
    if env in (None, "auto"):
        assert ops._mode() == "tpu"
    else:
        with pytest.raises((RuntimeError, ValueError)):
            ops._mode()


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_fixed_checkout_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
