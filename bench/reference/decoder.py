"""Plain reference of a decoder-only LM, in
float32 ``jax.numpy`` at ``highest`` matmul precision: no kernels, no
cache, no batching tricks. It imports nothing of the program; it reads
the benchmark's own weights by their names in the parameter tree and
the published sizes from the configuration file.

Model: token embedding, then per layer
x += Wo attn(RoPE(qk-norm(Wq h)), RoPE(qk-norm(Wk h)), Wv h) with
h = RMSNorm(x), causal grouped-query attention, and
x += W_down(silu(W_gate h') * W_up h') with h' = RMSNorm(x); a final
RMSNorm and the head (the embedding transposed where tied). RoPE
rotates the two halves of each head.

``quant`` makes the control: every matrix and the embedding table
rounded before use, with a scale per output channel, to ``"int8"``
(symmetric, 127 levels a side) or ``"fp8"`` (float8 e4m3): the
precision steps below the bf16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _f8(w, axis):
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _i8(w, axis):
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _w(x, quant, axis=-2):
    x = x.astype(jnp.float32)
    if quant == "fp8":
        return _f8(x, axis)
    if quant == "int8":
        return _i8(x, axis)
    return x


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None]     # (L, hd/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]    # (L, 1, hd/2)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1)


def scalar_sizes(s: dict) -> tuple:
    """The sizes' scalar entries, hashable (a static jit argument)."""
    return tuple(sorted((k, v) for k, v in s.items()
                        if not isinstance(v, dict)))


def logits_at(params, s: dict, tokens, out_pos, quant=None):
    """Logits (n, V) at sequence positions ``out_pos`` (n,) of one
    sequence of ``tokens`` (L,)."""
    with jax.default_matmul_precision("highest"):
        return _logits_jit(params, scalar_sizes(s), tokens, out_pos, quant)


@functools.partial(jax.jit, static_argnums=(1, 4))
def _logits_jit(params, s_items, tokens, out_pos, quant):
    table = embedding(params, quant)
    return decode(params, dict(s_items), table[tokens], out_pos, quant,
                  table)


def embedding(params, quant=None):
    """The token embedding table, as the model reads it."""
    return _w(params["embed"]["table"], quant, axis=-1)


def decode(params, s: dict, x, out_pos, quant, table):
    """The decoder over the embeddings ``x`` (L, d): every layer, the
    final norm and the head (``table`` transposed where tied), at
    positions ``out_pos``. Traced inside the caller's jit."""
    if params["tail"]:
        raise ValueError("the reference expects one stacked layer group")
    eps = s["norm_eps"]
    H, Hkv = s["num_heads"], s["num_kv_heads"]
    hd = s.get("head_dim") or s["d_model"] // H
    L = x.shape[0]
    pos = jnp.arange(L)
    causal = pos[:, None] >= pos[None, :]

    def layer(x, lp):
        h = _rms(x, lp["ln1"]["scale"], eps)
        at = lp["attn"]
        q = (h @ _w(at["wq"]["kernel"], quant)).reshape(L, H, hd)
        k = (h @ _w(at["wk"]["kernel"], quant)).reshape(L, Hkv, hd)
        v = (h @ _w(at["wv"]["kernel"], quant)).reshape(L, Hkv, hd)
        if s["qk_norm"]:
            q = _rms(q, at["q_norm"], eps)
            k = _rms(k, at["k_norm"], eps)
        q, k = _rope(q, pos, s["rope_theta"]), _rope(k, pos, s["rope_theta"])
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        x = x + o.reshape(L, H * hd) @ _w(at["wo"]["kernel"], quant)
        h = _rms(x, lp["ln2"]["scale"], eps)
        m = lp["mlp"]
        g = h @ _w(m["w_gate"]["kernel"], quant)
        u = h @ _w(m["w_up"]["kernel"], quant)
        return x + (jax.nn.silu(g) * u) @ _w(m["w_down"]["kernel"], quant), None

    x, _ = jax.lax.scan(layer, x, params["super"][0])
    h = _rms(x[out_pos], params["final_norm"]["scale"], eps)
    if s["tie_embeddings"]:
        return h @ table.T
    return h @ _w(params["unembed"]["kernel"], quant)


def sampled_logprobs(logits, toks, temperature, top_p, penalty):
    """Log-probability of each served token under the sampler's
    processing: repetition penalty over the tokens served before it,
    temperature, then the top-p nucleus (the served token's own
    processed logit over the nucleus' log-sum-exp)."""
    return _sampled_lp(logits, toks, float(temperature), float(top_p),
                       float(penalty))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _sampled_lp(z, toks, T, top_p, rep):
    n, V = z.shape
    first = jnp.full((V,), n, jnp.int32).at[toks].min(jnp.arange(n))
    seen = first[None, :] < jnp.arange(n)[:, None]
    z = jnp.where(seen, jnp.where(z > 0, z / rep, z * rep), z) / T
    srt = -jnp.sort(-z, axis=-1)
    p = jax.nn.softmax(srt, -1)
    cut = jnp.cumsum(p, -1) - p > top_p
    floor = jnp.min(jnp.where(cut, jnp.inf, srt), -1, keepdims=True)
    lse = jax.nn.logsumexp(jnp.where(z >= floor, z, -jnp.inf), -1)
    return jnp.take_along_axis(z, toks[:, None], 1)[:, 0] - lse
