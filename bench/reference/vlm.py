"""Plain reference of the repo's vision-language model as the program
builds it today, in float32 ``jax.numpy`` at ``highest`` matmul
precision. It imports nothing of the program; it reads the benchmark's
own weights by their names in the parameter tree and the sizes from the
configuration file (``sizes`` and ``sizes.vision``).

The tower is the repo's stand-in, not InternViT: the image (H, W, C)
cut into ``patch``-square patches in row-major order, each flattened
(row, column, channel) and projected (``patch_proj``), a learned
position table added (``pos_emb``); then per block
x += Wo attn(Wq h, Wk h, Wv h) with h = RMSNorm(x), every patch
attending to every patch, and x += W_out gelu(W_in h') with
h' = RMSNorm(x), gelu in its tanh form; a final RMSNorm and
``out_proj`` to the evidence width; no class token, no pixel shuffle.
The evidence embeddings (through ``evidence_proj`` where the model has
one) are prepended to the prompt's token embeddings and the decoder of
``decoder.py`` runs over both, positions counted from the image's first
token; ``out_pos`` indexes the text stream.

``quant`` rounds the tower's and the projector's matrices too (see
``decoder.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import decoder
from bench.reference.decoder import _rms, _w, sampled_logprobs  # noqa: F401


def logits_at(params, s: dict, tokens, out_pos, quant=None, image=None):
    """Logits (n, V) at text positions ``out_pos`` (n,) of one sequence
    of ``tokens`` (L,) that follows ``image`` (H, W, C), if any."""
    if image is None:
        return decoder.logits_at(params, s, tokens, out_pos, quant)
    v = s["vision"]
    with jax.default_matmul_precision("highest"):
        return _logits_jit(params, decoder.scalar_sizes(s),
                           (v["patch"], v["num_heads"]), tokens, out_pos,
                           quant, image)


@functools.partial(jax.jit, static_argnums=(1, 2, 5))
def _logits_jit(params, s_items, vision, tokens, out_pos, quant, image):
    s = dict(s_items)
    ev = encode(params, vision, s["norm_eps"], image, quant)
    if "evidence_proj" in params:
        ev = ev @ _w(params["evidence_proj"]["kernel"], quant)
    table = decoder.embedding(params, quant)
    x = jnp.concatenate([ev, table[tokens]])
    return decoder.decode(params, s, x, out_pos + ev.shape[0], quant, table)


def encode(params, vision, eps, image, quant=None):
    """The stand-in tower: ``image`` (H, W, C) to (patches, evidence
    width)."""
    patch, heads = vision
    p = params["vision"]
    H, W, C = image.shape
    x = image.astype(jnp.float32).reshape(H // patch, patch, W // patch,
                                          patch, C)
    x = x.transpose(0, 2, 1, 3, 4).reshape(-1, patch * patch * C)
    x = x @ _w(p["patch_proj"]["kernel"], quant) \
        + p["pos_emb"].astype(jnp.float32)
    N, d = x.shape
    hd = d // heads
    for b in p["blocks"]:
        h = _rms(x, b["ln1"]["scale"], eps)
        q = (h @ _w(b["wq"]["kernel"], quant)).reshape(N, heads, hd)
        k = (h @ _w(b["wk"]["kernel"], quant)).reshape(N, heads, hd)
        val = (h @ _w(b["wv"]["kernel"], quant)).reshape(N, heads, hd)
        sc = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), val)
        x = x + o.reshape(N, d) @ _w(b["wo"]["kernel"], quant)
        h = _rms(x, b["ln2"]["scale"], eps)
        m = b["mlp"]
        u = jax.nn.gelu(h @ _w(m["w_in"]["kernel"], quant), approximate=True)
        x = x + u @ _w(m["w_out"]["kernel"], quant)
    x = _rms(x, p["final_norm"]["scale"], eps)
    return x @ _w(p["out_proj"]["kernel"], quant)
