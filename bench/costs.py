"""Work the algorithm requires, from shapes: FLOPs and HBM bytes per
kernel call and per model step, whatever implements them.

``sizes`` is a configuration file's ``sizes`` group (published widths).
Attention counts only live tokens: a decode step over a context of
``ctx`` keys reads ``ctx`` keys and values, never the dead pages of a
block table; a causal prefill of ``L`` new tokens after ``ctx0`` cached
ones scores L * ctx0 + L (L + 1) / 2 pairs. A multiply-add is 2 FLOPs.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def head_dim(s: dict) -> int:
    return s.get("head_dim") or s["d_model"] // s["num_heads"]


def layer_matmul_params(s: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, hd = s["d_model"], head_dim(s)
    attn = d * s["num_heads"] * hd * 2 + d * s["num_kv_heads"] * hd * 2
    mlp = (3 if s.get("mlp", "swiglu") == "swiglu" else 2) * d * s["d_ff"]
    return attn + mlp


def weight_bytes(s: dict, dtype_bytes: int = 2) -> int:
    """Bytes of the decoder's weights: layers, embedding, and the head
    when it is not tied."""
    n = s["num_layers"] * (layer_matmul_params(s) + 2 * s["d_model"])
    n += s["vocab_size"] * s["d_model"] * (1 if s["tie_embeddings"] else 2)
    return n * dtype_bytes


def attention_pair_flops(s: dict) -> int:
    """FLOPs of one (query, key) pair in one layer: QK^T and PV over all
    query heads."""
    return 4 * s["num_heads"] * head_dim(s)


def kv_token_bytes(s: dict, kv_bytes: int = 2) -> int:
    """Bytes of one token's K and V in one layer."""
    return 2 * s["num_kv_heads"] * head_dim(s) * kv_bytes


def causal_pairs(L: int, ctx0: int = 0) -> int:
    return L * ctx0 + L * (L + 1) // 2


def paged_decode_call(s: dict, ctxs: Iterable[int], act_bytes: int = 2,
                      kv_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's paged decode attention over slots
    whose contexts (keys incl. the new token) are ``ctxs``."""
    ctxs = list(ctxs)
    flops = attention_pair_flops(s) * sum(ctxs)
    qo = 2 * s["num_heads"] * head_dim(s) * act_bytes * len(ctxs)
    return float(flops), float(sum(ctxs) * kv_token_bytes(s, kv_bytes) + qo)


def decode_token_flops(s: dict, ctx: int) -> float:
    """One decoded token through the whole model at context ``ctx``."""
    return float(2 * s["num_layers"] * layer_matmul_params(s)
                 + s["num_layers"] * attention_pair_flops(s) * ctx
                 + 2 * s["d_model"] * s["vocab_size"])


def prefill_flops(s: dict, L: int, ctx0: int = 0) -> float:
    """A prefill of ``L`` new tokens after ``ctx0`` cached ones; logits
    for the last token only."""
    return float(2 * s["num_layers"] * layer_matmul_params(s) * L
                 + s["num_layers"] * attention_pair_flops(s)
                 * causal_pairs(L, ctx0)
                 + 2 * s["d_model"] * s["vocab_size"])


def vision_encode_flops(s: dict) -> float:
    """One image through the vision tower and its projector, from
    ``s["vision"]`` alone: ``image_h`` x ``image_w`` x ``channels``
    pixels cut into ``patch``-square patches and embedded; ``tokens``
    (the patches plus any class token; default the patches) through
    ``num_layers`` bidirectional layers of width ``d_model`` (q, k, v, o
    projections, every token attending to every token) with a two-matrix
    MLP of ``d_ff``; then each ``[d_in, d_out]`` of ``projector`` applied
    to the tokens the tower hands on, ``num_evidence_tokens`` (after any
    pixel shuffle). Norms, activations and the softmax are not
    counted."""
    v = s["vision"]
    d = v["d_model"]
    patches = (v["image_h"] // v["patch"]) * (v["image_w"] // v["patch"])
    tokens = v.get("tokens", patches)
    embed = 2 * patches * v["patch"] ** 2 * v["channels"] * d
    layer = tokens * (2 * 4 * d * d + 2 * 2 * d * v["d_ff"]) \
        + 4 * tokens * tokens * d
    proj = sum(2 * s["num_evidence_tokens"] * a * b
               for a, b in v.get("projector", ()))
    return float(embed + v["num_layers"] * layer + proj)


def roofline_seconds(flops: float, nbytes: float, peak: dict
                     ) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    tc = flops / peak["bf16_flops"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
