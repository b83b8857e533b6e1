"""``costs.py`` against counts made by hand at qwen3-0.6b's published
shapes (the benchmarked configuration) and at internvl2-2b's language
side (a wider model with an untied head)."""
import json
from pathlib import Path

import pytest

from bench import costs

CONF = Path(__file__).resolve().parents[1] / "configs"
QW = json.loads((CONF / "qwen3-0.6b.json").read_text())["sizes"]
# InternVL2-2B's InternLM2-1.8B language model (arXiv:2404.16821)
IVL = {"num_layers": 24, "d_model": 2048, "num_heads": 16,
       "num_kv_heads": 8, "head_dim": 128, "d_ff": 8192,
       "vocab_size": 92553, "tie_embeddings": False, "mlp": "swiglu"}


def test_internvl2_weights():
    # q, o: 2048x2048 each; k, v: 2048x1024 each; MLP 3 x 2048x8192
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert layer == 62_914_560
    assert costs.layer_matmul_params(IVL) == layer
    # 24 layers with two norms each, embedding and untied head
    params = 24 * (layer + 2 * 2048) + 2 * 92553 * 2048
    assert params == 1_889_144_832
    assert costs.weight_bytes(IVL) == 2 * params


def test_qwen3_weights_and_kv():
    # head_dim 128 > 1024/16: q, o are 1024x2048, k, v 1024x1024
    layer = 2 * 1024 * 2048 + 2 * 1024 * 1024 + 3 * 1024 * 3072
    assert costs.layer_matmul_params(QW) == layer == 15_728_640
    assert costs.weight_bytes(QW) == 2 * (28 * (layer + 2048)
                                          + 151936 * 1024)
    # K and V of 8 heads x 128 in bf16, 28 layers: 114,688 B per token
    assert 28 * costs.kv_token_bytes(QW) == 114_688
    assert 24 * costs.kv_token_bytes(IVL) == 98_304


def test_paged_decode_call_counts_live_tokens_only():
    f, b = costs.paged_decode_call(IVL, [1100, 50])
    assert f == 4 * 16 * 128 * 1150
    assert b == 1150 * 2 * 8 * 128 * 2 + 2 * 2 * 16 * 128 * 2
    t, bound = costs.roofline_seconds(f, b, {"bf16_flops": 197e12,
                                             "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and t == pytest.approx(b / 819e9)


def test_model_step_flops():
    # one qwen3 token at context 100: 2 x matmul params, head, attention
    want = (2 * 28 * 15_728_640 + 2 * 1024 * 151936
            + 28 * 4 * 16 * 128 * 100)
    assert costs.decode_token_flops(QW, 100) == want
    # a 16-token prefill after 1024 cached tokens
    pairs = 16 * 1024 + 16 * 17 // 2
    assert costs.prefill_flops(IVL, 16, 1024) == (
        2 * 24 * 62_914_560 * 16 + 24 * 4 * 16 * 128 * pairs
        + 2 * 2048 * 92553)


# the repo's stand-in tower at internvl2-2b's serving widths
# (configs/internvl2_2b.py), and InternViT-300M-448px with its pixel
# shuffle and MLP projector (arXiv:2404.16821)
STAND_IN = {"num_evidence_tokens": 256, "vision": {
    "image_h": 448, "image_w": 448, "channels": 3, "patch": 28,
    "num_layers": 4, "d_model": 768, "num_heads": 12, "d_ff": 3072,
    "projector": [[768, 2048]]}}
INTERNVIT = {"num_evidence_tokens": 256, "vision": {
    "image_h": 448, "image_w": 448, "channels": 3, "patch": 14,
    "num_layers": 24, "d_model": 1024, "num_heads": 16, "d_ff": 4096,
    "tokens": 1025, "projector": [[4096, 2048], [2048, 2048]]}}


def test_vision_encode_flops_of_the_stand_in_tower():
    # 16 x 16 patches of 28 x 28 x 3 pixels embedded to 768
    embed = 2 * 256 * 2352 * 768
    # per layer: q, k, v, o (4 x 768^2) and the MLP (2 x 768 x 3072) for
    # 256 tokens, scores and values over 256 x 256 pairs
    layer = 256 * 2 * (4 * 768 ** 2 + 2 * 768 * 3072) + 2 * 2 * 256 ** 2 * 768
    assert layer == 3_825_205_248
    proj = 2 * 256 * 768 * 2048
    assert costs.vision_encode_flops(STAND_IN) == embed + 4 * layer + proj \
        == 17_030_971_392


def test_vision_encode_flops_of_internvit_300m():
    # 32 x 32 patches of 14 x 14 x 3 embedded to 1024; a class token
    # makes 1025 tokens in each of 24 layers
    embed = 2 * 1024 * 588 * 1024
    layer = 1025 * 2 * (4 * 1024 ** 2 + 2 * 1024 * 4096) \
        + 2 * 2 * 1025 ** 2 * 1024
    # pixel shuffle 0.5: 256 tokens of 4096, then 4096->2048->2048
    proj = 2 * 256 * (4096 * 2048 + 2048 * 2048)
    want = embed + 24 * layer + proj
    assert want == 730_035_486_720
    assert costs.vision_encode_flops(INTERNVIT) == want
