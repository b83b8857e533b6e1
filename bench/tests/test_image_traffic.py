"""Image ids and pixels drawn from the seed, the configuration file's
``init`` rules and tower sizes, and what the accepted text cell keeps:
its requests and its weights are those the harness drew before images
and ``init`` rules came in (digests taken from that harness)."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, images
from bench.gen import unique, zipf
from bench.weights import make_params

SEED = 2 ** 31 + 7
VISION = {"image_h": 32, "image_w": 4, "channels": 3}


def test_zipf_counts_follow_the_ranks_and_every_seed_gets_them():
    spec = {"pool": 64, "s": 1.0}
    c = zipf.counts(spec, 512)
    assert c.sum() == 512 and len(c) == 64
    assert list(c) == sorted(c, reverse=True)
    w = 1.0 / np.arange(1, 65)
    assert np.abs(c - 512 * w / w.sum()).max() < 1.0
    draws = [zipf.draw(spec, 512, np.random.default_rng(s))
             for s in (1, 2, 2 ** 33)]
    for d in draws:
        assert list(np.bincount(d, minlength=64)) == list(c)
    assert not np.array_equal(draws[0], draws[1])


def test_unique_ids_never_repeat():
    ids = unique.draw({}, 300, np.random.default_rng(0))
    assert len(set(ids.tolist())) == 300


def test_ids_come_from_a_stream_of_their_own():
    mix = {"arrivals": {"requests": 64},
           "prompt_len": {"kind": "lognormal", "median": 16, "sigma": 0.6,
                          "lo": 8, "hi": 40}}
    text = harness.Traffic(mix, 512, SEED)
    with_images = harness.Traffic(
        dict(mix, images={"kind": "zipf", "pool": 6, "s": 1.0}), 512, SEED,
        VISION)
    assert np.array_equal(text.lengths, with_images.lengths)
    for i in range(64):
        a, b = text.request(i, i), with_images.request(i, i)
        assert np.array_equal(a.prompt, b.prompt)
        assert a.image is None
        want = images.pixels(SEED, with_images.image_of(i), VISION)
        assert b.image.dtype == np.float32 and b.image.shape == (32, 4, 3)
        assert np.array_equal(b.image, want)
    assert with_images.image_ids.max() < 6 < images.WARM_ID0
    assert not np.array_equal(images.pixels(SEED, 0, VISION),
                              images.pixels(SEED, 1, VISION))


def test_text_requests_are_the_accepted_harness_s():
    mix = json.loads((harness.BENCH / "traffic" / "reasoning-backlog.json")
                     .read_text())
    t = harness.Traffic(mix, 151936, SEED)
    h = hashlib.sha256(np.asarray(t.lengths, np.int64).tobytes())
    for i in range(t.n):
        r = t.request(i, i)
        assert r.image is None and r.evidence is None
        h.update(np.asarray(r.prompt, np.int32).tobytes())
    h.update(np.asarray(t.request(0, 10 ** 9, length=77).prompt,
                        np.int32).tobytes())
    assert h.hexdigest() == ("90b0d85f2ba31e5606ce5fb8993cc833"
                             "3a0fe933a5601d01f996205243eb3117")


def _layout(*flags):
    from repro.launch.serve import build_model, build_parser
    args = build_parser().parse_args(list(flags))
    box = {}

    def layout():
        cfg, _, params = build_model(args)
        box["cfg"] = cfg
        return params
    return jax.eval_shape(layout), box["cfg"]


def test_qwen3_weights_are_the_accepted_harness_s():
    shapes, _ = _layout("--arch", "qwen3-0.6b", "--reduced")
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            make_params(shapes, SEED))[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == ("b3761781772e57a451a5c9a9e0946df9"
                             "04014a1df4f05c94c2010de228831dd8")


def test_init_rules_come_before_the_built_in_ones():
    shapes = {"vision": {"pos_emb": jax.ShapeDtypeStruct((8, 64),
                                                         jnp.float32),
                         "blocks": ({"ls1": jax.ShapeDtypeStruct(
                             (64,), jnp.bfloat16)},) * 2,
                         "cls": jax.ShapeDtypeStruct((64,), jnp.float32)},
              "w": {"kernel": jax.ShapeDtypeStruct((64, 32), jnp.float32)}}
    init = {"vision/pos_emb": {"normal": 0.02},
            "vision/blocks/*/ls1": {"const": 0.1},
            "vision/cls": {"const": 0.0}}
    p = make_params(shapes, 3, init)
    assert 0.01 < float(jnp.std(p["vision"]["pos_emb"])) < 0.03
    for b in p["vision"]["blocks"]:
        assert b["ls1"].dtype == jnp.bfloat16
        assert np.all(np.asarray(b["ls1"], np.float32) == np.float32(
            jnp.bfloat16(0.1)))
    assert not np.any(np.asarray(p["vision"]["cls"]))
    # the built-in rules and the key of each leaf are unchanged
    q = make_params({k: v for k, v in shapes.items()},
                    3, dict(init, **{"vision/*": {"const": 1.0}}))
    assert np.array_equal(p["w"]["kernel"], q["w"]["kernel"])
    with pytest.raises(ValueError, match="no initialisation rule"):
        make_params(shapes, 3, {"vision/pos_emb": {"normal": 0.02}})


def _run_of(sizes: dict, *flags):
    _, cfg = _layout(*flags)
    run = harness.Run({"config": {"sizes": sizes}, "mix": {}}, 1, 1.0,
                      False, 0.0)
    run.cfg = cfg
    return run, cfg


def _vlm_sizes(cfg) -> dict:
    v = cfg.vision
    return {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
            "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "qk_norm": cfg.qk_norm,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
            "tie_embeddings": cfg.tie_embeddings, "mlp": cfg.mlp_activation,
            "num_evidence_tokens": cfg.num_evidence_tokens,
            "evidence_dim": cfg.evidence_dim,
            "vision": {"image_h": v.image_h, "image_w": v.image_w,
                       "patch": v.patch, "channels": v.channels,
                       "num_layers": v.num_layers, "d_model": v.d_model,
                       "num_heads": v.num_heads, "d_ff": v.d_ff,
                       "tokens": 1025, "projector": [[4096, 2048]]}}


@pytest.mark.parametrize("key,value", [
    ("vision.patch", 14), ("vision.num_layers", 24),
    ("vision.d_model", 1024), ("vision.no_such_width", 1),
    ("num_evidence_tokens", 255), ("evidence_dim", 4096)])
def test_check_sizes_refuses_a_tower_that_differs(key, value):
    flags = ("--arch", "internvl2-2b", "--no-reduced")
    _, cfg = _layout(*flags)
    sizes = _vlm_sizes(cfg)
    run, _ = _run_of(sizes, *flags)
    run._check_sizes()                   # as declared: accepted
    group, _, k = key.rpartition(".")
    (sizes[group] if group else sizes)[k] = value
    with pytest.raises(SystemExit, match=k):
        run._check_sizes()


def test_check_sizes_refuses_a_tower_the_program_lacks():
    flags = ("--arch", "qwen3-0.6b", "--no-reduced")
    _, cfg = _layout(*flags)
    sizes = json.loads((harness.BENCH / "configs" / "qwen3-0.6b.json")
                       .read_text())["sizes"]
    run, _ = _run_of(dict(sizes), *flags)
    run._check_sizes()
    run.config["sizes"] = dict(sizes, vision={"patch": 14},
                               num_evidence_tokens=0, evidence_dim=0)
    with pytest.raises(SystemExit, match="vision.patch"):
        run._check_sizes()
