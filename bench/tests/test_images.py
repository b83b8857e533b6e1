"""Image-bearing traffic through a whole run of a tiny internvl2-2b cell
built here (the program's reduced float32 model and stand-in tower,
images from a Zipf pool, an ``init`` rule of the configuration file):
the run is correct, compiles nothing inside its window, encodes images
and hits the feature memo there; the same run with the reference handed
another image than the one served is not correct."""
import json

import pytest

from bench import harness, images
from bench.tests.tiny import run, tiny_spec

LIMITS = {"lp_kl": 0.002, "window_compiles": 0}


def vlm_spec(*flags: str) -> dict:
    conf = {"arch": "internvl2-2b",
            "sizes": {"qk_norm": False, "rope_theta": 10000.0,
                      "norm_eps": 1e-6, "tie_embeddings": False,
                      "mlp": "swiglu", "vision": {}},
            "init": {"vision/pos_emb": {"normal": 0.02}},
            "serve_flags": ["--arch", "internvl2-2b", "--no-reduced",
                            "--impl", "paged_pallas", "--slots", "24",
                            "--page-size", "8", "--macro-steps", "8",
                            *flags],
            "kernels": {"paged_decode": "^%_paged_decode_call"},
            "reference": "vlm"}
    mix = json.loads((harness.BENCH / "traffic" / "reasoning-backlog.json")
                     .read_text())
    mix["images"] = {"kind": "zipf", "pool": 6, "s": 1.0}
    return tiny_spec(conf, mix, LIMITS)


def _keep_counters(monkeypatch) -> dict:
    seen = {}
    context = harness.Run.context

    def keep(self):
        ctx = context(self)
        seen.update(ctx["counters"])
        return ctx
    monkeypatch.setattr(harness.Run, "context", keep)
    return seen


@pytest.mark.parametrize("flags", [(), ("--prefix-cache",)],
                         ids=["bucketed", "prefix-cache"])
def test_image_cell_is_correct_and_warm(monkeypatch, flags):
    counters = _keep_counters(monkeypatch)
    chk = run(vlm_spec(*flags))["check"]
    assert chk["correct"], chk["lines"]
    assert chk["numbers"]["window_compiles"] == 0
    sched = counters["sched"]
    assert sched["image_encodes"] > 0 and sched["image_feat_hits"] > 0
    if flags:
        assert counters["kv"]["prefix_cache"]["hits"] > 0


def test_reference_given_another_image_is_not_correct(monkeypatch):
    judge, pixels = harness.judge, images.pixels

    def other_image(*a, **kw):
        monkeypatch.setattr(images, "pixels",
                            lambda seed, i, v: pixels(seed, i + 1, v))
        return judge(*a, **kw)
    monkeypatch.setattr(harness, "judge", other_image)
    chk = run(vlm_spec())["check"]
    assert not chk["correct"], chk["lines"]
    assert chk["numbers"]["lp_kl"] > LIMITS["lp_kl"]
