"""The comparison that decides ``correct``, driven through a whole run
of the cell at a size the CPU holds (the chip check skipped): a sound
run is correct and compiles nothing inside its window; a run whose
served tokens are altered where the sampler produces them is not; the
int8 control reads above the sound program on the same sample."""
from bench.tests.tiny import run, tiny_spec

CELL = ("qwen3-0.6b", "reasoning-backlog",
        {"lp_kl": 0.002, "window_compiles": 0})


def _alter_tokens(monkeypatch):
    import repro.serving.engine as engine
    orig = engine.sample_token

    def altered(key, logits, *a, **kw):
        tok, lp = orig(key, logits, *a, **kw)
        return (tok + 1) % logits.shape[-1], lp
    monkeypatch.setattr(engine, "sample_token", altered)


def test_sound_run_is_correct_and_control_reads_higher():
    out = run(tiny_spec(*CELL), controls=("int8",))
    chk, ctl = out["check"], out["control"]["int8"]
    assert chk["correct"], chk["lines"]
    assert chk["sampled"] == 3
    assert ctl["numbers"]["lp_kl"] > chk["numbers"]["lp_kl"]


def test_altered_token_is_not_correct(monkeypatch):
    _alter_tokens(monkeypatch)
    chk = run(tiny_spec(*CELL))["check"]
    assert not chk["correct"], chk["lines"]
    assert chk["numbers"]["lp_kl"] > CELL[2]["lp_kl"]
