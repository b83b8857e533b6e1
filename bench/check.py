"""The comparison that decides ``correct``: what the timed path served,
against the plain reference run over the same prompts and served
tokens, after the window has closed and the program's state is freed.

The number compared (against the cell's limit in ``limits/<cell>.json``):

* ``lp_kl``: over every served token of the sampled candidates, the
  mean of (the program's log-probability of the token under its
  sampler's processing - the reference's), as an absolute value. The
  program reports each candidate's sum as ``sum_lp``. The tokens were
  drawn from the program's own distribution, so the mean estimates the
  per-token KL divergence of the program's sampling distribution from
  the reference's: it grows with the square of the program's logit
  error, and its noise falls with the number of tokens.

With ``quant`` ("int8", "fp8") the reference with its matrices rounded
to that precision stands in the program's place (the control): its own
log-probabilities of the same tokens against the float32 reference's.
"""
from __future__ import annotations

import gc
import importlib
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np


def pick_sample(done: List[dict], n: int, rng) -> List[dict]:
    """``n`` finished requests drawn from the seed, the longest (prompt
    plus served tokens) always among them."""
    if not done:
        return []
    size = [len(r["prompt"]) + max(len(c["tokens"]) for c in r["cands"])
            for r in done]
    longest = int(np.argmax(size))
    rest = [i for i in range(len(done)) if i != longest]
    take = rng.choice(rest, min(n - 1, len(rest)), replace=False) \
        if rest and n > 1 else []
    return [done[longest]] + [done[int(i)] for i in take]


def _pad(x: np.ndarray, fill: int, to: int = 128) -> np.ndarray:
    """``x`` padded to a multiple of ``to``: with ``fill``, or with its
    last value where ``fill`` is -1."""
    n = -(-len(x) // to) * to
    return np.concatenate([x, np.full(n - len(x), x[-1] if fill < 0
                                      else fill, x.dtype)])


def compare(params, config: dict, sampling: dict, sample: List[dict],
            quant: Optional[str] = None) -> Dict[str, float]:
    """The numbers compared, over ``sample`` (requests with their prompt
    and served candidates, and the ``pixels`` of the image each was
    served with, if any), by the configuration's reference module
    ``reference/<name>.py``. An image goes to the reference as
    ``image=``; positions index the text stream either way, and the
    reference places the image's span itself."""
    ref = importlib.import_module(f"bench.reference.{config['reference']}")
    sizes = config["sizes"]
    gap, tokens = 0.0, 0
    for r in sample:
        prompt = np.asarray(r["prompt"], np.int32)
        image = {} if r.get("pixels") is None else \
            {"image": jnp.asarray(r["pixels"], jnp.float32)}
        for c in r["cands"]:
            toks = np.asarray(c["tokens"], np.int32)
            n = len(toks)
            # padded to multiples of 128 (causal: the pad changes no
            # earlier position) so few reference programs compile
            seq = _pad(np.concatenate([prompt, toks[:-1]]), 0)
            pos = _pad(np.arange(n) + len(prompt) - 1, -1)

            def logprobs(q):
                z = ref.logits_at(params, sizes, jnp.asarray(seq),
                                  jnp.asarray(pos), q, **image)[:n]
                return ref.sampled_logprobs(z, jnp.asarray(toks), **sampling)
            want = float(jnp.sum(logprobs(None)))
            mine = c["sum_lp"] if quant is None else \
                float(jnp.sum(logprobs(quant)))
            gap += mine - want
            tokens += n
        gc.collect()
    return {"lp_kl": abs(gap) / tokens} if tokens else {}


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            wanted: List[str]):
    """(correct, lines): every wanted number present and within its
    limit; one plain line per number."""
    ok, lines = True, []
    for name in wanted:
        val = numbers.get(name)
        lim = limits.get(name)
        good = val is not None and lim is not None and val <= lim
        ok &= good
        lines.append(f"{name} {val!r} limit {lim!r} "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines
