"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished is drawn
from the seed, the longest among them, until it holds the configuration's
``sample_tokens`` served tokens.  The plain reference (``reference.py``)
runs over each prompt with the tokens served after it, and reads at each
served position how far the served token's logit lies below its own best
(the gap; 0 where the served token is the reference's greedy choice).
The numbers compared are statistics of those gaps, each with the limit the
configuration gives it (``correct.limits``).
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip import reference
from benchmarks.chip.spec import ModelSpec

NUMBERS = {
    "widest_gap": lambda g: float(g.max()),
    "mean_gap": lambda g: float(g.mean()),
}


def sample(served, spec: ModelSpec, seed: int) -> list:
    """The longest finished request, then others in a seeded order, until
    the sample holds ``spec.sample_tokens`` served tokens."""
    if not served:
        return []
    longest = max(range(len(served)),
                  key=lambda i: len(served[i].prompt) + len(served[i].tokens))
    rest = [i for i in np.random.default_rng(seed).permutation(len(served))
            if i != longest]
    picked, n = [], 0
    for i in [longest] + rest:
        picked.append(served[i])
        n += len(served[i].tokens)
        if n >= spec.sample_tokens:
            break
    return picked


def gaps(params, spec: ModelSpec, requests, precision: str = "f32",
         control: str = None) -> np.ndarray:
    """The reference's gap at every served token of ``requests``; with
    ``control``, the gap of the token the reference computed at that
    precision puts first, instead of the served one."""
    out = []
    for r in requests:
        seq = list(r.prompt) + list(r.tokens[:-1])
        first = len(r.prompt) - 1
        targets = np.asarray([r.tokens], np.int32)
        if control is not None:
            _, top, _ = reference.score(params, spec, seq, first, targets,
                                        control)
            targets = top[None].astype(np.int32)
        best, _, at = reference.score(params, spec, seq, first, targets,
                                      precision)
        out.append(best - at[0])
    return np.concatenate(out) if out else np.zeros(0)


def numbers(g: np.ndarray, spec: ModelSpec) -> dict:
    """Each configured number of the gaps ``g`` beside its limit; no
    gaps (nothing finished) reads as infinitely far."""
    return {name: {"value": NUMBERS[name](g) if g.size else float("inf"),
                   "limit": limit}
            for name, limit in spec.limits}


def compare(params, spec: ModelSpec, served, seed: int) -> dict:
    return numbers(gaps(params, spec, sample(served, spec, seed)), spec)
