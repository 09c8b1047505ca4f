"""The one traffic generator, driven by ``traffic/<name>.json``.

A mix file gives the loop and the length distributions; a file may name a
``base`` mix and override some of its keys (a rate, say).  Keys:

* ``loop``: ``"open"`` (Poisson arrivals at ``rate_per_s``, each request
  due at its own time whatever the server does) or ``"closed"``
  (``clients`` callers, each sending its next request when its previous
  one completes);
* ``prompt`` / ``output``: lognormal lengths, ``{"median", "sigma",
  "min", "max"}``, clipped to ``[min, max]``;
* ``lead_in_s``: seconds of traffic before the measured window opens, so
  the batch is in steady state when it does;
* ``block``: requests in one block of the population (below).

Every seed does the same work.  One block of sizes and gaps is drawn once
from ``POPULATION_SEED``; the request stream is that block repeated, each
repetition in an order of its own drawn from ``--seed``, which also draws
the prompt tokens.  So any stretch of whole blocks holds the same sizes
and arrivals on every seed.  A closed-loop client's first request is in
flight when the run starts: its total output is drawn length-biased and a
uniform share of it is left (the residual life of a renewal process), so
completions are spread out when the window opens.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
POPULATION_SEED = 20240702


def load(name: str, folder: Path = HERE / "traffic") -> dict:
    raw = json.loads((folder / f"{name}.json").read_text())
    base = raw.pop("base", None)
    mix = load(base, folder) if base else {}
    mix.update(raw)
    mix["name"] = name
    return mix


@dataclasses.dataclass
class Request:
    due_s: float          # open loop: due time after the run starts
    prompt: List[int]
    max_new: int


def _lengths(rng, dist: dict, n: int) -> np.ndarray:
    x = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def _prompts(rng, lens, vocab: int) -> List[List[int]]:
    """Random prompts whose first tokens all differ, so no two prompts
    share a cached prefix (shared prefixes are another mix's business)."""
    firsts = rng.choice(vocab, size=len(lens), replace=False)
    return [[int(f)] + rng.integers(0, vocab, n - 1).tolist()
            for f, n in zip(firsts, lens)]


def _stream(mix: dict, rng, blocks: int):
    """``blocks`` repetitions of the population block, each permuted:
    (gaps, prompt lengths, output lengths)."""
    pop = np.random.default_rng(POPULATION_SEED)
    b = mix["block"]
    gaps = pop.exponential(1.0 / mix.get("rate_per_s", 1.0), b)
    p_len = _lengths(pop, mix["prompt"], b)
    o_len = _lengths(pop, mix["output"], b)
    g_idx = np.concatenate([rng.permutation(b) for _ in range(blocks)])
    r_idx = np.concatenate([rng.permutation(b) for _ in range(blocks)])
    return gaps[g_idx], p_len[r_idx], o_len[r_idx]


def open_loop(mix: dict, seed: int, vocab: int,
              horizon_s: float) -> List[Request]:
    """Requests due up to ``horizon_s`` after the run starts (whole
    blocks, so at least that far)."""
    rng = np.random.default_rng(seed)
    blocks = int(np.ceil(horizon_s * mix["rate_per_s"] / mix["block"])) + 1
    gaps, p_len, o_len = _stream(mix, rng, blocks)
    prompts = _prompts(rng, p_len, vocab)
    return [Request(float(t), p, int(o))
            for t, p, o in zip(np.cumsum(gaps), prompts, o_len)]


def closed_loop(mix: dict, seed: int, vocab: int,
                n_requests: int) -> Tuple[List[Request], List[Request]]:
    """(one in-flight request per client, the stream clients draw their
    next requests from, in order)."""
    pop = np.random.default_rng(POPULATION_SEED + 1)
    clients = mix["clients"]
    pool = _lengths(pop, mix["output"], 64 * clients)
    total = pop.choice(pool, clients, p=pool / pool.sum())
    left = np.maximum(1, np.ceil(pop.uniform(size=clients) * total)).astype(int)
    first_p = _lengths(pop, mix["prompt"], clients)
    rng = np.random.default_rng(seed)
    order = rng.permutation(clients)
    blocks = -(-n_requests // mix["block"])
    _, p_len, o_len = _stream(mix, rng, blocks)
    prompts = _prompts(rng, np.concatenate([first_p[order], p_len]), vocab)
    first = [Request(0.0, prompts[c], int(left[order[c]]))
             for c in range(clients)]
    stream = [Request(0.0, p, int(o))
              for p, o in zip(prompts[clients:], o_len)]
    return first, stream
