"""Distributional differential harness for sampled serving.

The stochastic counterpart of the repo's bit-exact differentials: where
greedy streams must match token-for-token, sampled streams must match
*in distribution*.  The harness draws N independent streams (one request
per seed, all with the same prompt — per-request key folding makes them
batch-independent, so one engine run carries all N) from two engines and
compares per-position empirical token distributions with a two-sample
chi-squared homogeneity test (rare categories pooled).  A pinned seed
schedule (``SEED0 + i``) makes every run reproduce the same counts
exactly — a failure is a real distribution change, never flake.

Three layers of evidence:

  * **differential** — speculative sampling (identical *and* garbage
    draft) vs plain sampling: the rejection-sampling correction must
    make them indistinguishable position by position;
  * **analytic** — position 0's distribution is known in closed form
    (every stream shares the prompt, so token 0 ~ ``sampling_probs``
    of the prefill logits): a one-sample goodness-of-fit test anchors
    the empirical pipeline to ground truth;
  * **power** — a negative control (two genuinely different
    temperatures) must *reject*, proving the test can actually detect a
    broken distribution at this N.

Used by ``tests/test_sampling.py`` and standalone::

    PYTHONPATH=src python tests/dist_check.py [--n 300] [--max-new 6]
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Callable, List, Tuple

import numpy as np

# pinned schedule: stream i gets seed SEED0 + i — never vary this without
# regenerating expectations; determinism is what keeps the test unflaky
SEED0 = 1000
ALPHA = 1e-3  # per-position rejection threshold (pinned seeds → exact)


def tiny_cfg():
    """The serving test suite's standard tiny transformer."""
    from repro.configs import get_config

    cfg = get_config("qwen3-14b", reduced=True)
    return dataclasses.replace(cfg, num_layers=2, d_model=64, d_ff=128,
                               vocab_size=64, num_heads=2, num_kv_heads=1,
                               head_dim=32)


def collect_streams(engine_factory: Callable, prompt: List[int],
                    n_streams: int, max_new: int, base,
                    seed0: int = SEED0) -> np.ndarray:
    """N streams from one engine run: stream ``i`` is a request with
    ``base`` sampling params reseeded to ``seed0 + i``.  Batch
    composition cannot couple the streams (per-request key folding), so
    drawing them all in one continuous-batching run is both legitimate
    and the realistic serving condition."""
    eng = engine_factory()
    reqs = [eng.submit(list(prompt), max_new_tokens=max_new,
                       sampling=dataclasses.replace(base, seed=seed0 + i))
            for i in range(n_streams)]
    eng.run_until_drained()
    streams = np.array([r.generated for r in reqs], dtype=np.int64)
    assert streams.shape == (n_streams, max_new), streams.shape
    return streams


def position_counts(streams: np.ndarray, vocab: int) -> np.ndarray:
    """(T, vocab) token counts per stream position."""
    return np.stack([np.bincount(streams[:, t], minlength=vocab)
                     for t in range(streams.shape[1])]).astype(np.float64)


def _pool_rare(groups: List[Tuple[float, ...]], rest: np.ndarray,
               min_total: float) -> List[Tuple[float, ...]]:
    """Attach the pooled rare-category bucket: its own group when big
    enough, merged into the smallest regular group otherwise (expected
    counts below ~5 break the chi-squared approximation)."""
    if rest.sum() >= min_total:
        groups.append(tuple(rest))
    elif rest.sum() > 0 and groups:
        last = groups.pop()
        groups.append(tuple(np.asarray(last) + rest))
    return groups


def chi2_homogeneity(counts_a: np.ndarray, counts_b: np.ndarray,
                     min_total: float = 10.0) -> Tuple[float, int]:
    """Two-sample chi-squared test of homogeneity on category counts.

    Categories whose combined count is under ``min_total`` are pooled
    into one bucket.  Returns ``(p_value, n_groups)``; identical count
    vectors give p = 1.
    """
    from scipy.stats import chi2

    ca = np.asarray(counts_a, np.float64)
    cb = np.asarray(counts_b, np.float64)
    tot = ca + cb
    groups: List[Tuple[float, ...]] = []
    rest = np.zeros(2)
    for i in np.argsort(-tot, kind="stable"):
        if tot[i] <= 0:
            continue
        if tot[i] >= min_total:
            groups.append((ca[i], cb[i]))
        else:
            rest += (ca[i], cb[i])
    groups = _pool_rare(groups, rest, min_total)
    if len(groups) < 2:
        return 1.0, len(groups)  # one category → nothing to distinguish
    na, nb = ca.sum(), cb.sum()
    stat = 0.0
    for ga, gb in groups:
        t = ga + gb
        ea, eb = na * t / (na + nb), nb * t / (na + nb)
        stat += (ga - ea) ** 2 / ea + (gb - eb) ** 2 / eb
    return float(chi2.sf(stat, len(groups) - 1)), len(groups)


def chi2_gof(counts: np.ndarray, probs: np.ndarray,
             min_expected: float = 5.0) -> Tuple[float, int]:
    """One-sample goodness of fit: observed ``counts`` vs the analytic
    distribution ``probs`` (rare expected-counts pooled)."""
    from scipy.stats import chi2

    counts = np.asarray(counts, np.float64)
    n = counts.sum()
    expected = n * np.asarray(probs, np.float64)
    groups = []
    rest = np.zeros(2)
    for i in np.argsort(-expected, kind="stable"):
        if expected[i] >= min_expected:
            groups.append((counts[i], expected[i]))
        else:
            rest += (counts[i], expected[i])
    groups = _pool_rare(groups, rest, min_expected)
    if len(groups) < 2:
        return 1.0, len(groups)
    stat = sum((o - e) ** 2 / e for o, e in groups if e > 0)
    return float(chi2.sf(stat, len(groups) - 1)), len(groups)


def compare_streams(streams_a: np.ndarray, streams_b: np.ndarray,
                    vocab: int) -> List[Tuple[float, int]]:
    """Per-position two-sample tests; returns ``[(p_value, groups), …]``."""
    ca = position_counts(streams_a, vocab)
    cb = position_counts(streams_b, vocab)
    return [chi2_homogeneity(ca[t], cb[t]) for t in range(ca.shape[0])]


def prefill_probs(params, cfg, prompt: List[int], base) -> np.ndarray:
    """The analytic distribution of every stream's first token."""
    import jax.numpy as jnp
    from repro.models import model as MD
    from repro.serving import sampling as S

    logits, _ = MD.prefill(params, jnp.asarray(prompt, jnp.int32)[None],
                           cfg, 64, compute_dtype=jnp.float32)
    return np.asarray(S.sampling_probs(
        logits[0, -1], jnp.float32(base.temperature),
        jnp.int32(base.top_k), jnp.float32(base.top_p)), np.float64)


# ---------------------------------------------------------------------------
# Standalone driver.
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    import jax
    from repro.models import model as MD
    from repro.serving import (FixedSlotEngine, SamplingParams, ServeEngine,
                               SpeculativeEngine)

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200, help="streams per engine")
    ap.add_argument("--max-new", type=int, default=5)
    ap.add_argument("--temperature", type=float, default=1.3)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--top-p", type=float, default=0.95)
    args = ap.parse_args(argv)

    cfg = tiny_cfg()
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    garbage = MD.init_params(cfg, jax.random.PRNGKey(99))
    base = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p)
    prompt = [1, 2, 3]
    kw = dict(max_len=32, page_size=8, prefill_chunk=4)

    def paged():
        return ServeEngine(params, cfg, max_batch=8, **kw)

    def fixed():
        return FixedSlotEngine(params, cfg, max_batch=8, max_len=32)

    def spec(draft):
        return lambda: SpeculativeEngine(params, cfg, draft, spec_k=3,
                                         max_batch=8, **kw)

    print(f"[dist] drawing {args.n} streams × {args.max_new} positions "
          f"per engine (T={args.temperature}, top_k={args.top_k}, "
          f"top_p={args.top_p}, seeds {SEED0}..{SEED0 + args.n - 1})")
    plain = collect_streams(paged, prompt, args.n, args.max_new, base)
    cases = [
        ("fixed-slot vs paged", collect_streams(fixed, prompt, args.n,
                                                args.max_new, base)),
        ("spec(identical) vs paged", collect_streams(
            spec(params), prompt, args.n, args.max_new, base)),
        ("spec(garbage) vs paged", collect_streams(
            spec(garbage), prompt, args.n, args.max_new, base)),
    ]
    failures = 0
    for name, streams in cases:
        pvals = compare_streams(plain, streams, cfg.vocab_size)
        verdict = "ok" if all(p >= ALPHA for p, _ in pvals) else "FAIL"
        failures += verdict == "FAIL"
        print(f"  {name:28s} [{verdict}] p per position: "
              + " ".join(f"{p:.3f}" for p, _ in pvals))

    p0, g0 = chi2_gof(position_counts(plain, cfg.vocab_size)[0],
                      prefill_probs(params, cfg, prompt, base))
    ok0 = p0 >= ALPHA
    failures += not ok0
    print(f"  {'position-0 analytic':28s} "
          f"[{'ok' if ok0 else 'FAIL'}] p={p0:.3f} groups={g0}")

    # power: a real distribution difference must be detected at this N —
    # shrinking the nucleus (top_k 8 → 2) changes the support itself, the
    # kind of break a wrong transform or acceptance rule would cause
    narrow = collect_streams(paged, prompt, args.n, args.max_new,
                             dataclasses.replace(base, top_k=2))
    pvals = compare_streams(plain, narrow, cfg.vocab_size)
    rejected = any(p < ALPHA for p, _ in pvals)
    failures += not rejected
    print(f"  {'negative control (top_k=2)':28s} "
          f"[{'ok' if rejected else 'FAIL — no power'}] min p="
          f"{min(p for p, _ in pvals):.2e}")

    print(f"[dist] {'PASS' if failures == 0 else f'{failures} FAILURE(S)'}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
