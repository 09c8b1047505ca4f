"""Sampling test suite: transform properties vs a numpy oracle, RNG
stream determinism, and the distributional differential harness.

Layered like the rest of the repo's testing discipline:

  * **property tests** (hypothesis in CI, skipped via ``_hypothesis_stub``
    off-CI) — ``serving/sampling.py`` transforms against an independent
    float64 numpy oracle: top-k keeps exactly k, top-p keeps the minimal
    nucleus, T→0 equals argmax, transforms commute with batch ``vmap``
    — bitwise on the integer paths (masks, counts, token ids);
  * **corner grids** — the same properties on fixed edge cases (ties,
    k ∈ {0, 1, V, V+3}, one-hot distributions, u = 0), hypothesis-free
    so they always run;
  * **stream determinism** — same seed + same prompt → identical stream
    regardless of engine, batch composition and admission order (the
    per-request key-folding contract; a shared batch key would fail
    here);
  * **distributional differential** (``tests/dist_check.py``) —
    speculative sampling vs plain sampling per-position chi-squared at a
    pinned seed schedule, with an analytic anchor and a power control.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import assume, given, settings, strategies as st
except ModuleNotFoundError:
    from _hypothesis_stub import assume, given, settings, st  # noqa: F401

from repro.serving import sampling as S
from repro.serving import SamplingParams
from tests.dist_check import (ALPHA, SEED0, chi2_gof, chi2_homogeneity,
                              collect_streams, compare_streams,
                              position_counts, prefill_probs, tiny_cfg)

# ---------------------------------------------------------------------------
# float64 numpy oracle (independent of the jax implementation).
# ---------------------------------------------------------------------------


def np_softmax(x):
    x = np.asarray(x, np.float64)
    m = np.max(x)
    e = np.exp(x - m)
    return e / e.sum()


def np_top_k_mask(x, k):
    v = len(x)
    if k <= 0 or k >= v:
        return np.isfinite(np.asarray(x)) | True  # keep everything
    order = np.argsort(-np.asarray(x, np.float64), kind="stable")
    keep = np.zeros(v, bool)
    keep[order[:k]] = True
    return keep


def np_top_p_mask(x, p):
    v = len(x)
    if p >= 1:
        return np.ones(v, bool)
    probs = np_softmax(x)
    order = np.argsort(-np.asarray(x, np.float64), kind="stable")
    sp = probs[order]
    csum = np.cumsum(sp)
    keep_sorted = (csum - sp) < p
    keep_sorted[0] = True
    keep = np.zeros(v, bool)
    keep[order[keep_sorted]] = True
    return keep


def np_sampling_probs(logits, temperature, top_k, top_p):
    logits = np.asarray(logits, np.float64)
    if temperature <= 0:
        out = np.zeros(len(logits))
        out[int(np.argmax(logits))] = 1.0
        return out
    x = logits / temperature
    x = np.where(np_top_k_mask(x, top_k), x, -np.inf)
    x = np.where(np_top_p_mask(x, top_p), x, -np.inf)
    return np_softmax(x)


def np_categorical(probs, u):
    csum = np.cumsum(np.asarray(probs, np.float64))
    total = csum[-1]
    tok = int(np.sum(csum <= u * total))
    return min(tok, len(probs) - 1)


# grid-valued strategies: logits are multiples of 1/4 and temperatures
# powers of two, so ``logits / T`` is exact in BOTH float32 and float64 —
# the oracle and the jax path see identical sort keys and the integer
# comparisons (masks, counts) can be bitwise
def _logit_grids(v):
    return st.lists(st.integers(-16, 16).map(lambda q: q / 4.0),
                    min_size=v, max_size=v)


TEMPS = [0.25, 0.5, 1.0, 2.0, 4.0]


# ---------------------------------------------------------------------------
# Hypothesis properties (CI; stubbed to skips without hypothesis).
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(data=st.data())
def test_top_k_keeps_exactly_k(data):
    v = data.draw(st.integers(2, 24), label="V")
    logits = np.asarray(data.draw(_logit_grids(v)), np.float32)
    k = data.draw(st.integers(0, v + 3), label="k")
    out = np.asarray(S.apply_top_k(jnp.asarray(logits), jnp.int32(k)))
    kept = np.isfinite(out)
    assert kept.sum() == (v if k <= 0 or k >= v else k)
    np.testing.assert_array_equal(kept, np_top_k_mask(logits, k))
    np.testing.assert_array_equal(out[kept], logits[kept])  # values intact


@settings(max_examples=60, deadline=None)
@given(data=st.data())
def test_top_p_keeps_minimal_nucleus(data):
    v = data.draw(st.integers(2, 24), label="V")
    logits = np.asarray(data.draw(_logit_grids(v)), np.float32)
    p = data.draw(st.floats(0.05, 1.0), label="p")
    # skip razor-edge p where f32 vs f64 cumsum could legitimately differ
    probs = np_softmax(logits)
    order = np.argsort(-logits.astype(np.float64), kind="stable")
    csum = np.cumsum(probs[order])
    assume(p >= 1 or np.min(np.abs((csum - probs[order]) - p)) > 1e-4)
    out = np.asarray(S.apply_top_p(jnp.asarray(logits), jnp.float32(p)))
    kept = np.isfinite(out)
    np.testing.assert_array_equal(kept, np_top_p_mask(logits, p))
    if p < 1:
        # minimality: the nucleus reaches mass p, and dropping its least
        # likely member would fall below p
        assert probs[kept].sum() >= min(p, 1.0) - 1e-9
        if kept.sum() > 1:
            assert probs[kept].sum() - probs[kept].min() < p


@settings(max_examples=60, deadline=None)
@given(data=st.data())
def test_pipeline_matches_oracle_and_t0_is_argmax(data):
    v = data.draw(st.integers(2, 16), label="V")
    logits = np.asarray(data.draw(_logit_grids(v)), np.float32)
    temp = data.draw(st.sampled_from([0.0] + TEMPS), label="T")
    k = data.draw(st.integers(0, v), label="k")
    p = data.draw(st.sampled_from([0.25, 0.5, 0.9, 1.0]), label="p")
    probs64 = np_sampling_probs(logits, temp, k, p)
    if temp > 0:
        order = np.argsort(-logits.astype(np.float64) / temp, kind="stable")
        sp = np_softmax(logits / temp)[order]
        assume(p >= 1 or np.min(np.abs((np.cumsum(sp) - sp) - p)) > 1e-4)
    got = np.asarray(S.sampling_probs(jnp.asarray(logits), jnp.float32(temp),
                                      jnp.int32(k), jnp.float32(p)))
    np.testing.assert_array_equal(got > 0, probs64 > 0)  # same support
    np.testing.assert_allclose(got, probs64, atol=1e-5)
    if temp == 0:
        assert got[int(np.argmax(logits))] == 1.0  # exact one-hot


@settings(max_examples=60, deadline=None)
@given(data=st.data())
def test_categorical_matches_oracle(data):
    v = data.draw(st.integers(1, 16), label="V")
    # dyadic weights: cumsum is exact in f32 and f64 → bitwise agreement
    w = np.asarray(data.draw(st.lists(st.integers(0, 16), min_size=v,
                                      max_size=v)), np.float32) / 8.0
    assume(w.sum() > 0)
    u = data.draw(st.sampled_from([0.0, 0.124, 0.25, 0.5, 0.751, 0.999]))
    got = int(S.categorical_from_uniform(jnp.asarray(w), jnp.float32(u)))
    csum = np.cumsum(w.astype(np.float64))
    assume(np.min(np.abs(csum - u * csum[-1])) > 1e-6 or u == 0.0)
    assert got == np_categorical(w, u)
    assert w[got] > 0  # a zero-probability token is never emitted


@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_transforms_commute_with_vmap(data):
    b = data.draw(st.integers(1, 6), label="B")
    v = data.draw(st.integers(2, 12), label="V")
    logits = np.asarray([data.draw(_logit_grids(v)) for _ in range(b)],
                        np.float32)
    temp = np.asarray(data.draw(st.lists(st.sampled_from([0.0] + TEMPS),
                                         min_size=b, max_size=b)), np.float32)
    k = np.asarray(data.draw(st.lists(st.integers(0, v), min_size=b,
                                      max_size=b)), np.int32)
    p = np.asarray(data.draw(st.lists(st.sampled_from([0.3, 0.8, 1.0]),
                                      min_size=b, max_size=b)), np.float32)
    batched = S.sampling_probs(jnp.asarray(logits), jnp.asarray(temp),
                               jnp.asarray(k), jnp.asarray(p))
    mapped = jax.vmap(S.sampling_probs)(jnp.asarray(logits),
                                        jnp.asarray(temp), jnp.asarray(k),
                                        jnp.asarray(p))
    # bitwise: a row's distribution must not depend on its batch context
    np.testing.assert_array_equal(np.asarray(batched), np.asarray(mapped))


@settings(max_examples=40, deadline=None)
@given(data=st.data())
def test_speculative_accept_matches_oracle(data):
    """The in-jit rejection-sampling correction against a step-by-step
    host oracle consuming the same uniforms."""
    b = data.draw(st.integers(1, 3), label="B")
    k = data.draw(st.integers(1, 4), label="K")
    v = data.draw(st.integers(2, 8), label="V")
    rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
    # dyadic weights keep all comparisons exact across f32/f64
    p_probs = rng.integers(0, 8, (b, k + 1, v)).astype(np.float32) / 8.0
    q_probs = rng.integers(1, 8, (b, k, v)).astype(np.float32) / 8.0
    p_probs[..., 0] += 0.125  # no all-zero rows
    draft = rng.integers(0, v, (b, k)).astype(np.int32)
    seed = rng.integers(0, 2**31, b).astype(np.uint32)
    t0 = rng.integers(0, 50, b).astype(np.int32)
    n_valid = np.asarray(data.draw(st.lists(st.integers(0, k + 1),
                                            min_size=b, max_size=b)),
                         np.int32)
    acc, emit = S.speculative_accept(
        jnp.asarray(p_probs), jnp.asarray(q_probs), jnp.asarray(draft),
        jnp.asarray(seed), jnp.asarray(t0), jnp.asarray(n_valid))
    acc, emit = np.asarray(acc), np.asarray(emit)

    def u(role, row, t):
        return float(S.stream_uniform(jnp.uint32(seed[row]),
                                      jnp.int32(t), role))

    for row in range(b):
        a = 0
        while a < n_valid[row] - 1:
            x = draft[row, a]
            px = float(p_probs[row, a, x])
            qx = float(q_probs[row, a, x])
            margin = abs(u(S.ROLE_ACCEPT, row, t0[row] + a) * qx - px)
            assume(margin > 1e-6)  # f32 boundary would be a fair coin
            if not u(S.ROLE_ACCEPT, row, t0[row] + a) * qx < px:
                break
            a += 1
        assert a == acc[row], (row, a, acc[row])
        np.testing.assert_array_equal(emit[row, :a], draft[row, :a])
        last_pos = max(n_valid[row] - 1, 0)
        if a >= last_pos:  # full acceptance → bonus from p's last position
            want = np_categorical(p_probs[row, last_pos],
                                  u(S.ROLE_SAMPLE, row, t0[row] + last_pos))
        else:              # rejection → residual max(p - q, 0)
            resid = np.maximum(p_probs[row, a].astype(np.float64)
                               - q_probs[row, a], 0.0)
            assume(resid.sum() > 1e-9)  # p==q exactly can't co-occur w/ reject
            want = np_categorical(resid, u(S.ROLE_RESIDUAL, row, t0[row] + a))
        assert emit[row, a] == want, (row, a, emit[row], want)


# ---------------------------------------------------------------------------
# Corner grids (always run, no hypothesis needed).
# ---------------------------------------------------------------------------

TIE_LOGITS = np.asarray([1.0, 3.0, 3.0, -2.0, 3.0, 0.5], np.float32)


def test_t0_is_argmax_with_ties():
    """T=0 one-hots the argmax — lowest index on ties, exactly like
    ``jnp.argmax`` — and the sampler returns it for every seed."""
    probs = np.asarray(S.sampling_probs(jnp.asarray(TIE_LOGITS),
                                        jnp.float32(0.0), jnp.int32(4),
                                        jnp.float32(0.5)))
    np.testing.assert_array_equal(probs, np.eye(6)[1])
    for seed in (0, 1, 2**31):
        tok = S.sample_tokens(jnp.asarray(TIE_LOGITS)[None],
                              jnp.asarray([seed], jnp.uint32),
                              jnp.asarray([7], jnp.int32),
                              jnp.zeros(1), jnp.zeros(1, jnp.int32),
                              jnp.ones(1))
        assert int(tok[0]) == 1


def _greedy_case(name):
    """``(logits, seed, t, top_k, top_p)`` of one all-greedy batch."""
    rng = np.random.default_rng(14)
    b, v = (1, 4099) if name == "batch1" else (32, 4099)
    logits = rng.standard_normal((b, v)).astype(np.float32)
    seed = np.zeros(b, np.uint32)
    t = np.zeros(b, np.int32)
    top_k = np.zeros(b, np.int32)
    top_p = np.ones(b, np.float32)
    if name == "ties":  # the top logit repeated, lowest id wins
        logits[:, [5, 9, 4000]] = 50.0
        logits[1, 3] = 50.0
    elif name == "equal_rows":  # every logit of a row equal
        logits[0] = 0.0
        logits[1] = -3.5
        logits[2] = np.finfo(np.float32).max
    elif name == "inf_and_large":
        logits[0, ::2] = -np.inf  # half the vocab masked
        logits[1] = -np.inf       # a whole row masked
        logits[2, 17] = np.inf
        logits[3, [7, 70]] = np.finfo(np.float32).max
        logits[4] *= 1e30
        logits[5, 100] = -np.inf
    elif name == "inactive_rows":  # rows a caller left with stale params
        seed[:] = rng.integers(1, 2**32, b, dtype=np.uint32)
        t[:] = rng.integers(0, 1000, b)
        top_k[:] = rng.integers(1, 50, b)
        top_p[:] = rng.uniform(0.05, 0.95, b).astype(np.float32)
    return logits, seed, t, top_k, top_p


@pytest.mark.parametrize("name", ["batch1", "batch32", "ties", "equal_rows",
                                  "inf_and_large", "inactive_rows"])
def test_greedy_tokens_match_sample_tokens(name):
    """The engines' all-greedy program (one argmax) returns, bit for bit,
    what the full sampler returns when every row has T=0, whatever the
    rows' seed, emission index, top-k and top-p."""
    logits, seed, t, top_k, top_p = _greedy_case(name)
    temp = np.zeros(len(logits), np.float32)
    full = np.asarray(S.sample_tokens_jit(logits, seed, t, temp, top_k,
                                          top_p))
    fast = np.asarray(S.greedy_tokens_jit(logits))
    assert fast.dtype == full.dtype == np.int32
    assert fast.shape == full.shape == (len(logits),)
    np.testing.assert_array_equal(fast, full)
    np.testing.assert_array_equal(fast, np.asarray(S.greedy_tokens(logits)))
    if name == "ties":
        assert fast[0] == 5 and fast[1] == 3
    if name == "equal_rows":
        assert list(fast[:3]) == [0, 0, 0]


def test_top_k_corner_grid():
    for k in range(0, 9):
        out = np.asarray(S.apply_top_k(jnp.asarray(TIE_LOGITS), jnp.int32(k)))
        kept = np.isfinite(out)
        assert kept.sum() == (6 if k <= 0 or k >= 6 else k)
        np.testing.assert_array_equal(kept, np_top_k_mask(TIE_LOGITS, k))
    # ties at the boundary break toward lower vocab ids (argmax-consistent)
    np.testing.assert_array_equal(
        np.isfinite(np.asarray(S.apply_top_k(jnp.asarray(TIE_LOGITS),
                                             jnp.int32(2)))),
        [False, True, True, False, False, False])


def test_top_p_corner_grid():
    # uniform over 4 → each token has mass 1/4 exactly (dyadic, no
    # float ambiguity); p=0.5 keeps exactly the first two sorted tokens
    logits = jnp.zeros(4)
    for p, n_keep in [(0.2, 1), (0.5, 2), (0.6, 3), (0.75, 3), (0.8, 4),
                      (1.0, 4)]:
        kept = np.isfinite(np.asarray(S.apply_top_p(logits, jnp.float32(p))))
        assert kept.sum() == n_keep, (p, kept)
    # the top token always survives, however small p is
    assert np.isfinite(
        np.asarray(S.apply_top_p(jnp.asarray(TIE_LOGITS),
                                 jnp.float32(1e-6))))[1]


def test_categorical_corner_grid():
    onehot = jnp.asarray([0.0, 0.0, 1.0, 0.0])
    for u in (0.0, 0.3, 0.999):  # u=0 included: one-hot must be exact
        assert int(S.categorical_from_uniform(onehot, jnp.float32(u))) == 2
    half = jnp.asarray([0.5, 0.5])
    assert int(S.categorical_from_uniform(half, jnp.float32(0.25))) == 0
    assert int(S.categorical_from_uniform(half, jnp.float32(0.75))) == 1
    # unnormalised weights are scaled by their total, not assumed to sum
    # to 1 (the speculative residual path depends on this)
    w = jnp.asarray([1.0, 0.0, 3.0])
    assert int(S.categorical_from_uniform(w, jnp.float32(0.1))) == 0
    assert int(S.categorical_from_uniform(w, jnp.float32(0.9))) == 2


def test_stream_key_separates_roles_and_positions():
    u = {(t, role): float(S.stream_uniform(jnp.uint32(7), jnp.int32(t), role))
         for t in range(4) for role in (S.ROLE_SAMPLE, S.ROLE_ACCEPT,
                                        S.ROLE_RESIDUAL, S.ROLE_DRAFT)}
    assert len(set(u.values())) == len(u)  # all draws distinct
    # …and reproducible: the same (seed, t, role) gives the same draw
    assert u[(2, S.ROLE_SAMPLE)] == float(
        S.stream_uniform(jnp.uint32(7), jnp.int32(2), S.ROLE_SAMPLE))
    # a different seed moves every draw
    assert float(S.stream_uniform(jnp.uint32(8), jnp.int32(2),
                                  S.ROLE_SAMPLE)) != u[(2, S.ROLE_SAMPLE)]


def test_speculative_accept_greedy_is_prefix_match():
    """One-hot p/q (the T=0 case) must reduce the rejection-sampling
    correction to greedy prefix matching + the target's correction token."""
    v = 8
    target_toks = np.asarray([3, 5, 1, 2])     # target argmaxes (W=4)
    draft_toks = np.asarray([3, 5, 4])          # diverges at position 2
    p = np.eye(v, dtype=np.float32)[target_toks][None]
    q = np.eye(v, dtype=np.float32)[draft_toks][None]
    acc, emit = S.speculative_accept(
        jnp.asarray(p), jnp.asarray(q), jnp.asarray(draft_toks[None]),
        jnp.asarray([123], jnp.uint32), jnp.asarray([10], jnp.int32),
        jnp.asarray([4], jnp.int32))
    assert int(acc[0]) == 2
    # emitted: the accepted prefix + the target's own token at the
    # rejection point (the residual of one-hots is the target's one-hot)
    np.testing.assert_array_equal(np.asarray(emit)[0, :3], [3, 5, 1])
    # full acceptance: identical one-hots accept everything, bonus is
    # the target's last-position argmax
    acc2, emit2 = S.speculative_accept(
        jnp.asarray(p), jnp.asarray(p[:, :3]),
        jnp.asarray(target_toks[None, :3]),
        jnp.asarray([123], jnp.uint32), jnp.asarray([10], jnp.int32),
        jnp.asarray([4], jnp.int32))
    assert int(acc2[0]) == 3
    np.testing.assert_array_equal(np.asarray(emit2)[0], target_toks)


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=1.5)
    with pytest.raises(ValueError, match="seed"):
        SamplingParams(seed=2**32)
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.5).greedy


# ---------------------------------------------------------------------------
# Engine-level stream determinism + the distributional differential.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """Everything the engine-level tests share: tiny cfg, params, and the
    plain paged engine's N sampled streams at the pinned seed schedule."""
    from repro.models import model as MD
    from repro.serving import ServeEngine

    cfg = tiny_cfg()
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    base = SamplingParams(temperature=1.3, top_k=8, top_p=0.95)
    n, max_new = 150, 5
    plain = collect_streams(
        lambda: ServeEngine(params, cfg, max_batch=8, max_len=32,
                            page_size=8, prefill_chunk=4),
        [1, 2, 3], n, max_new, base)
    return cfg, params, base, n, max_new, plain


def test_same_seed_same_stream_across_batch_and_order(served):
    """Satellite: seed determinism.  The same (seed, prompt) must emit
    the identical stream whatever the batch composition, admission
    order, or engine — a shared batch key would fail all three legs."""
    from repro.serving import FixedSlotEngine, ServeEngine

    cfg, params, base, _, _, _ = served
    prompts = [[1, 2, 3], [7, 5], [9, 9, 9, 2], [4, 4, 1, 1, 5, 6, 7],
               [3, 1], [2, 2, 2]]
    sps = [dataclasses.replace(base, seed=SEED0 + i)
           for i in range(len(prompts))]

    def run(make_engine, order):
        eng = make_engine()
        reqs = [(i, eng.submit(prompts[i], max_new_tokens=4,
                               sampling=sps[i])) for i in order]
        eng.run_until_drained()
        return {i: r.generated for i, r in reqs}

    fwd = list(range(len(prompts)))
    runs = {
        "paged b=6": run(lambda: ServeEngine(params, cfg, max_batch=6,
                                             max_len=32, page_size=8,
                                             prefill_chunk=4), fwd),
        "paged b=2": run(lambda: ServeEngine(params, cfg, max_batch=2,
                                             max_len=32, page_size=8,
                                             prefill_chunk=4), fwd),
        "paged rev": run(lambda: ServeEngine(params, cfg, max_batch=3,
                                             max_len=32, page_size=8,
                                             prefill_chunk=4), fwd[::-1]),
        "fixed b=2": run(lambda: FixedSlotEngine(params, cfg, slots=2,
                                                 max_len=32), fwd),
    }
    want = runs["paged b=6"]
    assert all(len(s) == 4 for s in want.values())
    for name, got in runs.items():
        assert got == want, (name, got, want)
    # distinct seeds on the same prompt give distinct streams (T>0): the
    # test would be vacuous if sampling collapsed to one stream
    eng = ServeEngine(params, cfg, max_batch=4, max_len=32, page_size=8,
                      prefill_chunk=4)
    dup = [eng.submit([1, 2, 3], max_new_tokens=6,
                      sampling=dataclasses.replace(base, seed=s))
           for s in (SEED0, SEED0, SEED0 + 1, SEED0 + 2)]
    eng.run_until_drained()
    assert dup[0].generated == dup[1].generated
    assert len({tuple(r.generated) for r in dup}) >= 2


def test_spec_sampling_matches_plain_distribution(served):
    """THE tentpole proof: speculative sampling with a garbage draft
    (high rejection traffic — the correction path does real work) is
    per-position indistinguishable from plain sampling."""
    from repro.models import model as MD
    from repro.serving import SpeculativeEngine

    cfg, params, base, n, max_new, plain = served
    garbage = MD.init_params(cfg, jax.random.PRNGKey(99))
    spec = collect_streams(
        lambda: SpeculativeEngine(params, cfg, garbage, spec_k=3,
                                  max_batch=8, max_len=32, page_size=8,
                                  prefill_chunk=4),
        [1, 2, 3], n, max_new, base)
    assert not np.array_equal(plain, spec)  # equality is distributional,
    # not bitwise: the draft's proposals ride on their own RNG role
    pvals = compare_streams(plain, spec, cfg.vocab_size)
    assert all(p >= ALPHA for p, _ in pvals), pvals


def test_position0_matches_analytic_distribution(served):
    """Anchor the harness to ground truth: every stream's first token is
    one draw from ``sampling_probs`` of the prefill logits."""
    cfg, params, base, _, _, plain = served
    probs = prefill_probs(params, cfg, [1, 2, 3], base)
    p0, groups = chi2_gof(position_counts(plain, cfg.vocab_size)[0], probs)
    assert groups >= 3  # the test actually distinguishes several tokens
    assert p0 >= ALPHA, p0


def test_harness_detects_distribution_change(served):
    """Negative power control: a genuinely different distribution must
    be REJECTED — otherwise a passing differential means nothing.
    Shrinking the nucleus (top_k 8 → 2) changes the support itself, the
    kind of break a wrong transform or acceptance rule would cause."""
    from repro.serving import ServeEngine

    cfg, params, base, n, max_new, plain = served
    narrow = collect_streams(
        lambda: ServeEngine(params, cfg, max_batch=8, max_len=32,
                            page_size=8, prefill_chunk=4),
        [1, 2, 3], n, max_new, dataclasses.replace(base, top_k=2))
    pvals = compare_streams(plain, narrow, cfg.vocab_size)
    assert any(p < ALPHA for p, _ in pvals), pvals


def test_chi2_helpers_are_sane():
    """The statistics layer itself: identical counts → p=1; a gross
    mismatch → p≈0; rare categories pool instead of blowing up."""
    a = np.asarray([50, 30, 20, 1, 0, 0], np.float64)
    p1, _ = chi2_homogeneity(a, a)
    assert p1 == 1.0
    p2, _ = chi2_homogeneity(a, a[::-1])
    assert p2 < 1e-6
    pg, groups = chi2_gof(np.asarray([52, 30, 18, 1]),
                          np.asarray([0.5, 0.3, 0.19, 0.01]))
    assert pg > 0.1 and groups == 3  # the 1%-expected tail pooled away
