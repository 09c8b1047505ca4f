"""Speculative-decoding differential suite.

The subsystem's contract: **every** token stream a
:class:`~repro.serving.speculative.SpeculativeEngine` emits under greedy
decoding is bit-identical to what the plain paged
:class:`~repro.serving.engine.ServeEngine` would emit for the same
requests — independent of draft quality (an identical draft and a
garbage draft must both bit-match; only the acceptance rate may differ),
of ``k``, and of page-pressure eviction / cancellation schedules.

Plus the PR-5 satellites: the golden-token check (a bundle's int8 target
must reproduce ``tests/golden/serving_tokens.json`` through the
speculative engine), acceptance telemetry sanity, and the unified
``run_until_drained`` budget that now raises on exhaustion.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as MD
from repro.serving import FixedSlotEngine, ServeEngine, SpeculativeEngine

PROMPTS = [[1, 2, 3], [7, 5], [9, 9, 9, 2], [4, 4, 1, 1, 5, 6, 7],
           [3, 1], list(range(1, 21))]  # mixed lengths incl. multi-chunk


def _tiny_cfg():
    cfg = get_config("qwen3-14b", reduced=True)
    return dataclasses.replace(cfg, num_layers=2, d_model=64, d_ff=128,
                               vocab_size=64, num_heads=2, num_kv_heads=1,
                               head_dim=32)


@pytest.fixture(scope="module")
def setup():
    cfg = _tiny_cfg()
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    # the plain-engine oracle streams, computed once
    plain = ServeEngine(params, cfg, max_batch=3, max_len=64, page_size=16,
                        prefill_chunk=4)
    reqs = [plain.submit(p, max_new_tokens=8) for p in PROMPTS]
    plain.run_until_drained()
    oracle = {tuple(r.prompt): list(r.generated) for r in reqs}
    return cfg, params, oracle


def _drain_spec(params, cfg, draft_params, oracle, *, spec_k,
                max_new=8, **kwargs):
    kwargs.setdefault("max_batch", 3)
    kwargs.setdefault("page_size", 16)
    kwargs.setdefault("prefill_chunk", 4)
    spec = SpeculativeEngine(params, cfg, draft_params, spec_k=spec_k,
                             max_len=64, **kwargs)
    reqs = [spec.submit(p, max_new_tokens=max_new) for p in PROMPTS]
    spec.run_until_drained()
    for r in reqs:
        assert r.done
        assert r.generated == oracle[tuple(r.prompt)], (
            spec_k, r.prompt, r.generated, oracle[tuple(r.prompt)])
    spec.sched.check_invariants()
    spec.sched.prefix.clear()  # only the prefix index may hold pages now
    assert spec.kv.allocator.in_use == 0
    return spec


@pytest.mark.parametrize("spec_k", [1, 3])
def test_identical_draft_bitmatches_and_accepts_all(setup, spec_k):
    """Draft == target: every proposal must be accepted (the draft cache
    completeness guarantee — see ``paged_draft_loop``'s extra write-only
    step) and streams must bit-match the plain engine."""
    cfg, params, oracle = setup
    spec = _drain_spec(params, cfg, params, oracle, spec_k=spec_k)
    assert spec.acceptance_rate == 1.0
    assert spec.stats["proposed"] > 0


def test_identical_draft_accepts_all_under_sampling(setup):
    """Rejection sampling with q == p accepts with probability
    min(1, p/q) = 1 — so an identical draft must keep acceptance at
    exactly 1.0 under stochastic sampling too (the T>0 generalisation of
    the greedy prefix-match guarantee; ``u * q(x) < p(x)`` holds for
    every u < 1 when the distributions are bitwise equal)."""
    import dataclasses as dc

    from repro.serving import SamplingParams

    cfg, params, _ = setup
    spec = SpeculativeEngine(params, cfg, params, spec_k=3, max_batch=3,
                             max_len=64, page_size=16, prefill_chunk=4)
    base = SamplingParams(temperature=1.2, top_k=8, top_p=0.9)
    reqs = [spec.submit(p, max_new_tokens=8,
                        sampling=dc.replace(base, seed=i))
            for i, p in enumerate(PROMPTS)]
    spec.run_until_drained()
    assert all(r.done for r in reqs)
    assert spec.stats["proposed"] > 0
    assert spec.acceptance_rate == 1.0
    spec.sched.check_invariants()
    spec.sched.prefix.clear()
    assert spec.kv.allocator.in_use == 0


def test_mixed_batch_t0_rows_stay_greedy(setup):
    """T=0 requests decoded *in the same batch* as T>0 requests take the
    sampled round program (the all-greedy fast path only fires when every
    active row is greedy) — and their streams must still bit-match the
    plain engine's greedy oracle.  This pins the sampled program's T=0
    degeneration (one-hot p/q → prefix-match accept, argmax
    residual/bonus), which the fast path would otherwise mask."""
    import dataclasses as dc

    from repro.serving import SamplingParams

    cfg, params, oracle = setup
    spec = SpeculativeEngine(params, cfg, params, spec_k=3, max_batch=3,
                             max_len=64, page_size=16, prefill_chunk=4)
    hot = SamplingParams(temperature=1.2, top_k=8, top_p=0.9)
    reqs = []
    for i, p in enumerate(PROMPTS):
        # alternate greedy / sampled so every decode batch mixes both
        sp = SamplingParams() if i % 2 == 0 else dc.replace(hot, seed=i)
        reqs.append(spec.submit(p, max_new_tokens=8, sampling=sp))
    spec.run_until_drained()
    assert all(r.done for r in reqs)
    mixed_rounds = spec.stats["rounds"]
    for i, r in enumerate(reqs):
        if i % 2 == 0:
            assert r.generated == oracle[tuple(r.prompt)], (
                i, r.prompt, r.generated, oracle[tuple(r.prompt)])
    assert mixed_rounds > 0
    spec.sched.check_invariants()
    spec.sched.prefix.clear()
    assert spec.kv.allocator.in_use == 0


def test_garbage_draft_still_bitmatches(setup):
    """A draft proposing near-random tokens costs throughput, never
    correctness: rejected proposals are replaced by the target's own
    greedy tokens."""
    cfg, params, oracle = setup
    garbage = MD.init_params(cfg, jax.random.PRNGKey(99))
    spec = _drain_spec(params, cfg, garbage, oracle, spec_k=3)
    assert spec.acceptance_rate < 0.5  # it really is a bad draft
    assert spec.mean_emitted_per_round >= 1.0  # bonus token floor


def test_bitmatches_under_eviction(setup):
    """An undersized page pool forces mid-decode eviction (host swap of
    BOTH caches) and speculative rollback under pressure — streams still
    bit-match, and every page comes back to the pool."""
    cfg, params, oracle = setup
    spec = _drain_spec(params, cfg, params, oracle, spec_k=3,
                       page_size=4, num_pages=9)
    assert spec.acceptance_rate == 1.0  # swap restores the draft cache too


def test_shared_prefix_bitmatches_cold_start(setup):
    """PR-8 tentpole on the speculative engine: admissions reusing cached
    prefix pages — including the COW clone that must cover BOTH the
    target and draft caches (one page table) — bit-match cold starts,
    with verify-window garbage writes and rollback in the mix."""
    cfg, params, _ = setup
    stem = [5, 1, 4, 1, 5, 9, 2, 6, 5, 3]
    prompts = [stem + [7, 7, 7], stem + [7, 7, 7], stem + [8, 8],
               stem[:6] + [9, 9, 9, 9], [2, 7, 1, 8, 2, 8]]

    def drain(prefix_cache):
        spec = SpeculativeEngine(params, cfg, params, spec_k=3, max_batch=2,
                                 max_len=64, page_size=4, prefill_chunk=4,
                                 prefix_cache=prefix_cache)
        reqs = [spec.submit(p, max_new_tokens=8) for p in prompts]
        spec.run_until_drained()
        return reqs, spec

    rw, warm = drain(True)
    rc, _ = drain(False)
    for w, c in zip(rw, rc):
        assert w.generated == c.generated, (w.prompt, w.generated,
                                            c.generated)
    assert warm.acceptance_rate == 1.0  # identical draft stays complete
    warm.sched.check_invariants()
    warm.sched.prefix.clear()
    assert warm.kv.allocator.in_use == 0
    assert not warm._draft_host


def test_shared_prefix_bitmatches_under_eviction(setup):
    """Prefix reuse + undersized pool on the speculative engine: index
    eviction, host swap of both caches and rollback all interleave —
    streams must still bit-match the cold engine under the same pool."""
    cfg, params, _ = setup
    stem = [5, 1, 4, 1, 5, 9, 2, 6]
    prompts = [stem + [7, 7], stem + [7, 7], stem + [8], stem[:5] + [9, 9]]

    def drain(prefix_cache):
        spec = SpeculativeEngine(params, cfg, params, spec_k=2, max_batch=2,
                                 max_len=32, page_size=4, prefill_chunk=4,
                                 num_pages=10, prefix_cache=prefix_cache)
        reqs = [spec.submit(p, max_new_tokens=6) for p in prompts]
        spec.run_until_drained()
        spec.sched.check_invariants()
        return reqs

    for w, c in zip(drain(True), drain(False)):
        assert w.generated == c.generated, (w.prompt, w.generated,
                                            c.generated)


def test_cancellation(setup):
    cfg, params, oracle = setup
    spec = SpeculativeEngine(params, cfg, params, spec_k=3, max_batch=1,
                             max_len=64, page_size=16, prefill_chunk=4)
    a = spec.submit([1, 2, 3], max_new_tokens=6)
    b = spec.submit([7, 5], max_new_tokens=8)     # waits behind a
    c = spec.submit([9, 9, 9, 2], max_new_tokens=6)
    assert spec.cancel(c.uid)         # cancel while queued
    spec.step()
    assert spec.cancel(a.uid)         # cancel while active
    spec.run_until_drained()
    assert a.cancelled and c.cancelled and not b.cancelled
    assert b.generated == oracle[(7, 5)]
    assert not spec.cancel(b.uid)
    spec.sched.prefix.clear()
    assert spec.kv.allocator.in_use == 0
    assert not spec._draft_host       # no leaked swap copies


def test_eos_stops_early(setup):
    """eos inside an accepted window truncates emission exactly where the
    plain engine would stop."""
    cfg, params, oracle = setup
    stream = oracle[(1, 2, 3)]
    eos = stream[2]
    plain = ServeEngine(params, cfg, max_batch=1, max_len=64)
    rp = plain.submit([1, 2, 3], max_new_tokens=8, eos_id=eos)
    plain.run_until_drained()
    spec = SpeculativeEngine(params, cfg, params, spec_k=4, max_batch=1,
                             max_len=64, page_size=16, prefill_chunk=4)
    rs = spec.submit([1, 2, 3], max_new_tokens=8, eos_id=eos)
    spec.run_until_drained()
    assert rs.generated == rp.generated
    assert rs.generated[-1] == eos and len(rs.generated) == 3


def test_request_telemetry(setup):
    cfg, params, oracle = setup
    spec = _drain_spec(params, cfg, params, oracle, spec_k=3)
    for key in ("rounds", "proposed", "accepted", "emitted"):
        assert spec.stats[key] > 0
    assert spec.stats["accepted"] <= spec.stats["proposed"]
    # rounds emit everything except each request's first token (that one
    # comes from the prefill logits, exactly like the plain engine)
    total_emitted = sum(len(v) for v in oracle.values())
    assert spec.stats["emitted"] == total_emitted - len(oracle)
    # per-request counters roll up to the engine totals
    # (requests are drained inside _drain_spec's engine; recompute)
    spec2 = SpeculativeEngine(params, cfg, params, spec_k=3, max_batch=2,
                              max_len=64, page_size=16, prefill_chunk=4)
    r = spec2.submit([1, 2, 3], max_new_tokens=8)
    spec2.run_until_drained()
    assert r.spec_rounds == spec2.stats["rounds"]
    assert r.spec_accepted == r.spec_proposed  # identical draft
    assert r.acceptance_rate == 1.0


def test_spec_counter_conservation(setup):
    """Every token a speculative round emits is exactly one of: an
    accepted proposal, the residual correction on a rejection, or the
    full-acceptance bonus draw — so ``emitted == accepted + corrections
    + bonuses`` must hold (PR-7 fixed the asymmetry where ``emitted``
    alone accounted for eos truncation, which let the identity drift)."""
    cfg, params, oracle = setup

    def conserve(s):
        assert s["emitted"] == (s["accepted"] + s["corrections"]
                                + s["bonuses"]), s

    # garbage draft: plenty of rejections → correction tokens
    garbage = MD.init_params(cfg, jax.random.PRNGKey(99))
    bad = _drain_spec(params, cfg, garbage, oracle, spec_k=3)
    assert bad.stats["corrections"] > 0
    conserve(bad.stats)
    # identical draft: full acceptance → bonus tokens, no corrections
    good = _drain_spec(params, cfg, params, oracle, spec_k=3)
    assert good.stats["bonuses"] > 0 and good.stats["corrections"] == 0
    conserve(good.stats)
    # eos truncating an accepted window mid-emission: the identity must
    # still hold — only tokens that actually landed are counted
    eos = oracle[(1, 2, 3)][2]
    spec = SpeculativeEngine(params, cfg, params, spec_k=4, max_batch=1,
                             max_len=64, page_size=16, prefill_chunk=4)
    r = spec.submit([1, 2, 3], max_new_tokens=8, eos_id=eos)
    spec.run_until_drained()
    assert r.generated[-1] == eos and len(r.generated) == 3
    s = spec.stats
    conserve(s)
    # prefill emitted the first token; the (truncated) round emitted the
    # other two, stopping inside the accepted prefix — so no bonus draw
    assert s["emitted"] == 2 and s["bonuses"] == 0


def test_validation(setup):
    cfg, params, _ = setup
    with pytest.raises(ValueError, match="spec_k"):
        SpeculativeEngine(params, cfg, params, spec_k=0)
    bad_cfg = dataclasses.replace(cfg, num_kv_heads=2, num_heads=2)
    bad = MD.init_params(bad_cfg, jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="geometry"):
        SpeculativeEngine(params, cfg, bad, draft_cfg=bad_cfg)
    with pytest.raises(NotImplementedError, match="mesh"):
        SpeculativeEngine(params, cfg, params, mesh=object())


# ---------------------------------------------------------------------------
# Golden tokens: the bundle's int8 target through the speculative engine
# must reproduce the checked-in streams of tests/golden/serving_tokens.json.
# ---------------------------------------------------------------------------


def test_golden_streams_through_bundle(tmp_path):
    from repro.compiler import compile_lm_amm, compile_lm_bundle
    from tests.test_serving_golden import GOLDEN_PATH, MAX_NEW
    from tests.test_serving_golden import PROMPTS as GOLDEN_PROMPTS

    if not GOLDEN_PATH.is_file():
        pytest.skip("golden file not generated yet")
    cfg = _tiny_cfg()
    cfg = dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, enabled=True))
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    calib = np.random.default_rng(0).integers(0, 64, (4, 16))
    bundle = compile_lm_bundle(params, cfg, calib, target_resolution="int8",
                               draft_resolution="int4", spec_k=3,
                               out=str(tmp_path / "bundle"))
    # the bundle's target half IS the PR-2 compiler's int8 artifact,
    # tensor-for-tensor (one calibration, resolution-separable quantise)
    amm = compile_lm_amm(params, cfg, calib)
    assert set(bundle.target.tensors) == set(amm.artifact.tensors)
    for k_ in bundle.target.tensors:
        np.testing.assert_array_equal(bundle.target.tensors[k_],
                                      amm.artifact.tensors[k_])

    from repro.serving import load_engine
    eng = load_engine(tmp_path / "bundle", params, cfg, max_batch=2,
                      max_len=64, page_size=16, prefill_chunk=4)
    assert isinstance(eng, SpeculativeEngine)  # kind sniffed from manifest
    assert eng.spec_k == 3  # manifest-recorded suggestion
    reqs = [eng.submit(p, max_new_tokens=MAX_NEW) for p in GOLDEN_PROMPTS]
    eng.run_until_drained()
    streams = {",".join(map(str, r.prompt)): r.generated for r in reqs}
    golden = json.loads(GOLDEN_PATH.read_text())
    assert streams == golden, (
        "speculative streams drifted from tests/golden/serving_tokens.json")


def test_bundle_loading_guards(tmp_path):
    from repro.compiler import ArtifactError, compile_lm_bundle, load_artifact
    from repro.compiler.artifact import load_bundle

    cfg = _tiny_cfg()
    cfg = dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, enabled=True))
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    calib = np.random.default_rng(0).integers(0, 64, (2, 8))
    out = tmp_path / "b"
    compile_lm_bundle(params, cfg, calib, out=str(out))
    with pytest.raises(ArtifactError, match="load_bundle"):
        load_artifact(out)  # a bundle is not a tensor artifact
    t, d, manifest = load_bundle(out)
    assert t.resolution == "int8" and d.resolution == "int4"
    assert manifest["spec_k"] == 4
    # swapping a half behind the manifest's back must be detected
    (out / "draft" / "tensors.npz").write_bytes(
        (out / "target" / "tensors.npz").read_bytes())
    with pytest.raises(ArtifactError):
        load_bundle(out)


# ---------------------------------------------------------------------------
# PR-9 tentpole: the fused layer-major verify window must be bit-identical
# to the scan oracle through the whole engine, on the int8 KV path, under
# every schedule the scan path is pinned against (identical draft, garbage
# draft, eos truncation inside the window, eviction mid-stream).
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup_int8():
    """Like ``setup`` but with the int8-quantised KV cache — the path where
    the fused window's blockwise int32 accumulation is provably exact."""
    base = _tiny_cfg()
    cfg = dataclasses.replace(
        base, amm=dataclasses.replace(base.amm, enabled=True, kv_int8=True))
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    plain = ServeEngine(params, cfg, max_batch=3, max_len=64, page_size=16,
                        prefill_chunk=4)
    assert plain.kv.buffers["k"].dtype == jnp.int8  # really the int8 path
    reqs = [plain.submit(p, max_new_tokens=8) for p in PROMPTS]
    plain.run_until_drained()
    oracle = {tuple(r.prompt): list(r.generated) for r in reqs}
    return cfg, params, oracle


@pytest.mark.parametrize("backend", ["scan", "fused"])
def test_verify_backend_identical_draft_int8(setup_int8, backend):
    """Both verify backends bit-match the plain int8 engine and keep the
    identical-draft full-acceptance guarantee."""
    cfg, params, oracle = setup_int8
    spec = _drain_spec(params, cfg, params, oracle, spec_k=3,
                       verify_backend=backend)
    assert spec.verify_backend == backend
    assert spec.acceptance_rate == 1.0


@pytest.mark.parametrize("backend", ["scan", "fused"])
def test_verify_backend_garbage_draft_int8(setup_int8, backend):
    """Garbage drafts reject most of the window — the fused path's
    rollback/garbage-write handling must still bit-match."""
    cfg, params, oracle = setup_int8
    garbage = MD.init_params(cfg, jax.random.PRNGKey(99))
    spec = _drain_spec(params, cfg, garbage, oracle, spec_k=3,
                       verify_backend=backend)
    assert spec.acceptance_rate < 0.5


@pytest.mark.parametrize("backend", ["scan", "fused"])
def test_verify_backend_eos_truncated_window_int8(setup_int8, backend):
    """eos inside an accepted window truncates emission at the same token
    under both backends (the window past eos is written then rolled back)."""
    cfg, params, oracle = setup_int8
    stream = oracle[(1, 2, 3)]
    eos = stream[2]
    spec = SpeculativeEngine(params, cfg, params, spec_k=4, max_batch=1,
                             max_len=64, page_size=16, prefill_chunk=4,
                             verify_backend=backend)
    r = spec.submit([1, 2, 3], max_new_tokens=8, eos_id=eos)
    spec.run_until_drained()
    assert r.generated == stream[:3] and r.generated[-1] == eos


@pytest.mark.parametrize("backend", ["scan", "fused"])
def test_verify_backend_eviction_int8(setup_int8, backend):
    """Undersized pool: host swap of both caches and speculative rollback
    interleave with the fused window's batched page scatter."""
    cfg, params, oracle = setup_int8
    spec = _drain_spec(params, cfg, params, oracle, spec_k=3,
                       page_size=4, num_pages=9, verify_backend=backend)
    assert spec.acceptance_rate == 1.0


def test_verify_backend_resolution(setup, monkeypatch):
    """'auto' honours REPRO_VERIFY_BACKEND, defaults to fused, and rejects
    unknown names at the engine boundary."""
    cfg, params, _ = setup
    monkeypatch.delenv("REPRO_VERIFY_BACKEND", raising=False)
    assert MD.resolve_verify_backend("auto") == "fused"
    assert MD.resolve_verify_backend("scan") == "scan"
    monkeypatch.setenv("REPRO_VERIFY_BACKEND", "scan")
    assert MD.resolve_verify_backend("auto") == "scan"
    monkeypatch.delenv("REPRO_VERIFY_BACKEND", raising=False)
    with pytest.raises(ValueError, match="verify backend"):
        MD.resolve_verify_backend("jit")
    with pytest.raises(ValueError, match="verify backend"):
        SpeculativeEngine(params, cfg, params, spec_k=2, max_batch=1,
                          max_len=64, verify_backend="nope")
    eng = ServeEngine(params, cfg, max_batch=1, max_len=64,
                      verify_backend="scan")
    assert eng.verify_backend == "scan"


# ---------------------------------------------------------------------------
# Satellite: unified run_until_drained budgets that fail loudly.
# ---------------------------------------------------------------------------


def test_run_until_drained_exhaustion_raises(setup):
    cfg, params, _ = setup
    eng = ServeEngine(params, cfg, max_batch=1, max_len=64)
    eng.submit([1, 2, 3], max_new_tokens=8)
    with pytest.raises(RuntimeError, match="steps exhausted"):
        eng.run_until_drained(max_steps=2)
    eng.run_until_drained()  # default budget drains fine

    ssm = get_config("mamba2-370m", reduced=True)
    fixed = FixedSlotEngine(MD.init_params(ssm, jax.random.PRNGKey(0)), ssm,
                            max_batch=1, max_len=32)
    fixed.submit([1, 2, 3], max_new_tokens=4)
    assert fixed.has_work
    with pytest.raises(RuntimeError, match="steps exhausted"):
        fixed.run_until_drained(max_steps=1)
    # both engines share one default budget now (the PR-4 engines diverged
    # at 10000 vs 1000, silently truncating long fixed-slot workloads)
    import inspect

    assert (inspect.signature(FixedSlotEngine.run_until_drained)
            .parameters["max_steps"].default ==
            inspect.signature(ServeEngine.run_until_drained)
            .parameters["max_steps"].default == 10000)
    fixed.run_until_drained()
    assert not fixed.has_work
