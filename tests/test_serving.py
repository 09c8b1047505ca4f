"""Serving engine tests.

The load-bearing property: **every** engine (fixed-slot, paged
continuous-batching, paged under page-pressure eviction) produces token
streams bit-identical to sequential one-request-at-a-time decode — and the
paged and fixed-slot engines bit-match *each other* on the same request
set, including on the int-LUT AMM path (the PR-4 acceptance criterion).
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import model as MD
from repro.serving import FixedSlotEngine, ServeEngine, make_engine


def _tiny_cfg(amm=False):
    cfg = get_config("qwen3-14b", reduced=True)
    cfg = dataclasses.replace(cfg, num_layers=2, d_model=64, d_ff=128,
                              vocab_size=64, num_heads=2, num_kv_heads=1,
                              head_dim=32)
    if amm:
        cfg = dataclasses.replace(
            cfg, amm=dataclasses.replace(cfg.amm, enabled=True))
    return cfg


@pytest.fixture(scope="module")
def setup():
    cfg = _tiny_cfg()
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def setup_amm():
    """int8-LUT AMM serving params — the paper's unit on the decode path."""
    cfg = _tiny_cfg(amm=True)
    params = MD.init_params(cfg, jax.random.PRNGKey(0), serving=True)
    return cfg, params


def _reference_generate(params, cfg, prompt, n_new):
    tokens = jnp.asarray(prompt, jnp.int32)[None]
    logits, cache = MD.prefill(params, tokens, cfg, 64,
                               compute_dtype=jnp.float32)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        lg, cache = MD.decode_step(params, jnp.asarray([[out[-1]]], jnp.int32),
                                   jnp.asarray(pos, jnp.int32), cache, cfg,
                                   compute_dtype=jnp.float32)
        out.append(int(jnp.argmax(lg[0, -1])))
        pos += 1
    return out


def test_engine_matches_sequential(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_batch=2, max_len=64)
    prompts = [[1, 2, 3], [7, 5], [9, 9, 9, 2]]
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    done = eng.run_until_drained()
    assert len(done) == 3
    for p, r in zip(prompts, reqs):
        assert r.done
        ref = _reference_generate(params, cfg, p, 6)
        assert r.generated == ref, (p, r.generated, ref)


def test_engine_more_requests_than_slots(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, slots=2, max_len=64)  # slots alias
    reqs = [eng.submit([i + 1, i + 2], max_new_tokens=4) for i in range(5)]
    done = eng.run_until_drained()
    assert len(done) == 5
    assert all(len(r.generated) == 4 for r in reqs)


def test_engine_eos_stops_early(setup):
    cfg, params = setup
    ref = _reference_generate(params, cfg, [1, 2, 3], 8)
    eos = ref[2]  # force an early stop at the 3rd generated token
    eng = ServeEngine(params, cfg, max_batch=1, max_len=64)
    r = eng.submit([1, 2, 3], max_new_tokens=8, eos_id=eos)
    eng.run_until_drained()
    assert r.generated[-1] == eos
    assert len(r.generated) == 3


# ---------------------------------------------------------------------------
# Differential: paged continuous batching vs the fixed-slot oracle.
# ---------------------------------------------------------------------------

PROMPTS = [[1, 2, 3], [7, 5], [9, 9, 9, 2], [4, 4, 1, 1, 5, 6, 7],
           [3, 1], list(range(1, 21))]  # mixed lengths incl. multi-chunk


def _drain_both(params, cfg, *, paged_kwargs, submit_kwargs=None):
    """The PROMPTS through the fixed-slot oracle and the paged engine;
    ``submit_kwargs`` holds each prompt's extra ``submit`` options."""
    extra = submit_kwargs or [{}] * len(PROMPTS)
    fixed = FixedSlotEngine(params, cfg, slots=2, max_len=64)
    rf = [fixed.submit(p, max_new_tokens=8, **kw)
          for p, kw in zip(PROMPTS, extra)]
    fixed.run_until_drained()
    paged = ServeEngine(params, cfg, max_len=64, **paged_kwargs)
    rp = [paged.submit(p, max_new_tokens=8, **kw)
          for p, kw in zip(PROMPTS, extra)]
    paged.run_until_drained()
    return rf, rp, paged


@pytest.mark.parametrize("amm", [False, True], ids=["dense", "int-lut"])
def test_paged_bitmatches_fixed_slot(setup, setup_amm, amm):
    """The acceptance criterion: same request set through both engines →
    bit-identical token streams (chunked prefill included), dense and
    int-LUT decode paths."""
    cfg, params = setup_amm if amm else setup
    rf, rp, _ = _drain_both(params, cfg,
                            paged_kwargs=dict(max_batch=3, page_size=16,
                                              prefill_chunk=4))
    for f, p in zip(rf, rp):
        assert f.done and p.done
        assert f.generated == p.generated, (f.prompt, f.generated, p.generated)


def test_paged_bitmatches_under_eviction(setup):
    """A page pool too small for the workload forces mid-decode eviction
    (host swap) — streams must still bit-match the fixed-slot engine."""
    cfg, params = setup
    rf, rp, paged = _drain_both(
        params, cfg, paged_kwargs=dict(max_batch=3, page_size=4,
                                       prefill_chunk=4, num_pages=7))
    for f, p in zip(rf, rp):
        assert f.generated == p.generated, (f.prompt, f.generated, p.generated)
    # retired prompts stay referenced by the prefix index (that's the
    # point); clearing it must return every page to the pool
    held = set(paged.sched.prefix.pages_held())
    assert paged.kv.allocator.in_use == len(held)
    paged.sched.check_invariants()
    paged.sched.prefix.clear()
    assert paged.kv.allocator.in_use == 0  # every page returned


# ---------------------------------------------------------------------------
# One decode in flight: the paged engine reads a decode's tokens in the
# next step, after that step's decode is queued on the device.
# ---------------------------------------------------------------------------

# three rows, pages larger than any prompt: no eviction
IN_FLIGHT = dict(max_batch=3, page_size=16, prefill_chunk=4)


def test_eos_in_flight_discards_one_row_step(setup):
    """A request's EOS lands while its next decode is already queued: its
    stream ends at the EOS, the same as the fixed-slot oracle's, and the
    row-step decoded after it is dropped and counted as discarded."""
    from repro.serving import Recorder

    cfg, params = setup
    ref = _reference_generate(params, cfg, PROMPTS[3], 8)
    # a token a decode emits (not the prefill) for the first time
    i = next(i for i in range(1, 7) if ref[i] not in ref[:i])
    extra = [{}] * len(PROMPTS)
    extra[3] = {"eos_id": ref[i]}
    rec = Recorder(trace=False)
    rf, rp, _ = _drain_both(params, cfg,
                            paged_kwargs=dict(IN_FLIGHT, recorder=rec),
                            submit_kwargs=extra)
    assert [r.generated for r in rp] == [r.generated for r in rf]
    assert rp[3].generated == ref[: i + 1]
    v = rec.registry.value
    assert v("serve_tokens_discarded_total", reason="eos") == 1
    assert v("serve_tokens_discarded_total", reason="cancel") == 0


def test_cancel_with_its_decode_in_flight(setup):
    """Cancelling a request whose decode is in flight drops that token
    (counted as discarded): the request keeps the tokens that had
    landed, a prefix of its stream, and every other stream bit-matches
    the fixed-slot oracle."""
    from repro.serving import Recorder

    cfg, params = setup
    fixed = FixedSlotEngine(params, cfg, slots=2, max_len=64)
    rf = [fixed.submit(p, max_new_tokens=8) for p in PROMPTS]
    fixed.run_until_drained()
    rec = Recorder(trace=False)
    paged = ServeEngine(params, cfg, max_len=64, recorder=rec, **IN_FLIGHT)
    rp = [paged.submit(p, max_new_tokens=8) for p in PROMPTS]
    victim = rp[0]
    while len(victim.generated) < 2 or not victim.inflight:
        paged.step()
    landed = list(victim.generated)
    assert paged.cancel(victim.uid)
    paged.run_until_drained()
    assert victim.cancelled and victim.generated == landed
    assert landed == rf[0].generated[: len(landed)]
    for f, p in zip(rf[1:], rp[1:]):
        assert f.generated == p.generated, (f.prompt, f.generated,
                                            p.generated)
    v = rec.registry.value
    assert v("serve_tokens_discarded_total", reason="cancel") == 1
    assert v("serve_tokens_discarded_total", reason="eos") == 0
    assert not paged.has_work


def test_paged_bitmatches_fixed_slot_mixed_sampling(setup):
    """A batch of greedy and sampled rows: with each sampled row's input
    token taken from the decode in flight and its step counter counting
    that token, every stream matches the fixed-slot oracle for the same
    seeds."""
    from repro.serving import SamplingParams

    cfg, params = setup
    extra = [{"sampling": SamplingParams(temperature=0.8, top_k=8,
                                         top_p=0.9, seed=10 + i)}
             if i % 2 else {} for i in range(len(PROMPTS))]
    rf, rp, _ = _drain_both(params, cfg, paged_kwargs=IN_FLIGHT,
                            submit_kwargs=extra)
    for f, p in zip(rf, rp):
        assert f.generated == p.generated, (f.prompt, f.generated,
                                            p.generated)
        assert len(p.generated) == 8


def test_paged_bitmatches_with_swaps_in_flight(setup):
    """A pool too small for the workload swaps requests out while their
    token is in flight (the swap-out copy waits for that decode's cache
    writes) and back in later: streams still bit-match the fixed-slot
    oracle."""
    from repro.serving import Recorder

    swaps = []

    class Rec(Recorder):
        def on_evict(self, req, kind):
            super().on_evict(req, kind)
            if kind == "swap":
                swaps.append(req.inflight)

    cfg, params = setup
    rec = Rec(trace=False)
    rf, rp, paged = _drain_both(
        params, cfg, paged_kwargs=dict(max_batch=3, page_size=4,
                                       prefill_chunk=4, num_pages=7,
                                       recorder=rec))
    for f, p in zip(rf, rp):
        assert f.generated == p.generated, (f.prompt, f.generated,
                                            p.generated)
    assert any(swaps), "no request was swapped out with a token in flight"
    assert rec.registry.value("serve_resumed_total") == len(swaps)
    paged.sched.check_invariants()


def test_cancellation(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_batch=1, max_len=64, prefill_chunk=4)
    a = eng.submit([1, 2, 3], max_new_tokens=6)
    b = eng.submit([7, 5], max_new_tokens=6)      # waits behind a
    c = eng.submit([9, 9, 9, 2], max_new_tokens=6)
    assert eng.cancel(c.uid)          # cancel while queued
    eng.step()
    assert eng.cancel(a.uid)          # cancel while active
    eng.run_until_drained()
    assert a.cancelled and c.cancelled and not b.cancelled
    assert b.generated == _reference_generate(params, cfg, [7, 5], 6)
    assert not eng.cancel(b.uid)      # finished → not cancellable
    # prefilled prompts stay referenced by the prefix index; clearing it
    # must account for every page still out of the pool
    eng.sched.prefix.clear()
    assert eng.kv.allocator.in_use == 0


def test_priority_admission(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_batch=1, max_len=64)
    lo = eng.submit([1, 2, 3], max_new_tokens=3)
    hi = eng.submit([7, 5], max_new_tokens=3, priority=5)
    order = []
    while eng.has_work:
        for r in eng.step():
            order.append(r.uid)
    # with one row, the high-priority request must finish first
    assert order == [hi.uid, lo.uid]


def test_submit_validation(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_batch=1, max_len=16, page_size=4,
                      num_pages=2)
    with pytest.raises(ValueError, match="empty"):
        eng.submit([])
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(list(range(20)))
    with pytest.raises(ValueError, match="never"):
        eng.submit([1, 2, 3], max_new_tokens=12)  # needs 4 pages, pool has 2


def test_make_engine_family_fallback(setup):
    cfg, params = setup
    assert isinstance(make_engine(params, cfg, max_batch=2, max_len=64),
                      ServeEngine)
    ssm = get_config("mamba2-370m", reduced=True)
    assert not MD.supports_paged(ssm)
    with pytest.raises(ValueError, match="FixedSlotEngine"):
        ServeEngine(params, ssm)
    ssm_params = MD.init_params(ssm, jax.random.PRNGKey(0))
    eng = make_engine(ssm_params, ssm, max_batch=8, max_len=32,
                      page_size=4, prefill_chunk=4)
    assert isinstance(eng, FixedSlotEngine)
    assert eng.slots == 8  # max_batch maps to slots, not dropped


# ---------------------------------------------------------------------------
# Differential: prefix-sharing reuse vs cold start (the PR-8 tentpole).
# ---------------------------------------------------------------------------

# a 10-token common stem, then: identical, diverge mid-page, diverge on a
# page boundary, short prompt inside the stem, unrelated
STEM = [5, 1, 4, 1, 5, 9, 2, 6, 5, 3]
SHARED_PROMPTS = [STEM + [7, 7, 7],
                  STEM + [7, 7, 7],          # exact repeat
                  STEM + [8, 8],             # diverges after the stem
                  STEM[:6] + [9, 9, 9, 9],   # diverges mid-stem
                  STEM[:4],                  # prompt inside the stem
                  [2, 7, 1, 8, 2, 8]]        # no shared prefix


def _drain_prefix_pair(params, cfg, **paged_kwargs):
    from repro.serving import Recorder

    rec = Recorder()
    warm = ServeEngine(params, cfg, max_len=64, prefix_cache=True,
                       recorder=rec, **paged_kwargs)
    rw = [warm.submit(p, max_new_tokens=8) for p in SHARED_PROMPTS]
    warm.run_until_drained()
    cold = ServeEngine(params, cfg, max_len=64, prefix_cache=False,
                       **paged_kwargs)
    rc = [cold.submit(p, max_new_tokens=8) for p in SHARED_PROMPTS]
    cold.run_until_drained()
    return rw, rc, warm, rec


def test_shared_prefix_bitmatches_cold_start(setup):
    """The tentpole acceptance criterion: admissions that map cached
    prefix pages (read-only full pages + one COW-cloned partial page)
    produce streams bit-identical to prefilling from scratch."""
    cfg, params = setup
    rw, rc, warm, rec = _drain_prefix_pair(params, cfg, max_batch=2,
                                           page_size=4, prefill_chunk=4)
    for w, c in zip(rw, rc):
        assert w.generated == c.generated, (w.prompt, w.generated,
                                            c.generated)
    v = rec.registry.value
    assert v("serve_prefix_lookups_total", result="hit") > 0
    assert v("serve_prefix_reused_tokens_total") > 0
    assert v("serve_cow_clones_total") > 0  # mid-page divergence clones
    warm.sched.check_invariants()


def test_shared_prefix_bitmatches_under_eviction(setup):
    """Prefix reuse under page pressure: the pool is too small for the
    workload, so admissions race index eviction and host swap — streams
    must still bit-match a cold engine with the same (tight) pool."""
    cfg, params = setup
    rw, rc, warm, _ = _drain_prefix_pair(params, cfg, max_batch=2,
                                         page_size=4, prefill_chunk=4,
                                         num_pages=10)
    for w, c in zip(rw, rc):
        assert w.generated == c.generated, (w.prompt, w.generated,
                                            c.generated)
    warm.sched.check_invariants()


def test_shared_prefix_with_cancellation(setup):
    """Cancelling a sharer must not corrupt the cached prefix other
    requests keep reading: survivors still bit-match cold streams."""
    cfg, params = setup
    cold = ServeEngine(params, cfg, max_batch=2, max_len=64, page_size=4,
                       prefill_chunk=4, prefix_cache=False)
    ref = cold.submit(SHARED_PROMPTS[0], max_new_tokens=8)
    cold.run_until_drained()

    warm = ServeEngine(params, cfg, max_batch=2, max_len=64, page_size=4,
                       prefill_chunk=4, prefix_cache=True)
    warm.submit(SHARED_PROMPTS[0], max_new_tokens=8)
    warm.run_until_drained()
    # two sharers admitted together; kill one mid-flight
    a = warm.submit(SHARED_PROMPTS[0], max_new_tokens=8)
    b = warm.submit(SHARED_PROMPTS[1], max_new_tokens=8)
    warm.step()
    assert warm.cancel(a.uid)
    warm.run_until_drained()
    assert b.generated == ref.generated
    warm.sched.check_invariants()


def test_prefix_index_match_semantics(setup):
    """Unit-level: coverage is capped at len(prompt)-1, partial matches
    report the page to clone, and inserts only ref newly created nodes."""
    from repro.serving import PageAllocator, RadixPrefixIndex

    alloc = PageAllocator(16)
    idx = RadixPrefixIndex(alloc, page_size=4)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
    pages = alloc.alloc(3)
    idx.insert(prompt, pages)
    assert len(idx) == 3

    # exact repeat: 9 of 10 tokens covered (cap), 2 full pages + partial
    full, partial, covered = idx.match(list(prompt))
    assert covered == 9 and full == pages[:2]
    assert partial == (pages[2], 1)
    # divergence after one full page: page 0 read-only, page 1 cloned
    full, partial, covered = idx.match([1, 2, 3, 4, 5, 6, 99, 99])
    assert covered == 6 and full == [pages[0]]
    assert partial == (pages[1], 2)
    # prompt strictly inside the first page: clone with rem = len-1
    full, partial, covered = idx.match([1, 2, 3])
    assert covered == 2 and full == []
    assert partial == (pages[0], 2)
    # no match
    assert idx.match([9, 8, 7]) == ([], None, 0)
    # re-inserting the same prompt adds no nodes and no refs
    before = [alloc.refcount(p) for p in pages]
    assert idx.insert(prompt, pages) == 0
    assert [alloc.refcount(p) for p in pages] == before


def test_page_pool_pads_to_dp_degree(setup):
    """The physical page axis (pool + trash) rounds up to the DP degree so
    pages-over-DP sharding activates for any pool size; the trash page is
    always the last physical page."""
    from repro.serving import PagedKVCache

    cfg, _ = setup
    kv = PagedKVCache(cfg, num_pages=8, page_size=4, pad_to=2)
    assert kv.buffers["k"].shape[1] == 10  # 8 pool + 1 trash → padded to 10
    assert kv.trash == 9
    assert kv.allocator.num_pages == 8
    kv1 = PagedKVCache(cfg, num_pages=8, page_size=4)
    assert kv1.buffers["k"].shape[1] == 9 and kv1.trash == 8
