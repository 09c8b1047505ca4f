"""Observability suite (PR 7 core + PR 10 deep observability).

The load-bearing property: recording is **observation only** — engines
driven with a live :class:`~repro.serving.obs.Recorder` must emit token
streams bit-identical to the same engines with recording off, through
the paged, fixed-slot and speculative paths, including under
page-pressure eviction.  The same holds for the deep-observability
layers — the approximation-quality probe (``serving/quality.py``) and
the SLO health tracker — and for the engines' step spans with a
``jax.profiler`` session collecting; the step records themselves must be
well-formed.  Plus the
subsystem's own contracts: the Prometheus exposition parses (hostile
label values included), the Chrome trace is schema-valid with sorted
non-overlapping spans per request lane, the ``NullRecorder`` default is
a guaranteed no-op, and ``REPRO_LOG`` drives the leveled logger.
"""
import dataclasses
import json
from collections import deque

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as MD
from repro.serving import (NULL_RECORDER, FixedSlotEngine,
                           MetricsRegistry, NullRecorder, QualityProbe,
                           Recorder, SamplingParams, ServeEngine,
                           SloThresholds, SloTracker, SpeculativeEngine,
                           load_engine, slo_report, validate_chrome_trace,
                           validate_prometheus)
from repro.serving.obs import (STEP_PHASES, STEP_RING, STEP_SPAN, Counter,
                               Histogram, Tracer, log, log_enabled,
                               summary_table)

PROMPTS = [[1, 2, 3], [7, 5], [9, 9, 9, 2], [4, 4, 1, 1, 5, 6, 7],
           [3, 1], list(range(1, 21))]  # the PR-4 differential workload

# the PR-4 eviction workload: a pool too small for the request set, so
# recording must survive (and observe) host swap without changing streams
# (7 pages: with one decode in flight, 9 no longer runs dry)
EVICT_KWARGS = dict(max_batch=3, page_size=4, prefill_chunk=4, num_pages=7)


def _tiny_cfg():
    cfg = get_config("qwen3-14b", reduced=True)
    return dataclasses.replace(cfg, num_layers=2, d_model=64, d_ff=128,
                               vocab_size=64, num_heads=2, num_kv_heads=1,
                               head_dim=32)


@pytest.fixture(scope="module")
def setup():
    cfg = _tiny_cfg()
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# Registry / exporter units.
# ---------------------------------------------------------------------------


def test_counter_is_monotonic():
    c = Counter("x_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="decrease"):
        c.inc(-1)


def test_histogram_buckets_and_quantiles():
    h = Histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    assert h.count == 4 and h.counts == [1, 2, 1, 0]
    assert h.sum == pytest.approx(6.05)
    assert h.mean == pytest.approx(6.05 / 4)
    assert 0.1 <= h.quantile(0.5) <= 1.0   # median falls in (0.1, 1.0]
    assert h.quantile(0.99) > 1.0
    h.observe(100.0)                        # lands in +Inf
    assert h.counts[-1] == 1
    with pytest.raises(ValueError, match="sorted"):
        Histogram("bad", buckets=(1.0, 0.1))


def test_registry_prometheus_exposition():
    r = MetricsRegistry()
    r.counter("req_total", "requests", kind="a").inc(3)
    r.counter("req_total", "requests", kind="b").inc()
    r.gauge("pool_free", "free pages").set(7)
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = r.to_prometheus()
    assert validate_prometheus(text) == []
    assert '# TYPE req_total counter' in text
    assert 'req_total{kind="a"} 3' in text
    assert 'pool_free 7' in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert 'lat_seconds_count 2' in text
    # same value through the read API
    assert r.value("req_total", kind="a") == 3
    assert r.sum_values("req_total") == 4
    # one name cannot be two metric types
    with pytest.raises(ValueError, match="registered"):
        r.gauge("req_total")


def test_validators_reject_malformed():
    assert validate_prometheus("9bad_name 1\n")
    assert validate_prometheus("x_total nan-ish\n")
    assert validate_chrome_trace({}) == ["missing traceEvents key"]
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0},
        {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 5.0, "dur": 10.0},
    ]}
    assert any("overlaps" in e for e in validate_chrome_trace(bad))
    unsorted = {"traceEvents": [
        {"name": "a", "ph": "i", "s": "t", "pid": 1, "tid": 1, "ts": 5.0},
        {"name": "b", "ph": "i", "s": "t", "pid": 1, "tid": 1, "ts": 1.0},
    ]}
    assert any("sorted" in e for e in validate_chrome_trace(unsorted))


def test_tracer_lanes_and_export():
    fake = [0.0]

    def clock():
        fake[0] += 1.0
        return fake[0]

    tr = Tracer(clock=clock)
    tr.span(1, "queued", 2.0, 3.0)
    tr.span(Tracer.ENGINE_TID, "decode", 3.0, 4.0, rows=2)
    obj = tr.to_chrome()
    assert validate_chrome_trace(obj) == []
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M"}
    assert names == {"engine", "req 0"}  # tid 1 is request uid 0
    spans = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert [s["name"] for s in spans] == ["queued", "decode"]
    assert spans[1]["args"]["rows"] == 2


# ---------------------------------------------------------------------------
# NullRecorder: the zero-overhead-off guarantee.
# ---------------------------------------------------------------------------


def test_null_recorder_noop_guarantee():
    """Engines guard every hook with ``if obs:`` — so the default must be
    falsy — and any un-guarded call must still be a harmless no-op that
    allocates no state on the recorder."""
    n = NULL_RECORDER
    assert isinstance(n, NullRecorder)
    assert not n            # the `if obs:` guard compiles the hook away
    assert n.enabled is False
    # every hook (present or future) resolves to the same shared no-op
    assert n.on_submit(object()) is None
    assert n.on_decode([], 0.0, 0.0) is None
    assert n.some_hook_added_next_year(1, 2, kw=3) is None
    assert n.on_tokens is n.poll_jit  # one function object, no per-call state
    with pytest.raises(AttributeError):
        n.__html__  # dunders are not swallowed
    # __slots__ = (): a NullRecorder cannot accumulate state at all
    with pytest.raises(AttributeError):
        n.x = 1


def test_engines_default_to_null_recorder(setup):
    cfg, params = setup
    assert ServeEngine(params, cfg, max_batch=1, max_len=64).obs is \
        NULL_RECORDER
    ssm = get_config("mamba2-370m", reduced=True)
    fixed = FixedSlotEngine(MD.init_params(ssm, jax.random.PRNGKey(0)), ssm,
                            slots=1, max_len=32)
    assert fixed.obs is NULL_RECORDER
    # the speculative engine keeps telemetry always-on (PR-5 `stats`
    # back-compat): metrics-only recorder, no tracer
    spec = SpeculativeEngine(params, cfg, params, max_batch=1, max_len=64)
    assert isinstance(spec.obs, Recorder) and spec.obs.tracer is None


# ---------------------------------------------------------------------------
# Recorder-on vs recorder-off differentials (the hard requirement).
# ---------------------------------------------------------------------------


def _streams(engine_factory):
    eng = engine_factory()
    reqs = [eng.submit(p, max_new_tokens=8) for p in PROMPTS]
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    return [list(r.generated) for r in reqs], eng


def test_paged_bitexact_with_recording_under_eviction(setup):
    """Recording on vs off through the paged engine on the PR-4 eviction
    workload (host swap + restart evictions happen WHILE spans and swap
    bytes are recorded) — streams must be bit-identical."""
    cfg, params = setup
    off, _ = _streams(lambda: ServeEngine(params, cfg, max_len=64,
                                          **EVICT_KWARGS))
    rec = Recorder()
    on, eng = _streams(lambda: ServeEngine(params, cfg, max_len=64,
                                           recorder=rec, **EVICT_KWARGS))
    assert on == off
    v = rec.registry.value
    assert v("serve_requests_submitted_total") == len(PROMPTS)
    assert v("serve_requests_finished_total") == len(PROMPTS)
    evictions = (v("serve_evicted_total", kind="swap")
                 + v("serve_evicted_total", kind="restart"))
    assert evictions > 0, "workload was supposed to trigger eviction"
    if v("serve_evicted_total", kind="swap"):
        assert rec.registry.sum_values("serve_swap_bytes_total") > 0
    # latency histograms: one TTFT/TPOT sample per request, ITL per gap
    assert rec.registry.find("serve_ttft_seconds")[0].count == len(PROMPTS)
    assert rec.registry.find("serve_tpot_seconds")[0].count == len(PROMPTS)
    assert rec.registry.find("serve_batch_occupancy")[0].count > 0
    # token conservation: generated = decode + one first-token per request
    assert (v("serve_generated_tokens_total")
            == v("serve_decode_tokens_total") + len(PROMPTS))
    # >= : a restart eviction legitimately re-prefills its victim; prefix
    # reuse legitimately skips tokens covered by cached pages
    assert (v("serve_prefill_tokens_total")
            + v("serve_prefix_reused_tokens_total")) >= sum(map(len, PROMPTS))
    # at drain, live pages are exactly the ones the prefix index retains
    assert eng.kv.allocator.in_use == len(set(eng.sched.prefix.pages_held()))
    eng.sched.prefix.clear()
    assert eng.kv.allocator.in_use == 0


def test_fixed_slot_bitexact_with_recording(setup):
    cfg, params = setup
    off, _ = _streams(lambda: FixedSlotEngine(params, cfg, slots=2,
                                              max_len=64))
    rec = Recorder()
    on, _ = _streams(lambda: FixedSlotEngine(params, cfg, slots=2,
                                             max_len=64, recorder=rec))
    assert on == off
    v = rec.registry.value
    assert v("serve_requests_submitted_total") == len(PROMPTS)
    assert v("serve_requests_finished_total") == len(PROMPTS)
    assert rec.registry.find("serve_ttft_seconds")[0].count == len(PROMPTS)


def test_speculative_bitexact_with_recording(setup):
    """A tracing recorder through the speculative engine (its default is
    metrics-only) — streams, acceptance and the stats view must agree."""
    cfg, params = setup

    def mk(recorder=None):
        kw = dict(spec_k=3, max_batch=3, max_len=64, page_size=16,
                  prefill_chunk=4)
        if recorder is not None:
            kw["recorder"] = recorder
        return SpeculativeEngine(params, cfg, params, **kw)

    off, spec_off = _streams(mk)
    rec = Recorder()
    on, spec_on = _streams(lambda: mk(rec))
    assert on == off
    assert spec_on.stats == spec_off.stats
    assert spec_on.acceptance_rate == 1.0  # identical draft
    v = rec.registry.value
    assert v("spec_rounds_total", path="greedy") > 0
    assert v("spec_rounds_total", path="sampled") == 0
    assert v("serve_requests_finished_total") == len(PROMPTS)
    # spans exist for the spec rounds
    names = {e["name"] for e in rec.to_chrome()["traceEvents"]}
    assert "spec-round" in names


# ---------------------------------------------------------------------------
# Trace schema through a real engine run.
# ---------------------------------------------------------------------------


def test_trace_schema_from_engine_run(setup):
    cfg, params = setup
    rec = Recorder()
    _streams(lambda: ServeEngine(params, cfg, max_len=64, recorder=rec,
                                 **EVICT_KWARGS))
    obj = rec.to_chrome()
    assert validate_chrome_trace(obj) == []
    # round-trips through JSON (what --trace-out writes)
    assert validate_chrome_trace(json.loads(json.dumps(obj))) == []
    events = obj["traceEvents"]
    lanes = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert "engine" in lanes
    assert {f"req {i}" for i in range(len(PROMPTS))} <= lanes
    names = {e["name"] for e in events if e["ph"] != "M"}
    assert {"queued", "prefill[0]", "decode", "finish"} <= names
    # the step phases, on a lane of their own
    assert "steps" in lanes
    phases = {e["name"] for e in events
              if e["ph"] == "X" and e["tid"] == Tracer.STEP_TID}
    assert {"serve.schedule", "serve.kv_move", "serve.prefill",
            "serve.decode", "serve.tokens", "serve.retire"} <= phases
    # the eviction workload leaves evict/swap marks in the trace
    assert any(n.startswith("evict[") for n in names)
    # Prometheus artifact from the same run parses too
    assert validate_prometheus(rec.to_prometheus()) == []
    table = summary_table(rec.registry)
    assert "TTFT" in table and "page pool" in table


def test_jit_cache_miss_counter(setup):
    """A cold engine compiles decode/prefill/sampler programs — the
    registered dispatch sites must report those cache misses; a second
    identical workload must add none."""
    cfg, params = setup
    rec = Recorder(trace=False)
    eng = ServeEngine(params, cfg, max_batch=2, max_len=64, recorder=rec)
    for p in PROMPTS[:2]:
        eng.submit(p, max_new_tokens=4)
    eng.run_until_drained()
    misses = rec.registry.sum_values("jit_cache_misses_total")
    assert misses >= 2  # decode + prefill compile at least
    for p in PROMPTS[:2]:
        eng.submit(p, max_new_tokens=4)
    eng.run_until_drained()
    assert rec.registry.sum_values("jit_cache_misses_total") == misses


def test_recorder_reset(setup):
    cfg, params = setup
    rec = Recorder()
    eng = ServeEngine(params, cfg, max_batch=2, max_len=64, recorder=rec)
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.run_until_drained()
    assert rec.registry.value("serve_requests_finished_total") == 1
    rec.reset()  # what benchmarks do after jit warm-up
    assert rec.registry.value("serve_requests_finished_total") == 0
    assert rec.registry.find("serve_ttft_seconds")[0].count == 0
    assert rec.to_chrome()["traceEvents"] == []
    assert not rec.steps
    # warm-up compiles must not re-count as misses after the reset
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.run_until_drained()
    assert rec.registry.sum_values("jit_cache_misses_total") == 0


# ---------------------------------------------------------------------------
# Leveled logger (REPRO_LOG).
# ---------------------------------------------------------------------------


def test_logger_levels(monkeypatch, capsys):
    monkeypatch.delenv("REPRO_LOG", raising=False)
    log("serve", "hello")                      # default: info prints
    log("serve", "noise", level="debug")       # debug suppressed
    assert capsys.readouterr().out == "[serve] hello\n"
    assert log_enabled("info") and not log_enabled("debug")

    monkeypatch.setenv("REPRO_LOG", "debug")
    log("spec", "detail", level="debug")
    assert capsys.readouterr().out == "[spec] detail\n"

    monkeypatch.setenv("REPRO_LOG", "quiet")
    log("serve", "hidden")
    assert capsys.readouterr().out == ""
    assert not log_enabled("info")


# ---------------------------------------------------------------------------
# PR-10 satellites: exposition hardening, quantile edges, jit degrade,
# deterministic summaries.
# ---------------------------------------------------------------------------


def test_prometheus_hostile_label_values():
    """Label values carrying backslashes, double quotes and newlines must
    render per the exposition-format escaping rules — a raw newline in a
    label would split the sample line and corrupt the whole scrape."""
    r = MetricsRegistry()
    r.counter("h_total", "hostile", path='a"b\\c\nd').inc()
    text = r.to_prometheus()
    assert validate_prometheus(text) == []
    assert 'h_total{path="a\\"b\\\\c\\nd"} 1' in text
    # no raw newline survived inside any sample line
    for line in text.splitlines():
        if line.startswith("h_total"):
            assert line.endswith(" 1")


def test_histogram_quantile_edge_cases():
    # empty histogram: every quantile is 0, not an error
    h = Histogram("h", buckets=(0.1, 1.0))
    assert h.quantile(0.0) == 0.0 and h.quantile(0.5) == 0.0
    assert h.mean == 0.0

    # single observation: q=0 pins the bucket's lower edge, q=1 its upper
    h.observe(0.05)
    assert h.quantile(0.0) == 0.0
    assert h.quantile(1.0) == pytest.approx(0.1)
    # out-of-range q clamps instead of extrapolating
    assert h.quantile(-3.0) == h.quantile(0.0)
    assert h.quantile(7.0) == h.quantile(1.0)

    # +Inf-bucket observations clamp to the top finite edge — the
    # estimator must not fabricate a bound that was never configured
    top = Histogram("t", buckets=(0.1, 1.0))
    top.observe(50.0)
    assert top.counts[-1] == 1
    assert top.quantile(0.5) == 1.0
    assert top.quantile(0.99) == 1.0
    assert top.mean == 50.0  # sum/count still carry the true value


def test_jit_site_counts_each_new_shape_once():
    """Every new input shape at a registered site is one compile-cache
    miss; a repeated shape is none, and ``reset`` re-baselines."""
    import jax
    import jax.numpy as jnp

    rec = Recorder(trace=False)
    fn = jax.jit(lambda x: x + 1)
    rec.register_jit_site("toy.site", fn)
    fn(jnp.zeros(3))
    rec.poll_jit()
    assert rec.registry.sum_values("jit_cache_misses_total") == 1
    fn(jnp.zeros(3))
    rec.poll_jit()
    assert rec.registry.sum_values("jit_cache_misses_total") == 1
    fn(jnp.zeros(4))
    rec.reset()      # the new shape compiled before the reset: no miss
    rec.poll_jit()
    assert rec.registry.sum_values("jit_cache_misses_total") == 0


def test_summary_table_deterministic_order():
    """The ``--metrics`` summary's detail section must not depend on
    metric insertion order: sorted by name, then label set."""
    def build(reverse):
        r = MetricsRegistry()
        items = [("z_custom_total", {"a": "1"}),
                 ("a_custom_total", {}),
                 ("m_custom_total", {"b": "2"}),
                 ("m_custom_total", {"b": "1"})]
        for name, labels in (reversed(items) if reverse else items):
            r.counter(name, "", **labels).inc(2)
        r.histogram("q_hist", "", buckets=(1.0,)).observe(0.5)
        return summary_table(r)

    assert build(False) == build(True)
    t = build(False)
    ia = t.index("a_custom_total")
    im1 = t.index('m_custom_total{b="1"}')
    im2 = t.index('m_custom_total{b="2"}')
    iz = t.index("z_custom_total")
    assert ia < im1 < im2 < iz
    assert "q_hist" in t  # histograms render as mean (n=...)
    # the CI-grepped header line survives
    assert "── serving metrics" in t


# ---------------------------------------------------------------------------
# SLO health layer.
# ---------------------------------------------------------------------------


def test_slo_tracker_window_budgets_and_crossings():
    r = MetricsRegistry()
    th = SloThresholds(ttft_p99_s=0.1, tpot_p99_s=1.0, min_tok_s=1.0,
                       min_acceptance=0.5, budget_target=0.9)
    slo = SloTracker(r, clock=lambda: 100.0, window_s=30.0, thresholds=th)
    slo.note_tokens(85.0, 30)
    slo.note_tokens(95.0, 30)
    slo.note_ttft(90.0, 0.05)
    slo.note_ttft(95.0, 0.2)            # violates the 100ms objective
    slo.note_tpot(95.0, 0.01)
    slo.note_acceptance(95.0, proposed=10, accepted=3)  # 0.3 < 0.5

    s = slo.snapshot(now=100.0)
    assert s["tok_s"] == pytest.approx(60 / 15)  # span = oldest→now
    assert s["ttft_p99_s"] == 0.2 and s["ttft_samples"] == 2
    assert s["acceptance"] == pytest.approx(0.3)
    # 1 of 2 TTFT samples violate; allowed fraction is 0.1 → exhausted
    assert s["error_budget_remaining"]["ttft"] == 0.0
    assert s["error_budget_remaining"]["tpot"] == 1.0
    assert s["error_budget_remaining"]["tok_s"] == 1.0  # 4 tok/s >= 1
    assert s["error_budget_remaining"]["acceptance"] == 0.0
    assert s["violating"] == ["acceptance", "ttft"]
    assert r.value("slo_violations_total", slo="ttft") == 1
    # the same violation is counted once per CROSSING, not per snapshot
    slo.snapshot(now=100.0)
    assert r.value("slo_violations_total", slo="ttft") == 1
    # gauges published into the shared registry
    assert r.value("slo_window_tok_s") == pytest.approx(4.0)
    assert r.value("slo_ttft_p99_seconds") == 0.2
    assert r.value("slo_error_budget_remaining", slo="ttft") == 0.0

    # recovery: fresh healthy samples clear the violation, and the NEXT
    # crossing counts again
    slo.note_ttft(140.0, 0.01)
    s2 = slo.snapshot(now=141.0)
    assert s2["ttft_samples"] == 1 and "ttft" not in s2["violating"]
    slo.note_ttft(142.0, 0.5)
    slo.snapshot(now=143.0)
    assert r.value("slo_violations_total", slo="ttft") == 2

    # an empty window spends no budget and reads 0 tok/s
    s3 = slo.snapshot(now=500.0)
    assert s3["tok_s"] == 0.0 and s3["ttft_samples"] == 0
    assert s3["error_budget_remaining"]["ttft"] == 1.0

    slo.reset()
    assert slo.snapshot(now=500.0)["violating"] == []


def test_slo_report_renders():
    r = MetricsRegistry()
    slo = SloTracker(r, clock=lambda: 10.0, window_s=30.0)
    slo.note_tokens(5.0, 20)
    slo.note_ttft(5.0, 0.05)
    slo.note_tpot(6.0, 0.01)
    text = slo_report(slo)
    assert "── slo health" in text
    assert "throughput (tok/s)" in text and "violations" in text
    assert "none" in text


def test_recorder_feeds_slo_from_engine_run(setup):
    """A real engine run must populate the recorder's SLO window — the
    /slo endpoint and --slo-report read exactly this snapshot."""
    cfg, params = setup
    rec = Recorder(trace=False)
    eng = ServeEngine(params, cfg, max_batch=2, max_len=64, recorder=rec)
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.submit([4, 5], max_new_tokens=4)
    eng.run_until_drained()
    s = rec.slo.snapshot()
    assert s["ttft_samples"] == 2 and s["tpot_samples"] == 2
    assert s["tok_s"] > 0
    rec.reset()
    assert rec.slo.snapshot()["ttft_samples"] == 0


def test_request_id_trace_instant():
    """``on_request_id`` must land the client id on the request's tracer
    lane (the X-Request-Id propagation path)."""
    rec = Recorder()

    class _Req:
        uid = 3

    rec.on_request_id(_Req(), "abc-123")
    obj = rec.to_chrome()
    inst = [e for e in obj["traceEvents"] if e["ph"] == "i"]
    assert any(e["name"] == "x-request-id"
               and e["args"]["id"] == "abc-123" for e in inst)


# ---------------------------------------------------------------------------
# Step spans: bit-exactness, well-formed step records, profiler trace.
# ---------------------------------------------------------------------------

ENGINES = ["paged", "fixed", "speculative"]


def _engine_factory(kind, params, cfg):
    def mk(recorder=None):
        kw = {} if recorder is None else {"recorder": recorder}
        if kind == "paged":
            return ServeEngine(params, cfg, max_len=64, **EVICT_KWARGS, **kw)
        if kind == "fixed":
            return FixedSlotEngine(params, cfg, slots=2, max_len=64, **kw)
        return SpeculativeEngine(params, cfg, params, spec_k=3, max_batch=3,
                                 max_len=64, page_size=16, prefill_chunk=4,
                                 **kw)
    return mk


@pytest.mark.parametrize("kind", ENGINES)
def test_streams_bitexact_with_step_spans(setup, kind, tmp_path):
    """Step spans recorded (a tracing recorder) with a CPU
    ``jax.profiler`` session collecting, against spans off (the default
    recorder, no session): streams bit-identical through each engine."""
    cfg, params = setup
    mk = _engine_factory(kind, params, cfg)
    off, _ = _streams(mk)
    rec = Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        on, _ = _streams(lambda: mk(rec))
    finally:
        jax.profiler.stop_trace()
    assert on == off
    assert rec.steps
    assert validate_chrome_trace(rec.to_chrome()) == []


SAMPLED = SamplingParams(temperature=0.8, top_k=8, top_p=0.9, seed=77)


@pytest.mark.parametrize("workload", ["greedy", "one_sampled"])
@pytest.mark.parametrize("kind", ENGINES[:2])
def test_sample_calls_by_path(setup, kind, workload):
    """Every sampler call of a plain engine counts once, by program: an
    all-greedy workload only ``path="greedy"``; a sampled request
    ``path="sampled"`` on each step it is in the batch (its final prefill
    chunk and each decode step after it, one token each), and the greedy
    streams beside it stay those of the all-greedy run."""
    cfg, params = setup
    mk = _engine_factory(kind, params, cfg)
    greedy_streams, _ = _streams(mk)
    rec = Recorder(trace=False)
    eng = mk(rec)
    sreq = (eng.submit([2, 7, 1], max_new_tokens=8, sampling=SAMPLED)
            if workload == "one_sampled" else None)
    reqs = [eng.submit(p, max_new_tokens=8) for p in PROMPTS]
    eng.run_until_drained()
    assert [list(r.generated) for r in reqs] == greedy_streams
    v = rec.registry.value
    greedy = v("serve_sample_calls_total", path="greedy")
    sampled = v("serve_sample_calls_total", path="sampled")
    n_reqs = len(PROMPTS) + (sreq is not None)
    # one call a decode step, one after each request's final prefill chunk
    assert greedy + sampled == (v("serve_steps_total", kind="decode")
                                + n_reqs)
    assert sampled == (0 if sreq is None else len(sreq.generated))
    assert greedy > 0


@pytest.mark.parametrize("temps", [(0.0, 0.0, 0.0), (0.0, 0.7, 0.0)],
                         ids=["all_greedy", "one_sampled"])
def test_sample_batch_dispatch(monkeypatch, temps):
    """``_sample_batch`` dispatches the argmax program, with the logits
    alone, on an all-greedy batch (an unlisted row included), and
    otherwise ``sample_tokens_jit`` with the arrays ``batch_rows`` makes."""
    from contextlib import nullcontext
    from types import SimpleNamespace

    from repro.serving import engine as E
    from repro.serving import sampling as S

    calls = []
    for name in ("greedy_tokens_jit", "sample_tokens_jit"):
        real = getattr(S, name)
        monkeypatch.setattr(S, name, lambda *a, _n=name, _f=real: (
            calls.append((_n, a)) or _f(*a)))
    logits = jax.random.normal(jax.random.PRNGKey(3), (4, 64))
    rows = [(row, SimpleNamespace(
        emitted=row,
        sampling=SamplingParams(temperature=temp, top_k=row, top_p=0.5,
                                seed=row + 9)))
        for row, temp in enumerate(temps)]
    rec = Recorder(trace=False)
    toks = E._sample_batch(rec, logits, rows, 4, nullcontext())
    (name, args), = calls
    if max(temps) == 0:
        assert name == "greedy_tokens_jit" and args == (logits,)
        np.testing.assert_array_equal(toks, np.argmax(logits, axis=-1))
    else:
        assert name == "sample_tokens_jit" and args[0] is logits
        for got, want in zip(args[1:], S.batch_rows(rows, 4)):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)
    path = "greedy" if max(temps) == 0 else "sampled"
    assert rec.registry.value("serve_sample_calls_total", path=path) == 1
    assert rec.registry.sum_values("serve_sample_calls_total") == 1


@pytest.mark.parametrize("kind", ENGINES)
def test_step_records_are_well_formed(setup, kind):
    """Every step's phases are known, in order, inside their step; a
    step's self time is never negative; a decode's tokens are waited for
    exactly once after the decode (the paged engine: in the next step,
    with its own decode queued behind); the ring keeps the newest."""
    cfg, params = setup
    rec = Recorder(trace=False)
    assert rec.steps.maxlen == STEP_RING
    rec.steps = deque(maxlen=6)  # a short ring, so the run overflows it
    eng = _engine_factory(kind, params, cfg)(rec)
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=8)
    records = []
    while eng.has_work:
        eng.step()
        records.append(rec.steps[-1])
    n = len(records)
    assert n > 6 and [s.num for s in rec.steps] == list(range(n - 5, n + 1))
    assert [s.num for s in records] == list(range(1, n + 1))
    seen = set()
    in_flight = False  # the paged engine's decode lands a step later
    for s in records:
        names = [p[0] for p in s.phases]
        seen.update(names)
        assert set(names) <= set(STEP_PHASES)
        assert names[0] == "serve.schedule"
        prev = s.t0
        for i, (name, t0, t1, after) in enumerate(s.phases):
            assert prev <= t0 <= t1 <= s.t1, (s.num, name)
            prev = t1
            if name == "serve.tokens":
                if kind != "paged":
                    # the wait follows the dispatch it waits for
                    assert names[i - 1] in ("serve.sample",
                                            f"serve.{after}")
            else:
                assert after is None
        waits = [(t1 - t0, after) for name, t0, t1, after in s.phases
                 if name == "serve.tokens"]
        assert s.t1 - s.t0 - sum(w for w, _ in waits) >= 0
        decoded = "serve.decode" in names
        assert [a for _, a in waits].count("decode") == (
            in_flight if kind == "paged" else decoded)
        in_flight = decoded
    want = {"serve.schedule", "serve.prefill", "serve.decode",
            "serve.tokens", "serve.retire"}
    if kind != "speculative":
        want.add("serve.sample")  # the round samples inside its program
    if kind == "paged":
        want.add("serve.kv_move")  # the eviction workload swaps
    assert want <= seen


def test_speculative_first_token_lands_before_round(setup):
    """The speculative engine keeps nothing in flight: a prompt's first
    token is read, appended and stamped (TTFT) before the draft+verify
    round of the step that finished its prefill."""
    cfg, params = setup
    rec = Recorder(trace=False)
    eng = _engine_factory("speculative", params, cfg)(rec)
    reqs = [eng.submit(p, max_new_tokens=8) for p in PROMPTS]
    with_round = 0
    while eng.has_work:
        fresh = [r for r in reqs if not r.generated]
        eng.step()
        s = rec.steps[-1]
        names = [p[0] for p in s.phases]
        for req in fresh:
            if not req.generated:
                continue
            ts = rec._req[req.uid].first_tok_ts
            i = names.index("serve.retire")
            assert names[i - 2:i] == ["serve.prefill", "serve.tokens"]
            assert s.phases[i - 1][3] == "prefill"
            assert s.phases[i][1] <= ts <= s.phases[i][2]
            if "serve.decode" in names:
                with_round += 1
                assert i < names.index("serve.decode")
                assert ts <= s.phases[names.index("serve.decode")][1]
    assert with_round > 0


# the phases of a paged step, each at most once, in this order: the
# previous step's decode lands before this step's first token
PAGED_ORDER = [("serve.schedule", None), ("serve.kv_move", None),
               ("serve.prefill", None), ("serve.decode", None),
               ("serve.sample", None), ("serve.tokens", "decode"),
               ("serve.tokens", "prefill"), ("serve.retire", None)]


@pytest.mark.parametrize("workload", ["greedy", "sampled", "evict"])
def test_paged_step_phases_once_in_order(setup, workload):
    """With one decode in flight, each phase of a paged step runs at
    most once, in :data:`PAGED_ORDER`, and no two phases overlap."""
    cfg, params = setup
    rec = Recorder(trace=False)
    kw = (EVICT_KWARGS if workload == "evict"
          else dict(max_batch=3, page_size=16, prefill_chunk=4))
    eng = ServeEngine(params, cfg, max_len=64, recorder=rec, **kw)
    for i, p in enumerate(PROMPTS):
        eng.submit(p, max_new_tokens=8, sampling=(
            SAMPLED if workload == "sampled" and i % 2 else None))
    eng.run_until_drained()
    seen = set()
    for s in rec.steps:
        keys = [(name, after) for name, _, _, after in s.phases]
        assert keys == [k for k in PAGED_ORDER if k in keys], (s.num, keys)
        seen.update(keys)
        end = s.t0
        for _, t0, t1, _ in s.phases:
            assert end <= t0 <= t1 <= s.t1, (s.num, keys)
            end = t1
    # every phase ran somewhere; the eviction workload moves KV
    assert set(PAGED_ORDER) - seen <= {("serve.kv_move", None)}
    assert (("serve.kv_move", None) in seen) >= (workload == "evict")


def test_tokens_discarded_counter(setup):
    """``serve_tokens_discarded_total`` counts each row-step decoded for
    a request that had ended by the time its token landed, by reason: an
    EOS that landed first, or a cancel; a run with neither counts none."""
    cfg, params = setup
    kw = dict(max_batch=3, max_len=64, page_size=16, prefill_chunk=4)
    rec = Recorder(trace=False)
    eng = ServeEngine(params, cfg, recorder=rec, **kw)
    plain = [eng.submit(p, max_new_tokens=8) for p in PROMPTS[:3]]
    eng.run_until_drained()
    v = rec.registry.value
    assert rec.registry.sum_values("serve_tokens_discarded_total") == 0
    # the stream's first token that a decode emits and none before it
    gen = plain[0].generated
    i = next(i for i in range(1, 7) if gen[i] not in gen[:i])
    ended = eng.submit(PROMPTS[0], max_new_tokens=8, eos_id=gen[i])
    cancelled = eng.submit(PROMPTS[1], max_new_tokens=8)
    while len(cancelled.generated) < 2 or not cancelled.inflight:
        eng.step()
    eng.cancel(cancelled.uid)
    eng.run_until_drained()
    assert ended.generated == gen[: i + 1]
    assert v("serve_tokens_discarded_total", reason="eos") == 1
    assert v("serve_tokens_discarded_total", reason="cancel") == 1
    assert "serve_tokens_discarded_total" in rec.to_prometheus()


def test_no_compiles_after_warm_up(setup):
    """After a warm-up like the chip benchmark's (a two-chunk prefill,
    first tokens, decodes at a full and a partial batch), new traffic
    compiles nothing at any registered site, the merge of in-flight
    tokens (``serve.decode_tokens``) included."""
    cfg, params = setup
    rec = Recorder(trace=False)
    eng = ServeEngine(params, cfg, max_batch=3, max_len=64, page_size=16,
                      prefill_chunk=4, recorder=rec)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, 64, 5).tolist(), max_new_tokens=3)
    for _ in range(3):
        eng.submit(rng.integers(0, 64, 2).tolist(), max_new_tokens=3)
    eng.run_until_drained()
    assert eng._merge_tokens._cache_size() == 1
    rec.reset()
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=8)
    eng.run_until_drained()
    v = rec.registry.value
    assert v("jit_cache_misses_total", -1.0, site="serve.decode_tokens") == 0
    assert rec.registry.sum_values("jit_cache_misses_total") == 0


def test_profiler_trace_holds_step_spans(setup, tmp_path):
    """A CPU ``jax.profiler`` trace of a few engine steps, taken as
    ``launch/serve.py --profile-dir`` takes it and read back with
    ``ProfileData``: ``serve.step`` host events numbered by the engine's
    step counter, each holding ``serve.*`` phases, and no phase outside
    a step."""
    import glob

    from jax.profiler import ProfileData

    from repro.launch.serve import drain_profiled

    cfg, params = setup
    eng = ServeEngine(params, cfg, max_len=64, **EVICT_KWARGS)
    handles = [eng.submit(p, max_new_tokens=8) for p in PROMPTS]
    done = drain_profiled(eng, handles, str(tmp_path), 4)
    assert len(done) == len(PROMPTS)
    (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    steps, phases = [], []
    for plane in ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for ev in line.events:
                span = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == STEP_SPAN:
                    steps.append((dict(ev.stats)["step_num"],) + span)
                elif ev.name.startswith("serve."):
                    phases.append(span)
    nums = sorted(n for n, *_ in steps)
    assert len(nums) == 4 and nums == list(range(nums[0], nums[0] + 4))
    assert {"serve.schedule", "serve.decode", "serve.sample",
            "serve.tokens", "serve.retire"} <= {n for n, _, _ in phases}
    for name, s, e in phases:
        assert any(s0 <= s and e <= e0 for _, _, s0, e0 in steps), name
    for _, _, s0, e0 in steps:
        assert any(s0 <= s and e <= e0 for _, s, e in phases)


def test_dispatch_hook_counts_compiled_programs():
    """``attach_dispatch_hook`` counts LUT-MU backend selections on
    static call metadata; detach stops the counting."""
    from repro.kernels import dispatch as D
    from repro.kernels.dispatch import attach_dispatch_hook

    rng = np.random.default_rng(0)
    c, depth, d_sub, n = 2, 2, 4, 3
    p = D.params_from_arrays(
        rng.integers(0, d_sub, (c, depth)).astype(np.int32),
        rng.standard_normal((c, 2 ** depth - 1)).astype(np.float32),
        rng.standard_normal((c, 2 ** depth, n)).astype(np.float32),
        np.ones(n, np.float32), np.zeros(n, np.float32))
    x = rng.standard_normal((5, c * d_sub)).astype(np.float32)

    r = MetricsRegistry()
    detach = attach_dispatch_hook(r)
    try:
        D.lutmu_matmul(jax.numpy.asarray(x), p, backend="ref",
                       input_kind="full")
        assert r.value("lutmu_dispatch_total", backend="ref",
                       input_kind="full") == 1
    finally:
        detach()
    D.lutmu_matmul(jax.numpy.asarray(x), p, backend="ref",
                   input_kind="full")
    assert r.value("lutmu_dispatch_total", backend="ref",
                   input_kind="full") == 1


# ---------------------------------------------------------------------------
# Quality probe: bit-exactness + recorded quality metrics.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def amm_artifact(setup, tmp_path_factory):
    """A compiled amm_lm artifact over the tiny config (real fitted
    trees/LUTs, so probe replays exercise the true serving path)."""
    cfg, params = setup
    from repro.compiler import compile_lm_amm

    rng = np.random.default_rng(0)
    calib = rng.integers(0, cfg.vocab_size, (2, 8))
    out = str(tmp_path_factory.mktemp("pr10_amm") / "lm")
    compile_lm_amm(params, cfg, calib, out=out)
    return out


def test_quality_probe_bitexact_and_metrics(setup, amm_artifact):
    """Probe at rate=1.0 (every finished request replayed) vs no probe on
    the AMM paged engine — streams bit-identical, and the probed run
    records rel-error histograms per projection, codebook utilisation
    and saturation counters with zero probe errors."""
    cfg, params = setup

    def mk(rec=None):
        return load_engine(amm_artifact, params, cfg, max_batch=2,
                           max_len=64, recorder=rec)

    off, _ = _streams(mk)
    rec = Recorder(trace=False)
    rec.quality = QualityProbe(rec.registry, rate=1.0, dense_params=params)
    on, _ = _streams(lambda: mk(rec))
    assert on == off
    v = rec.registry.value
    assert v("quality_probes_total") == len(PROMPTS)
    assert v("quality_probe_errors_total") == 0
    assert v("quality_probe_tokens_total") > 0
    rels = rec.registry.find("quality_rel_error")
    assert rels and all(h.count > 0 for h in rels)
    assert {dict(h.labels)["proj"] for h in rels} == {"gate", "up", "down"}
    # int8 tables: lookups counted, utilisation gauges live
    assert v("quality_lookups_total", layer="0", proj="gate") > 0
    assert rec.registry.find("quality_bucket_utilisation")
    snap = rec.quality.snapshot()
    assert snap["dense_reference"] is True and snap["supported"] is True
    assert snap["probes"] == len(PROMPTS)
    assert snap["layers"]["0"]["rel_error"]["gate"]["n"] > 0
    assert snap["layers"]["0"]["buckets"]["up"]["total"] > 0


def test_quality_probe_without_dense_reference(setup, amm_artifact):
    """No dense weights → the rel-error section degrades away but
    utilisation/saturation still record, with zero errors."""
    cfg, params = setup
    rec = Recorder(trace=False)
    rec.quality = QualityProbe(rec.registry, rate=1.0)
    eng = load_engine(amm_artifact, params, cfg, max_batch=2, max_len=64,
                      recorder=rec)
    eng.submit([1, 2, 3, 4], max_new_tokens=4)
    eng.run_until_drained()
    v = rec.registry.value
    assert v("quality_probes_total") == 1
    assert v("quality_probe_errors_total") == 0
    assert rec.registry.find("quality_rel_error") == []
    assert rec.registry.find("quality_bucket_utilisation")
    assert rec.quality.snapshot()["dense_reference"] is False


def test_quality_probe_sampling_rate(setup, amm_artifact):
    """rate=0.5 probes a deterministic half of finished requests, and a
    dense engine (no AMM layers) skips with a reason instead of raising."""
    cfg, params = setup
    rec = Recorder(trace=False)
    rec.quality = QualityProbe(rec.registry, rate=0.5)
    eng = load_engine(amm_artifact, params, cfg, max_batch=2, max_len=64,
                      recorder=rec)
    for p in PROMPTS[:4]:
        eng.submit(p, max_new_tokens=4)
    eng.run_until_drained()
    assert rec.registry.value("quality_probes_total") == 2

    # dense engine sharing a fresh probe: every probe opportunity skips
    rec2 = Recorder(trace=False)
    rec2.quality = QualityProbe(rec2.registry, rate=1.0)
    dense = ServeEngine(params, cfg, max_batch=2, max_len=64, recorder=rec2)
    dense.submit([1, 2, 3], max_new_tokens=4)
    dense.run_until_drained()
    assert rec2.registry.value("quality_probes_total") == 0
    assert rec2.registry.value("quality_probe_skipped_total",
                               reason="no_amm") == 1

    with pytest.raises(ValueError, match="rate"):
        QualityProbe(MetricsRegistry(), rate=0.0)


def test_quality_probe_bitexact_speculative(setup):
    """Probe riding the speculative engine's recorder: greedy streams
    stay bit-identical to the unprobed engine (the probe binds the
    TARGET half — first engine bind wins)."""
    cfg, params = setup

    def mk(recorder=None):
        kw = dict(spec_k=3, max_batch=3, max_len=64, page_size=16,
                  prefill_chunk=4)
        if recorder is not None:
            kw["recorder"] = recorder
        return SpeculativeEngine(params, cfg, params, **kw)

    off, _ = _streams(mk)
    rec = Recorder(trace=False)
    rec.quality = QualityProbe(rec.registry, rate=1.0, dense_params=params)
    on, _ = _streams(lambda: mk(rec))
    assert on == off
    # dense tiny model has no AMM layers: probes all skip, none error
    assert rec.registry.value("quality_probe_errors_total") == 0
    assert rec.registry.value("quality_probe_skipped_total",
                              reason="no_amm") == len(PROMPTS)
