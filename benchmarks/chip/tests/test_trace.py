"""Reduction of a device trace to the per-layer metrics, on intervals
worked by hand.

Run by path: ``python -m pytest benchmarks/chip/tests``."""
import pytest

from benchmarks.chip import trace


def test_union_and_gaps_by_hand():
    spans = [(0, 10), (5, 12), (20, 25), (24, 30), (40, 41)]
    assert trace.union_ns(spans) == 12 + 10 + 1
    assert trace.gaps_ns(spans, 0, 50) == [(12, 20), (30, 40), (41, 50)]
    assert trace.gaps_ns(spans, 2, 22) == [(12, 20)]
    assert trace.union_ns([]) == 0


def test_idle_is_charged_to_the_host_span_over_it():
    r = trace.Reduced(
        window_ns=(0, 100),
        ops={0: [("fusion", 0, 40), ("fused_lutmu", 60, 90)]},
        modules={0: [("jit__decode", 0, 40), ("jit__decode", 60, 90)]},
        host=[("bench.step", 0, 45), ("bench.stamp", 45, 58),
              ("bench.wait", 58, 100)])
    assert r.busy_s == pytest.approx(70e-9)
    assert r.idle_share() == pytest.approx(0.3)
    # gap 40..60: stamp covers 13 of it, step 5, wait 2 -> bench.stamp;
    # gap 90..100 lies in bench.wait
    assert dict(r.idle_by_host()) == pytest.approx(
        {"bench.stamp": 20e-9, "bench.wait": 10e-9})
    assert r.device_ops()[0] == ("fusion", pytest.approx(40e-9))
    assert [len(x[3]) for x in r.executions("jit__decode")] == [1, 1]


def test_readers_on_a_hand_built_decode_execution():
    import types

    from benchmarks.chip import spec, work
    from benchmarks.chip.harness import load_reader

    s = spec.load("qwen3-14b-lutmu")
    pk = work.peaks("TPU v5 lite")
    # one decode execution of 40 ms holding 16 layers x (gate, up, down)
    # kernel calls of 0.5 ms each, and one other op
    calls = [(f"fused_lutmu_pallas.{i}", 1000 + i * 600_000,
              1000 + i * 600_000 + 500_000) for i in range(48)]
    r = trace.Reduced(window_ns=(0, 50_000_000),
                      ops={0: calls + [("fusion.1", 30_000_000, 39_000_000)]},
                      modules={0: [("jit__decode", 0, 40_000_000)]}, host=[])
    ctx = types.SimpleNamespace(spec=s, trace=r, peaks=pk)
    assert load_reader("decode_step_ms")(ctx) == pytest.approx(40.0)
    assert load_reader("prefill_chunk_ms")(ctx) is None
    # gate and up: 90_678_784 bytes each, down 180_198_912 (B=32), all
    # bound by bytes at 819 GB/s; over 48 calls of 0.5 ms
    need = 16 * (2 * 90_678_784 + 180_198_912) / 819e9
    assert load_reader("lutmu_roofline")(ctx) == pytest.approx(
        100 * need / (48 * 0.5e-3))


def test_excerpt_reads_back_as_the_trace_it_cuts(tmp_path):
    import json

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    for name in ("bench.step", "bench.stamp", "bench.step"):
        with jax.profiler.TraceAnnotation(name):
            jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    whole = trace.reduce(str(tmp_path))
    cut = json.loads(json.dumps(trace.excerpt(str(tmp_path), 60.0)))
    again = trace.reduce_planes(trace.planes_from_json(cut))
    assert [n for n, _, _ in again.host] == [
        "bench.step", "bench.stamp", "bench.step"]
    assert again.host == whole.host and again.window_ns == whole.window_ns
    assert trace.excerpt(str(tmp_path), 0.0)["planes"] == []


def test_readers_on_a_trace_recorded_on_the_chip():
    """0.6 s of a traced ``dense-batch`` run on a TPU v5e, cut by
    ``trace.excerpt``: the program names the readers match are the ones
    the chip writes, and the readers give what the raw events say."""
    import json
    import re
    import types
    from pathlib import Path

    from benchmarks.chip.harness import load_reader

    raw = json.loads((Path(__file__).parent / "data" / "trace"
                      / "dense-batch.json").read_text())
    r = trace.reduce_planes(trace.planes_from_json(raw))
    names = {re.sub(r"\(\d+\)$", "", n) for n, _, _ in r.modules[0]}
    assert {"jit__decode", "jit_sample_tokens"} <= names
    assert list(r.ops) == [0] and len(r.ops[0]) > 1000

    mods = [ln for p in raw["planes"] if p["name"] == "/device:TPU:0"
            for ln in p["lines"] if ln["name"] == "XLA Modules"][0]
    # the reduction keeps what starts before the last host span ends
    decodes = [d for n, s, d in mods["events"]
               if n.startswith("jit__decode") and s < r.window_ns[1]]
    ctx = types.SimpleNamespace(trace=r)
    assert len(decodes) == 3
    assert load_reader("decode_step_ms")(ctx) == pytest.approx(
        sum(decodes) / len(decodes) * 1e-6)
    assert 30 < load_reader("decode_step_ms")(ctx) < 45

    ops = [ln for p in raw["planes"] if p["name"] == "/device:TPU:0"
           for ln in p["lines"] if ln["name"] == "XLA Ops"][0]["events"]
    end = r.window_ns[1]
    busy = trace.union_ns([(s, min(s + d, end)) for _, s, d in ops
                           if s < end])
    idle = load_reader("device_idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - busy / r.window_ns[1]))
    assert 0 < idle < 100
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= trace.TOP
    assert {n for n, _ in b["idle_gaps"]} <= {"bench.step", "bench.stamp",
                                              "bench.submit", "bench.wait",
                                              "untraced"}
