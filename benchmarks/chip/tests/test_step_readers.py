"""The readers of the program's step records (``step_host_ms``,
``token_wait_ms``) on a step log worked by hand, and through a traced
run of a tiny cell on the CPU.

Run by path: ``python -m pytest benchmarks/chip/tests``."""
import types
from pathlib import Path

import pytest

from benchmarks.chip import harness
from benchmarks.chip.harness import load_reader

DATA = Path(__file__).resolve().parent / "data"


def _step(num, t0, t1, phases):
    from repro.serving.obs import StepRecord

    return StepRecord(num, t0, t1, list(phases))


def _ctx(steps, t_open=10.0, t_close=20.0):
    log = types.SimpleNamespace(steps=steps)
    window = types.SimpleNamespace(t_open=t_open, t_close=t_close)
    return types.SimpleNamespace(window=window, log=log)


def test_readers_on_a_hand_built_step_log():
    steps = [
        # before the window: not counted
        _step(1, 9.0, 9.5, [("serve.tokens", 9.1, 9.4, "decode")]),
        # a decode step: 0.2 s, of which 0.15 s waiting for tokens
        _step(2, 10.0, 10.2, [("serve.schedule", 10.0, 10.01, None),
                              ("serve.decode", 10.01, 10.03, None),
                              ("serve.sample", 10.03, 10.04, None),
                              ("serve.tokens", 10.04, 10.19, "decode"),
                              ("serve.retire", 10.19, 10.2, None)]),
        # a final prefill chunk, then a decode: 0.5 s, of which 0.1 s
        # after the prefill and 0.3 s after the decode
        _step(3, 11.0, 11.5, [("serve.prefill", 11.0, 11.02, None),
                              ("serve.tokens", 11.02, 11.12, "prefill"),
                              ("serve.decode", 11.12, 11.15, None),
                              ("serve.tokens", 11.15, 11.45, "decode")]),
        # a step with no wait (a non-final prefill chunk)
        _step(4, 12.0, 12.03, [("serve.prefill", 12.0, 12.03, None)]),
        # after the window: not counted
        _step(5, 20.5, 21.0, [("serve.tokens", 20.6, 20.9, "decode")]),
    ]
    ctx = _ctx(steps)
    host = (0.05 + 0.1 + 0.03) / 3
    assert load_reader("step_host_ms")(ctx) == pytest.approx(host * 1e3)
    assert load_reader("token_wait_ms")(ctx) == pytest.approx(
        (0.15 + 0.3) / 2 * 1e3)


@pytest.mark.parametrize("name", ["step_host_ms", "token_wait_ms"])
def test_readers_find_nothing_without_step_records(name):
    read = load_reader(name)
    # a recorder of a program that keeps no step records
    no_ring = types.SimpleNamespace(
        window=types.SimpleNamespace(t_open=0.0, t_close=1.0),
        log=types.SimpleNamespace(decodes=[]))
    assert read(no_ring) is None
    assert read(_ctx([])) is None
    # records, but none in the window
    assert read(_ctx([_step(1, 5.0, 5.1, [("serve.tokens", 5.0, 5.1,
                                           "decode")])])) is None


def test_traced_tiny_run_reports_the_step_readers(monkeypatch, tmp_path):
    import json

    monkeypatch.setattr(harness, "_install_cache", lambda: "off")
    bench = json.loads((DATA / "bench.json").read_text())
    bench["per_layer"] = [
        {"name": n, "unit": "ms", "better": "lower", "source": "program_span",
         "layer": "engine / scheduler", "moves": "output_tok_s"}
        for n in ("step_host_ms", "token_wait_ms")]
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(bench))
    out = harness.run("tiny-dense-open", 7, 3.0, True, require_chip=False,
                      bench_path=path, data_dir=DATA)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["step_host_ms"]["value"] > 0
    assert m["token_wait_ms"]["value"] > 0
