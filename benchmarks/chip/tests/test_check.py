"""The check that decides ``correct``, driven through a whole run.

Each test runs the harness on the CPU at a tiny size (``data/``), with
the look for a chip skipped: once as it is, where it must read correct,
and once with the served path broken underneath in each way a one-chip
serving cell can be broken, where it must read not correct.  The
control test reads the reference computed in fp8 in the program's place
on the same sample, and sees the limits reject it.

Run by path: ``python -m pytest benchmarks/chip/tests``."""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import check, harness, spec, traffic, weights

DATA = Path(__file__).resolve().parent / "data"
SECONDS = 3.0


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    # tests never write a compile cache
    monkeypatch.setattr(harness, "_install_cache", lambda: "off")


def run(cell, seed=7, traced=False, fault=None):
    return harness.run(cell, seed, SECONDS, traced, require_chip=False,
                       bench_path=DATA / "bench.json", data_dir=DATA,
                       fault=fault)


@pytest.mark.parametrize("cell", ["tiny-lutmu-open", "tiny-lutmu-closed",
                                  "tiny-dense-open"])
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    for name in ("output_tok_s", "itl_p95_ms", "setup_s"):
        assert out["metrics"][name]["value"] > 0


def _token_altered(monkeypatch):
    from repro.serving import engine as E

    orig = E._sample_batch

    def fault(eng):
        monkeypatch.setattr(E, "_sample_batch", lambda *a: (
            orig(*a) + 1) % eng.cfg.vocab_size)
    return fault


def _state_unchanged(monkeypatch):
    def fault(eng):
        orig = eng._decode

        def decode(params, tok, pos, table, cache):
            kept = jax.tree.map(jnp.copy, cache)
            return orig(params, tok, pos, table, cache)[0], kept
        eng._decode = decode
    return fault


def _half_batch(monkeypatch):
    def fault(eng):
        orig = eng._decode

        def decode(*args):
            logits, cache = orig(*args)
            return logits.at[1::2].set(0.0), cache  # every other row
        eng._decode = decode
    return fault


@pytest.mark.parametrize("make", [_token_altered, _state_unchanged,
                                  _half_batch])
@pytest.mark.parametrize("cell", ["tiny-lutmu-open", "tiny-dense-open"])
def test_fault_is_caught(cell, make, monkeypatch):
    out = run(cell, fault=make(monkeypatch))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("config", ["tiny-lutmu", "tiny-dense"])
def test_control_is_rejected(config):
    s = spec.load(config, DATA / "configs")
    mix = traffic.load("tiny-open", DATA / "traffic")
    params = weights.make_params(s, 3)
    eng = harness.build_engine(s, params)
    harness.warm_up(eng, s, s.vocab)
    w = harness.serve_window(eng, mix, 3, SECONDS, s.vocab)
    picked = check.sample([r for r in w.served if r.done_at], s, 3)
    program = check.numbers(check.gaps(params, s, picked), s)
    control = check.numbers(check.gaps(params, s, picked, control="fp8"), s)
    assert all(v["value"] <= v["limit"] for v in program.values()), program
    assert any(v["value"] > v["limit"] for v in control.values()), control


def test_traced_run_reports_per_layer_metrics():
    out = run("tiny-lutmu-open", traced=True)
    assert out["correct"]
    assert "decode_rows_mean" in out["metrics"]
    assert "busy_s" in out["device"] and "window_s" in out["device"]
    # no device plane on the CPU: device readers find nothing, and say so
    assert "device_idle_share" not in out["metrics"]
