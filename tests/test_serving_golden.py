"""Golden-token regression: a deterministic tiny LM artifact (built by the
PR-2 compiler in-test) must decode a fixed prompt set to the checked-in
token streams in ``tests/golden/serving_tokens.json``.

This pins the *whole* pipeline — calibration → int8 LUT quantisation →
artifact pack/load → table splice → paged continuous-batching decode — so
a kernel or serving refactor cannot silently change outputs.  If a change
is *intentionally* supposed to alter tokens, regenerate with::

    REPRO_UPDATE_GOLDEN=1 PYTHONPATH=src python -m pytest \
        tests/test_serving_golden.py

and commit the diff (reviewers then see the semantic change explicitly).
"""
import dataclasses
import json
import os
from pathlib import Path

import jax
import numpy as np
import pytest

GOLDEN_PATH = Path(__file__).parent / "golden" / "serving_tokens.json"

PROMPTS = [[1, 2, 3], [7, 5], [9, 9, 9, 2], [4, 4, 1, 1, 5, 6, 7],
           list(range(1, 18))]
MAX_NEW = 8


def _tiny_artifact(out):
    """Compile the deterministic tiny LM artifact into ``out``; returns
    ``(params, cfg, compile result)``."""
    from repro.compiler import compile_lm_amm
    from repro.configs import get_config
    from repro.models import model as MD

    cfg = get_config("qwen3-14b", reduced=True)
    cfg = dataclasses.replace(cfg, num_layers=2, d_model=64, d_ff=128,
                              vocab_size=64, num_heads=2, num_kv_heads=1,
                              head_dim=32)
    cfg = dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, enabled=True))  # int8 LUTs
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    calib_tokens = np.random.default_rng(0).integers(0, 64, (4, 16))
    res = compile_lm_amm(params, cfg, calib_tokens, out=str(out))
    return params, cfg, res


def _fixed_slot_engine(out, params, cfg, **kw):
    """The fixed-slot oracle serving the artifact at ``out`` (for this
    family ``load_engine`` builds the paged engine)."""
    from repro.compiler.artifact import load_artifact
    from repro.serving import FixedSlotEngine
    from repro.serving.engine import _splice_artifact

    params, cfg = _splice_artifact(load_artifact(out), params, cfg, None)
    return FixedSlotEngine(params, cfg, **kw)


def _decode_streams(tmp_path):
    from repro.serving import load_engine

    out = tmp_path / "lm_art"
    params, cfg, _ = _tiny_artifact(out)
    eng = load_engine(out, params, cfg, max_batch=2, max_len=64,
                      page_size=16, prefill_chunk=4)
    reqs = [eng.submit(p, max_new_tokens=MAX_NEW) for p in PROMPTS]
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    return {",".join(map(str, r.prompt)): r.generated for r in reqs}


def test_golden_token_streams(tmp_path):
    streams = _decode_streams(tmp_path)
    if os.environ.get("REPRO_UPDATE_GOLDEN") == "1":
        GOLDEN_PATH.parent.mkdir(parents=True, exist_ok=True)
        GOLDEN_PATH.write_text(json.dumps(streams, indent=2) + "\n")
        pytest.skip(f"regenerated {GOLDEN_PATH}")
    assert GOLDEN_PATH.is_file(), (
        f"missing {GOLDEN_PATH}; regenerate with REPRO_UPDATE_GOLDEN=1")
    golden = json.loads(GOLDEN_PATH.read_text())
    assert streams == golden, (
        "token streams drifted from tests/golden/serving_tokens.json — if "
        "this change is intentional, regenerate with REPRO_UPDATE_GOLDEN=1 "
        "and commit the diff")


def test_golden_t0_bitexact_across_all_engines(tmp_path):
    """Greedy is the T=0 special case of sampling — so an explicit
    ``SamplingParams(temperature=0)`` (with a non-zero seed and
    active-looking top-k/top-p, all of which greedy must ignore) has to
    reproduce the golden streams bit-identically through ALL three
    engines: paged, fixed-slot, and speculative."""
    from repro.serving import SamplingParams, load_engine

    if not GOLDEN_PATH.is_file():
        pytest.skip("golden file not generated yet")
    golden = json.loads(GOLDEN_PATH.read_text())

    out = tmp_path / "lm_art"
    params, cfg, res = _tiny_artifact(out)

    # T=0 must make seed/top_k/top_p inert: give them loud values
    t0 = SamplingParams(temperature=0.0, top_k=3, top_p=0.5, seed=1234)
    engines = {
        "paged": load_engine(out, params, cfg, max_batch=2, max_len=64,
                             page_size=16, prefill_chunk=4),
        "fixed": _fixed_slot_engine(out, params, cfg, max_batch=2,
                                    max_len=64),
        "speculative": load_engine(
            (res.artifact, res.artifact), params, cfg, spec_k=3, max_batch=2,
            max_len=64, page_size=16, prefill_chunk=4),
    }
    for name, eng in engines.items():
        reqs = [eng.submit(p, max_new_tokens=MAX_NEW, sampling=t0)
                for p in PROMPTS]
        eng.run_until_drained()
        streams = {",".join(map(str, r.prompt)): r.generated for r in reqs}
        assert streams == golden, (
            f"{name} engine at temperature=0 drifted from the golden "
            f"greedy streams")


@pytest.mark.parametrize("kind", ["paged", "fixed"])
def test_golden_streams_through_both_sampler_programs(tmp_path, kind):
    """The plain engines sample an all-greedy batch with one argmax
    (``greedy_tokens``) and any other batch with the full sampler: the
    golden greedy streams come out of both.  Run alone, the golden
    prompts take only the argmax program; beside one sampled request,
    their rows also go through the full sampler and must not move."""
    from repro.serving import Recorder, SamplingParams, load_engine

    if not GOLDEN_PATH.is_file():
        pytest.skip("golden file not generated yet")
    golden = json.loads(GOLDEN_PATH.read_text())
    out = tmp_path / "lm_art"
    params, cfg, _ = _tiny_artifact(out)
    sampled = SamplingParams(temperature=0.9, top_k=5, top_p=0.8, seed=4)

    for with_sampled in (False, True):
        rec = Recorder(trace=False)
        if kind == "paged":
            eng = load_engine(out, params, cfg, max_batch=2, max_len=64,
                              page_size=16, prefill_chunk=4, recorder=rec)
        else:
            eng = _fixed_slot_engine(out, params, cfg, max_batch=2,
                                     max_len=64, recorder=rec)
        if with_sampled:
            eng.submit([3, 1, 4], max_new_tokens=MAX_NEW, sampling=sampled)
        reqs = [eng.submit(p, max_new_tokens=MAX_NEW) for p in PROMPTS]
        eng.run_until_drained()
        streams = {",".join(map(str, r.prompt)): r.generated for r in reqs}
        assert streams == golden, (kind, with_sampled)
        v = rec.registry.value
        assert v("serve_sample_calls_total", path="greedy") > 0
        assert (v("serve_sample_calls_total", path="sampled") > 0) == \
            with_sampled


@pytest.mark.parametrize("opts", [
    dict(max_batch=1, page_size=16),
    dict(max_batch=3, page_size=4, num_pages=7),
], ids=["one_row", "tight_pool"])
def test_golden_streams_with_one_decode_in_flight(tmp_path, opts):
    """``ServeEngine`` keeps one decode in flight: a decode takes its
    rows' input tokens from the previous decode on the device, and the
    host reads them a step later.  The checked-in greedy streams come
    out unchanged with one row (every decode continues the last) and
    with a pool small enough that requests are swapped out while their
    token is in flight."""
    from repro.serving import Recorder, load_engine

    if not GOLDEN_PATH.is_file():
        pytest.skip("golden file not generated yet")
    golden = json.loads(GOLDEN_PATH.read_text())
    out = tmp_path / "lm_art"
    params, cfg, _ = _tiny_artifact(out)
    swapped_in_flight = []

    class Rec(Recorder):
        def on_evict(self, req, kind):
            super().on_evict(req, kind)
            swapped_in_flight.append(kind == "swap" and req.inflight > 0)

    eng = load_engine(out, params, cfg, max_len=64, prefill_chunk=4,
                      recorder=Rec(), **opts)
    reqs = [eng.submit(p, max_new_tokens=MAX_NEW) for p in PROMPTS]
    eng.run_until_drained()
    streams = {",".join(map(str, r.prompt)): r.generated for r in reqs}
    assert streams == golden
    assert any(swapped_in_flight) == ("num_pages" in opts)
