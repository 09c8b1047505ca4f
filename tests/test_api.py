"""API suite: the ``load_engine`` factory and the ``RequestHandle``
contract.

Pins:

  * ``load_engine`` sniffs artifact vs bundle sources and picks the
    paged / fixed-slot / speculative engine, the paged-or-fixed choice
    made from the model family alone;
  * ``submit()`` takes a frozen :class:`SamplingParams` and keyword-only
    options, and returns a :class:`RequestHandle` with the shared
    lifecycle surface;
  * ``repro.serving.__all__`` is the supported surface and imports clean.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as MD
from repro.serving import (FixedSlotEngine, RequestHandle, SamplingParams,
                           ServeEngine, SpeculativeEngine, load_engine)


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
    return cfg, MD.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory):
    """One compiled amm_lm artifact dir + one target/draft bundle dir."""
    from repro.compiler import compile_lm_amm, compile_lm_bundle

    cfg = _tiny_cfg(amm=True)
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    calib = np.random.default_rng(0).integers(0, 64, (2, 8))
    root = tmp_path_factory.mktemp("artifacts")
    res = compile_lm_amm(params, cfg, calib, out=str(root / "lm"))
    compile_lm_bundle(params, cfg, calib, spec_k=2, out=str(root / "bundle"))
    return cfg, params, root, res.artifact


# ---------------------------------------------------------------------------
# load_engine: source sniffing and the family's engine.
# ---------------------------------------------------------------------------

# the paged engine's options the fixed-slot engine drops
PAGED_ONLY = dict(page_size=4, prefill_chunk=4, num_pages=30,
                  prefix_cache=False, verify_backend="scan")


@pytest.mark.parametrize("arch,paged", [
    ("qwen3-14b", True),              # dense
    ("mamba2-370m", False),           # ssm
    ("jamba-1.5-large-398b", False),  # hybrid
    ("whisper-tiny", False),          # audio enc-dec
])
def test_load_engine_none_source_family_dispatch(arch, paged):
    """``source=None`` serves a family with a paged KV layout through
    :class:`ServeEngine` and every other family through
    :class:`FixedSlotEngine`; ``max_batch`` reaches either engine, and
    the fixed-slot path drops the paged-only options."""
    cfg = get_config(arch, reduced=True)
    assert MD.supports_paged(cfg) == paged
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    eng = load_engine(None, params, cfg, max_batch=3, max_len=32,
                      **PAGED_ONLY)
    if paged:
        assert type(eng) is ServeEngine
        assert eng.max_batch == 3
        assert (eng.page_size, eng.prefill_chunk) == (4, 4)
        assert eng.kv.num_pages == 30 and eng.verify_backend == "scan"
    else:
        assert type(eng) is FixedSlotEngine
        assert eng.slots == 3
        with pytest.raises(ValueError, match="FixedSlotEngine"):
            ServeEngine(params, cfg)
    with pytest.raises(ValueError, match="bundle"):
        load_engine(None, params, cfg, speculative=True)


def test_load_engine_artifact_path(artifacts):
    cfg, params, root, _ = artifacts
    eng = load_engine(root / "lm", params, cfg, max_batch=2, max_len=64)
    assert isinstance(eng, ServeEngine)
    assert eng.cfg.amm.enabled  # the artifact's LUT-MU path is spliced in
    with pytest.raises(ValueError, match="bundle"):
        load_engine(root / "lm", params, cfg, speculative=True)


def test_load_engine_bundle_path(artifacts):
    cfg, params, root, _ = artifacts
    eng = load_engine(root / "bundle", params, cfg, max_batch=2, max_len=64)
    assert isinstance(eng, SpeculativeEngine)
    assert eng.spec_k == 2  # manifest-recorded suggestion
    # speculative=False serves the bundle's target half on the plain engine
    eng = load_engine(root / "bundle", params, cfg, speculative=False,
                      max_batch=2, max_len=64)
    assert isinstance(eng, ServeEngine)
    assert not isinstance(eng, SpeculativeEngine)


def test_load_engine_artifact_objects(artifacts):
    from repro.compiler.artifact import load_bundle

    cfg, params, root, art = artifacts
    eng = load_engine(art, params, cfg, max_batch=2, max_len=64)
    assert isinstance(eng, ServeEngine) and eng.cfg.amm.enabled
    target, draft, _ = load_bundle(root / "bundle")
    eng = load_engine((target, draft), params, cfg, spec_k=2, max_batch=2,
                      max_len=64)
    assert isinstance(eng, SpeculativeEngine)
    with pytest.raises(ValueError, match="target, draft"):
        load_engine((target,), params, cfg)
    with pytest.raises(TypeError, match="unsupported source"):
        load_engine(42, params, cfg)


# ---------------------------------------------------------------------------
# RequestHandle: the shared per-request surface.
# ---------------------------------------------------------------------------


def test_handle_lifecycle_paged(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_batch=1, max_len=64, prefill_chunk=4)
    a = eng.submit([1, 2, 3], max_new_tokens=4)
    b = eng.submit([7, 5], max_new_tokens=4)  # queued behind a
    assert isinstance(a, RequestHandle)
    assert a.status == "queued" and b.status == "queued"
    assert a.request_id != b.request_id
    eng.step()
    assert a.status == "running"
    assert a.tokens() == a.generated[:]  # snapshot, not alias
    got = a.result()
    assert a.status == "done" and a.done and got == a.generated
    assert b.result() and b.status == "done"
    assert not eng.has_work
    # back-compat delegation: pre-handle call sites read request attrs
    assert a.uid == a.request_id and a.prompt == [1, 2, 3]
    assert "done" in repr(a)


def test_handle_cancel(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_batch=1, max_len=64)
    a = eng.submit([1, 2, 3], max_new_tokens=4)
    b = eng.submit([7, 5], max_new_tokens=4)
    assert b.cancel() is True
    assert b.status == "cancelled" and b.cancelled
    assert b.cancel() is False  # already gone
    a.result()
    assert a.status == "done"


def test_handle_lifecycle_fixed_slot(setup):
    cfg, params = setup
    ssm = get_config("mamba2-370m", reduced=True)
    eng = FixedSlotEngine(MD.init_params(ssm, jax.random.PRNGKey(0)), ssm,
                          max_batch=1, max_len=32)
    a = eng.submit([1, 2, 3], max_new_tokens=3)
    b = eng.submit([4, 5], max_new_tokens=3)
    assert a.status == "queued"
    assert b.cancel() and b.status == "cancelled"
    assert a.result() == a.generated and a.status == "done"


def test_handle_result_budget(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_batch=1, max_len=64)
    h = eng.submit([1, 2, 3], max_new_tokens=8)
    with pytest.raises(RuntimeError, match="steps exhausted"):
        h.result(max_steps=2)
    assert h.result() == h.generated  # default budget drains fine


def test_handle_async_stream(setup):
    import asyncio

    cfg, params = setup
    eng = ServeEngine(params, cfg, max_batch=2, max_len=64)
    ref = ServeEngine(params, cfg, max_batch=2, max_len=64)
    want = ref.submit([1, 2, 3], max_new_tokens=6).result()

    async def collect():
        h = eng.submit([1, 2, 3], max_new_tokens=6)
        return [t async for t in h.stream()]

    assert asyncio.run(collect()) == want


# ---------------------------------------------------------------------------
# submit(): frozen SamplingParams and keyword-only options.
# ---------------------------------------------------------------------------


def test_submit_rejects_bad_kwargs(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_batch=1, max_len=64)
    with pytest.raises(TypeError, match="unexpected keyword"):
        eng.submit([1, 2, 3], temperatur=0.9)  # typo must not pass silently
    with pytest.raises(TypeError, match="unexpected keyword"):
        eng.submit([1, 2, 3], temperature=0.9)  # sampling=SamplingParams
    with pytest.raises(TypeError, match="unexpected keyword"):
        eng.submit([1, 2, 3], sampling=SamplingParams(), temperature=0.9)


def test_all_exports_resolve():
    import repro.serving as srv

    for name in srv.__all__:
        assert getattr(srv, name, None) is not None, name
    assert sorted(set(srv.__all__)) == sorted(srv.__all__)
