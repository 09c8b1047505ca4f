"""Differential suite for the fused speculative-verify window.

Three layers of pinning, from kernel to model:

1. the portable XLA lowering (``verify_window_attend``) is **bitwise** the
   per-token ``decode_attend`` oracle for every dtype, both compiled — it
   runs that function per position against the hoisted view;
2. the Pallas kernel (interpret mode on CPU) matches the portable lowering
   bitwise on the int8 KV path at *every* staging size (int32 accumulation
   is order-independent) and ``allclose`` on the float path (blockwise f32
   accumulation reorders sums);
3. ``model.paged_verify_step(backend="fused")`` is bitwise the ``scan``
   oracle — logits at every valid window position and every non-trash
   cache page.

Plus the ``verify`` autotune namespace: keying, the VMEM budget arithmetic,
the empty-candidates → portable fallback, and measured persistence.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import autotune as AT
from repro.kernels import fused_verify as FV
from repro.models import attention as A
from repro.models import model as MD


def _mk_paged(seed, *, b=2, max_pages=4, page_size=8, nkv=2, hd=8, w=3,
              g=2, int8=False):
    """Synthetic page pool + trash-padded table + in-range positions."""
    rng = np.random.default_rng(seed)
    n_pages = b * max_pages + 1  # + trash (last physical page)
    trash = n_pages - 1
    if int8:
        kp = jnp.asarray(rng.integers(-127, 128,
                                      (n_pages, page_size, nkv, hd)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128,
                                      (n_pages, page_size, nkv, hd)),
                         jnp.int8)
    else:
        kp = jnp.asarray(rng.normal(size=(n_pages, page_size, nkv, hd)),
                         jnp.float32)
        vp = jnp.asarray(rng.normal(size=(n_pages, page_size, nkv, hd)),
                         jnp.float32)
    # each row owns a distinct page run; trailing entries point at trash
    pt = np.full((b, max_pages), trash, np.int32)
    for i in range(b):
        pt[i] = np.arange(i * max_pages, (i + 1) * max_pages)
    pt = jnp.asarray(pt)
    s_len = max_pages * page_size
    # window must fit: pos + w <= s_len
    pos = jnp.asarray(rng.integers(0, s_len - w, b), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, w, nkv, g, hd)), jnp.float32)
    return q, kp, vp, pt, pos


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("window", [None, 7])
def test_portable_lowering_is_bitwise_the_oracle(int8, window):
    """The window's per-position decode_attend vs W independent
    decode_attend calls on the gathered view, both compiled as the engine
    runs them: bitwise for every dtype and window flag."""
    q, kp, vp, pt, pos = _mk_paged(0, int8=int8)
    nkv, hd = kp.shape[2], kp.shape[3]
    k_view = kp[pt].reshape(pt.shape[0], -1, nkv, hd)
    v_view = vp[pt].reshape(pt.shape[0], -1, nkv, hd)
    win = None if window is None else jnp.asarray(window, jnp.int32)
    got = jax.jit(FV.verify_window_attend)(q, k_view, v_view, pos, win)
    oracle = jax.jit(FV.decode_attend)
    for j in range(q.shape[1]):
        want = oracle(q[:, j:j + 1], k_view, v_view, pos + j, win)
        np.testing.assert_array_equal(
            np.asarray(got[:, j]), np.asarray(want[:, 0]),
            err_msg=f"int8={int8} window={window} j={j}")


@pytest.mark.parametrize("block_s", [8, 16, 32])
def test_pallas_kernel_bitwise_on_int8_at_every_staging(block_s):
    """int32 accumulation is order-independent → the block decomposition
    is exact at every ``block_s``."""
    q, kp, vp, pt, pos = _mk_paged(1, int8=True)
    nkv, hd = kp.shape[2], kp.shape[3]
    k_view = kp[pt].reshape(pt.shape[0], -1, nkv, hd)
    v_view = vp[pt].reshape(pt.shape[0], -1, nkv, hd)
    win = jnp.asarray(2**30, jnp.int32)
    want = FV.verify_window_attend(q, k_view, v_view, pos, None)
    got = FV.verify_window_attend_pallas(q, kp, vp, pt, pos, win,
                                         block_s=block_s, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=f"block_s={block_s}")


@pytest.mark.parametrize("block_s", [8, 32])
def test_pallas_kernel_allclose_on_float(block_s):
    q, kp, vp, pt, pos = _mk_paged(2, int8=False)
    nkv, hd = kp.shape[2], kp.shape[3]
    k_view = kp[pt].reshape(pt.shape[0], -1, nkv, hd)
    v_view = vp[pt].reshape(pt.shape[0], -1, nkv, hd)
    win = jnp.asarray(2**30, jnp.int32)
    want = FV.verify_window_attend(q, k_view, v_view, pos, None)
    got = FV.verify_window_attend_pallas(q, kp, vp, pt, pos, win,
                                         block_s=block_s, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_pallas_kernel_respects_sliding_window():
    """The in-kernel mask is the decode mask: ``pos-window`` slots drop."""
    q, kp, vp, pt, pos = _mk_paged(3, int8=True)
    nkv, hd = kp.shape[2], kp.shape[3]
    k_view = kp[pt].reshape(pt.shape[0], -1, nkv, hd)
    v_view = vp[pt].reshape(pt.shape[0], -1, nkv, hd)
    win = jnp.asarray(5, jnp.int32)
    want = FV.verify_window_attend(q, k_view, v_view, pos, win)
    got = FV.verify_window_attend_pallas(q, kp, vp, pt, pos, win,
                                         block_s=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_block_size_validation():
    q, kp, vp, pt, pos = _mk_paged(4)
    win = jnp.asarray(2**30, jnp.int32)
    with pytest.raises(ValueError, match="block_s"):
        FV.verify_window_attend_pallas(q, kp, vp, pt, pos, win,
                                       block_s=12, interpret=True)
    with pytest.raises(ValueError, match="block_s"):
        FV.verify_window_attend_pallas(q, kp, vp, pt, pos, win,
                                       block_s=64, interpret=True)


def test_resolve_impl():
    assert FV.resolve_impl("xla") == "xla"
    assert FV.resolve_impl("pallas") == "pallas"
    assert FV.resolve_impl("auto") in FV.VERIFY_IMPLS
    with pytest.raises(ValueError, match="verify attend impl"):
        FV.resolve_impl("cuda")


# ---------------------------------------------------------------------------
# Layer and model level: fused window vs the scan oracle, bitwise.
# ---------------------------------------------------------------------------


def _tiny_cfg(int8_kv=False):
    cfg = get_config("qwen3-14b", reduced=True)
    cfg = dataclasses.replace(cfg, num_layers=2, d_model=64, d_ff=128,
                              vocab_size=64, num_heads=2, num_kv_heads=1,
                              head_dim=32)
    if int8_kv:
        cfg = dataclasses.replace(
            cfg, amm=dataclasses.replace(cfg.amm, enabled=True,
                                         kv_int8=True))
    return cfg


def _mk_model_state(cfg, *, b=2, max_pages=3, page_size=8, w=3,
                    kv_dtype=jnp.float32, seed=0):
    params = MD.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    n_pages = b * max_pages + 1
    cache = MD.init_paged_cache(cfg, n_pages, page_size, kv_dtype)
    trash = n_pages - 1
    pt = np.full((b, max_pages), trash, np.int32)
    for i in range(b):
        pt[i] = np.arange(i * max_pages, (i + 1) * max_pages)
    rng = np.random.default_rng(seed + 1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, w)), jnp.int32)
    pos = jnp.asarray([5, 2], jnp.int32)[:b]
    n_valid = jnp.asarray([w, w - 1], jnp.int32)[:b]
    # prefill some real KV below each row's pos so the window attends over
    # genuine history, not just zeros
    warm = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)
    for p in range(int(pos.max())):
        ok = jnp.asarray([p < int(pos[i]) for i in range(b)])
        _, cache = MD.paged_decode_step(
            params, warm, jnp.minimum(jnp.asarray(p), pos), jnp.asarray(pt),
            cache, cfg, compute_dtype=jnp.float32, write_ok=ok)
    return params, cache, jnp.asarray(pt), tokens, pos, n_valid, trash


@pytest.mark.parametrize("int8_kv", [False, True])
def test_fused_step_bitwise_matches_scan_oracle(int8_kv):
    """The tentpole contract at the model boundary: logits at every valid
    window position and every non-trash cache page are bitwise equal."""
    cfg = _tiny_cfg(int8_kv)
    kv_dtype = jnp.int8 if int8_kv else jnp.float32
    params, cache, pt, tokens, pos, n_valid, trash = _mk_model_state(
        cfg, kv_dtype=kv_dtype)
    cache2 = jax.tree.map(jnp.copy, cache)
    ls, cs = MD.paged_verify_step(params, tokens, pos, n_valid, pt, cache,
                                  cfg, compute_dtype=jnp.float32,
                                  backend="scan")
    lf, cf = MD.paged_verify_step(params, tokens, pos, n_valid, pt, cache2,
                                  cfg, compute_dtype=jnp.float32,
                                  backend="fused")
    for i in range(tokens.shape[0]):
        nv = int(n_valid[i])
        np.testing.assert_array_equal(
            np.asarray(ls[i, :nv]), np.asarray(lf[i, :nv]),
            err_msg=f"row {i} int8={int8_kv}")
    for kk in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(cs[kk][:, :trash]), np.asarray(cf[kk][:, :trash]),
            err_msg=f"cache {kk} int8={int8_kv}")


def test_fused_step_respects_n_valid_writes():
    """Invalid window slots scatter to trash under both backends — the
    real pages see only ``n_valid`` writes per row."""
    cfg = _tiny_cfg()
    params, cache, pt, tokens, pos, _, trash = _mk_model_state(cfg)
    n_valid = jnp.asarray([1, 0], jnp.int32)
    cache2 = jax.tree.map(jnp.copy, cache)
    _, cs = MD.paged_verify_step(params, tokens, pos, n_valid, pt, cache,
                                 cfg, compute_dtype=jnp.float32,
                                 backend="scan")
    _, cf = MD.paged_verify_step(params, tokens, pos, n_valid, pt, cache2,
                                 cfg, compute_dtype=jnp.float32,
                                 backend="fused")
    for kk in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cs[kk][:, :trash]),
                                      np.asarray(cf[kk][:, :trash]))


def test_paged_verify_window_impl_validation():
    cfg = _tiny_cfg()
    params, cache, pt, tokens, pos, n_valid, _ = _mk_model_state(cfg)
    x = jnp.zeros((2, 3, cfg.d_model), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])  # layer-0 slice
    with pytest.raises(ValueError, match="verify attend impl"):
        A.paged_verify_window(lp["attn"], x, cfg,
                              cache["k"], cache["v"], jnp.int32(0), pt, pos,
                              n_valid, None, attend_impl="tpu")


# ---------------------------------------------------------------------------
# Autotune: the ``verify`` cache namespace and its VMEM budget.
# ---------------------------------------------------------------------------


def test_verify_shape_key_namespaced_and_batch_free():
    k = AT.verify_shape_key("cpu", 128, 4, 2, 4, 64, jnp.int8)
    assert "|verify|" in k and "int8" in k
    assert k != AT.verify_shape_key("cpu", 128, 4, 2, 4, 64, jnp.float32)
    assert k != AT.verify_shape_key("tpu", 128, 4, 2, 4, 64, jnp.int8)


def test_verify_vmem_budget_gates_candidates():
    # generous budget: every lane-legal power-of-2 page multiple dividing S
    # (a multiple of 128, or S itself), largest first (fewest DMA
    # round-trips)
    cands = AT.verify_candidate_tiles(512, 4, 2, 4, 64, 1, 16,
                                      budget_bytes=1 << 30)
    assert [t.block_s for t in cands] == [512, 256, 128]
    for t in cands:
        assert AT.verify_vmem_bytes(t, 512, 4, 2, 4, 64, 1) <= (
            (1 << 30) * AT.VMEM_FRACTION)
    assert [t.block_s for t in AT.verify_candidate_tiles(
        64, 4, 2, 4, 64, 1, 16, budget_bytes=1 << 30)] == [64]
    # the logits term (W·n_kv·g·S·4) alone blows a tiny budget: no staging
    # fits and the caller must take the portable lowering
    assert AT.verify_candidate_tiles(128, 4, 2, 4, 64, 1, 16,
                                     budget_bytes=4096) == []
    assert AT.verify_heuristic_tiles(128, 4, 2, 4, 64, 1, 16,
                                     budget_bytes=4096) is None


def test_get_verify_tiles_cache_hit_and_fallback(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    cache = AT.AutotuneCache(tmp_path / "tune.json")
    key = AT.verify_shape_key("cpu", 64, 3, 2, 2, 8, jnp.int8)
    cache.put(key, AT.VerifyTileConfig(16), us=1.0)
    hit = AT.get_verify_tiles(64, 3, 2, 2, 8, jnp.int8, page_size=8,
                              platform="cpu", cache=cache)
    assert hit == AT.VerifyTileConfig(16)
    # un-cached shape: heuristic (largest in-budget candidate)
    t = AT.get_verify_tiles(64, 3, 2, 2, 8, jnp.float32, page_size=8,
                            platform="cpu", cache=cache)
    assert t is not None and t.block_s == 64
    # shapes whose window footprint cannot fit → None (portable fallback)
    monkeypatch.setattr(AT, "VMEM_BUDGET_BYTES", 2048)
    assert AT.get_verify_tiles(64, 3, 2, 2, 8, jnp.float32, page_size=8,
                               platform="cpu", cache=cache) is None


def test_measured_verify_tiles_persist_and_rehit(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    cache = AT.AutotuneCache(tmp_path / "tune.json")
    shape = dict(s=32, w=2, nkv=1, g=2, hd=8)
    got = AT.get_verify_tiles(*shape.values(), jnp.int8, page_size=8,
                              platform="cpu", allow_measure=True,
                              cache=cache)
    assert got is not None
    # measurement persisted: a FRESH cache object on the same path re-hits
    # without measuring (candidates monkeypatched away would now raise)
    cache2 = AT.AutotuneCache(tmp_path / "tune.json")
    cache2.load()
    monkeypatch.setattr(AT, "measure_verify_tiles",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("re-measured a cached shape")))
    rehit = AT.get_verify_tiles(*shape.values(), jnp.int8, page_size=8,
                                platform="cpu", allow_measure=True,
                                cache=cache2)
    assert rehit == got
