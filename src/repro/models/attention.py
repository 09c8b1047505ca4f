"""GQA attention: training (chunked/flash), prefill, and decode-with-cache.

Design notes:
  * weights are stored **flat** ``(D, H·hd)`` so tensor-parallel sharding
    constraints apply to divisible feature dims even when the head count
    does not divide the mesh axis (e.g. qwen2.5's 40 heads on a 16-way
    model axis);
  * training/prefill attention is **blockwise** (flash-style running
    log-sum-exp over KV chunks) so the (S, S) logits tensor never
    materialises — required for the 32k-prefill dry-run cells to fit;
  * decode consumes a KV cache of shape (B, S_max, n_kv, hd) and supports
    sliding-window masking (gemma3/mixtral local layers).
"""
from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune as AT
from repro.kernels import fused_verify as FV
from repro.models import layers as L
from repro.models.config import ModelConfig

Array = jax.Array

# Single source of truth in kernels/fused_verify.py (the fused verify
# window shares the mask/softmax/rescale math bit-for-bit); re-exported
# here because every cache path builds on them.
NEG_INF = FV.NEG_INF

# §Perf-C3: static dequant scale for the int8 KV cache.  In production this
# is calibrated offline per (layer, head) like the LUT quantisation scales;
# a single constant keeps the dry-run program shape identical.
KV_INT8_SCALE = FV.KV_INT8_SCALE

_log = logging.getLogger(__name__)


@functools.lru_cache(maxsize=None)
def _note_verify_fallback(s: int, w: int, nkv: int, g: int, hd: int,
                          kv_dtype: str) -> None:
    """Say once per shape that the verify window left the Pallas kernel."""
    _log.warning(
        "verify window S=%d W=%d n_kv=%d g=%d hd=%d %s: no staging fits the "
        "VMEM budget; using the XLA lowering", s, w, nkv, g, hd, kv_dtype)


def init_attn_params(cfg: ModelConfig, key, dtype=jnp.float32) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d, nq * hd, dtype),
        "wk": L.dense_init(ks[1], d, nkv * hd, dtype),
        "wv": L.dense_init(ks[2], d, nkv * hd, dtype),
        "wo": L.dense_init(ks[3], nq * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(params: dict, x: Array, cfg: ModelConfig,
                 positions: Array) -> Tuple[Array, Array, Array]:
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, nq, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _grouped(q: Array, nkv: int) -> Array:
    """(B, S, Hq, hd) → (B, S, n_kv, group, hd)."""
    b, s, nq, hd = q.shape
    return q.reshape(b, s, nkv, nq // nkv, hd)


def _direct_attention(q: Array, k: Array, v: Array, mask: Array) -> Array:
    """Materialised-logits attention for short sequences.

    q: (B, S, n_kv, g, hd); k/v: (B, T, n_kv, hd); mask: (S, T) additive.
    """
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bsngh,btnh->bngst", q, k).astype(jnp.float32) * scale
    logits = logits + mask[None, None, None]
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bngst,btnh->bsngh", w, v)
    return out


# When True, _chunked_attention unrolls its KV-chunk loop.  The lowered
# production module keeps lax.scan (correct buffer reuse in
# memory_analysis); analysis/scan_cost.py flips this on while measuring
# block bodies so cost_analysis sees every chunk (it counts while bodies
# once regardless of trip count).
UNROLL_CHUNKS = False


class unroll_chunks:
    """Context manager: python-unroll the attention chunk loop."""

    def __enter__(self):
        global UNROLL_CHUNKS
        self._prev = UNROLL_CHUNKS
        UNROLL_CHUNKS = True

    def __exit__(self, *a):
        global UNROLL_CHUNKS
        UNROLL_CHUNKS = self._prev


def _chunked_attention(q: Array, k: Array, v: Array, window,
                       causal: bool, chunk: int = 1024) -> Array:
    """Flash-style blockwise attention (running LSE), pure JAX.

    Iterates KV chunks carrying per-(q-position) running max / sum /
    weighted values.  Memory is O(S·chunk) instead of O(S²).  ``window`` may
    be None, a python int, or a traced scalar (uniform-scan layer stacks pass
    a per-layer window array).
    """
    b, s, nkv, g, hd = q.shape
    t = k.shape[1]
    scale = 1.0 / np.sqrt(hd)
    n_chunks = (t + chunk - 1) // chunk
    t_pad = n_chunks * chunk
    k = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, chunk, nkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, nkv, hd).transpose(1, 0, 2, 3, 4)

    q_pos = jnp.arange(s)
    qf = q.astype(jnp.float32)

    def step(carry, kb, vb, c_idx):
        m, l, acc = carry
        kv_pos = c_idx * chunk + jnp.arange(chunk)
        logits = jnp.einsum("bsngh,btnh->bngst", qf,
                            kb.astype(jnp.float32)) * scale
        valid = kv_pos[None, :] < t
        if causal:
            valid = valid & (kv_pos[None, :] <= q_pos[:, None])
        if window is not None:
            valid = valid & (kv_pos[None, :] > q_pos[:, None] - window)
        logits = jnp.where(valid[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bngst,btnh->bngsh", p, vb.astype(jnp.float32))
        return m_new, l, acc

    carry = (jnp.full((b, nkv, g, s), NEG_INF, jnp.float32),
             jnp.zeros((b, nkv, g, s), jnp.float32),
             jnp.zeros((b, nkv, g, s, hd), jnp.float32))
    if UNROLL_CHUNKS:
        for c_idx in range(n_chunks):
            carry = step(carry, kc[c_idx], vc[c_idx], c_idx)
        m, l, acc = carry
    else:
        def body(c, inp):
            kb, vb, ci = inp
            return step(c, kb, vb, ci), None
        (m, l, acc), _ = jax.lax.scan(
            body, carry, (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B,S,nkv,g,hd)


def attention(
    params: dict,
    x: Array,
    cfg: ModelConfig,
    *,
    positions: Array,
    causal: bool = True,
    window: Optional[Array] = None,  # scalar array or None
    chunked_threshold: int = 4096,
    constrain=lambda x, kind: x,
) -> Array:
    """Self-attention over a full sequence (train / prefill)."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    q, k, v = _project_qkv(params, x, cfg, positions)
    qg = constrain(_grouped(q, nkv), "attn_q")

    if s >= chunked_threshold:
        out = _chunked_attention(qg, k, v, window, causal)
    else:
        pos = jnp.arange(s)
        mask = jnp.zeros((s, s), jnp.float32)
        if causal:
            mask = jnp.where(pos[None, :] <= pos[:, None], 0.0, NEG_INF)
        if window is not None:
            mask = jnp.where(pos[None, :] > pos[:, None] - window, mask, NEG_INF)
        out = _direct_attention(qg, k, v, mask)
    out = out.reshape(b, s, nq * hd)
    return out.astype(x.dtype) @ params["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# KV-cache prefill / decode
# ---------------------------------------------------------------------------


def prefill_with_cache(params: dict, x: Array, cfg: ModelConfig,
                       positions: Array, window: Optional[Array],
                       cache_len: int, constrain=lambda x, kind: x,
                       ) -> Tuple[Array, Tuple[Array, Array]]:
    """Full-sequence attention that also returns the populated KV cache."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, positions)
    qg = constrain(_grouped(q, cfg.num_kv_heads), "attn_q")
    if s >= 4096:
        out = _chunked_attention(qg, k, v, window, True)
    else:
        pos = jnp.arange(s)
        mask = jnp.where(pos[None, :] <= pos[:, None], 0.0, NEG_INF)
        if window is not None:
            mask = jnp.where(pos[None, :] > pos[:, None] - window, mask, NEG_INF)
        out = _direct_attention(qg, k, v, mask)
    out = out.reshape(b, s, -1).astype(x.dtype) @ params["wo"].astype(x.dtype)
    pad = cache_len - s
    k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return out, (k_c, v_c)


def _quantize_kv_int8(k: Array, v: Array) -> Tuple[Array, Array]:
    """§Perf-C3: quantise new KV on write (int8 caches)."""
    k = jnp.clip(jnp.round(k.astype(jnp.float32) / KV_INT8_SCALE), -127, 127)
    v = jnp.clip(jnp.round(v.astype(jnp.float32) / KV_INT8_SCALE), -127, 127)
    return k, v


def _decode_attend(qg: Array, cache_k: Array, cache_v: Array, pos_b: Array,
                   window: Optional[Array]) -> Array:
    """Masked one-token attention read over a ``(B, S, n_kv, hd)`` cache
    view.  Shared by the slot cache, the paged cache (which passes a
    page-table *gather* of its physical pages) and the fused verify window
    so the read paths cannot drift — the paged engine's
    bit-identical-token guarantee rests on this being literally the same
    computation.  The body lives in ``kernels/fused_verify.py`` (which the
    Pallas verify kernel mirrors reduction-for-reduction).

    qg: (B, 1, n_kv, g, hd); returns (B, 1, n_kv, g, hd) float.
    """
    return FV.decode_attend(qg, cache_k, cache_v, pos_b, window)


def _paged_view(k_pool: Array, v_pool: Array, layer: Array,
                page_table: Array, nkv: int, hd: int) -> Tuple[Array, Array]:
    """Gather layer ``layer``'s logical ``(B, S, n_kv, hd)`` view of the
    physical pages.

    THE paged-cache read: decode, chunked prefill and the fused verify
    window all gather through this one helper, so "each step reads its
    pages exactly once" is structural.  ``k_pool``/``v_pool`` are the whole
    ``(L, P, page_size, n_kv, hd)`` pools the layer scan carries; the read
    is one gather at ``[layer, page_table]`` straight out of the pool (no
    ``pool[layer]`` slice first, which XLA may materialise as a copy of
    the layer's pages).  Under a mesh the pages shard over the DP axis and
    XLA inserts the cross-shard collective.
    """
    b = page_table.shape[0]
    k_view = k_pool[layer, page_table].reshape(b, -1, nkv, hd)
    v_view = v_pool[layer, page_table].reshape(b, -1, nkv, hd)
    return k_view, v_view


def decode_step(params: dict, x: Array, cfg: ModelConfig,
                cache_k: Array, cache_v: Array, pos: Array,
                window: Optional[Array]) -> Tuple[Array, Tuple[Array, Array]]:
    """One-token decode against a KV cache.

    x: (B, 1, D); cache_k/v: (B, S_max, n_kv, hd); pos: scalar int32 or a
    (B,) vector of per-row positions (continuous-batching slots decode at
    their own offsets) — the index of the new token (cache row ``b``'s
    ``[0:pos[b]]`` is valid history).
    """
    b, _, d = x.shape
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    positions = pos_b[:, None]
    q, k, v = _project_qkv(params, x, cfg, positions)
    if cache_k.dtype == jnp.int8:
        k, v = _quantize_kv_int8(k, v)
    # per-row scatter: row b writes its new KV at its own position
    rows = jnp.arange(b)
    cache_k = cache_k.at[rows, pos_b].set(k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[rows, pos_b].set(v[:, 0].astype(cache_v.dtype))
    qg = _grouped(q, nkv)  # (B, 1, n_kv, g, hd)
    out = _decode_attend(qg, cache_k, cache_v, pos_b, window)
    out = out.reshape(b, 1, nq * hd).astype(x.dtype)
    return out @ params["wo"].astype(x.dtype), (cache_k, cache_v)


def paged_decode_step(params: dict, x: Array, cfg: ModelConfig,
                      k_pool: Array, v_pool: Array, layer: Array,
                      page_table: Array, pos: Array, window: Optional[Array],
                      write_ok: Optional[Array] = None,
                      ) -> Tuple[Array, Tuple[Array, Array]]:
    """One-token decode of layer ``layer`` against the **paged** KV cache.

    x: (B, 1, D); k_pool/v_pool: the whole (L, P, page_size, n_kv, hd)
    physical page pools (last page is the engine's trash page); layer:
    traced int32 layer index; page_table: (B, max_pages) int32
    logical→physical map, trash-padded; pos: (B,) int32 write index per
    row.  Rows without an active request point their whole page-table row
    at the trash page.  Returns the updated pools.

    ``write_ok`` ((B,) bool, optional) redirects a row's K/V write to the
    trash page — the speculative draft/verify loops use it to mask steps
    past a row's verify window so out-of-budget positions can never touch
    a real page (a ``pos // page_size`` past the table's end would
    otherwise *clamp* onto the row's last real page and corrupt it).
    ``None`` preserves the historical always-write behaviour bit-exactly.

    The new token's K/V is scattered in place into the pool at ``[layer,
    phys, off]`` (one scatter, no copy of the pool), then the layer's
    logical view is gathered (:func:`_paged_view`) and handed to the *same*
    masked read used by the slot cache, so valid positions see
    bit-identical values and the trash/garbage rows are masked to exact
    zeros.
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    positions = pos_b[:, None]
    q, k, v = _project_qkv(params, x, cfg, positions)
    if k_pool.dtype == jnp.int8:
        k, v = _quantize_kv_int8(k, v)
    ps = k_pool.shape[2]
    trash = k_pool.shape[1] - 1
    rows = jnp.arange(b)
    phys = page_table[rows, pos_b // ps]  # (B,) physical page per row
    if write_ok is not None:
        phys = jnp.where(write_ok, phys, trash)
    off = pos_b % ps
    k_pool = k_pool.at[layer, phys, off].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[layer, phys, off].set(v[:, 0].astype(v_pool.dtype))
    k_view, v_view = _paged_view(k_pool, v_pool, layer, page_table, nkv, hd)
    qg = _grouped(q, nkv)
    out = _decode_attend(qg, k_view, v_view, pos_b, window)
    out = out.reshape(b, 1, nq * hd).astype(x.dtype)
    return out @ params["wo"].astype(x.dtype), (k_pool, v_pool)


def paged_verify_window(params: dict, x: Array, cfg: ModelConfig,
                        k_pool: Array, v_pool: Array, layer: Array,
                        page_table: Array, pos: Array, n_valid: Array,
                        window: Optional[Array], attend_impl: str = "auto",
                        ) -> Tuple[Array, Tuple[Array, Array]]:
    """Layer ``layer``'s attention over the whole speculative-verify window.

    x: (B, W, D) — the (already ln1-normalised) hidden states of the
    ``W = k+1`` window tokens; k_pool/v_pool: the whole (L, P, page_size,
    n_kv, hd) pools, written in place and returned; layer: traced int32
    layer index; pos: (B,) first window position per row; n_valid: (B,)
    real tokens in each row's window (the rest scatter to the trash page,
    exactly like ``paged_decode_step``'s ``write_ok``).

    Bit-identical to W successive ``paged_decode_step`` attention blocks
    while gathering the page view **once** instead of W times:

    * Q/K/V are projected per token inside a ``lax.scan`` — every matmul
      sees the oracle's exact ``(B, 1, ·)`` shapes, so XLA cannot re-block
      a reduction differently;
    * all W keys/values scatter in one batched page write (real slots are
      writer-exclusive, trash-slot collisions are never read unmasked);
    * every window position then attends against the single gathered view
      under its own ``kv_pos <= pos + j`` mask — later window slots are
      masked to exact zeros, which is why the W reads need no sequential
      replay (the scan oracle's later-token writes were invisible to
      earlier tokens for the same reason).

    ``attend_impl``: ``auto`` → the Pallas kernel on TPU (pages staged
    through VMEM, never materialising the view in HBM), the portable XLA
    lowering elsewhere or when no staging fits the VMEM budget.  The kernel
    takes one layer's pages, so that path slices ``pool[layer]`` out.
    """
    b, w, d = x.shape
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    offs = jnp.arange(w, dtype=jnp.int32)

    def proj(_, xs):
        xj, off = xs  # (B, D), scalar window offset
        q, k, v = _project_qkv(params, xj[:, None], cfg, (pos_b + off)[:, None])
        if k_pool.dtype == jnp.int8:
            k, v = _quantize_kv_int8(k, v)
        return None, (q[:, 0], k[:, 0], v[:, 0])

    _, (qs, ks, vs) = jax.lax.scan(proj, None, (jnp.swapaxes(x, 0, 1), offs))
    q = jnp.swapaxes(qs, 0, 1)                       # (B, W, nq, hd)
    k = jnp.swapaxes(ks, 0, 1).astype(k_pool.dtype)
    v = jnp.swapaxes(vs, 0, 1).astype(v_pool.dtype)

    ps = k_pool.shape[2]
    trash = k_pool.shape[1] - 1
    rows = jnp.arange(b)
    wpos = pos_b[:, None] + offs[None, :]            # (B, W) logical pos
    phys = jnp.where(offs[None, :] < n_valid[:, None],
                     page_table[rows[:, None], wpos // ps], trash)
    off = wpos % ps
    k_pool = k_pool.at[layer, phys, off].set(k)
    v_pool = v_pool.at[layer, phys, off].set(v)

    qg = _grouped(q, nkv)                            # (B, W, n_kv, g, hd)
    impl = FV.resolve_impl(attend_impl)
    tiles = None
    if impl == "pallas":
        s_len = page_table.shape[1] * ps
        tiles = AT.get_verify_tiles(s_len, w, nkv, nq // nkv, hd,
                                    k_pool.dtype, page_size=ps)
        if tiles is None:
            _note_verify_fallback(s_len, w, nkv, nq // nkv, hd,
                                  jnp.dtype(k_pool.dtype).name)
    if tiles is not None:
        win = jnp.asarray(2**30, jnp.int32) if window is None else window
        out = FV.verify_window_attend_pallas(
            qg, k_pool[layer], v_pool[layer], page_table, pos_b, win,
            block_s=tiles.block_s, interpret=AT.default_interpret())
    else:
        k_view, v_view = _paged_view(k_pool, v_pool, layer, page_table,
                                     nkv, hd)
        out = FV.verify_window_attend(qg, k_view, v_view, pos_b, window)

    def proj_o(_, oj):  # (B, n_kv, g, hd) — the oracle's (B, 1, ·) @ wo
        o = oj.reshape(b, 1, nq * hd).astype(x.dtype)
        return None, (o @ params["wo"].astype(x.dtype))[:, 0]

    _, outs = jax.lax.scan(proj_o, None, jnp.swapaxes(out, 0, 1))
    return jnp.swapaxes(outs, 0, 1), (k_pool, v_pool)


def paged_prefill_chunk(params: dict, x: Array, cfg: ModelConfig,
                        start: Array, n_valid: Array,
                        k_pool: Array, v_pool: Array, layer: Array,
                        page_row: Array, window: Optional[Array],
                        ) -> Tuple[Array, Tuple[Array, Array]]:
    """Chunked-prefill attention of layer ``layer`` for ONE request
    against the paged cache.

    x: (1, cs, D) — the chunk's hidden states, right-padded to the engine's
    fixed ``prefill_chunk`` width (one compiled program for every prompt
    length); ``start``: tokens already prefilled (traced scalar);
    ``n_valid`` ≤ cs: real tokens in this chunk; k_pool/v_pool: the whole
    (L, P, page_size, n_kv, hd) pools; layer: traced int32 layer index;
    page_row: (max_pages,) int32, trash-padded.

    Writes the chunk's K/V in place into the pool at ``[layer, phys, off]``
    (padding rows scatter to the trash page) and returns the updated
    pools.  It attends the chunk queries against the gathered logical
    view under the standard causal(+window) mask.  Because masked
    positions contribute exact zeros, every valid row's output is
    bit-identical to the full-sequence prefill's corresponding row — which
    is what lets the differential tests demand exact token equality with
    the fixed-slot engine.
    """
    b, cs, d = x.shape
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    idx = start + jnp.arange(cs)      # logical positions of the chunk
    positions = idx[None]             # (1, cs)
    q, k, v = _project_qkv(params, x, cfg, positions)
    if k_pool.dtype == jnp.int8:
        k, v = _quantize_kv_int8(k, v)
    ps = k_pool.shape[2]
    trash = k_pool.shape[1] - 1
    valid_tok = jnp.arange(cs) < n_valid
    phys = jnp.where(valid_tok, page_row[idx // ps], trash)
    off = idx % ps
    k_pool = k_pool.at[layer, phys, off].set(k[0].astype(k_pool.dtype))
    v_pool = v_pool.at[layer, phys, off].set(v[0].astype(v_pool.dtype))
    # the chunk reads its pages exactly once, through the same gather the
    # decode step and the fused verify window use
    k_view, v_view = _paged_view(k_pool, v_pool, layer, page_row[None],
                                 nkv, hd)
    if k_pool.dtype == jnp.int8:
        # int8 pages: prefill reads the dequantised view in float (mirrors
        # the fixed-slot engine, whose prefill is float regardless)
        k_view = k_view.astype(jnp.float32) * KV_INT8_SCALE
        v_view = v_view.astype(jnp.float32) * KV_INT8_SCALE
    kv_pos = jnp.arange(k_view.shape[1])
    ok = kv_pos[None, :] <= idx[:, None]  # causal over logical positions
    if window is not None:
        ok = ok & (kv_pos[None, :] > idx[:, None] - window)
    mask = jnp.where(ok, 0.0, NEG_INF)    # (cs, S_logical) additive
    qg = _grouped(q, nkv)
    out = _direct_attention(qg, k_view.astype(x.dtype),
                            v_view.astype(x.dtype), mask)
    out = out.reshape(b, cs, nq * hd).astype(x.dtype)
    return out @ params["wo"].astype(x.dtype), (k_pool, v_pool)


# ---------------------------------------------------------------------------
# Cross attention (Whisper decoder → encoder states)
# ---------------------------------------------------------------------------


def init_cross_attn_params(cfg: ModelConfig, key, dtype=jnp.float32) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], d, nq * hd, dtype),
        "wk": L.dense_init(ks[1], d, nkv * hd, dtype),
        "wv": L.dense_init(ks[2], d, nkv * hd, dtype),
        "wo": L.dense_init(ks[3], nq * hd, d, dtype),
    }


def cross_attention(params: dict, x: Array, enc: Array, cfg: ModelConfig,
                    constrain=lambda x, kind: x) -> Array:
    """x: (B, S, D) decoder states; enc: (B, T, D) encoder states."""
    b, s, d = x.shape
    t = enc.shape[1]
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, s, nq, hd)
    k = (enc @ params["wk"].astype(x.dtype)).reshape(b, t, nkv, hd)
    v = (enc @ params["wv"].astype(x.dtype)).reshape(b, t, nkv, hd)
    qg = constrain(_grouped(q, nkv), "attn_q")
    mask = jnp.zeros((s, t), jnp.float32)
    out = _direct_attention(qg, k, v, mask)
    return out.reshape(b, s, nq * hd).astype(x.dtype) @ params["wo"].astype(x.dtype)
