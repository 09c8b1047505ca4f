"""Fused speculative-verify window attention.

``models/model.py::paged_verify_step`` has to score a ``k+1``-token draft
window against the paged KV cache.  The scan oracle replays one
``paged_decode_step`` per window position, which re-gathers every layer's
logical page view (``pages[page_table]`` — the dominant HBM read of decode)
``W = k+1`` times per layer.  The fused window restructures the step
layer-major: per layer the pages are gathered **once** and every window
position attends against that single view.  Causality needs no sequential
replay — position ``j``'s mask (``kv_pos <= pos + j``) already hides the
later window slots, and masked slots contribute exact zeros — so the W
attends are independent.

Two lowerings, selected by :func:`resolve_impl`:

* ``xla`` (portable, every backend): :func:`verify_window_attend` — a
  ``lax.scan`` over window positions of literally the same
  :func:`decode_attend` the oracle uses, against the hoisted view.  Every
  reduction therefore has the oracle's exact shape and order, which is what
  lets greedy speculative streams stay *bit-identical* while reading the
  pages once.
* ``pallas`` (TPU): :func:`verify_window_attend_pallas` — one kernel
  instance per batch row DMAs the row's pages into VMEM ``block_s``
  positions at a time and computes all W masked attends from the staged
  copy, so the gathered view never materialises in HBM at all.  The int8
  path accumulates in int32 (order-independent → still bit-exact); the
  float path tiles its f32 accumulation and is validated ``allclose``.
  Tile sizes come from the ``verify`` namespace of the
  ``kernels/autotune.py`` cache, budgeted by ``verify_vmem_bytes``; shapes
  whose window footprint cannot fit the VMEM budget fall back to ``xla``.

:func:`decode_attend` itself *lives here* and is re-exported by
``models/attention.py`` — single source of truth, so the decode path, the
scan oracle and the fused window cannot drift.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# Shared with models/attention.py (which imports them from here).
NEG_INF = -1e30
KV_INT8_SCALE = 0.05

VERIFY_IMPLS = ("xla", "pallas")


def resolve_impl(impl: str = "auto") -> str:
    """``auto`` → ``pallas`` on TPU, else the portable ``xla`` lowering."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in VERIFY_IMPLS:
        raise ValueError(
            f"verify attend impl must be 'auto' or one of {VERIFY_IMPLS}, "
            f"got {impl!r}")
    return impl


# ---------------------------------------------------------------------------
# The one masked attention read (moved verbatim from models/attention.py).
# ---------------------------------------------------------------------------


def decode_attend(qg: Array, cache_k: Array, cache_v: Array, pos_b: Array,
                  window: Optional[Array]) -> Array:
    """Masked one-token attention read over a ``(B, S, n_kv, hd)`` cache
    view.  Shared by the slot cache, the paged cache and the fused verify
    window (all via ``models/attention.py``) so the read paths cannot
    drift — the paged engine's bit-identical-token guarantee rests on this
    being literally the same computation.

    qg: (B, 1, n_kv, g, hd); returns (B, 1, n_kv, g, hd) float.
    """
    hd = qg.shape[-1]
    s_max = cache_k.shape[1]
    kv_pos = jnp.arange(s_max)
    valid = kv_pos[None, :] <= pos_b[:, None]  # (B, S_max)
    if window is not None:
        valid = valid & (kv_pos[None, :] > pos_b[:, None] - window)
    scale = 1.0 / np.sqrt(hd)
    if cache_k.dtype == jnp.int8:
        # §Perf-C3: int8 KV cache.  Decode is KV-bandwidth-bound, so halving
        # cache bytes halves the dominant roofline term.  q and the softmax
        # weights are quantised on the fly (they are tiny); the int8×int8
        # dot accumulates in int32 on the MXU and is rescaled afterwards.
        sq = jnp.max(jnp.abs(qg), axis=(-1,), keepdims=True) / 127.0 + 1e-9
        q_i8 = jnp.clip(jnp.round(qg / sq), -127, 127).astype(jnp.int8)
        logits = jax.lax.dot_general(
            q_i8, cache_k,
            (((4,), (3,)), ((0, 2), (0, 2))),  # contract hd; batch b, n_kv
            preferred_element_type=jnp.int32)
        # dims: (b, n_kv, 1(s), g, t) → (b, n_kv, g, s, t)
        logits = logits.transpose(0, 1, 3, 2, 4).astype(jnp.float32)
        logits = logits * (sq.transpose(0, 2, 3, 1, 4) * KV_INT8_SCALE * scale)
        logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        w_i8 = jnp.clip(jnp.round(w * 127.0), 0, 127).astype(jnp.int8)
        out = jax.lax.dot_general(
            w_i8, cache_v,
            (((4,), (1,)), ((0, 1), (0, 2))),  # contract t; batch b, n_kv
            preferred_element_type=jnp.int32)
        # (b, n_kv, g, s, hd) → scale back
        out = out.astype(jnp.float32) * (KV_INT8_SCALE / 127.0)
        out = out.transpose(0, 3, 1, 2, 4)  # (b, s, n_kv, g, hd)
    else:
        # accumulate in f32 via preferred_element_type — casting the
        # (possibly multi-GiB, seq-sharded) cache itself to f32 would
        # materialise a full f32 copy in HBM.
        logits = jnp.einsum("bsngh,btnh->bngst", qg, cache_k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bngst,btnh->bsngh", w.astype(cache_v.dtype),
                         cache_v, preferred_element_type=jnp.float32)
    return out


# ---------------------------------------------------------------------------
# Portable lowering: the whole window against ONE gathered view.
# ---------------------------------------------------------------------------


def verify_window_attend(qg: Array, k_view: Array, v_view: Array,
                         pos: Array, window: Optional[Array]) -> Array:
    """All W window positions attend against one ``(B, S, n_kv, hd)`` view.

    qg: (B, W, n_kv, g, hd); ``pos``: (B,) first window position per row.
    Position ``j`` reads with the mask ``kv_pos <= pos + j`` — a scan over
    positions of the exact :func:`decode_attend` call the oracle makes, so
    the result is bitwise the oracle's for every dtype.  The view is read
    W times but *gathered* zero times here: hoisting the gather out of the
    per-token loop is the whole point.
    """
    w = qg.shape[1]

    def one(_, xs):
        qj, off = xs  # (B, n_kv, g, hd), scalar offset
        out = decode_attend(qj[:, None], k_view, v_view, pos + off, window)
        return None, out[:, 0]

    _, out = jax.lax.scan(
        one, None, (jnp.swapaxes(qg, 0, 1), jnp.arange(w, dtype=jnp.int32)))
    return jnp.swapaxes(out, 0, 1)


# ---------------------------------------------------------------------------
# Pallas kernel: page gather + all W attends, staged through VMEM.
# ---------------------------------------------------------------------------


def _window_offsets(rows: int, g: int, s_len: int) -> Array:
    """``(rows, s_len)`` int32: window slot ``r // g`` of each q row.

    A compare-sum instead of an integer division, which the TPU vector
    unit lacks."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, s_len), 0)
    off = jnp.zeros((rows, s_len), jnp.int32)
    for j in range(1, rows // g):
        off = off + jnp.where(r >= j * g, 1, 0)
    return off


def _verify_window_kernel(pos_ref, win_ref, pt_ref, q_ref, kp_ref, vp_ref,
                          out_ref, k_s, v_s, sem, *, page_size: int,
                          max_pages: int, block_s: int, g: int,
                          int8_kv: bool):
    """One grid step = one batch row, every KV head.

    ``pos_ref``/``win_ref``/``pt_ref`` are scalar-prefetch (SMEM) refs: the
    row's first window position, the layer's window flag and the flattened
    ``(B·max_pages,)`` page table.  ``q_ref`` is ``(n_kv, W·g, hd)``.

    Stage 1 DMAs the row's K pages ``block_s`` positions at a time into
    ``k_s`` and computes each head's window logits blockwise; after a flat
    masked softmax over the full row (the oracle's reduction shape), stage
    2 re-stages the V pages and accumulates the weighted sum blockwise —
    int32 on the int8 path, so the block decomposition is exact.
    """
    row = pl.program_id(0)
    s_len = max_pages * page_size
    n_blocks = s_len // block_s
    pages_per_block = block_s // page_size
    nkv, rows, hd = q_ref.shape
    scale = 1.0 / np.sqrt(hd)
    acc_t = jnp.int32 if int8_kv else jnp.float32

    def stage(pages_ref, scratch, blk):
        def copy(p):
            phys = pt_ref[row * max_pages + blk * pages_per_block + p]
            return pltpu.make_async_copy(
                pages_ref.at[phys],
                scratch.at[pl.ds(p * page_size, page_size)], sem)

        def start(p, c):
            copy(p).start()
            return c

        def wait(p, c):
            copy(p).wait()
            return c

        jax.lax.fori_loop(0, pages_per_block, start, 0)
        jax.lax.fori_loop(0, pages_per_block, wait, 0)

    qs, sqs = [], []
    for h in range(nkv):
        q = q_ref[h]  # (W·g, hd) f32
        if int8_kv:
            sq = jnp.max(jnp.abs(q), axis=-1, keepdims=True) / 127.0 + 1e-9
            qs.append(jnp.clip(jnp.round(q / sq), -127, 127).astype(jnp.int8))
            sqs.append(sq)
        else:
            qs.append(q.astype(k_s.dtype))

    # -- QK: blockwise over the staged view, logits kept whole ------------
    parts = [[] for _ in range(nkv)]
    for blk in range(n_blocks):
        stage(kp_ref, k_s, blk)
        for h in range(nkv):
            lg = jax.lax.dot_general(
                qs[h], k_s[:, h, :], (((1,), (1,)), ((), ())),
                preferred_element_type=acc_t)  # (W·g, block_s)
            if int8_kv:
                lg = lg.astype(jnp.float32) * (sqs[h] * KV_INT8_SCALE * scale)
            else:
                lg = lg * scale
            parts[h].append(lg)

    # -- flat masked softmax over the full row (oracle reduction shape) ---
    pos = pos_ref[row]
    win = win_ref[0]
    kv_pos = jax.lax.broadcasted_iota(jnp.int32, (rows, s_len), 1)
    pj = pos + _window_offsets(rows, g, s_len)
    valid = (kv_pos <= pj) & (kv_pos > pj - win)  # (W·g, S)
    wgts = []
    for h in range(nkv):
        logits = (jnp.concatenate(parts[h], axis=-1) if n_blocks > 1
                  else parts[h][0])
        wgt = jax.nn.softmax(jnp.where(valid, logits, NEG_INF), axis=-1)
        if int8_kv:
            wgt = jnp.clip(jnp.round(wgt * 127.0), 0, 127).astype(jnp.int8)
        else:
            wgt = wgt.astype(v_s.dtype)
        wgts.append(wgt)

    # -- AV: blockwise, int32/f32 accumulate ------------------------------
    accs = [None] * nkv
    for blk in range(n_blocks):
        stage(vp_ref, v_s, blk)
        for h in range(nkv):
            part = jax.lax.dot_general(
                wgts[h][:, blk * block_s:(blk + 1) * block_s], v_s[:, h, :],
                (((1,), (0,)), ((), ())), preferred_element_type=acc_t)
            accs[h] = part if accs[h] is None else accs[h] + part
    for h in range(nkv):
        acc = accs[h]
        if int8_kv:
            acc = acc.astype(jnp.float32) * (KV_INT8_SCALE / 127.0)
        out_ref[h] = acc


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def verify_window_attend_pallas(qg: Array, k_pages: Array, v_pages: Array,
                                page_table: Array, pos: Array,
                                window: Array, *, block_s: int,
                                interpret: bool = False) -> Array:
    """TPU lowering: gather + all W attends in one kernel per batch row.

    qg: (B, W, n_kv, g, hd); k_pages/v_pages: (P, page_size, n_kv, hd)
    physical pages (stay in HBM — ``memory_space=ANY``); page_table:
    (B, max_pages) trash-padded; pos: (B,); window: scalar int32 (the
    layer's window flag, ``2**30`` sentinel = global).  Returns
    (B, W, n_kv, g, hd) f32.  ``block_s`` (a multiple of ``page_size``
    dividing the view length) sets how many KV positions are resident in
    VMEM at once — resolved via ``autotune.get_verify_tiles``.  The
    position, window and page table ride in as scalar prefetch (SMEM), so
    the page DMAs index HBM from scalar registers.
    """
    b, w, nkv, g, hd = qg.shape
    ps = k_pages.shape[1]
    max_pages = page_table.shape[1]
    s_len = max_pages * ps
    if block_s % ps or s_len % block_s:
        raise ValueError(
            f"block_s={block_s} must be a page_size={ps} multiple dividing "
            f"the view length {s_len}")
    # head-major q rows: row r of head h is window slot r // g, group r % g
    q_rows = jnp.transpose(qg.astype(jnp.float32), (0, 2, 1, 3, 4)).reshape(
        b, nkv, w * g, hd)
    kernel = functools.partial(
        _verify_window_kernel, page_size=ps, max_pages=max_pages,
        block_s=block_s, g=g, int8_kv=k_pages.dtype == jnp.int8)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, nkv, w * g, hd),
                             lambda i, *_: (i, 0, 0, 0)),      # q
                pl.BlockSpec(memory_space=pl.ANY),          # k pages
                pl.BlockSpec(memory_space=pl.ANY),          # v pages
            ],
            out_specs=pl.BlockSpec((None, nkv, w * g, hd),
                                   lambda i, *_: (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_s, nkv, hd), k_pages.dtype),
                pltpu.VMEM((block_s, nkv, hd), v_pages.dtype),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nkv, w * g, hd), jnp.float32),
        interpret=interpret,
        name="verify_window",
    )(jnp.asarray(pos, jnp.int32).reshape(b),
      jnp.asarray(window, jnp.int32).reshape(1),
      page_table.astype(jnp.int32).reshape(-1), q_rows, k_pages, v_pages)
    return jnp.transpose(out.reshape(b, nkv, w, g, hd), (0, 2, 1, 3, 4))
