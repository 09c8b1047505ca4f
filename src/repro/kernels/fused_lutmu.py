"""Pallas TPU kernel: fused LUT-MU (encode + aggregate in one pass).

The flagship kernel — the TPU analogue of the paper's allocator→encoder→
aggregator pipeline with no stage stalls.  Per grid step it

  1. runs the parallel-comparator encode for a (B_t, C_t) tile of split
     values (VPU, no loop-carried dependency), producing one leaf
     indicator per prototype *in registers/VMEM* — integer codes never
     materialise;
  2. contracts each leaf's ``(B_t, C_t)`` indicator with that leaf's
     ``(C_t, N_t)`` LUT slice on the MXU, accumulating over leaves and
     over the C grid axis.

Layout (what Mosaic lowers): codebooks sit on lanes.  ``x`` enters as
``(I, B, C)`` and the thresholds as ``(G-1, C)``, so level ``l``'s split
values are the plain ``(B_t, C_t)`` tile ``x_ref[l]`` and node ``m``'s
thresholds are the sublane row ``thr_ref[m]``.  The leaf masks come from a
level-by-level expansion kept as a Python list of ``(B_t, C_t)`` arrays —
no lane indexing, no stack-and-reshape interleave, no minor-dim merge.
The LUT keeps its stored ``(C, G, N)`` layout; leaf ``g``'s slice is the
strided read ``lut_ref[:, g, :]``.

Grid = (B/B_t, N/N_t, C/C_t) with C innermost so the output tile accumulates
in place.  The encode is recomputed for each N-tile: it is VPU-cheap
relative to the MXU contraction, and recompute buys us never spilling the
one-hot to HBM — the same compute-for-bandwidth trade the paper makes with
its comparator arrays.  ``C_t`` is a multiple of 128 or the whole ``C``
(the lane rule); ``kernels/autotune.py`` budgets the VMEM footprint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def leaf_masks(x_ref, thr_ref, depth: int) -> list:
    """``2**depth`` boolean ``(B_t, C_t)`` leaf indicators, leaf order.

    ``x_ref``: ``(I, B_t, C_t)`` split values; ``thr_ref``: ``(G-1, C_t)``
    heap-ordered thresholds.  Node ``j`` of a level has children ``2j``
    (``x < thr``) and ``2j+1`` (``x >= thr``), so the final list index is
    the leaf id of :func:`repro.core.maddness.encode`.
    """
    masks = [None]
    for level in range(depth):
        xl = x_ref[level]
        nxt = []
        for j, m in enumerate(masks):
            node = 2**level - 1 + j
            right = xl >= thr_ref[node:node + 1, :]
            left = jnp.logical_not(right)
            if m is not None:
                left = jnp.logical_and(m, left)
                right = jnp.logical_and(m, right)
            nxt += [left, right]
        masks = nxt
    return masks


def _fused_kernel(x_ref, thr_ref, lut_ref, out_ref, *, depth: int, acc_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    oh_dtype = jnp.int8 if acc_dtype == jnp.int32 else lut_ref.dtype
    acc = out_ref[...]
    for g, mask in enumerate(leaf_masks(x_ref, thr_ref, depth)):
        acc += jax.lax.dot_general(
            jnp.where(mask, 1, 0).astype(oh_dtype),
            lut_ref[:, g, :],
            (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype,
        )
    out_ref[...] = acc


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def split_lanes(x_split: Array, thresholds: Array, bp: int,
                cp: int) -> tuple:
    """(B, C, I), (C, G-1) → zero-padded ``(I, bp, cp)``, ``(G-1, cp)``."""
    b, c, _ = x_split.shape
    x_t = jnp.pad(jnp.transpose(x_split, (2, 0, 1)),
                  ((0, 0), (0, bp - b), (0, cp - c)))
    t_t = jnp.pad(thresholds.T, ((0, 0), (0, cp - c)))
    return x_t, t_t


@functools.partial(
    jax.jit,
    static_argnames=("depth", "block_b", "block_n", "block_c", "interpret"),
)
def fused_lutmu_pallas(
    x_split: Array,
    thresholds: Array,
    lut: Array,
    lut_scale: Array,
    lut_offset: Array,
    *,
    depth: int,
    block_b: int = 256,
    block_n: int = 256,
    block_c: int = 128,
    interpret: bool = False,
) -> Array:
    """Fused LUT-MU: split values → approximate matmul output.

    Args:
      x_split: (B, C, I) gathered split-dim values (the pruned package,
        already in cluster order, is ``reshape+transpose`` away — see
        ``core.pruning.pruned_to_split_values``).
      thresholds: (C, 2**I - 1) heap-ordered.
      lut: (C, G, N) float32/bf16 or int8.
      lut_scale / lut_offset: dequant epilogue, () or (N,).

    Returns:
      (B, N) float32.
    """
    b, c, i = x_split.shape
    assert i == depth
    g = 2**depth
    n = lut.shape[-1]
    int_path = lut.dtype == jnp.int8
    acc_dtype = jnp.int32 if int_path else jnp.float32

    bb = min(block_b, _ceil_to(b, 8))
    bn = min(block_n, _ceil_to(n, 128))
    bc = min(block_c, c)
    bp, np_, cp = _ceil_to(b, bb), _ceil_to(n, bn), _ceil_to(c, bc)

    # Padding: padded codebooks hit zero LUT rows → contribute nothing;
    # padded batch rows are sliced off; padded N columns are sliced off.
    x_t, t_t = split_lanes(x_split, thresholds, bp, cp)
    l_p = jnp.pad(lut, ((0, cp - c), (0, 0), (0, np_ - n)))

    out = pl.pallas_call(
        functools.partial(_fused_kernel, depth=depth, acc_dtype=acc_dtype),
        grid=(bp // bb, np_ // bn, cp // bc),
        in_specs=[
            pl.BlockSpec((depth, bb, bc), lambda ib, jn, kc: (0, ib, kc)),
            pl.BlockSpec((g - 1, bc), lambda ib, jn, kc: (0, kc)),
            pl.BlockSpec((bc, g, bn), lambda ib, jn, kc: (kc, 0, jn)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda ib, jn, kc: (ib, jn)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), acc_dtype),
        interpret=interpret,
        name="fused_lutmu",
    )(x_t, t_t, l_p)
    out = out[:b, :n].astype(jnp.float32)
    return out * lut_scale + lut_offset
