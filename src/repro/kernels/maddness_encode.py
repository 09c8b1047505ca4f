"""Pallas TPU kernel: MADDNESS parallel-comparator encode.

TPU adaptation of the paper's Encoder (Section V-B3): instead of walking the
depth-``I`` decision tree sequentially (a loop-carried dependency the paper
calls out as bottleneck ③), evaluate **all** ``2**I - 1`` node comparisons in
one VPU pass and derive the one-hot leaf indicator by a level-by-level
valid-mask expansion.  No gathers, no loop-carried state — the exact shape
the paper's comparator arrays give in hardware.

The kernel emits the **one-hot** form because the downstream aggregation is
a one-hot MXU contraction (see ``lut_aggregate.py``); integer codes, when
needed, are an argmax the wrapper provides.

Layout notes (TPU): codebooks sit on lanes, as in ``fused_lutmu.py`` (whose
:func:`~repro.kernels.fused_lutmu.leaf_masks` this kernel shares).  The
kernel reads ``(I, B_t, C_t)`` split values and ``(G-1, C_t)`` thresholds
and writes a leaf-major ``(G, B_t, C_t)`` one-hot; the wrapper transposes
that to the ``(B, C, G)`` form callers consume.  Thresholds live in VMEM
once per C-tile and are reused across the B grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_lutmu import _ceil_to, leaf_masks, split_lanes

Array = jax.Array


def _encode_kernel(x_ref, thr_ref, out_ref, *, depth: int):
    """One (B_t, C_t) tile: comparisons → one-hot over G = 2**depth leaves.

    x_ref:   (I, B_t, C_t)      split-dim values
    thr_ref: (2**I - 1, C_t)    heap-ordered node thresholds
    out_ref: (2**I, B_t, C_t)   leaf-major one-hot
    """
    for g, mask in enumerate(leaf_masks(x_ref, thr_ref, depth)):
        out_ref[g] = jnp.where(mask, 1, 0).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("depth", "block_b", "block_c", "out_dtype", "interpret"),
)
def encode_onehot_pallas(
    x_split: Array,
    thresholds: Array,
    *,
    depth: int,
    block_b: int = 256,
    block_c: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> Array:
    """(B, C, I), (C, 2**I - 1) → one-hot (B, C, 2**I).

    Pads B and C up to block multiples; padded codebooks produce garbage
    one-hots that are sliced off before returning.
    """
    b, c, i = x_split.shape
    g = 2**depth
    assert i == depth, (i, depth)
    bb = min(block_b, _ceil_to(b, 8))
    bc = min(block_c, c)
    bp = _ceil_to(b, bb)
    cp = _ceil_to(c, bc)
    x_t, t_t = split_lanes(x_split, thresholds, bp, cp)

    out = pl.pallas_call(
        functools.partial(_encode_kernel, depth=depth),
        grid=(bp // bb, cp // bc),
        in_specs=[
            pl.BlockSpec((depth, bb, bc), lambda ib, ic: (0, ib, ic)),
            pl.BlockSpec((g - 1, bc), lambda ib, ic: (0, ic)),
        ],
        out_specs=pl.BlockSpec((g, bb, bc), lambda ib, ic: (0, ib, ic)),
        out_shape=jax.ShapeDtypeStruct((g, bp, cp), out_dtype),
        interpret=interpret,
        name="maddness_encode",
    )(x_t, t_t)
    return jnp.transpose(out[:, :b, :c], (1, 2, 0))
