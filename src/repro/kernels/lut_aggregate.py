"""Pallas TPU kernel: LUT aggregation as a one-hot MXU contraction.

The paper's Aggregator fights the incoherent LUT gather (bottleneck ④) with
a distributed dual-port ROM group — more read ports.  On TPU the systolic
array *is* the multi-ported memory: we lower the gather+sum to

    out[b, n] = Σ_{c,g} onehot[b, c·G+g] · lut[c·G+g, n]

a dense (B, C·G) × (C·G, N) matmul, tiled over (B, N, C·G) with 128-aligned
``BlockSpec``s.  The one-hot rows are 1/G dense; the MXU chews the structural
zeros for free while HBM traffic stays proportional to the (pruned) LUT —
which is exactly the quantity the paper's parameter pruning minimises.

Two accumulation paths:
  * float (f32/bf16 one-hot × f32/bf16 LUT → f32), and
  * int8 (int8 one-hot × int8-quantised LUT → int32), mirroring the paper's
    2W-bit entries / 4W-bit accumulators; dequant (scale/offset) happens in
    the wrapper epilogue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _matmul_kernel(lhs_ref, rhs_ref, out_ref, *, acc_dtype):
    """Tiled matmul with accumulation over the innermost (K) grid dim."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        lhs_ref[...],
        rhs_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=acc_dtype,
    )


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_n", "block_k", "interpret"),
)
def lut_aggregate_pallas(
    onehot: Array,
    lut: Array,
    lut_scale: Array,
    lut_offset: Array,
    *,
    block_b: int = 256,
    block_n: int = 256,
    block_k: int = 128,
    interpret: bool = False,
) -> Array:
    """One-hot aggregation.

    Args:
      onehot: (B, C, G) from the encode kernel (float or int8).
      lut: (C, G, N) float32/bf16, or int8 (quantised).
      lut_scale / lut_offset: dequant epilogue, () or (N,).

    Returns:
      (B, N) float32.
    """
    b, c, g = onehot.shape
    n = lut.shape[-1]
    int_path = lut.dtype == jnp.int8
    lhs = onehot.reshape(b, c * g)
    rhs = lut.reshape(c * g, n)
    if int_path:
        lhs = lhs.astype(jnp.int8)
        acc_dtype = jnp.int32
    else:
        acc_dtype = jnp.float32
        rhs = rhs.astype(lhs.dtype)

    k_dim = c * g
    bb = min(block_b, _ceil_to(b, 8))
    bn = min(block_n, _ceil_to(n, 128))
    bk = min(block_k, _ceil_to(k_dim, 128))
    bp, np_, kp = _ceil_to(b, bb), _ceil_to(n, bn), _ceil_to(k_dim, bk)
    lhs = jnp.pad(lhs, ((0, bp - b), (0, kp - k_dim)))
    rhs = jnp.pad(rhs, ((0, kp - k_dim), (0, np_ - n)))

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, acc_dtype=acc_dtype),
        grid=(bp // bb, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bb, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), acc_dtype),
        interpret=interpret,
        name="lut_aggregate",
    )(lhs, rhs)
    out = out[:b, :n].astype(jnp.float32)
    return out * lut_scale + lut_offset
