"""Public jit'd entry points for the LUT-MU kernels.

``interpret`` defaults to True on CPU (this container) and False on TPU, so
the same call sites work in tests and on hardware.  All ops accept either a
``MaddnessParams`` bundle or raw arrays.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.maddness import HashTree, MaddnessParams, gather_split_values
from repro.core.pruning import PruningPlan, pruned_to_split_values
from repro.kernels.dispatch import default_interpret as _default_interpret
from repro.kernels.fused_lutmu import fused_lutmu_pallas
from repro.kernels.lut_aggregate import lut_aggregate_pallas
from repro.kernels.maddness_encode import encode_onehot_pallas

Array = jax.Array


def encode_onehot(x_split: Array, tree: HashTree, *,
                  block_b: int = 256, block_c: int = 128,
                  interpret: Optional[bool] = None) -> Array:
    """(B, C, I) split values → (B, C, G) one-hot via the encode kernel."""
    if interpret is None:
        interpret = _default_interpret()
    return encode_onehot_pallas(
        x_split, tree.thresholds, depth=tree.depth,
        block_b=block_b, block_c=block_c, interpret=interpret,
    )


def encode_codes(x_split: Array, tree: HashTree, **kw) -> Array:
    """(B, C, I) → (B, C) int32 prototype ids."""
    onehot = encode_onehot(x_split, tree, **kw)
    return jnp.argmax(onehot, axis=-1).astype(jnp.int32)


def lut_aggregate(onehot: Array, lut: Array, lut_scale: Array,
                  lut_offset: Array, *, block_b: int = 256,
                  block_n: int = 256, block_k: int = 128,
                  interpret: Optional[bool] = None) -> Array:
    """(B, C, G) one-hot × (C, G, N) LUT → (B, N) f32."""
    if interpret is None:
        interpret = _default_interpret()
    return lut_aggregate_pallas(
        onehot, lut, lut_scale, lut_offset,
        block_b=block_b, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )


def fused_lutmu(x_split: Array, params: MaddnessParams, *,
                block_b: int = 256, block_n: int = 256, block_c: int = 128,
                interpret: Optional[bool] = None) -> Array:
    """Fused encode+aggregate from split values.  → (B, N) f32."""
    if interpret is None:
        interpret = _default_interpret()
    return fused_lutmu_pallas(
        x_split, params.tree.thresholds, params.lut,
        params.lut_scale, params.lut_offset,
        depth=params.tree.depth, block_b=block_b, block_n=block_n,
        block_c=block_c, interpret=interpret,
    )


def amm_matmul(x: Array, params: MaddnessParams, **kw) -> Array:
    """Drop-in ``x @ W`` replacement: full-width input → fused kernel."""
    x_split = gather_split_values(x, params.tree)
    return fused_lutmu(x_split, params, **kw)


def amm_matmul_package(x_pruned: Array, params: MaddnessParams,
                       plan_codebooks: int, plan_depth: int, **kw) -> Array:
    """Chained (data-pruned) input path: cluster-ordered package → output."""
    plan = PruningPlan(jnp.zeros((0,), jnp.int32), plan_codebooks, plan_depth)
    x_split = pruned_to_split_values(x_pruned, plan)
    return fused_lutmu(x_split, params, **kw)
