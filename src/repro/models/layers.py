"""Shared neural-net building blocks (pure JAX, pytree-dict params).

No flax/haiku — params are nested dicts of arrays, init functions mirror
apply functions, everything jit/pjit/scan-friendly.  Compute dtype is the
caller's (we cast weights at use sites for mixed precision).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32) -> Array:
    # a Python float keeps the product in ``dtype`` (a numpy scalar would
    # promote bf16 weights to f32)
    scale = float(1.0 / np.sqrt(d_in))
    return jax.random.normal(key, (d_in, d_out), dtype) * scale


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> Array:
    return jax.random.normal(key, (vocab, d), dtype) * 0.02


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x: Array, weight: Array, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def layer_norm(x: Array, weight: Array, bias: Array, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight + bias).astype(dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).

    Rotation via a static permutation + full-width cos/sin instead of
    split/concat halves — bit-identical to the halves form, but never
    slices ``hd`` at its midpoint, which the SPMD partitioner handles
    incorrectly when ``hd`` itself ends up sharded inside a scanned layer
    stack (the sharding rules keep whole heads per shard exactly to avoid
    that regime; this form stays safe even for hand-sharded params).
    """
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.concatenate([cos, cos], axis=-1)
    sin = jnp.concatenate([sin, sin], axis=-1)
    perm = jnp.concatenate([jnp.arange(hd // 2, hd), jnp.arange(0, hd // 2)])
    sign = jnp.concatenate([-jnp.ones(hd // 2), jnp.ones(hd // 2)])
    xf = x.astype(jnp.float32)
    rot = jnp.take(xf, perm, axis=-1) * sign
    return (xf * cos + rot * sin).astype(x.dtype)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
}


def gated_mlp(x: Array, w_gate: Array, w_up: Array, w_down: Array,
              act: str = "silu") -> Array:
    h = ACTS[act](x @ w_gate) * (x @ w_up)
    return h @ w_down


def softmax_cross_entropy(logits: Array, labels: Array) -> Array:
    """Mean token-level CE.  logits (..., V) f32, labels (...) int32.

    The gold logit is extracted with a masked reduction rather than
    ``take_along_axis`` — a gather along a tensor-parallel-sharded vocab axis
    makes GSPMD all-gather the full logits (tens of GiB at 150k vocab); the
    mask-sum keeps everything local + one small all-reduce.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0), axis=-1)
    return jnp.mean(logz - gold)
