"""Seeded weights for a benchmark configuration.

One jitted call makes every parameter on the device, in the parameter
layout the serving program reads and in the types it serves: bfloat16 for
embeddings, attention, norms, the head and dense MLPs; int8 tables with
float32 split thresholds, scales and offsets for LUT-MU MLPs.  The layers
are made one at a time inside the call (``lax.map``), so the temporaries
of one layer are all that is live beside the output.

The sizes of the random draws (set here, listed in each configuration's
``assumed``):

* embedding N(0, 1); projections N(0, 1/fan_in), as a trained model's
  activations keep unit scale; norm weights 1 + N(0, 0.1) (stored as the
  offset from 1, the program's convention).
* LUT-MU: a table is what MADDNESS would build from a dense weight
  W ~ N(0, 1/fan_in) and the prototypes of its tree.  Each codebook's
  tree splits ``depth`` distinct dimensions of its ``d_sub``; each node's
  threshold is the median of its input plus N(0, 0.2) of its spread; a
  leaf's prototype, along each split dimension, is the mean of the input
  on its side of the threshold on its path (0 along the others).  The
  table is prototype @ W, quantised to int8 per column as the MADDNESS
  scheme does (per-codebook minimum, one scale per column).  With
  pruning, the gate and up tables hold only the columns the down tree
  reads, in its cluster order (level-major), which is the package the
  down projection encodes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtr

from benchmarks.chip.spec import ModelSpec

BF16 = jnp.bfloat16
F32 = jnp.float32
THRESHOLD_JITTER = 0.2   # node thresholds: median + N(0, 0.2) x spread
NORM_JITTER = 0.1        # norm weights: 1 + N(0, 0.1)
H_SAMPLES = 1 << 16      # samples that place the down tree's thresholds


def root_key(seed: int):
    """A PRNG key for any non-negative seed below 2**62."""
    if seed < 0 or seed >= 1 << 62:
        raise ValueError(f"seed {seed} is outside [0, 2**62)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _normal(key, shape, std, dtype=BF16):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def _split_dims(key, books: int, d_sub: int, depth: int):
    """(books, depth) distinct split dimensions per codebook."""
    perm = jax.vmap(lambda k: jax.random.permutation(k, d_sub))(
        jax.random.split(key, books))
    return perm[:, :depth].astype(jnp.int32)


def _leaf_sides(depth: int):
    """(leaves, depth) node index and side (1 = right) on each leaf's path,
    heap-ordered as the program's encoder walks its trees."""
    nodes, sides = [], []
    for leaf in range(2 ** depth):
        node, n_row, s_row = 0, [], []
        for level in range(depth):
            bit = (leaf >> (depth - 1 - level)) & 1
            n_row.append(node)
            s_row.append(bit)
            node = 2 * node + 1 + bit
        nodes.append(n_row)
        sides.append(s_row)
    return jnp.asarray(nodes, jnp.int32), jnp.asarray(sides, bool)


def _quantise(lut):
    """The MADDNESS int8 scheme: (C, G, N) float -> int8 codes with one
    scale and one offset per column, out = (sum_c q) * scale + offset."""
    mins = lut.min(axis=1)
    rng = (lut.max(axis=1) - mins).max(axis=0)
    scale = jnp.maximum(rng, 1e-8) / 255.0
    q = jnp.clip(jnp.round((lut - mins[:, None, :]) / scale) - 128.0,
                 -128, 127).astype(jnp.int8)
    offset = mins.sum(axis=0) + 128.0 * lut.shape[0] * scale
    return q, scale.astype(F32), offset.astype(F32)


def _tree_and_protos(key, books, spec: ModelSpec, right_mean, left_mean,
                     spread):
    """Split dims, thresholds (books, G-1) and prototypes (books, G, depth)
    for inputs whose conditional means on either side of a threshold are
    given by ``right_mean(t)`` / ``left_mean(t)``."""
    k_dims, k_thr = jax.random.split(key)
    dims = _split_dims(k_dims, books, spec.d_sub, spec.depth)
    thr = jax.random.normal(k_thr, (books, spec.leaves - 1), F32) * (
        THRESHOLD_JITTER * spread)
    nodes, sides = _leaf_sides(spec.depth)
    t_path = thr[:, nodes]                                  # (C, G, depth)
    protos = jnp.where(sides[None], right_mean(t_path), left_mean(t_path))
    return dims, thr, protos


def _gauss_right(t):
    return jnp.exp(-0.5 * t * t) / jnp.sqrt(2 * jnp.pi) / jnp.maximum(
        1.0 - ndtr(t), 1e-6)


def _gauss_left(t):
    return -jnp.exp(-0.5 * t * t) / jnp.sqrt(2 * jnp.pi) / jnp.maximum(
        ndtr(t), 1e-6)


def _lutmu_layer(key, spec: ModelSpec) -> dict:
    d, ff = spec.d_model, spec.d_ff
    k_up, k_dn, k_wg, k_wu, k_wd, k_h = jax.random.split(key, 6)
    up_dims, up_thr, up_protos = _tree_and_protos(
        k_up, spec.c_up, spec, _gauss_right, _gauss_left, 1.0)
    # gate/up tables: prototype @ W over the split dims of each codebook
    cols = spec.package
    w_g = jax.random.normal(k_wg, (spec.c_up, spec.depth, cols), F32)
    w_u = jax.random.normal(k_wu, (spec.c_up, spec.depth, cols), F32)
    lut_g = jnp.einsum("cgl,cln->cgn", up_protos, w_g) / jnp.sqrt(d)
    lut_u = jnp.einsum("cgl,cln->cgn", up_protos, w_u) / jnp.sqrt(d)
    # the gate/up outputs are sums over codebooks of prototype . W: normal
    # with this variance for unit-variance inputs
    var = (up_protos ** 2).mean() * spec.depth * spec.c_up / d
    hs = jax.random.normal(k_h, (2, H_SAMPLES), F32) * jnp.sqrt(var)
    h = jnp.sort(jax.nn.silu(hs[0]) * hs[1])
    csum = jnp.concatenate([jnp.zeros((1,), F32), jnp.cumsum(h)])

    def right(t):
        i = jnp.searchsorted(h, t)
        return (csum[-1] - csum[i]) / jnp.maximum(H_SAMPLES - i, 1)

    def left(t):
        i = jnp.searchsorted(h, t)
        return csum[i] / jnp.maximum(i, 1)

    dn_dims, dn_thr, dn_protos = _tree_and_protos(
        k_dn, spec.c_down, spec, right, left, h.std())
    dn_thr = dn_thr + jnp.median(h)
    w_d = jax.random.normal(k_wd, (spec.c_down, spec.depth, d), F32)
    lut_d = jnp.einsum("cgl,cln->cgn", dn_protos, w_d) / jnp.sqrt(ff)
    out = {"up_split_dims": up_dims, "up_thresholds": up_thr,
           "down_split_dims": dn_dims, "down_thresholds": dn_thr}
    for site, lut in (("gate", lut_g), ("up", lut_u), ("down", lut_d)):
        q, scale, offset = _quantise(lut)
        out[f"lut_{site}"] = q
        out[f"lut_{site}_scale"] = scale
        out[f"lut_{site}_offset"] = offset
    return out


def _layer(key, spec: ModelSpec) -> dict:
    d, hd = spec.d_model, spec.head_dim
    nq, nkv = spec.n_heads, spec.n_kv_heads
    ks = jax.random.split(key, 12)
    layer = {
        "ln1": _normal(ks[0], (d,), NORM_JITTER),
        "ln2": _normal(ks[1], (d,), NORM_JITTER),
        "attn": {
            "wq": _normal(ks[2], (d, nq * hd), d ** -0.5),
            "wk": _normal(ks[3], (d, nkv * hd), d ** -0.5),
            "wv": _normal(ks[4], (d, nkv * hd), d ** -0.5),
            "wo": _normal(ks[5], (nq * hd, d), (nq * hd) ** -0.5),
            "q_norm": _normal(ks[6], (hd,), NORM_JITTER),
            "k_norm": _normal(ks[7], (hd,), NORM_JITTER),
        },
    }
    if spec.lutmu:
        layer["amm_mlp"] = _lutmu_layer(ks[8], spec)
    else:
        layer["mlp"] = {
            "w_gate": _normal(ks[9], (d, spec.d_ff), d ** -0.5),
            "w_up": _normal(ks[10], (d, spec.d_ff), d ** -0.5),
            "w_down": _normal(ks[11], (spec.d_ff, d), spec.d_ff ** -0.5),
        }
    return layer


def make(spec: ModelSpec, key):
    """The whole parameter tree (traceable; see :func:`make_params`)."""
    k_emb, k_head, k_norm, k_layers = jax.random.split(key, 4)
    return {
        "embed": _normal(k_emb, (spec.vocab, spec.d_model), 1.0),
        "final_norm": _normal(k_norm, (spec.d_model,), NORM_JITTER),
        "lm_head": _normal(k_head, (spec.d_model, spec.vocab),
                           spec.d_model ** -0.5),
        "layers": jax.lax.map(lambda k: _layer(k, spec),
                              jax.random.split(k_layers, spec.layers)),
    }


def make_params(spec: ModelSpec, seed: int, device=None):
    """Every parameter, made on ``device`` (default: JAX's first) in one
    jitted call from ``seed``."""
    device = device or jax.devices()[0]
    out = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(lambda k: make(spec, k), out_shardings=out)(
        root_key(seed))
