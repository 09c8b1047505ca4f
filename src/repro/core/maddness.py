"""MADDNESS (Blalock & Guttag, ICML'21) offline training + online inference.

This module implements the product-quantisation substrate the paper's LUT-MU
builds on:

  * offline training  — learn, per codebook, a depth-``I`` bisecting hash
    tree (split dims + per-node thresholds), the ``G = 2**I`` prototypes, and
    the LUT of partial dot products against a known weight matrix;
  * online encode     — map an input sub-vector to a prototype id, either by
    the sequential tree walk (reference semantics) or by the
    parallel-comparator evaluation of all ``2**I`` leaves (the paper's
    Encoder, Section V-B3 — and the form our Pallas kernels use);
  * online aggregate  — sum the selected LUT rows (Section IV-B Eq. 4).

Shapes and notation follow the paper: an input vector of dimension ``D`` is
split into ``C`` codebooks of ``d_sub = D // C`` dims; each codebook has
``G = 2**I`` prototypes selected by ``I`` split dimensions.

Offline training is plain numpy (it is a host-side, one-off procedure); the
online path is pure jnp and jit-friendly.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# Parameter containers (registered as pytrees so they pass through jit/pjit).
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HashTree:
    """Per-codebook bisecting decision trees.

    Attributes:
      split_dims:  (C, I) int32 — the dim (within the codebook's ``d_sub``
        subspace) compared at each level.  All nodes of one level share a
        split dim (MADDNESS's "4 uint8s" trick).
      thresholds:  (C, 2**I - 1) float32 — per-node split values in heap
        order (node 0 = root, level ``l`` occupies ``[2**l - 1, 2**(l+1)-1)``).
    """

    split_dims: Array
    thresholds: Array

    @property
    def num_codebooks(self) -> int:
        return self.split_dims.shape[0]

    @property
    def depth(self) -> int:
        return self.split_dims.shape[1]

    @property
    def num_prototypes(self) -> int:
        return 2 ** self.depth

    def tree_flatten(self):
        return (self.split_dims, self.thresholds), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MaddnessParams:
    """Everything needed for one LUT-based approximate matmul ``x @ W``.

    Attributes:
      tree:        the hash trees (encode parameters).
      prototypes:  (C, G, d_sub) float32 — cluster centroids (used for
        LUT (re)builds and the STE retraining path; not needed at inference).
      lut:         (C, G, N) — precomputed partial dot products
        ``prototypes[c, g] @ W[c*d_sub:(c+1)*d_sub, n]``.  float32, or int8
        when quantised.
      lut_scale:   () or (N,) float32 — dequant scale (1.0 when float LUT).
      lut_offset:  () or (N,) float32 — dequant offset summed over codebooks.
    """

    tree: HashTree
    prototypes: Array
    lut: Array
    lut_scale: Array
    lut_offset: Array

    @property
    def out_features(self) -> int:
        return self.lut.shape[-1]

    def tree_flatten(self):
        return (
            self.tree,
            self.prototypes,
            self.lut,
            self.lut_scale,
            self.lut_offset,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


# ---------------------------------------------------------------------------
# Offline training (numpy, host side).
# ---------------------------------------------------------------------------


def _optimal_split(rows: np.ndarray, dim: int) -> Tuple[float, float]:
    """Best threshold on ``dim`` for one bucket, scored over the full subspace.

    Sorting the bucket by the candidate dim and accumulating the moments of
    *every* dim gives, for each cut point, the exact two-sided SSE of the
    resulting partition measured in the whole ``d_sub``-dim subspace — the
    objective an axis-aligned bisecting k-means would minimise.  (MADDNESS's
    original ``optimal_split_val`` scores only the split dim's own 1-D SSE,
    which ignores how well the cut separates the other dims; on cascaded
    LUT-MUs that gap compounds per layer.)  O(n·(log n + d_sub)).

    Returns ``(loss, threshold)``.  A bucket no threshold can cut (one
    row, or one value on ``dim``) gets ``-inf``: every row goes right with
    no value on the boundary.
    """
    m = rows.shape[0]
    if m <= 1:
        return 0.0, -np.inf
    v = rows[np.argsort(rows[:, dim], kind="stable")]
    csum = np.cumsum(v, axis=0)
    csq = np.cumsum(v * v, axis=0)
    total_sum, total_sq = csum[-1], csq[-1]
    # split after index i (left = v[:i+1], right = v[i+1:]), i in [0, m-2]
    cnt = np.arange(1, m, dtype=np.float64)[:, None]  # left counts 1..m-1
    left_sum, left_sq = csum[:-1], csq[:-1]
    right_sum = total_sum - left_sum
    right_sq = total_sq - left_sq
    right_cnt = m - cnt
    sse = ((left_sq - left_sum**2 / cnt)
           + (right_sq - right_sum**2 / right_cnt)).sum(axis=1)
    # a threshold can only cut between distinct values: a cut inside a run
    # of ties scores a partition no threshold produces, and its midpoint
    # lands exactly on calibration values, where any perturbation of the
    # input (e.g. a quantised upstream layer) flips the decision
    gap = v[1:, dim] > v[:-1, dim]
    if not gap.any():  # one value: no cut, the bucket stays whole
        return float((total_sq - total_sum**2 / m).sum()), -np.inf
    sse = np.where(gap, sse, np.inf)
    best = int(np.argmin(sse))
    # threshold midway between the two straddling sorted values
    thr = 0.5 * (v[best, dim] + v[best + 1, dim])
    return float(sse[best]), thr


def _learn_hash_tree_one_codebook(
    x: np.ndarray, depth: int, rng: np.random.Generator
) -> Tuple[np.ndarray, np.ndarray]:
    """Learn split dims + thresholds for one codebook (MADDNESS §4.1).

    Args:
      x: (N, d_sub) training sub-vectors.
      depth: I — number of bisection rounds.

    Returns:
      split_dims (I,) int32, thresholds (2**depth - 1,) float32.
    """
    n, d_sub = x.shape
    split_dims = np.zeros(depth, dtype=np.int32)
    thresholds = np.zeros(2**depth - 1, dtype=np.float32)
    # bucket assignment = current node id within the level (0 .. 2**level-1)
    bucket = np.zeros(n, dtype=np.int64)
    for level in range(depth):
        n_buckets = 2**level
        # All nodes of one level share a split dim (MADDNESS's "4 uint8s"
        # trick); with small d_sub we can afford to score every dim by the
        # exact full-subspace post-split SSE.
        rows_by_bucket = [x[bucket == b] for b in range(n_buckets)]
        best_dim, best_loss, best_thr = -1, np.inf, None
        for dim in range(d_sub):
            loss = 0.0
            thr_per_bucket = np.zeros(n_buckets, dtype=np.float32)
            for b in range(n_buckets):
                rows = rows_by_bucket[b]
                if rows.size == 0:
                    thr_per_bucket[b] = -np.inf
                    continue
                l, t = _optimal_split(rows, dim)
                loss += l
                thr_per_bucket[b] = t
            if loss < best_loss:
                best_dim, best_loss, best_thr = dim, loss, thr_per_bucket
        split_dims[level] = best_dim
        lo = 2**level - 1
        thresholds[lo : lo + n_buckets] = best_thr
        # descend
        go_right = x[:, best_dim] >= best_thr[bucket]
        bucket = bucket * 2 + go_right.astype(np.int64)
    return split_dims, thresholds


def learn_hash_trees(
    x: np.ndarray, num_codebooks: int, depth: int, seed: int = 0
) -> HashTree:
    """Learn the full bank of hash trees from calibration data.

    Args:
      x: (N, D) calibration activations; D must divide by ``num_codebooks``.
    """
    n, d = x.shape
    if d % num_codebooks:
        raise ValueError(f"D={d} not divisible by C={num_codebooks}")
    d_sub = d // num_codebooks
    rng = np.random.default_rng(seed)
    dims, thrs = [], []
    for c in range(num_codebooks):
        xs = np.asarray(x[:, c * d_sub : (c + 1) * d_sub], dtype=np.float64)
        sd, th = _learn_hash_tree_one_codebook(xs, depth, rng)
        dims.append(sd)
        thrs.append(th)
    return HashTree(
        split_dims=jnp.asarray(np.stack(dims), dtype=jnp.int32),
        thresholds=jnp.asarray(np.stack(thrs), dtype=jnp.float32),
    )


def _assign_buckets_np(x_sub: np.ndarray, split_dims: np.ndarray,
                       thresholds: np.ndarray) -> np.ndarray:
    """Sequential tree walk in numpy — offline-side twin of ``encode``."""
    n = x_sub.shape[0]
    node = np.zeros(n, dtype=np.int64)  # global heap index
    depth = split_dims.shape[0]
    for level in range(depth):
        t = thresholds[node]
        b = x_sub[:, split_dims[level]] >= t
        node = 2 * node + 1 + b.astype(np.int64)
    return (node - (2**depth - 1)).astype(np.int32)


def learn_prototypes(
    x: np.ndarray,
    tree: HashTree,
    ridge_lambda: float = 1.0,
    optimize: bool = True,
) -> Array:
    """Prototypes = bucket means, optionally globally ridge-optimised.

    MADDNESS §4.2: after hashing, solve ``min_P ||X - A P||^2 + λ||P||^2``
    where ``A`` is the (N, C*G) one-hot assignment matrix.  Crucially the
    optimised prototypes are **full-width** (non-zero outside their own
    subspace) — each codebook's prototype compensates the quantisation error
    of the others.  Encode still only reads the tree's split dims.

    Returns:
      (C, G, d_sub) bucket means when ``optimize=False``, else (C, G, D)
      full-width ridge solution.
    """
    n, d = x.shape
    split_dims = np.asarray(tree.split_dims)
    thresholds = np.asarray(tree.thresholds)
    c_books, depth = split_dims.shape
    g = 2**depth
    d_sub = d // c_books
    assign = np.zeros((n, c_books), dtype=np.int32)
    for c in range(c_books):
        xs = x[:, c * d_sub : (c + 1) * d_sub]
        assign[:, c] = _assign_buckets_np(xs, split_dims[c], thresholds[c])

    if not optimize:
        protos = np.zeros((c_books, g, d_sub), dtype=np.float64)
        for c in range(c_books):
            for b in range(g):
                mask = assign[:, c] == b
                if mask.any():
                    protos[c, b] = x[mask, c * d_sub : (c + 1) * d_sub].mean(0)
        return jnp.asarray(protos, dtype=jnp.float32)

    # Global ridge via normal equations — O((CG)^2·N) build, offline only.
    a = np.zeros((n, c_books * g), dtype=np.float64)
    a[np.arange(n)[:, None], assign + np.arange(c_books)[None, :] * g] = 1.0
    gram = a.T @ a + ridge_lambda * np.eye(c_books * g)
    rhs = a.T @ x  # (CG, D)
    sol = np.linalg.solve(gram, rhs)  # (CG, D) full-width prototypes
    return jnp.asarray(sol.reshape(c_books, g, d), dtype=jnp.float32)


def quantize_lut_bits(
    lut: Array,
    bits: int = 8,
    bias: Optional[Array] = None,
) -> Tuple[Array, Array, Array]:
    """Quantise a float (C, G, N) LUT to ``bits``-wide integer codes.

    The MADDNESS quantisation scheme, generalised to any entry width:
    per-(c, n) offsets (min over prototypes) absorbed into a single
    per-column offset, a shared per-column scale covering the widest
    codebook's range, and codes stored as int8 (int4 codes live in
    ``[-8, 7]``).  ``bits=8`` reproduces the historical int8 path of
    :func:`build_lut` bit-for-bit — the resolution-config compiler relies
    on that to quantise one float calibration at several resolutions
    without changing existing artifacts.

    Every step is per-column separable, so quantisation commutes with
    column pruning (``pruning.prune_lut``) exactly.

    Returns:
      (q, scale, offset): int8 codes plus per-column (N,) float32
      scale/offset such that ``out ≈ (Σ_c q[c, g_c]) · scale + offset``.
    """
    if bits not in (4, 8):
        raise ValueError(f"LUT codes must be 4 or 8 bits, got {bits}")
    c_books = lut.shape[0]
    levels = 2**bits
    half = levels // 2
    mins = lut.min(axis=1)  # (C, N)
    rng = (lut.max(axis=1) - mins).max(axis=0)  # (N,)
    scale = jnp.maximum(rng, 1e-8) / (levels - 1.0)
    q = jnp.round((lut - mins[:, None, :]) / scale) - float(half)
    q = jnp.clip(q, -half, half - 1).astype(jnp.int8)
    offset = mins.sum(axis=0) + float(half) * c_books * scale
    if bias is not None:
        offset = offset + bias
    return q, scale.astype(jnp.float32), offset.astype(jnp.float32)


def build_lut(
    prototypes: Array,
    weight: Array,
    bias: Optional[Array] = None,
    quantize_int8: bool = False,
) -> Tuple[Array, Array, Array]:
    """Precompute the LUT of partial dot products (Eq. 2).

    Args:
      prototypes: (C, G, d_sub) subspace prototypes, or (C, G, D) full-width
        ridge-optimised prototypes (MADDNESS §4.2).
      weight: (D, N) with D = C * d_sub.
      bias: optional (N,), folded into the dequant offset (or spread across
        codebooks for float LUTs).

    Returns:
      (lut, scale, offset): float32 (C, G, N) with scale=1/offset=bias, or
      int8 LUT with per-column scale/offset such that
      ``out ≈ (Σ_c lut[c,g_c]) * scale + offset``.
    """
    c_books, g, pdim = prototypes.shape
    d, n = weight.shape
    if pdim == d:  # full-width prototypes
        lut = jnp.einsum("cgD,Dn->cgn", prototypes, weight)
    elif pdim * c_books == d:
        w = weight.reshape(c_books, pdim, n)
        lut = jnp.einsum("cgd,cdn->cgn", prototypes, w)  # float32
    else:
        raise ValueError(f"prototype dim {pdim} incompatible with D={d}, C={c_books}")

    if not quantize_int8:
        offset = bias if bias is not None else jnp.zeros((n,), jnp.float32)
        return lut.astype(jnp.float32), jnp.ones((), jnp.float32), offset
    return quantize_lut_bits(lut, bits=8, bias=bias)


def fit_maddness(
    calib_x: np.ndarray,
    weight: np.ndarray,
    num_codebooks: int,
    depth: int = 4,
    bias: Optional[np.ndarray] = None,
    quantize_int8: bool = False,
    optimize_prototypes: bool = True,
    ridge_lambda: float = 1.0,
    seed: int = 0,
) -> MaddnessParams:
    """One-shot offline training: trees → prototypes → LUT."""
    tree = learn_hash_trees(calib_x, num_codebooks, depth, seed=seed)
    protos = learn_prototypes(calib_x, tree, ridge_lambda=ridge_lambda,
                              optimize=optimize_prototypes)
    lut, scale, offset = build_lut(
        protos,
        jnp.asarray(weight, jnp.float32),
        None if bias is None else jnp.asarray(bias, jnp.float32),
        quantize_int8=quantize_int8,
    )
    return MaddnessParams(tree, protos, lut, scale, offset)


# ---------------------------------------------------------------------------
# Online path (jnp, jit-friendly).
# ---------------------------------------------------------------------------


def gather_split_values(x: Array, tree: HashTree) -> Array:
    """(B, D) → (B, C, I): the only input values 'encode' ever reads.

    This is the paper's *data pruning* boundary: everything not returned here
    is inter-layer redundancy when the producer is also a LUT-MU.
    """
    b = x.shape[0]
    c_books, depth = tree.split_dims.shape
    d_sub = x.shape[1] // c_books
    xs = x.reshape(b, c_books, d_sub)
    idx = tree.split_dims[None].astype(jnp.int32)  # (1, C, I)
    return jnp.take_along_axis(xs, jnp.broadcast_to(idx, (b, c_books, depth)), axis=2)


def encode(x_split: Array, tree: HashTree) -> Array:
    """Sequential tree-walk encode — the reference semantics (Eq. 3).

    Args:
      x_split: (B, C, I) gathered split-dim values.
    Returns:
      (B, C) int32 prototype ids in [0, 2**I).
    """
    b, c_books, depth = x_split.shape
    node = jnp.zeros((b, c_books), jnp.int32)  # global heap index
    for level in range(depth):
        thr = jnp.take_along_axis(
            jnp.broadcast_to(tree.thresholds[None], (b,) + tree.thresholds.shape),
            node[..., None],
            axis=2,
        )[..., 0]
        bit = (x_split[:, :, level] >= thr).astype(jnp.int32)
        node = 2 * node + 1 + bit
    return node - (2**depth - 1)


def _leaf_paths(depth: int) -> Tuple[np.ndarray, np.ndarray]:
    """Static (G, I) node indices + expected bits along each root→leaf path."""
    g = 2**depth
    nodes = np.zeros((g, depth), dtype=np.int32)
    bits = np.zeros((g, depth), dtype=np.int32)
    for leaf in range(g):
        node = 0
        for level in range(depth):
            nodes[leaf, level] = node
            bit = (leaf >> (depth - 1 - level)) & 1
            bits[leaf, level] = bit
            node = 2 * node + 1 + bit
    return nodes, bits


def encode_onehot(x_split: Array, tree: HashTree, dtype=jnp.float32) -> Array:
    """Parallel-comparator encode → one-hot over prototypes.

    The TPU analogue of the paper's Encoder (Section V-B3): evaluate all
    ``2**I - 1`` node comparisons at once, then AND along each of the ``2**I``
    root→leaf paths.  Output feeds the one-hot aggregation matmul directly.

    Returns:
      (B, C, G) one-hot (exactly one 1 per (b, c)).
    """
    b, c_books, depth = x_split.shape
    g = 2**depth
    # level of each heap node, static
    levels = np.floor(np.log2(np.arange(1, g))).astype(np.int32)  # (G-1,)
    # cmp[b, c, m] = x_split[b, c, level(m)] >= thresholds[c, m]
    cmp = x_split[:, :, levels] >= tree.thresholds[None]  # (B, C, G-1) bool
    nodes, bits = _leaf_paths(depth)  # (G, I)
    # match[b, c, g, l] = cmp[b, c, nodes[g, l]] == bits[g, l]
    path_cmp = cmp[:, :, nodes.reshape(-1)].reshape(b, c_books, g, depth)
    match = jnp.where(jnp.asarray(bits, bool)[None, None], path_cmp, ~path_cmp)
    return jnp.all(match, axis=-1).astype(dtype)


def aggregate(codes: Array, lut: Array, lut_scale: Array, lut_offset: Array) -> Array:
    """Reference LUT aggregation (Eq. 4): gather + sum.

    Args:
      codes: (B, C) int32.
      lut: (C, G, N).
    Returns:
      (B, N) float32.
    """
    # (B, C, N) gather then sum over C
    gathered = jnp.take_along_axis(
        lut[None], codes[:, :, None, None].astype(jnp.int32), axis=2
    )[:, :, 0, :]
    acc = gathered.astype(jnp.int32 if lut.dtype == jnp.int8 else jnp.float32)
    total = acc.sum(axis=1)
    return total.astype(jnp.float32) * lut_scale + lut_offset


def aggregate_onehot(onehot: Array, lut: Array, lut_scale: Array,
                     lut_offset: Array) -> Array:
    """MXU-friendly aggregation: one-hot contraction (the TPU 'ROM group').

    ``out[b, n] = Σ_{c,g} onehot[b, c, g] · lut[c, g, n]`` — a dense matmul
    of shape (B, C·G) × (C·G, N).
    """
    b = onehot.shape[0]
    n = lut.shape[-1]
    lhs = onehot.reshape(b, -1)
    rhs = lut.reshape(-1, n).astype(lhs.dtype)
    out = lhs @ rhs
    return out.astype(jnp.float32) * lut_scale + lut_offset


def maddness_matmul(x: Array, params: MaddnessParams) -> Array:
    """Full online path: gather → encode → aggregate.  x: (B, D) → (B, N)."""
    xs = gather_split_values(x, params.tree)
    codes = encode(xs, params.tree)
    return aggregate(codes, params.lut, params.lut_scale, params.lut_offset)


def contract_onehot(onehot: Array, lut: Array, lut_scale: Array,
                    lut_offset: Array) -> Array:
    """dtype-dispatching one-hot contraction: int8 LUTs accumulate in int32
    (integer one-hot), float LUTs go through :func:`aggregate_onehot`."""
    if lut.dtype == jnp.int8:
        oh = onehot.astype(jnp.int8).reshape(onehot.shape[0], -1)
        acc = jax.lax.dot_general(
            oh, lut.reshape(-1, lut.shape[-1]),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return acc.astype(jnp.float32) * lut_scale + lut_offset
    return aggregate_onehot(onehot, lut, lut_scale, lut_offset)


def maddness_matmul_onehot(x: Array, params: MaddnessParams) -> Array:
    """One-hot (MXU) online path — numerically identical to the reference."""
    xs = gather_split_values(x, params.tree)
    onehot = encode_onehot(xs, params.tree)
    return contract_onehot(onehot, params.lut, params.lut_scale,
                           params.lut_offset)
