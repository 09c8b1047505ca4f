"""Unified LUT-MU execution engine: one entry point, three backends.

``lutmu_matmul(x, params, backend="auto")`` is the single call site the rest
of the repo (``core/``, ``models/``, ``launch/``) uses to run the paper's
allocator→encoder→aggregator pipeline.  It normalises the input form, picks a
backend per shape/dtype/platform, resolves fused-kernel tile sizes through the
autotuner, and runs:

  * ``"ref"``     — pure jnp/XLA, no Pallas: parallel-comparator one-hot
    encode + dense contraction (``core.maddness``).  Semantically identical
    to the ``kernels/ref.py`` oracles (parity-tested); the fastest path off
    TPU and for sub-MXU-tile problems.
  * ``"unfused"`` — two Pallas kernels: ``maddness_encode`` then
    ``lut_aggregate``.  The one-hot round-trips through HBM, but the encode
    runs exactly once — wins when many N-tiles × deep trees make the fused
    kernel's per-N-tile encode recompute dominate.
  * ``"fused"``   — the flagship single-pass Pallas kernel
    (``fused_lutmu``): the one-hot never leaves VMEM.

Selection rules live in :func:`select_backend` and are documented (with the
VMEM tile-budget table) in ``docs/kernels.md``; ``REPRO_LUTMU_BACKEND``
force-overrides ``"auto"``.  On non-TPU platforms the Pallas backends run in
interpret mode so parity tests execute everywhere.
"""
from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.maddness import (HashTree, MaddnessParams, contract_onehot,
                                 gather_split_values)
from repro.core.maddness import encode_onehot as _encode_onehot_xla
from repro.core.pruning import PruningPlan, pruned_to_split_values
from repro.kernels import autotune as AT
from repro.kernels.autotune import default_interpret
from repro.kernels.fused_lutmu import fused_lutmu_pallas
from repro.kernels.lut_aggregate import lut_aggregate_pallas
from repro.kernels.maddness_encode import encode_onehot_pallas

Array = jax.Array

BACKENDS = ("ref", "unfused", "fused")
INPUT_KINDS = ("full", "split", "package")

# Optional observability hook (``attach_dispatch_hook``): called with static
# call metadata after backend selection.  Fires at trace time — once per
# compiled program, never per executed step — and only ever receives
# python ints/strings (shapes/dtypes/backend), so it cannot leak tracers
# or perturb compiled computations.  None (the default) costs one host
# ``is not None`` check per trace.
_PROFILE_HOOK = None


def set_profile_hook(hook) -> None:
    """Install (or clear, with ``None``) the dispatch-metadata hook."""
    global _PROFILE_HOOK
    _PROFILE_HOOK = hook


def attach_dispatch_hook(registry):
    """Count LUT-MU backend selections in ``registry`` (a
    ``serving.obs.MetricsRegistry``) as ``lutmu_dispatch_total{backend,
    input_kind}``; returns a detach callable.  Counts on static metadata
    at trace time — one event per compiled program, zero per-step cost."""

    def hook(*, backend: str, input_kind: str, **_meta) -> None:
        registry.counter(
            "lutmu_dispatch_total",
            "LUT-MU programs compiled per selected backend",
            backend=backend, input_kind=input_kind).inc()

    set_profile_hook(hook)
    return lambda: set_profile_hook(None)


# Below either threshold the MXU tiles are mostly padding — see docs/kernels.md.
_MIN_MXU_ROWS = 8
_MIN_MXU_COLS = 128
# N-tile count past which the fused kernel's encode recompute (one VPU encode
# per N-tile) outweighs the unfused path's one-hot HBM round-trip, for deep
# trees (G ≥ 64) where the encode is no longer trivially cheap.
_UNFUSED_N_TILES = 8
_UNFUSED_MIN_G = 64


def params_from_arrays(split_dims: Array, thresholds: Array, lut: Array,
                       lut_scale: Array, lut_offset: Array) -> MaddnessParams:
    """Bundle raw arrays (e.g. a serving param dict) into ``MaddnessParams``.

    Prototypes are only needed offline (LUT rebuilds / STE retraining), so the
    bundle carries an empty placeholder.
    """
    tree = HashTree(split_dims, thresholds)
    protos = jnp.zeros(lut.shape[:2] + (0,), jnp.float32)
    return MaddnessParams(tree, protos, lut, lut_scale, lut_offset)


def select_backend(
    b: int,
    c: int,
    n: int,
    depth: int,
    lut_dtype=jnp.float32,
    platform: Optional[str] = None,
    tiles: Optional[AT.TileConfig] = None,
) -> str:
    """Shape/dtype/platform → backend name (the ``"auto"`` policy).

    Rules (measured by ``benchmarks/bench_fig11_scalability.py``, documented
    in ``docs/kernels.md``):

      1. off-TPU → ``ref``: interpret-mode Pallas exists for correctness,
         never for speed;
      2. sub-tile problems (B < 8, N < 128, or C·G < 128) → ``ref``: the MXU
         would chew mostly padding;
      3. int8 LUTs → ``fused``: the int8 one-hot and int32 accumulator stay
         in VMEM;
      4. many N-tiles × deep trees → ``unfused``: encode once, spill the
         one-hot, instead of re-encoding per N-tile;
      5. otherwise → ``fused``.
    """
    platform = platform or jax.default_backend()
    g = 2**depth
    if platform != "tpu":
        return "ref"
    if b < _MIN_MXU_ROWS or n < _MIN_MXU_COLS or c * g < _MIN_MXU_COLS:
        return "ref"
    if jnp.dtype(lut_dtype) == jnp.int8:
        return "fused"
    tiles = tiles or AT.heuristic_tiles(b, c, n, depth,
                                        jnp.dtype(lut_dtype).itemsize)
    if math.ceil(n / tiles.block_n) >= _UNFUSED_N_TILES and g >= _UNFUSED_MIN_G:
        return "unfused"
    return "fused"


def _to_split_values(x: Array, params: MaddnessParams, input_kind: str) -> Array:
    if input_kind == "full":
        return gather_split_values(x, params.tree)
    if input_kind == "split":
        return x
    if input_kind == "package":
        plan = PruningPlan(
            keep_idx=jnp.zeros((0,), jnp.int32),  # already gathered upstream
            consumer_codebooks=params.tree.num_codebooks,
            consumer_depth=params.tree.depth,
        )
        return pruned_to_split_values(x, plan)
    raise ValueError(f"input_kind must be one of {INPUT_KINDS}, got {input_kind!r}")


def _run_ref(xs: Array, params: MaddnessParams) -> Array:
    """Pure-XLA path: one-hot encode + dense contraction (no Pallas)."""
    onehot = _encode_onehot_xla(xs, params.tree)
    return contract_onehot(onehot, params.lut, params.lut_scale,
                           params.lut_offset)


def _run_unfused(xs: Array, params: MaddnessParams, tiles: AT.TileConfig,
                 interpret: bool) -> Array:
    onehot = encode_onehot_pallas(
        xs, params.tree.thresholds, depth=params.tree.depth,
        block_b=tiles.block_b, block_c=tiles.block_c, interpret=interpret,
    )
    return lut_aggregate_pallas(
        onehot, params.lut, params.lut_scale, params.lut_offset,
        block_b=tiles.block_b, block_n=tiles.block_n, interpret=interpret,
    )


def _run_fused(xs: Array, params: MaddnessParams, tiles: AT.TileConfig,
               interpret: bool) -> Array:
    return fused_lutmu_pallas(
        xs, params.tree.thresholds, params.lut,
        params.lut_scale, params.lut_offset,
        depth=params.tree.depth, block_b=tiles.block_b,
        block_n=tiles.block_n, block_c=tiles.block_c, interpret=interpret,
    )


def _run_backend(xs: Array, params: MaddnessParams, backend: str,
                 tiles: Optional[AT.TileConfig], interpret: bool) -> Array:
    if backend == "ref":
        return _run_ref(xs, params)
    if backend == "unfused":
        return _run_unfused(xs, params, tiles, interpret)
    return _run_fused(xs, params, tiles, interpret)


def lutmu_matmul(
    x: Array,
    params: MaddnessParams,
    *,
    backend: str = "auto",
    input_kind: str = "full",
    tiles: Optional[AT.TileConfig] = None,
    autotune: bool = False,
    interpret: Optional[bool] = None,
    cache: Optional[AT.AutotuneCache] = None,
) -> Array:
    """The unified LUT-MU entry point: ``x`` → approximate ``x @ W``.

    Args:
      x: the input, per ``input_kind``:
        ``"full"``    (B, D) activations — split dims are gathered here;
        ``"split"``   (B, C, I) pre-gathered split values;
        ``"package"`` (B, I·C) cluster-ordered pruned package from an
        upstream LUT-MU (the paper's chained hand-off).
      params: tree + LUT (+ dequant epilogue).  Use
        :func:`params_from_arrays` to bundle a raw param dict.
      backend: ``"auto"`` (see :func:`select_backend`) or one of
        ``"ref" | "unfused" | "fused"``.  ``REPRO_LUTMU_BACKEND`` overrides
        ``"auto"``.
      tiles: explicit fused-kernel tiling; default resolves through the
        autotuner (cache → measured if ``autotune`` → heuristic).
      autotune: measure candidate tilings for unseen shapes and persist the
        winner (also enabled globally by ``REPRO_AUTOTUNE=1``).
      interpret: Pallas interpret mode; default: on unless running on TPU.

    Returns:
      (B, N) float32.
    """
    if interpret is None:
        interpret = default_interpret()
    xs = _to_split_values(x, params, input_kind)
    b, c, depth = xs.shape
    n = params.lut.shape[-1]

    if backend == "auto":
        backend = os.environ.get("REPRO_LUTMU_BACKEND", "auto")
    if backend == "auto":
        backend = select_backend(b, c, n, depth, params.lut.dtype, tiles=tiles)
    if backend not in BACKENDS:
        raise ValueError(f"backend must be 'auto' or one of {BACKENDS}, "
                         f"got {backend!r}")
    if _PROFILE_HOOK is not None:
        _PROFILE_HOOK(backend=backend, input_kind=input_kind, b=int(b),
                      c=int(c), n=int(n), depth=int(depth),
                      lut_dtype=str(params.lut.dtype))

    if backend != "ref" and tiles is None:
        tiles = AT.get_tiles(
            b, c, n, depth, params.lut.dtype, backend=backend,
            allow_measure=autotune, interpret=interpret, cache=cache,
        )
    return _run_backend(xs, params, backend, tiles, interpret)


def lutmu_matmul_sharded(
    x: Array,
    params: MaddnessParams,
    *,
    mesh,
    axis: str = "model",
    backend: str = "auto",
    input_kind: str = "full",
    tiles: Optional[AT.TileConfig] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """Codebook-sharded LUT-MU: per-shard aggregate + psum, no gathers.

    The TP-sharded twin of :func:`lutmu_matmul` for serving under a mesh
    (``distributed/sharding.py`` shards LUT tables over the codebook axis on
    ``axis``).  Each device runs the chosen backend over its *local*
    codebooks only — encode reads local split values/thresholds, the
    aggregate contracts the local LUT shard — then the pre-epilogue partial
    outputs are ``psum``-reduced over ``axis`` and the dequant epilogue
    (scale/offset, which fold per-codebook terms of the *full* table) is
    applied once on the replicated result.  The LUT never leaves its shard.

    Integer LUTs stay bit-identical to the unsharded path: per-shard int32
    partials are exact in float32 (< 2**24), so the psum and the single
    epilogue reproduce ``contract_onehot`` arithmetic exactly.  Float LUTs
    reassociate the codebook sum across shards (≈1e-6 relative).

    Falls back to :func:`lutmu_matmul` when ``axis`` has size 1 or the
    codebook count does not divide by it (the sharding rules replicate such
    tables anyway).
    """
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = default_interpret()
    xs = _to_split_values(x, params, input_kind)
    b, c, depth = xs.shape
    n = params.lut.shape[-1]
    tp = int(mesh.shape[axis])
    if tp <= 1 or c % tp != 0:
        return lutmu_matmul(xs, params, backend=backend, input_kind="split",
                            tiles=tiles, interpret=interpret)
    c_local = c // tp

    # batch rows stay sharded over the data-parallel axes when they divide
    # (the psum runs only over the TP axis), so DP devices never gather or
    # recompute each other's rows.
    dp_axes = tuple(n_ for n_ in mesh.axis_names if n_ != axis)
    dp_size = math.prod(mesh.shape[n_] for n_ in dp_axes) if dp_axes else 1
    batch_ax = None
    b_local = b
    if dp_axes and dp_size > 1 and b % dp_size == 0:
        batch_ax = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        b_local = b // dp_size

    # backend/tile choices see the *per-shard* problem — that is the shape
    # the kernel actually executes (and the autotune-cache key).
    if backend == "auto":
        backend = os.environ.get("REPRO_LUTMU_BACKEND", "auto")
    if backend == "auto":
        backend = select_backend(b_local, c_local, n, depth, params.lut.dtype,
                                 tiles=tiles)
    if backend not in BACKENDS:
        raise ValueError(f"backend must be 'auto' or one of {BACKENDS}, "
                         f"got {backend!r}")
    if _PROFILE_HOOK is not None:
        _PROFILE_HOOK(backend=backend, input_kind="sharded:" + input_kind,
                      b=int(b_local), c=int(c_local), n=int(n),
                      depth=int(depth), lut_dtype=str(params.lut.dtype))
    if backend != "ref" and tiles is None:
        tiles = AT.get_tiles(b_local, c_local, n, depth, params.lut.dtype,
                             backend=backend, interpret=interpret)

    def local_shard(xs_l, split_dims_l, thresholds_l, lut_l):
        # unit scale / zero offset: the epilogue runs once, after the psum
        p_l = params_from_arrays(split_dims_l, thresholds_l, lut_l,
                                 jnp.ones((), jnp.float32),
                                 jnp.zeros((), jnp.float32))
        acc = _run_backend(xs_l, p_l, backend, tiles, interpret)
        return jax.lax.psum(acc, axis)

    # check_vma=False: shard_map's replication checker has no rule for
    # pallas_call, so the fused/unfused backends would fail at trace time;
    # the psum + out_specs make replication over ``axis`` explicit anyway.
    out = jax.shard_map(
        local_shard, mesh=mesh,
        in_specs=(P(batch_ax, axis, None), P(axis, None), P(axis, None),
                  P(axis, None, None)),
        out_specs=P(batch_ax),
        check_vma=False,
    )(xs, params.tree.split_dims, params.tree.thresholds, params.lut)
    return out * params.lut_scale + params.lut_offset
