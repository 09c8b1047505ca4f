"""The offline LUT-MU compiler: calibrate → prune → quantise → pack.

The paper's deployment story is two-phase.  The *online* half (encode +
aggregate) is the unified execution engine (``kernels.dispatch``); this
package is the *offline* half — everything that happens once, before
serving:

  1. **calibrate**  (``compiler.calibrate``) — fit per-layer MADDNESS hash
     trees, ridge-optimised prototypes and float LUTs from a trained model
     plus calibration batches;
  2. **plan**       (``compiler.planner``) — wire the paper's pruning
     transforms across consecutive layers and fix per-layer backend/tile
     choices via the autotuner;
  3. **quantise**   (``compiler.quantize``) — bake LUT entries at a chosen
     resolution config (float32 / int16 / int8 / int4-packed) with
     per-codebook offsets folded into the engine's fused dequant epilogue;
  4. **pack**       (``compiler.artifact``) — a versioned, checksummed,
     atomically-written artifact directory that round-trips through
     ``load_artifact`` into a servable ``AMMChain`` (or, for ``amm_lm``
     artifacts, into ``ServeEngine`` params).

``python -m repro.compiler`` drives the pipeline from the command line.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Optional, Sequence

import jax
import numpy as np

from repro.compiler.artifact import (  # noqa: F401
    ARTIFACT_FORMAT,
    ARTIFACT_VERSION,
    BUNDLE_VERSION,
    Artifact,
    ArtifactError,
    load_artifact,
    load_bundle,
    peek_manifest,
    save_artifact,
    save_bundle,
    tiles_to_json,
)
from repro.compiler.calibrate import (  # noqa: F401
    ACTIVATIONS,
    CalibrationConfig,
    LayerCalibration,
    calibrate_chain,
    calibrate_layer,
    calibrate_lm_mlp_layers,
    calibrate_lm_mlp_layers_float,
)
from repro.compiler.planner import LayerPlan, plan_chain  # noqa: F401
from repro.compiler.quantize import (  # noqa: F401
    RESOLUTIONS,
    ResolutionConfig,
    get_resolution,
    pack_int4,
    quantize_lut,
    resource_report,
    unpack_int4,
)
from repro.core import lut_mu as LM


@dataclasses.dataclass
class CompileResult:
    """What one ``compile_chain`` call produced."""

    artifact: Artifact
    chain: Optional[LM.AMMChain]  # in-memory servable chain (amm_chain kind)
    path: Optional[Path]          # artifact dir when ``out`` was given
    report: dict                  # resolution-config resource report


def compile_chain(
    weights: Sequence[np.ndarray],
    biases: Sequence[Optional[np.ndarray]],
    calib_x: np.ndarray,
    *,
    num_codebooks: Sequence[int],
    depths: Sequence[int],
    activations: Sequence[Optional[str]] = (),
    resolution: str = "float32",
    prune: bool = True,
    batch_hint: int = 256,
    autotune: bool = False,
    calibration: CalibrationConfig = CalibrationConfig(),
    name: str = "amm_chain",
    out: Optional[str] = None,
) -> CompileResult:
    """Compile a dense cascade into a servable LUT-MU artifact.

    The full offline pipeline: calibrate each layer on propagated approximate
    activations, plan the pruned hand-offs + execution configs, quantise at
    ``resolution``, and (when ``out`` is given) pack to disk.  The returned
    in-memory ``chain`` and a ``load_artifact(out).to_chain()`` are built
    from identical arrays — float32 artifacts reproduce the in-memory
    pipeline bit-exactly.
    """
    import jax.numpy as jnp

    from repro.core import maddness as M

    res = get_resolution(resolution)
    calibs = calibrate_chain(weights, biases, calib_x, num_codebooks, depths,
                             activations, config=calibration)
    plans = plan_chain(calibs, res, prune=prune, batch_hint=batch_hint,
                       autotune=autotune)

    tensors = {}
    layer_recs = []
    shapes = []
    chain_layers = []
    for i, (cal, plan) in enumerate(zip(calibs, plans)):
        lut = np.asarray(cal.params.lut, np.float32)
        offset = np.asarray(cal.params.lut_offset, np.float32)
        if plan.prune_plan is not None:
            keep = np.asarray(plan.prune_plan.keep_idx)
            lut, offset = lut[..., keep], offset[..., keep]
            tensors[f"layer{i}/keep_idx"] = keep.astype(np.int32)
        int4_packed = False
        if res.is_float:
            q = lut
            scale = np.ones((lut.shape[-1],), np.float32)
        else:
            q, scale, offset = quantize_lut(lut, offset, res.bits)
            if res.bits == 4:
                q = pack_int4(q)
                int4_packed = True
        tensors[f"layer{i}/split_dims"] = np.asarray(
            cal.params.tree.split_dims, np.int32)
        tensors[f"layer{i}/thresholds"] = np.asarray(
            cal.params.tree.thresholds, np.float32)
        tensors[f"layer{i}/lut"] = q
        tensors[f"layer{i}/lut_scale"] = scale
        tensors[f"layer{i}/lut_offset"] = np.asarray(offset, np.float32)
        layer_recs.append({
            "num_codebooks": cal.num_codebooks,
            "depth": cal.depth,
            "in_features": cal.in_features,
            "out_features_full": cal.out_features,
            "cols": plan.cols,
            "pruned": plan.prune_plan is not None,
            "consumer_codebooks": (plan.prune_plan.consumer_codebooks
                                   if plan.prune_plan else None),
            "consumer_depth": (plan.prune_plan.consumer_depth
                               if plan.prune_plan else None),
            "backend": plan.backend,
            "tiles": tiles_to_json(plan.tiles),
            "lut_dtype": str(np.asarray(q).dtype),
            "int4_packed": int4_packed,
        })
        shapes.append((cal.num_codebooks, cal.depth, plan.cols,
                       cal.out_features))
        # in-memory twin: same lut/scale/offset arrays as the artifact, but
        # keeping the calibrated prototypes so retrain/rebuild still work
        run_lut = unpack_int4(q, plan.cols) if int4_packed else q
        chain_layers.append(LM.AMMLinear(
            params=M.MaddnessParams(
                tree=cal.params.tree,
                prototypes=cal.params.prototypes,
                lut=jnp.asarray(run_lut),
                lut_scale=jnp.asarray(scale),
                lut_offset=jnp.asarray(offset, jnp.float32)),
            out_plan=plan.prune_plan,
            full_out_features=cal.out_features,
            tiles=plan.tiles))

    report = resource_report(shapes)
    acts = (tuple(activations) if activations
            else (None,) * (len(list(weights)) - 1))
    manifest = {
        "format": ARTIFACT_FORMAT,
        "version": ARTIFACT_VERSION,
        "kind": "amm_chain",
        "name": name,
        "platform": jax.default_backend(),
        "resolution": res.name,
        "activations": list(acts),
        "layers": layer_recs,
        "resource_report": report,
    }
    art = Artifact(manifest=manifest, tensors=tensors)
    path = save_artifact(out, art) if out is not None else None
    chain = LM.AMMChain(
        layers=chain_layers, activation_names=acts,
        backends=tuple(rec["backend"] for rec in layer_recs))
    return CompileResult(artifact=art, chain=chain, path=path, report=report)


def _pack_amm_lm(fitted: list, cfg, resolution: str, name: Optional[str],
                 mesh_shape: Optional[dict]) -> Artifact:
    """Per-layer fitted AMM-MLP param dicts → an in-memory ``amm_lm``
    artifact (shared by :func:`compile_lm_amm` and
    :func:`compile_lm_bundle`)."""
    from repro.compiler import quantize as Q

    tensors = {}
    int4_cols = {}
    lut_bytes = 0
    for i, d in enumerate(fitted):
        for k, v in d.items():
            arr = np.asarray(v)
            is_lut = (k.startswith("lut_") and "scale" not in k
                      and "offset" not in k)
            if is_lut and resolution == "int4":
                # ship two codes per byte (the paper's stored-bits saving);
                # ``lm_layer_params`` unpacks back to runtime int8 codes
                int4_cols[f"layer{i}/{k}"] = int(arr.shape[-1])
                arr = Q.pack_int4(arr)
            tensors[f"layer{i}/{k}"] = arr
            if is_lut:
                lut_bytes += arr.nbytes
    a = cfg.amm
    manifest = {
        "format": ARTIFACT_FORMAT,
        "version": ARTIFACT_VERSION,
        "kind": "amm_lm",
        "name": name or f"{cfg.name}-amm",
        "arch": cfg.name,
        "platform": jax.default_backend(),
        "resolution": resolution,
        "num_layers": int(cfg.num_layers),
        "amm": {"d_sub": a.d_sub, "depth": a.depth, "prune": a.prune,
                "quantize_int8": resolution != "float32",
                "backend": a.backend},
        "resource_report": {"lut_bytes": int(lut_bytes)},
    }
    if int4_cols:
        manifest["int4_cols"] = int4_cols
    if mesh_shape is not None:
        manifest["mesh"] = {k: int(v) for k, v in mesh_shape.items()}
    return Artifact(manifest=manifest, tensors=tensors)


def compile_lm_amm(
    params: dict,
    cfg,
    tokens: np.ndarray,
    *,
    name: Optional[str] = None,
    out: Optional[str] = None,
    mesh_shape: Optional[dict] = None,
    seed: int = 0,
    resolution: Optional[str] = None,
) -> CompileResult:
    """Compile a trained LM's MLP blocks into an ``amm_lm`` artifact.

    Captures each layer's real MLP-input activations on ``tokens``, fits
    the AMM-MLP tables per layer (gate/up share a tree; gate/up LUTs are
    pruned to the down-encode's split dims per ``cfg.amm``), quantises
    them at ``resolution`` (``float32`` / ``int8`` / ``int4``; default:
    ``cfg.amm.quantize_int8``'s historical meaning), and packs them.
    Load side: ``repro.serving.load_engine`` /
    ``Artifact.splice_lm_params``.

    ``mesh_shape`` (e.g. ``{"data": 2, "model": 4}``) records the serving
    mesh the artifact is intended for — ``launch/serve.py --mesh auto``
    reads it back; the engine only warns on mismatch since the sharding
    rules re-derive placement for any mesh.
    """
    if resolution is None:
        resolution = "int8" if cfg.amm.quantize_int8 else "float32"
    fitted = calibrate_lm_mlp_layers(params, cfg, tokens, seed=seed,
                                     resolution=resolution)
    art = _pack_amm_lm(fitted, cfg, resolution, name, mesh_shape)
    path = save_artifact(out, art) if out is not None else None
    return CompileResult(artifact=art, chain=None, path=path,
                         report=art.manifest["resource_report"])


@dataclasses.dataclass
class BundleResult:
    """What one ``compile_lm_bundle`` call produced."""

    target: Artifact              # full-resolution verifier
    draft: Artifact               # low-resolution proposer
    manifest: dict                # bundle-level manifest
    path: Optional[Path]          # bundle dir when ``out`` was given
    report: dict                  # per-half LUT bytes + draft savings


def compile_lm_bundle(
    params: dict,
    cfg,
    tokens: np.ndarray,
    *,
    target_resolution: str = "int8",
    draft_resolution: str = "int4",
    spec_k: int = 4,
    name: Optional[str] = None,
    out: Optional[str] = None,
    mesh_shape: Optional[dict] = None,
    seed: int = 0,
) -> BundleResult:
    """Compile a target+draft artifact pair from **one** calibration pass.

    The speculative-decoding packaging: each layer's trees / prototypes /
    float tables are fitted once on the captured activations, then baked
    at two resolution configs — the full-resolution *target* (the
    verifier, whose greedy streams define correctness) and a
    low-resolution *draft* (the proposer; lower entry width = the paper's
    1.3–2.6× resource saving, converted into throughput at zero accuracy
    cost because the target verifies every token).  Identical trees mean
    the draft differs from the target only in LUT entry width, which is
    what keeps greedy agreement (and so acceptance rates) high.

    Load side: :func:`repro.compiler.artifact.load_bundle` /
    ``repro.serving.load_engine``.
    """
    from repro.models.amm_mlp import AMM_RESOLUTIONS, quantize_amm_layer

    for which, res in (("target", target_resolution),
                       ("draft", draft_resolution)):
        if res not in AMM_RESOLUTIONS:
            raise ValueError(f"{which}_resolution must be one of "
                             f"{AMM_RESOLUTIONS}, got {res!r}")
    if spec_k < 1:
        # fail at compile time, not after the serve-side engine rejects
        # the recorded value post-calibration
        raise ValueError(f"spec_k must be >= 1, got {spec_k}")
    float_layers = calibrate_lm_mlp_layers_float(params, cfg, tokens,
                                                 seed=seed)
    base = name or f"{cfg.name}-spec"
    target = _pack_amm_lm(
        [quantize_amm_layer(fp, target_resolution) for fp in float_layers],
        cfg, target_resolution, f"{base}-target", mesh_shape)
    draft = _pack_amm_lm(
        [quantize_amm_layer(fp, draft_resolution) for fp in float_layers],
        cfg, draft_resolution, f"{base}-draft", mesh_shape)
    t_bytes = target.manifest["resource_report"]["lut_bytes"]
    d_bytes = draft.manifest["resource_report"]["lut_bytes"]
    report = {
        "target": {"resolution": target_resolution, "lut_bytes": t_bytes},
        "draft": {"resolution": draft_resolution, "lut_bytes": d_bytes},
        # stored int4 codes occupy int8 at runtime; count the shipped
        # information width for the paper-style savings ratio
        "draft_vs_target_stored": round(t_bytes / max(d_bytes, 1), 3),
    }
    manifest = {
        "format": ARTIFACT_FORMAT,
        "version": BUNDLE_VERSION,
        "kind": "bundle",
        "name": base,
        "arch": cfg.name,
        "num_layers": int(cfg.num_layers),
        "spec_k": int(spec_k),
        "resource_report": report,
    }
    path = None
    if out is not None:
        path = save_bundle(out, manifest, target, draft)
        manifest = peek_manifest(path)  # pick up sub-checksums + defaults
    return BundleResult(target=target, draft=draft, manifest=manifest,
                        path=path, report=report)
