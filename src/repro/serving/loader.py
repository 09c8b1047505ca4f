"""``load_engine`` — the one serving factory.

Sniffs what ``source`` is and picks the engine:

====================================  =====================================
``source``                            engine
====================================  =====================================
``None``                              family dispatch: paged
                                      :class:`ServeEngine` when the family
                                      supports paged KV, else
                                      :class:`FixedSlotEngine`
path to an ``amm_lm`` artifact        paged/fixed engine serving the
                                      artifact's LUT-MU tables
path to a target+draft bundle         :class:`SpeculativeEngine` (or the
                                      bundle's target half with
                                      ``speculative=False``)
a loaded ``Artifact`` object          same as an ``amm_lm`` path
``(target_art, draft_art)`` tuple     :class:`SpeculativeEngine` from
                                      in-memory artifacts
====================================  =====================================

Every other keyword is forwarded to the engine constructor
(``max_batch``, ``max_len``, ``page_size``, ``prefill_chunk``,
``num_pages``, ``prefix_cache``, ``spec_k``, ``mesh``, ``recorder``, ...);
the fixed-slot engine drops the paged-only ones.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

from repro.models import model as MD
from repro.models.config import ModelConfig
from repro.serving.engine import FixedSlotEngine, ServeEngine, _splice_artifact
from repro.serving.speculative import SpeculativeEngine

# ServeEngine options the fixed-slot engine has no use for
_PAGED_ONLY = ("page_size", "prefill_chunk", "num_pages", "prefix_cache",
               "verify_backend")


def _is_pathlike(source) -> bool:
    return isinstance(source, (str, os.PathLike))


def _is_artifact(source) -> bool:
    # a loaded repro.compiler.artifact.Artifact (duck-typed: the compiler
    # is an optional layer below serving, so no isinstance import here)
    return hasattr(source, "kind") and hasattr(source, "manifest")


def _engine_for_family(params, cfg: ModelConfig, opts):
    """The paged engine when the family has a paged KV layout, else fixed
    slots without the paged-only options."""
    if MD.supports_paged(cfg):
        return ServeEngine(params, cfg, **opts)
    return FixedSlotEngine(params, cfg, **{
        k: v for k, v in opts.items() if k not in _PAGED_ONLY})


def load_engine(source, params, cfg: ModelConfig, *,
                speculative: Optional[bool] = None, **opts):
    """Build a serving engine from ``source`` (see module docstring).

    ``speculative`` controls what a bundle becomes (default True →
    :class:`SpeculativeEngine`; False → the bundle's target half through
    the paged/fixed engine).  ``params`` is always the dense-model tree
    artifacts were compiled against.
    """
    # (target, draft) in-memory artifact pair → speculative
    if isinstance(source, (tuple, list)):
        if len(source) != 2:
            raise ValueError(
                f"artifact-pair source must be (target, draft), got "
                f"{len(source)} elements")
        if speculative is not False:
            return SpeculativeEngine._from_artifacts(source[0], source[1],
                                                     params, cfg, **opts)
        source = source[0]

    # a path → sniff the manifest kind, then load it
    if _is_pathlike(source):
        from repro.compiler.artifact import load_artifact, peek_manifest

        kind = peek_manifest(source).get("kind")
        if kind == "bundle":
            if speculative is not False:
                return SpeculativeEngine._from_bundle(source, params, cfg,
                                                      **opts)
            source = load_artifact(Path(source) / "target")
        elif kind == "amm_lm":
            if speculative:
                raise ValueError(
                    "speculative=True needs a target+draft bundle source, "
                    f"got an {kind!r} artifact — compile one with "
                    "`python -m repro.compiler bundle`")
            source = load_artifact(source)
        else:
            raise ValueError(
                f"cannot serve artifact kind {kind!r} from {source!r}")

    # a single loaded artifact → splice its tables into the dense params
    if _is_artifact(source):
        params, cfg = _splice_artifact(source, params, cfg,
                                       opts.get("mesh"))
        return _engine_for_family(params, cfg, opts)

    # no source → plain dense (or amm-enabled cfg) serving
    if source is None:
        if speculative:
            raise ValueError(
                "speculative=True needs a bundle path or an artifact pair "
                "as source")
        return _engine_for_family(params, cfg, opts)

    raise TypeError(
        f"unsupported source {type(source).__name__!r}: expected None, a "
        "path, a loaded Artifact, or a (target, draft) pair")
