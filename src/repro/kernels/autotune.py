"""Tile-size autotuning for the fused LUT-MU Pallas kernel.

The fused kernel's grid is ``(B/B_t, N/N_t, C/C_t)`` and its per-step VMEM
footprint (see ``docs/kernels.md`` for the full table) is

    x    tile  I · B_t · C_t · 4                 bytes (f32 split values)
    thr  tile  (G-1) · C_t · 4                   bytes
    lut  tile  C_t · G' · N_t · itemsize         bytes (G' = G rounded up to
                                                 the dtype's sublane tile)
    out  tile  B_t · N_t · 4                     bytes (f32/i32 accumulator)

each double-buffered by the Pallas pipeline, plus the leaf masks and the
one-hot the kernel builds.  Every candidate tiling must fit inside
``VMEM_FRACTION`` of the 16 MiB scoped-VMEM default.  ``C_t`` sits on
lanes, so it is a multiple of 128 that divides ``C``, or ``C`` itself.
Two selection modes:

  * **heuristic** (default, free): the candidate that minimises grid steps —
    i.e. the largest tiles that fit — with ties broken toward fewer N-tiles
    (each N-tile re-runs the VPU encode) and then smaller VMEM;
  * **measured** (``autotune=True`` on the dispatch entry point, or
    ``REPRO_AUTOTUNE=1``): run each candidate on synthetic data of the real
    shape and keep the fastest.

Measured winners persist in a JSON cache keyed by
``(platform, backend, B, C, N, I, lut_dtype)`` so a shape is tuned once per
machine.  Cache path: ``$REPRO_AUTOTUNE_CACHE`` or
``~/.cache/repro/lutmu_autotune.json``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
import warnings
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

VMEM_BUDGET_BYTES = 16 * 1024 * 1024  # Mosaic's default scoped VMEM limit
VMEM_FRACTION = 0.75  # headroom for Mosaic's own temporaries

_BLOCK_B_CHOICES = (64, 128, 256, 512)
_BLOCK_N_CHOICES = (128, 256, 512)
_BLOCK_C_CHOICES = (128, 256, 512)


def default_interpret() -> bool:
    """Pallas interpret mode: on for every platform except real TPUs."""
    return jax.default_backend() != "tpu"


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """Fused-kernel tiling ``(B_t, N_t, C_t)``."""

    block_b: int = 256
    block_n: int = 256
    block_c: int = 128

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TileConfig":
        return cls(int(d["block_b"]), int(d["block_n"]), int(d["block_c"]))


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _ceil_div(x: int, m: int) -> int:
    return (x + m - 1) // m


def fused_vmem_bytes(tiles: TileConfig, depth: int, lut_itemsize: int) -> int:
    """Per-grid-step VMEM footprint of the fused kernel (docstring formula).

    The x/thr/lut/out blocks count twice (the pipeline double-buffers
    them).  The LUT block's ``G`` axis is the second-minor one, so it pads
    to the dtype's sublane tile (32 rows for int8, 16 for bf16, 8 for
    f32).  Besides the blocks the kernel materialises the leaf-mask
    pyramid (Σ_l B_t·C_t·2^l ≈ 2·B_t·C_t·G mask words) and one
    ``(B_t, C_t)`` one-hot per leaf — negligible at the default I = 4,
    dominant for deep trees, so they are counted here.
    """
    g = 2**depth
    g_pad = _ceil_to(g, 32 // lut_itemsize)
    x = tiles.block_b * tiles.block_c * depth * 4
    thr = _ceil_to(g - 1, 8) * tiles.block_c * 4
    lut = tiles.block_c * g_pad * tiles.block_n * lut_itemsize
    out = tiles.block_b * tiles.block_n * 4
    interm = tiles.block_b * tiles.block_c * g * (4 + lut_itemsize)
    return 2 * (x + thr + lut + out) + interm


def _effective(tiles: TileConfig, b: int, c: int, n: int) -> TileConfig:
    """Clamp a tiling to the (padded) problem, mirroring the kernel wrapper."""
    return TileConfig(
        block_b=min(tiles.block_b, _ceil_to(b, 8)),
        block_n=min(tiles.block_n, _ceil_to(n, 128)),
        block_c=min(tiles.block_c, c),
    )


def _c_tiles(c: int) -> List[int]:
    """Lane-legal C tiles: multiples of 128 that divide ``C`` (no padded
    copy of the LUT per call), and ``C`` itself."""
    return sorted({bc for bc in _BLOCK_C_CHOICES if c % bc == 0} | {c})


def candidate_tiles(
    b: int,
    c: int,
    n: int,
    depth: int,
    lut_itemsize: int = 4,
    budget_bytes: Optional[int] = None,
) -> List[TileConfig]:
    """All distinct in-budget tilings for this problem, largest-tile first."""
    budget = int((budget_bytes or VMEM_BUDGET_BYTES) * VMEM_FRACTION)
    seen: Dict[TileConfig, TileConfig] = {}
    for bb in _BLOCK_B_CHOICES:
        for bn in _BLOCK_N_CHOICES:
            for bc in _c_tiles(c):
                t = _effective(TileConfig(bb, bn, bc), b, c, n)
                if fused_vmem_bytes(t, depth, lut_itemsize) <= budget:
                    seen.setdefault(t, t)
    out = list(seen)
    out.sort(key=lambda t: _grid_score(t, b, c, n, depth, lut_itemsize))
    if not out:  # degenerate budget: fall back to the smallest tiling
        out = [_effective(TileConfig(64, 128, _c_tiles(c)[0]), b, c, n)]
    return out


def _grid_score(t: TileConfig, b: int, c: int, n: int, depth: int,
                lut_itemsize: int) -> Tuple:
    """Lexicographic heuristic rank: fewer grid steps, then fewer N-tiles
    (each re-runs the encode), then the smaller VMEM footprint."""
    steps = (
        _ceil_div(b, t.block_b) * _ceil_div(n, t.block_n) * _ceil_div(c, t.block_c)
    )
    return (steps, _ceil_div(n, t.block_n),
            fused_vmem_bytes(t, depth, lut_itemsize))


def heuristic_tiles(
    b: int,
    c: int,
    n: int,
    depth: int,
    lut_itemsize: int = 4,
    budget_bytes: Optional[int] = None,
) -> TileConfig:
    """Best in-budget tiling without measuring anything."""
    return candidate_tiles(b, c, n, depth, lut_itemsize, budget_bytes)[0]


# ---------------------------------------------------------------------------
# Persistent per-shape cache.
# ---------------------------------------------------------------------------


def default_cache_path() -> Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "lutmu_autotune.json"


def shape_key(platform: str, backend: str, b: int, c: int, n: int,
              depth: int, lut_dtype) -> str:
    return f"{platform}|{backend}|b{b}|c{c}|n{n}|i{depth}|{jnp.dtype(lut_dtype).name}"


class AutotuneCache:
    """JSON-backed map ``shape key → TileConfig`` (plus timing metadata)."""

    def __init__(self, path: Optional[Path] = None):
        self.path = Path(path) if path is not None else default_cache_path()
        self._entries: Dict[str, dict] = {}
        self.load()

    def load(self) -> None:
        self._entries = {}
        try:
            text = self.path.read_text()
        except OSError:
            return  # no cache yet — normal first run
        except UnicodeDecodeError:
            text = ""  # binary garbage: corrupt, same degradation below
        entries = self._parse(text)
        if entries is None:
            # A process killed mid-write (pre-merge-on-save versions wrote
            # in place) leaves truncated JSON behind.  Degrade to an empty
            # cache — tuning re-measures, nothing else should break.
            warnings.warn(
                f"autotune cache {self.path} is corrupt; starting empty "
                "(it will be rewritten on the next save)",
                RuntimeWarning, stacklevel=2)
            return
        self._entries = entries

    @staticmethod
    def _parse(text: str) -> Optional[Dict[str, dict]]:
        try:
            entries = json.loads(text)
        except ValueError:
            return None
        return entries if isinstance(entries, dict) else None

    def save(self) -> None:
        """Merge-on-save: concurrent writers (bench + serve tuning different
        shapes against one cache file) union their entries instead of the
        last save clobbering the first.  The re-read + in-memory union is
        racy in principle, but the rename is atomic and each entry is
        self-contained, so the worst interleaving loses a *re-measurable
        timing*, never corrupts the file.  The tmp name carries the pid —
        a fixed ``.tmp`` would itself be a cross-process collision.
        """
        self.path.parent.mkdir(parents=True, exist_ok=True)
        try:
            on_disk = self._parse(self.path.read_text())
        except (OSError, UnicodeDecodeError):
            on_disk = None  # missing or corrupt: nothing worth merging
        if on_disk:
            self._entries = on_disk | self._entries
        tmp = self.path.with_name(f"{self.path.name}.tmp.{os.getpid()}")
        tmp.write_text(json.dumps(self._entries, indent=1, sort_keys=True))
        os.replace(tmp, self.path)

    def get(self, key: str, cls=TileConfig):
        e = self._entries.get(key)
        if not e:
            return None
        try:
            return cls.from_dict(e)
        except (KeyError, TypeError, ValueError):
            return None

    def put(self, key: str, tiles: TileConfig, us: Optional[float] = None,
            source: str = "measured") -> None:
        entry = tiles.to_dict() | {"source": source}
        if us is not None:
            entry["us"] = round(float(us), 2)
        self._entries[key] = entry

    def __len__(self) -> int:
        return len(self._entries)


_default_cache: Optional[AutotuneCache] = None


def get_default_cache() -> AutotuneCache:
    global _default_cache
    if _default_cache is None or _default_cache.path != default_cache_path():
        _default_cache = AutotuneCache()
    return _default_cache


# ---------------------------------------------------------------------------
# Measurement.
# ---------------------------------------------------------------------------


def _time_us(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def measure_fused_tiles(
    b: int,
    c: int,
    n: int,
    depth: int,
    lut_dtype=jnp.float32,
    *,
    interpret: Optional[bool] = None,
    candidates: Optional[Sequence[TileConfig]] = None,
    iters: int = 3,
) -> Tuple[TileConfig, Dict[TileConfig, float]]:
    """Time every candidate tiling on synthetic data of the real shape.

    Synthetic inputs (fixed seed) are fine because the kernel is data-
    oblivious: comparisons and the one-hot contraction run the same work for
    any values.  Returns ``(best, {tiles: µs})``.
    """
    from repro.kernels.fused_lutmu import fused_lutmu_pallas

    if interpret is None:
        interpret = default_interpret()
    lut_itemsize = jnp.dtype(lut_dtype).itemsize
    if candidates is None:
        candidates = candidate_tiles(b, c, n, depth, lut_itemsize)
    g = 2**depth
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(b, c, depth)).astype(np.float32))
    thr = jnp.asarray(rng.normal(size=(c, g - 1)).astype(np.float32))
    if jnp.dtype(lut_dtype) == jnp.int8:
        lut = jnp.asarray(rng.integers(-128, 128, (c, g, n)), jnp.int8)
    else:
        lut = jnp.asarray(rng.normal(size=(c, g, n)), lut_dtype)
    scale = jnp.ones((), jnp.float32)
    offset = jnp.zeros((n,), jnp.float32)

    timings: Dict[TileConfig, float] = {}
    for t in candidates:
        us = _time_us(
            lambda xv, tv, lv: fused_lutmu_pallas(
                xv, tv, lv, scale, offset, depth=depth,
                block_b=t.block_b, block_n=t.block_n, block_c=t.block_c,
                interpret=interpret,
            ),
            x, thr, lut, iters=iters,
        )
        timings[t] = us
    best = min(timings, key=timings.get)
    return best, timings


def get_tiles(
    b: int,
    c: int,
    n: int,
    depth: int,
    lut_dtype=jnp.float32,
    *,
    platform: Optional[str] = None,
    backend: str = "fused",
    allow_measure: bool = False,
    interpret: Optional[bool] = None,
    cache: Optional[AutotuneCache] = None,
) -> TileConfig:
    """Resolve the tiling for one shape: cache hit → measured → heuristic.

    Measured results are written back to the persistent cache; heuristic
    picks are free to recompute and are not persisted.  Only the fused
    backend is measured — the candidates and timings model the fused
    kernel's footprint, so other backends always get the heuristic (their
    B/C tiles are shape-compatible, and ``lut_aggregate``'s K tile keeps
    its own default).
    """
    platform = platform or jax.default_backend()
    cache = cache if cache is not None else get_default_cache()
    key = shape_key(platform, backend, b, c, n, depth, lut_dtype)
    hit = cache.get(key)
    if hit is not None:
        return hit
    if backend == "fused" and (
            allow_measure or os.environ.get("REPRO_AUTOTUNE") == "1"):
        best, timings = measure_fused_tiles(
            b, c, n, depth, lut_dtype, interpret=interpret)
        cache.put(key, best, us=timings[best])
        try:
            cache.save()
        except OSError:
            pass  # read-only filesystem: keep the in-memory entry
        return best
    return heuristic_tiles(b, c, n, depth, jnp.dtype(lut_dtype).itemsize)


# ---------------------------------------------------------------------------
# The ``verify`` namespace: fused speculative-verify window tiles.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class VerifyTileConfig:
    """Fused-verify kernel tiling: KV positions staged in VMEM per block.

    ``block_s`` must be a ``page_size`` multiple that divides the logical
    view length ``max_pages * page_size`` (the kernel DMAs whole pages and
    its block loop is static).
    """

    block_s: int = 256

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "VerifyTileConfig":
        return cls(int(d["block_s"]))


def verify_shape_key(platform: str, s: int, w: int, nkv: int, g: int,
                     hd: int, kv_dtype) -> str:
    """Cache key for the ``verify`` backend namespace (batch-independent:
    the grid is one step per row, so the per-step footprint is too)."""
    return (f"{platform}|verify|s{s}|w{w}|kv{nkv}|g{g}|h{hd}|"
            f"{jnp.dtype(kv_dtype).name}")


def verify_vmem_bytes(tiles: VerifyTileConfig, s: int, w: int, nkv: int,
                      g: int, hd: int, kv_itemsize: int) -> int:
    """Per-grid-step VMEM footprint of the fused verify kernel.

    K/V staging is bounded by ``block_s``; the window logits are kept whole
    (``W · n_kv · g · S`` f32) because the masked softmax must reduce over
    the full row in the oracle's flat order — that term is the budget
    ceiling for long contexts, and shapes over budget fall back to the
    portable XLA lowering.
    """
    nkv_pad = _ceil_to(nkv, 32 // kv_itemsize)  # n_kv is the sublane axis
    staging = 2 * tiles.block_s * nkv_pad * hd * kv_itemsize
    logits = w * nkv * g * s * 4
    qio = 2 * 2 * w * nkv * g * hd * 4  # q + out blocks (f32), 2 buffers
    return staging + logits + qio


def verify_candidate_tiles(
    s: int,
    w: int,
    nkv: int,
    g: int,
    hd: int,
    kv_itemsize: int,
    page_size: int,
    budget_bytes: Optional[int] = None,
) -> List[VerifyTileConfig]:
    """In-budget stagings, largest (fewest DMA round-trips) first.  Empty
    when no staging fits — callers then use the portable lowering.

    The kernel joins the per-block logits along lanes, so a staging is a
    multiple of 128 positions, or the whole view."""
    budget = int((budget_bytes or VMEM_BUDGET_BYTES) * VMEM_FRACTION)
    out = []
    blk = page_size
    while blk <= s:
        if s % blk == 0 and (blk % 128 == 0 or blk == s):
            t = VerifyTileConfig(blk)
            if verify_vmem_bytes(t, s, w, nkv, g, hd, kv_itemsize) <= budget:
                out.append(t)
        blk *= 2
    out.reverse()
    return out


def verify_heuristic_tiles(
    s: int,
    w: int,
    nkv: int,
    g: int,
    hd: int,
    kv_itemsize: int,
    page_size: int,
    budget_bytes: Optional[int] = None,
) -> Optional[VerifyTileConfig]:
    """Largest in-budget staging, or ``None`` (→ portable lowering)."""
    cands = verify_candidate_tiles(
        s, w, nkv, g, hd, kv_itemsize, page_size, budget_bytes)
    return cands[0] if cands else None


def measure_verify_tiles(
    s: int,
    w: int,
    nkv: int,
    g: int,
    hd: int,
    kv_dtype=jnp.float32,
    *,
    page_size: int = 16,
    interpret: Optional[bool] = None,
    candidates: Optional[Sequence[VerifyTileConfig]] = None,
    iters: int = 3,
) -> Tuple[VerifyTileConfig, Dict[VerifyTileConfig, float]]:
    """Time candidate stagings on synthetic pages of the real shape."""
    from repro.kernels.fused_verify import verify_window_attend_pallas

    if interpret is None:
        interpret = default_interpret()
    kv_itemsize = jnp.dtype(kv_dtype).itemsize
    if candidates is None:
        candidates = verify_candidate_tiles(
            s, w, nkv, g, hd, kv_itemsize, page_size)
    if not candidates:
        raise ValueError("no in-budget verify tilings to measure")
    max_pages = s // page_size
    n_pages = max_pages + 1  # + trash
    rng = np.random.default_rng(0)
    if jnp.dtype(kv_dtype) == jnp.int8:
        kp = jnp.asarray(
            rng.integers(-127, 128, (n_pages, page_size, nkv, hd)), jnp.int8)
    else:
        kp = jnp.asarray(
            rng.normal(size=(n_pages, page_size, nkv, hd)), kv_dtype)
    vp = kp
    pt = jnp.asarray(
        rng.integers(0, n_pages, (2, max_pages)), jnp.int32)
    pos = jnp.asarray([s - w - 1, s // 2], jnp.int32)
    win = jnp.asarray(2**30, jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, w, nkv, g, hd)), jnp.float32)

    timings: Dict[VerifyTileConfig, float] = {}
    for t in candidates:
        us = _time_us(
            lambda qv, kv, vv: verify_window_attend_pallas(
                qv, kv, vv, pt, pos, win, block_s=t.block_s,
                interpret=interpret),
            q, kp, vp, iters=iters)
        timings[t] = us
    best = min(timings, key=timings.get)
    return best, timings


def get_verify_tiles(
    s: int,
    w: int,
    nkv: int,
    g: int,
    hd: int,
    kv_dtype=jnp.float32,
    *,
    page_size: int = 16,
    platform: Optional[str] = None,
    allow_measure: bool = False,
    interpret: Optional[bool] = None,
    cache: Optional[AutotuneCache] = None,
) -> Optional[VerifyTileConfig]:
    """Resolve the verify-window staging: cache hit → measured → heuristic.

    Returns ``None`` when no staging fits the VMEM budget — the caller
    falls back to the portable XLA lowering.  Mirrors :func:`get_tiles`
    but stores entries under the ``verify`` namespace of the same cache.
    """
    platform = platform or jax.default_backend()
    cache = cache if cache is not None else get_default_cache()
    key = verify_shape_key(platform, s, w, nkv, g, hd, kv_dtype)
    hit = cache.get(key, cls=VerifyTileConfig)
    if hit is not None:
        return hit
    kv_itemsize = jnp.dtype(kv_dtype).itemsize
    cands = verify_candidate_tiles(s, w, nkv, g, hd, kv_itemsize, page_size)
    if not cands:
        return None
    if allow_measure or os.environ.get("REPRO_AUTOTUNE") == "1":
        best, timings = measure_verify_tiles(
            s, w, nkv, g, hd, kv_dtype, page_size=page_size,
            interpret=interpret, candidates=cands)
        cache.put(key, best, us=timings[best])
        try:
            cache.save()
        except OSError:
            pass  # read-only filesystem: keep the in-memory entry
        return best
    return cands[0]
