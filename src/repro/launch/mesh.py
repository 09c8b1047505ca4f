"""Production mesh construction (single-pod 16×16, multi-pod 2×16×16).

A function — not a module-level constant — so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    The sharding rules (``distributed/sharding.py``) place arrays with
    ``NamedSharding`` and ``with_sharding_constraint`` and leave the rest
    to the partitioner, which ``Explicit`` axes (``jax.make_mesh``'s
    default) refuse.
    """
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return make_mesh((data, model), ("data", "model"))


def parse_mesh_spec(spec: str):
    """``"DxM"`` (data × model) → ``(data, model)``; raises on junk.

    The single parser every mesh-taking CLI shares (serve ``--mesh``,
    compiler ``lm --mesh``), so spec syntax cannot drift between them.
    """
    try:
        data, model = (int(v) for v in spec.lower().split("x"))
    except ValueError:
        raise ValueError(
            f"mesh spec must be 'DxM' (e.g. 2x4), got {spec!r}") from None
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be positive, got {spec!r}")
    return data, model


def make_serve_mesh(spec: str):
    """Parse a ``"DxM"`` serving-mesh spec (data × model) into a mesh.

    Unlike :func:`make_host_mesh` this is strict: an unparsable spec or a
    shape that needs more devices than exist raises, rather than silently
    serving on a different topology than the operator asked for.
    """
    data, model = parse_mesh_spec(spec)
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices, have {n} "
            "(hint: XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "fakes N host devices)")
    return make_mesh((data, model), ("data", "model"))
