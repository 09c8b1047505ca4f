"""Sharded-vs-single-device serving parity.

The multi-device checks run in a subprocess with 8 faked host devices
(``tests/sharded_check.py``), mirroring how ``test_distributed`` fakes
devices; the in-process tests cover the single-device fallback paths.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")


def test_sharded_fallback_single_device():
    """A 1x1 mesh must reproduce the unsharded dispatch result exactly."""
    from sharded_check import _random_params
    from repro.kernels.dispatch import lutmu_matmul, lutmu_matmul_sharded
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    for int8 in (True, False):
        xs, params = _random_params(8, 4, 16, 3, int8=int8)
        ref = lutmu_matmul(xs, params, backend="ref", input_kind="split")
        shd = lutmu_matmul_sharded(xs, params, mesh=mesh, input_kind="split")
        assert bool(jnp.all(ref == shd))


def test_serve_mesh_spec_validation():
    from repro.launch.mesh import make_serve_mesh
    import pytest

    with pytest.raises(ValueError, match="DxM"):
        make_serve_mesh("banana")
    with pytest.raises(ValueError, match="devices"):
        make_serve_mesh("64x64")


def test_sharded_parity_subprocess():
    """Same requests through 1-device and faked 2x2-mesh engines must give
    identical token streams (dense and int-LUT AMM paths)."""
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "sharded_check.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=1200,
        cwd=str(ROOT),
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "all OK" in proc.stdout
