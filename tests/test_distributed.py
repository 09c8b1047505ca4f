"""Distributed config tests: sharding rules + an 8-device dry-run smoke in a
subprocess (so this test process keeps its single real CPU device)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _abstract_mesh_16x16():
    from jax.sharding import AbstractMesh

    return AbstractMesh((16, 16), ("data", "model"))


def test_sharding_rules_unit():
    """Rule engine: spec shapes + divisibility guards (pure metadata — uses
    an abstract 16x16 mesh, no devices needed)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import MeshAxes, _guarded_spec

    mesh = _abstract_mesh_16x16()
    axes = MeshAxes.for_mesh(mesh)
    # divisible dims shard
    spec = _guarded_spec((5120, 27648), ("fsdp", "tp"), mesh, axes)
    assert spec == P("data", "model")
    # leading stacked-layer dims replicate
    spec = _guarded_spec((64, 5120, 27648), ("fsdp", "tp"), mesh, axes)
    assert spec == P(None, "data", "model")
    # non-divisible dims fall back to replication, not failure: whisper's
    # 51865 vocab drops the tp shard; 384 still takes fsdp ('data')
    spec = _guarded_spec((51865, 384), ("tp", "fsdp"), mesh, axes)
    assert spec[0] is None
    assert spec == P(None, "data")


def test_expert_parallel_choice():
    from repro.configs import get_config
    from repro.distributed.sharding import MeshAxes, use_expert_parallel

    mesh = _abstract_mesh_16x16()
    axes = MeshAxes.for_mesh(mesh)
    assert use_expert_parallel(get_config("qwen3-moe-30b-a3b"), mesh, axes)
    assert use_expert_parallel(get_config("jamba-1.5-large-398b"), mesh, axes)
    # mixtral: 8 experts on a 16-way axis → TP-in-expert instead
    assert not use_expert_parallel(get_config("mixtral-8x7b"), mesh, axes)


@pytest.mark.slow
def test_dryrun_smoke_subprocess():
    """All 10 archs lower+compile on an 8-device host mesh (train + decode).

    Runs in a subprocess because jax pins the device count at first init.
    """
    env = dict(os.environ, REPRO_DRYRUN_DEVICES="8",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--smoke"],
        env=env, capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]


def test_collective_bytes_parser():
    from repro.analysis.hlo_stats import collective_bytes_from_hlo

    hlo = """
  %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[8,4096]{1,0} all-gather(%y), dimensions={0}
  %st = (f32[16]{0}, f32[256]{0}) all-gather-start(%z)
  %dn = f32[256]{0} all-gather-done(%st)
  %a2a = s8[64,64]{1,0} all-to-all(%w)
"""
    out = collective_bytes_from_hlo(hlo)
    assert out["all-reduce"]["bytes"] == 128 * 256 * 4
    assert out["all-gather"]["bytes"] == 8 * 4096 * 2 + 256 * 4  # start: max
    assert out["all-gather"]["count"] == 2  # -done skipped
    assert out["all-to-all"]["bytes"] == 64 * 64
    assert out["total_bytes"] == sum(
        v["bytes"] for k, v in out.items() if isinstance(v, dict))
