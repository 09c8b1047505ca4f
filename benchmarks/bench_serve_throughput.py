"""Serving throughput: continuous batching (paged) vs fixed slots.

Drives both engines over a **mixed-length** request workload (the regime
continuous batching exists for) on a tiny reduced config, sweeping the
decode-batch size and every mesh shape that fits the host device count
(fake devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
to exercise the sharded cells — the CI jobs do).  Emitted per cell:
``us`` = µs per generated token, ``derived`` = tokens/s, mean decode-batch
occupancy and mean TTFT (ms) plus the request mix — all read from the
PR-7 metrics registry (each engine runs with a metrics-only
:class:`repro.serving.Recorder`, reset after the warm-up drain, so the
reported numbers and ``--metrics`` serving snapshots share one source of
truth); plus a ``paged_vs_fixed`` ratio record per batch size — the record
``benchmarks/check_trajectory.py`` gates on (paged must beat fixed slots,
and every engine cell must carry numeric ``occupancy``/``ttft_ms``).

The fixed-slot engine re-runs an eager whole-prompt prefill per admission
(every distinct prompt length is a fresh set of op shapes); the paged
engine prefils in fixed-width chunks through one compiled program and
interleaves them with decode — that is where the mixed-length win comes
from.

Run:  PYTHONPATH=src python -m benchmarks.run --only serve_throughput
"""
import dataclasses
import time

import jax

from benchmarks.common import emit

BATCH = (2, 4)
MESH_SHAPES = ((2, 2),)
# mixed prompt lengths: short chat turns next to long-context requests
MIX = (2, 5, 9, 14, 20, 3, 12, 7)


def _tiny_cfg():
    from repro.configs import get_config

    cfg = get_config("qwen3-14b", reduced=True)
    return dataclasses.replace(
        cfg,
        num_layers=2,
        d_model=64,
        d_ff=128,
        vocab_size=128,
        num_heads=2,
        num_kv_heads=1,
        head_dim=32,
    )


def _prompts(cfg, requests):
    return [
        [(7 * i + j) % cfg.vocab_size for j in range(MIX[i % len(MIX)])]
        for i in range(requests)
    ]


def _drain(engine, prompts, max_new):
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    t0 = time.perf_counter()
    engine.run_until_drained()
    return time.perf_counter() - t0


def _registry_cells(rec, dt):
    """tok/s, occupancy and TTFT for a measured drain — read from the
    recorder's registry, the same numbers ``--metrics`` serving reports."""
    reg = rec.registry
    n_tok = int(reg.value("serve_generated_tokens_total"))
    occ = reg.find("serve_batch_occupancy")[0]
    ttft = reg.find("serve_ttft_seconds")[0]
    return n_tok, {
        "tok_s": n_tok / max(dt, 1e-9),
        "occupancy": occ.mean,
        "ttft_ms": ttft.mean * 1e3,
    }


def _build(kind, params, cfg, batch, mesh, rec):
    from repro.serving import FixedSlotEngine, ServeEngine

    if kind == "fixed":
        return FixedSlotEngine(
            params, cfg, slots=batch, max_len=64, mesh=mesh, recorder=rec
        )
    return ServeEngine(
        params,
        cfg,
        max_batch=batch,
        max_len=64,
        page_size=16,
        prefill_chunk=8,
        mesh=mesh,
        recorder=rec,
    )


def run(requests: int = 8, max_new: int = 8) -> None:
    from repro.launch.mesh import make_mesh
    from repro.models import model as MD
    from repro.serving import Recorder

    cfg = _tiny_cfg()
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg, requests)

    n_dev = len(jax.devices())
    meshes = [None] + [
        make_mesh((d, m), ("data", "model"))
        for d, m in MESH_SHAPES
        if d * m <= n_dev
    ]
    for mesh in meshes:
        tag = "1x1" if mesh is None else f"{mesh.shape['data']}x{mesh.shape['model']}"
        for batch in BATCH:
            tok_s = {}
            for kind in ("fixed", "paged"):
                rec = Recorder(trace=False)
                engine = _build(kind, params, cfg, batch, mesh, rec)
                # first drain warms the compiled prefill/decode, second is
                # timed — same mixed workload for both engines; the reset
                # drops warm-up samples (and jit compiles) from the cells
                _drain(engine, prompts[:1], 2)
                rec.reset()
                dt = _drain(engine, prompts, max_new)
                n_tok, cells = _registry_cells(rec, dt)
                tok_s[kind] = cells["tok_s"]
                emit(
                    f"serve/mesh{tag}/{kind}/batch{batch}",
                    dt / max(n_tok, 1) * 1e6,
                    f"tok_s={cells['tok_s']:.1f};"
                    f"occupancy={cells['occupancy']:.2f};"
                    f"ttft_ms={cells['ttft_ms']:.2f};requests={requests};"
                    f"max_new={max_new};mix={'-'.join(map(str, MIX))}",
                )
            emit(
                f"serve/mesh{tag}/paged_vs_fixed/batch{batch}",
                0.0,
                f"ratio={tok_s['paged'] / max(tok_s['fixed'], 1e-9):.2f};"
                f"paged_tok_s={tok_s['paged']:.1f};"
                f"fixed_tok_s={tok_s['fixed']:.1f}",
            )


if __name__ == "__main__":
    run()
