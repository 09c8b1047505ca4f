import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 host-platform placeholder devices stand in for 2 pods of 256
TPU v5e chips.  For each cell we

  1. build the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. eval_shape the train/serve state (no allocation ever happens),
  3. assign NamedShardings via the rule engine (FSDP×TP×EP×SP),
  4. ``jax.jit(step).lower(...).compile()`` and record
     ``memory_analysis()`` (fits-per-device proof), ``cost_analysis()``
     (FLOPs/bytes for §Roofline) and the per-collective byte totals parsed
     from the optimized HLO.

Results are cached incrementally as JSON under ``dryrun_results/`` so reruns
only compile missing cells.  ``repro.analysis.roofline`` reads the JSON.

Usage:
  python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force] [--amm]
  python -m repro.launch.dryrun --smoke   # tiny mesh/arch sanity (tests)
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo_stats import (collective_bytes_from_hlo,
                                      cost_analysis_dict as _cost_dict)
from repro.configs import ARCH_IDS, get_config
from repro.distributed.sharding import (batch_spec, cache_shardings,
                                        make_constrainer, param_shardings)
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.shapes import SHAPES, ShapeCell, cell_is_applicable, input_specs
from repro.models import model as MD
from repro.optim import cosine_schedule
from repro.runtime.steps import (TrainState, init_train_state,
                                 make_decode_step, make_prefill_step,
                                 make_train_step)

from jax.sharding import NamedSharding, PartitionSpec as P

RESULTS_DIR = Path(__file__).resolve().parents[3] / "dryrun_results"


def _mesh_tag(multi_pod: bool) -> str:
    return "2x16x16" if multi_pod else "16x16"


def _cell_path(arch: str, shape: str, multi_pod: bool, amm: bool) -> Path:
    tag = _mesh_tag(multi_pod) + ("__amm" if amm else "")
    return RESULTS_DIR / f"{arch}__{shape}__{tag}.json"


def _eval_shape_tree(fn, *args):
    return jax.eval_shape(fn, *args)


def _with_amm(cfg):
    return dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, enabled=True))


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, amm: bool = False,
             force: bool = False, cfg_override=None, mesh_override=None,
             cell_override=None, save: bool = True) -> dict:
    out_path = _cell_path(arch, shape_name, multi_pod, amm)
    if save and out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cell = cell_override or SHAPES[shape_name]
    ok, reason = cell_is_applicable(arch, shape_name)
    record = {
        "arch": arch, "shape": shape_name, "mesh": _mesh_tag(multi_pod),
        "amm": amm, "kind": cell.kind,
    }
    if not ok:
        record.update(status="skipped", reason=reason)
        if save:
            RESULTS_DIR.mkdir(exist_ok=True)
            out_path.write_text(json.dumps(record, indent=2))
        return record

    cfg = cfg_override or get_config(arch)
    if amm and cfg.family not in ("ssm",):
        cfg = _with_amm(cfg)
    mesh = mesh_override or make_production_mesh(multi_pod=multi_pod)
    constrain = make_constrainer(cfg, mesh)
    key = jax.random.PRNGKey(0)
    t0 = time.time()

    with mesh:
        if cell.kind == "train":
            state_shape = _eval_shape_tree(
                lambda k: init_train_state(cfg, k), key)
            state_sh = _state_shardings(state_shape, cfg, mesh)
            specs = input_specs(cfg, cell)
            batch_sh = _batch_shardings(specs, mesh)
            step = make_train_step(cfg, cosine_schedule(3e-4, 100, 10000),
                                   constrain)
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_shape, specs)
        elif cell.kind == "prefill":
            params_shape = _eval_shape_tree(
                lambda k: MD.init_params(cfg, k, jnp.bfloat16, serving=True), key)
            p_sh = param_shardings(params_shape, cfg, mesh)
            specs = input_specs(cfg, cell)
            batch_sh = _batch_shardings(specs, mesh)
            extra = (cfg.num_frontend_tokens
                     if cfg.family == "vlm" else 0)
            # round the cache length up so its seq axis stays tp-shardable
            max_len = -(-(cell.seq_len + extra + 8) // 512) * 512
            step = make_prefill_step(cfg, max_len=max_len,
                                     constrain=constrain)
            jitted = jax.jit(step, in_shardings=(p_sh, batch_sh))
            lowered = jitted.lower(params_shape, specs)
        else:  # decode
            params_shape = _eval_shape_tree(
                lambda k: MD.init_params(cfg, k, jnp.bfloat16, serving=True), key)
            p_sh = param_shardings(params_shape, cfg, mesh)
            kv_dtype = (jnp.int8 if (cfg.amm.enabled and cfg.amm.kv_int8)
                        else jnp.bfloat16)
            cache_shape = _eval_shape_tree(
                lambda: MD.init_cache(cfg, cell.global_batch, cell.seq_len,
                                      kv_dtype))
            c_sh = cache_shardings(cache_shape, cfg, mesh,
                                   batch=cell.global_batch)
            specs = input_specs(cfg, cell)
            tok_sh = NamedSharding(mesh, batch_spec(mesh, cell.global_batch))
            pos_sh = NamedSharding(mesh, P())
            step = make_decode_step(cfg, constrain=constrain)
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, tok_sh, pos_sh, c_sh),
                out_shardings=(None, c_sh),
                donate_argnums=(3,))
            lowered = jitted.lower(params_shape, specs["token"],
                                   specs["pos"], cache_shape)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = _cost_dict(compiled)
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)

    # scan bodies are counted once by cost_analysis — measure them standalone
    # and assemble trip-count-corrected totals (see analysis/scan_cost.py).
    from repro.analysis.scan_cost import body_costs, corrected_totals
    try:
        bodies = body_costs(cfg, cell, mesh)
    except Exception as e:  # noqa — record, don't fail the cell
        bodies = []
        record["body_cost_error"] = repr(e)

    record.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        num_devices=int(np.prod(list(mesh.shape.values()))),
        flops_per_device=float(cost.get("flops", -1.0)),
        bytes_per_device=float(cost.get("bytes accessed", -1.0)),
        memory_analysis={
            "argument_size_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_size_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_size_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_size_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        collectives=coll,
        tokens=cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1),
        seq_len=cell.seq_len,
        global_batch=cell.global_batch,
        param_count=cfg.param_count(),
        active_param_count=cfg.active_param_count(),
    )
    record["scan_bodies"] = [
        {k: v for k, v in b.items() if k != "collectives"} for b in bodies]
    record["corrected"] = corrected_totals(record, bodies) if bodies else None
    if save:
        RESULTS_DIR.mkdir(exist_ok=True)
        out_path.write_text(json.dumps(record, indent=2))
    print(f"[dryrun] {arch} × {shape_name} × {record['mesh']}"
          f"{' (amm)' if amm else ''}: OK — "
          f"{record['flops_per_device']:.3e} flops/dev, "
          f"temp {record['memory_analysis']['temp_size_bytes']/2**30:.2f} GiB, "
          f"compile {t_compile:.0f}s")
    return record


def _state_shardings(state_shape, cfg, mesh):
    p_sh = param_shardings(state_shape.params, cfg, mesh)
    mu_sh = param_shardings(state_shape.opt.mu, cfg, mesh)
    nu_sh = param_shardings(state_shape.opt.nu, cfg, mesh)
    rep = NamedSharding(mesh, P())
    from repro.optim import AdamWState
    return TrainState(params=p_sh,
                      opt=AdamWState(step=rep, mu=mu_sh, nu=nu_sh),
                      step=rep)


def _batch_shardings(specs, mesh):
    out = {}
    for k, v in specs.items():
        if v.ndim >= 1:
            out[k] = NamedSharding(mesh, batch_spec(mesh, v.shape[0]))
        else:
            out[k] = NamedSharding(mesh, P())
    return out


def smoke() -> int:
    """Tiny end-to-end dry-run over reduced configs on a small host mesh."""
    n = len(jax.devices())
    mesh = make_mesh((2, n // 2) if n >= 4 else (1, n), ("data", "model"))
    failures = 0
    for arch in ARCH_IDS:
        cfg = get_config(arch, reduced=True)
        for shape_name in ("train_4k", "decode_32k"):
            cell = SHAPES[shape_name]
            small = ShapeCell(cell.name, 64, 4, cell.kind)
            try:
                rec = run_cell(arch, shape_name, multi_pod=False,
                               cfg_override=cfg, mesh_override=mesh,
                               cell_override=small, save=False, force=True)
                assert rec["status"] == "ok", rec
                print(f"[smoke] {arch} × {shape_name}: OK")
            except Exception as e:  # noqa
                print(f"[smoke] {arch} × {shape_name}: FAIL {e}")
                failures += 1
                continue
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--amm", action="store_true",
                    help="enable the paper's LUT-MU substitution in MLPs")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    if args.smoke:
        raise SystemExit(smoke())

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = tuple(SHAPES) if (args.all or not args.shape) else [args.shape]
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                cells.append((arch, shape, mp))

    failed = []
    for arch, shape, mp in cells:
        try:
            run_cell(arch, shape, multi_pod=mp, amm=args.amm, force=args.force)
        except Exception as e:  # noqa
            traceback.print_exc()
            failed.append((arch, shape, mp, repr(e)))
    if failed:
        print(f"\n{len(failed)} FAILED cells:")
        for f in failed:
            print("  ", f)
        raise SystemExit(1)
    print(f"\nall {len(cells)} cells OK")


if __name__ == "__main__":
    main()
