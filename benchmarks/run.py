"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; ``--json PATH`` additionally
writes the collected records plus the per-module failure list as JSON (the
CI ``bench-smoke`` job uploads it as the perf-trajectory artifact and gates
on the exit code).  ``--only`` selects a comma-separated subset of module
suffixes (e.g. ``--only fig1_scaling,serve_throughput``) for reduced
sweeps.  Figure benches reproduce the paper's relative claims at reduced
scale; table2 reads the dry-run roofline artifacts when present.
"""
import argparse
import importlib
import json
import sys
import traceback
from pathlib import Path

# execution order: cheap analytic sweeps first, end-to-end serving last
MODULES = ("fig1_scaling", "fig11_scalability", "fig12_problem_size",
           "fig13_pareto", "table2_e2e", "fig10_depth", "fig9_pruning",
           "resolution_configs", "serve_throughput", "prefix_reuse",
           "speculative", "obs_overhead")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", dest="json_path", metavar="PATH",
                    help="write {records, failed, errors, ok} JSON here")
    ap.add_argument("--only", metavar="MOD[,MOD...]",
                    help="run only these module suffixes "
                         f"(known: {', '.join(MODULES)})")
    args = ap.parse_args(argv)

    names = list(MODULES)
    if args.only:
        wanted = [w.strip() for w in args.only.split(",") if w.strip()]
        unknown = [w for w in wanted if w not in MODULES]
        if unknown:
            ap.error(f"unknown modules {unknown}; known: {list(MODULES)}")
        names = [n for n in MODULES if n in wanted]

    from benchmarks import common
    common.reset_records()
    print("name,us_per_call,derived")
    failed, errors = [], {}
    for name in names:
        modname = f"benchmarks.bench_{name}"
        try:
            importlib.import_module(modname).run()
        except Exception as e:  # noqa — import errors must reach the JSON too
            traceback.print_exc()
            failed.append(modname)
            errors[modname] = repr(e)
    if args.json_path:
        payload = {"records": common.RECORDS, "failed": failed,
                   "errors": errors, "ok": not failed}
        Path(args.json_path).write_text(json.dumps(payload, indent=2))
        print(f"[bench] wrote {len(common.RECORDS)} records → "
              f"{args.json_path}", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
