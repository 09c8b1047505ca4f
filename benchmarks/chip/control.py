"""Readings that set a cell's correctness limits, on the chip.

For each seed: make the cell's weights, serve its traffic for a short
window through the program exactly as a run does, draw the check's
sample, and read the gap statistics three ways on that sample:

* ``program``: the served tokens against the float32 reference (what a
  run compares);
* ``control``: the token the reference computed in fp8 (operands of every
  product and every tree input rounded to float8_e4m3fn, LUT-MU tables to
  16 levels) puts first, against the float32 reference -- the run the
  comparison has to reject;
* ``witness``: the same with bfloat16 rounding, the configuration's own
  precision computed by the reference instead of the program.

Prints one JSON line per seed (mean and widest gap, share of tokens
where the served or chosen token is the reference's own greedy choice);
``--dump`` also writes each seed's raw gaps.  All seeds run in one
process.

    python3 benchmarks/chip/control.py --workload lutmu-chat \\
        --seeds 1,2,3 --seconds 10
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(_root), str(_root / "src")]

from benchmarks.chip import check, harness, spec as spec_mod, traffic  # noqa: E402


def readings(cell_name: str, seeds, seconds: float, dump=None,
             sample_tokens: int = None) -> list:
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = {c["name"]: c for c in bench["workloads"]}[cell_name]
    s = spec_mod.load(cell["config"])
    if sample_tokens:
        s = dataclasses.replace(s, sample_tokens=sample_tokens)
    mix = traffic.load(cell["traffic"])
    harness._install_cache()
    import jax

    from benchmarks.chip import weights

    dev = jax.devices()[0]
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        params = weights.make_params(s, seed, dev)
        eng = harness.build_engine(s, params)
        harness.warm_up(eng, s, s.vocab)
        w = harness.serve_window(eng, mix, seed, seconds, s.vocab)
        done = [r for r in w.served if r.done_at is not None
                and w.t_open <= r.done_at <= w.t_close]
        del eng
        for r in w.served:
            r.handle = None
        gc.collect()
        picked = check.sample(done, s, seed)
        row = {"workload": cell_name, "seed": seed,
               "requests": len(picked),
               "tokens": sum(len(r.tokens) for r in picked)}
        raw = {}
        for name, kw in (("program", {}), ("control", {"control": "fp8"}),
                         ("witness", {"control": "bf16"})):
            raw[name] = [check.gaps(params, s, [r], **kw) for r in picked]
            g = np.concatenate(raw[name])
            row[name] = {"mean": float(g.mean()), "widest": float(g.max()),
                         "agree": float(np.mean(g == 0))}
        if dump is not None:
            dump.mkdir(parents=True, exist_ok=True)
            (dump / f"gaps_{cell_name}_{seed}.json").write_text(json.dumps(
                {k: [g.tolist() for g in v] for k, v in raw.items()}))
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        out.append(row)
        del params
        gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--dump", type=Path, default=None,
                    help="directory for each seed's raw gaps, per request "
                         "in the order sampled (JSON)")
    ap.add_argument("--sample-tokens", type=int, default=None,
                    help="served tokens to sample (default: the "
                         "configuration's)")
    args = ap.parse_args(argv)
    readings(args.workload, [int(x) for x in args.seeds.split(",")],
             args.seconds, args.dump, args.sample_tokens)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
