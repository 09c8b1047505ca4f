"""Find an open-loop cell's knee once, by a sweep of fixed rates.

One process, one engine: for each rate the cell's mix is served at that
rate (lead-in, then ``--seconds`` measured), the window's end-to-end
numbers are printed with the backlog (requests due but not yet admitted
at the close, against the same count halfway through), then every live
request is cancelled before the next rate.  The knee is the highest rate
whose backlog does not grow; a cell then fixes its rate in its traffic
file, at about four fifths of the knee.

    python3 benchmarks/chip/sweep.py --workload lutmu-chat \\
        --rates 1,1.5,2,2.5 --seconds 30 --seed 5
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(_root), str(_root / "src")]

from benchmarks.chip import harness, spec as spec_mod, stats, traffic  # noqa: E402


def backlog(w, at: float) -> int:
    """Requests due by ``at`` whose first token had not come by then."""
    return sum(r.due <= at and not (r.stamps and r.stamps[0] <= at)
               for r in w.served)


def sweep(cell_name: str, rates, seconds: float, seed: int) -> None:
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = {c["name"]: c for c in bench["workloads"]}[cell_name]
    s = spec_mod.load(cell["config"])
    mix = traffic.load(cell["traffic"])
    harness._install_cache()
    from benchmarks.chip import weights

    eng = harness.build_engine(s, weights.make_params(s, seed))
    harness.warm_up(eng, s, s.vocab)
    for rate in rates:
        w = harness.serve_window(eng, dict(mix, rate_per_s=rate), seed,
                                 seconds, s.vocab)
        mid = (w.t_open + w.t_close) / 2
        ttft = stats.ttft(w)
        print(json.dumps({
            "workload": cell_name, "rate_per_s": rate, "seconds": seconds,
            "output_tok_s": stats.tokens_in_window(w) / seconds,
            "ttft_p50_ms": stats.quantile_ms(ttft, 0.5),
            "ttft_p90_ms": stats.quantile_ms(ttft, 0.9),
            "itl_p95_ms": stats.quantile_ms(stats.itl_in_window(w), 0.95),
            "due_in_window": len(stats.due_in_window(w)),
            "backlog_mid": backlog(w, mid),
            "backlog_close": backlog(w, w.t_close),
            "steps": w.steps}), flush=True)
        for r in w.served:
            if r.done_at is None:
                eng.cancel(r.uid)
        eng.run_until_drained()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sweep(args.workload, [float(x) for x in args.rates.split(",")],
          args.seconds, args.seed)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
