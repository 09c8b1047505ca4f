"""Gate a fresh ``benchmarks/run.py --json`` output against the committed
perf trajectory (``BENCH_PR4.json`` at the repo root).

Checks, in order:

  1. the new run is ``ok`` (no benchmark module failed);
  2. **coverage** — every record name in the baseline appears in the new
     run (a refactor cannot silently drop a measured cell);
  3. **the serving claim** — every ``serve/.../paged_vs_fixed/...`` record
     in the new run shows the continuous-batching engine at or above
     ``--min-ratio`` × the fixed-slot engine's tokens/s (default 1.0:
     paged must not lose to fixed slots on the mixed-length workload);
  4. **the speculative claim** — every ``spec/spec_vs_plain/...`` record
     shows the speculative engine at or above ``--min-spec-ratio`` ×
     plain decode's tokens/s at its recorded acceptance rate (default
     1.0: an int4 draft must convert the paper's resolution saving into
     throughput, not lose it).  Presence is enforced by the coverage
     check against the committed baseline (``BENCH_PR5.json`` carries
     the speculative cells), so pre-PR-5 subset runs stay valid;
  5. **the sampling claim** — whenever speculative records exist, at
     least one ``spec/spec_sampling/...`` cell must exist and carry a
     numeric acceptance rate in ``[0, 1]``: the rejection-sampling
     acceptance path (PR 6) cannot silently fall out of the measured
     surface;
  6. **the observability claim** — every engine-throughput record
     (``serve/mesh*/fixed|paged/...``) must carry numeric ``occupancy``
     (> 0 rows) and ``ttft_ms`` (> 0) cells: PR 7 derives benchmark
     numbers from the serving metrics registry, and a refactor cannot
     silently drop the registry-backed cells from the measured surface;
  7. **the prefix-reuse claim** — every ``serve/prefix_reuse/
     warm_vs_cold`` record shows shared-prefix TTFT at or below cold-
     start TTFT (``ttft_ratio`` = cold/warm ≥ ``--min-prefix-ratio``,
     default 1.0) with non-zero prefix-hit and reused-token counters
     from the metrics registry (PR 8: the radix-index admission path
     cannot silently fall out of the measured surface).  Presence is
     enforced by coverage against ``BENCH_PR8.json``;
  8. **the fused-verify claim** — whenever speculative records exist, a
     ``spec/fused_verify/...`` cell must exist and show the fused
     layer-major verify window at or above ``--min-verify-ratio`` ×
     the scan oracle's speed (default 1.1: gathering each layer's pages
     once instead of W times must actually pay — PR 9).  Presence is
     enforced by coverage against ``BENCH_PR9.json``;
  9. **the overhead claim** — every ``serve/obs_overhead/...`` record
     shows the metrics-on engine at or above ``--min-obs-ratio`` × the
     recorder-less engine's tokens/s (default 0.95: a live metrics
     registry may cost at most 5 % — the sampled quality probe must
     keep the default-off path free).  Presence is
     enforced by coverage against ``BENCH_PR10.json``.

Absolute µs numbers are *not* compared — CI machines vary too much; the
trajectory tracks structure and engine-vs-engine ordering, which are
machine-independent.

Usage::

    python benchmarks/check_trajectory.py \
        --baseline BENCH_PR4.json --new /tmp/bench_new.json
"""
import argparse
import json
import sys
from pathlib import Path


def _parse_derived(derived: str) -> dict:
    out = {}
    for part in derived.split(";"):
        if "=" in part:
            k, v = part.split("=", 1)
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
    return out


def check(baseline: dict, new: dict, min_ratio: float,
          min_spec_ratio: float = 1.0, min_prefix_ratio: float = 1.0,
          min_verify_ratio: float = 1.1, min_obs_ratio: float = 0.95) -> list:
    errors = []
    if not new.get("ok", False):
        errors.append(f"new run not ok: failed={new.get('failed')} "
                      f"errors={new.get('errors')}")
    base_names = {r["name"] for r in baseline.get("records", [])}
    new_names = {r["name"] for r in new.get("records", [])}
    missing = sorted(base_names - new_names)
    if missing:
        errors.append(f"records dropped vs baseline: {missing}")
    ratio_recs = [r for r in new.get("records", [])
                  if "/paged_vs_fixed/" in r["name"]]
    if not ratio_recs:
        errors.append("no paged_vs_fixed records in the new run")
    for rec in ratio_recs:
        ratio = _parse_derived(rec["derived"]).get("ratio")
        if ratio is None:
            errors.append(f"{rec['name']}: no ratio in derived")
        elif ratio < min_ratio:
            errors.append(
                f"{rec['name']}: continuous batching at {ratio:.2f}x fixed "
                f"slots (< required {min_ratio:.2f}x)")
    for rec in [r for r in new.get("records", [])
                if "/spec_vs_plain/" in r["name"]]:
        d = _parse_derived(rec["derived"])
        ratio = d.get("ratio")
        if ratio is None:
            errors.append(f"{rec['name']}: no ratio in derived")
        elif ratio < min_spec_ratio:
            errors.append(
                f"{rec['name']}: speculative decode at {ratio:.2f}x plain "
                f"(< required {min_spec_ratio:.2f}x) at acceptance "
                f"{d.get('acceptance')}")
    spec_plain = [r for r in new.get("records", [])
                  if "/spec_vs_plain/" in r["name"]]
    spec_sampling = [r for r in new.get("records", [])
                     if "/spec_sampling/" in r["name"]]
    if spec_plain and not spec_sampling:
        errors.append(
            "speculative records present but no spec_sampling cell — the "
            "rejection-sampling acceptance path is unmeasured")
    for rec in spec_sampling:
        acc = _parse_derived(rec["derived"]).get("acceptance")
        if not isinstance(acc, float) or not 0.0 <= acc <= 1.0:
            errors.append(
                f"{rec['name']}: acceptance {acc!r} is not a number in "
                f"[0, 1]")
    for rec in [r for r in new.get("records", [])
                if "/prefix_reuse/warm_vs_cold" in r["name"]]:
        d = _parse_derived(rec["derived"])
        ratio = d.get("ttft_ratio")
        if not isinstance(ratio, float):
            errors.append(f"{rec['name']}: no ttft_ratio in derived")
        elif ratio < min_prefix_ratio:
            errors.append(
                f"{rec['name']}: shared-prefix TTFT at {1 / ratio:.2f}x "
                f"cold start (cold/warm {ratio:.2f} < required "
                f"{min_prefix_ratio:.2f})")
        for key in ("hits", "reused_tokens"):
            v = d.get(key)
            if not isinstance(v, float) or v <= 0.0:
                errors.append(
                    f"{rec['name']}: {key} {v!r} is not positive — the "
                    f"prefix-reuse path went unmeasured")
    verify_recs = [r for r in new.get("records", [])
                   if "/fused_verify/" in r["name"]]
    if spec_plain and not verify_recs:
        errors.append(
            "speculative records present but no fused_verify cell — the "
            "fused verify-window kernel is unmeasured")
    for rec in verify_recs:
        ratio = _parse_derived(rec["derived"]).get("ratio")
        if ratio is None:
            errors.append(f"{rec['name']}: no ratio in derived")
        elif ratio < min_verify_ratio:
            errors.append(
                f"{rec['name']}: fused verify window at {ratio:.2f}x the "
                f"scan oracle (< required {min_verify_ratio:.2f}x)")
    for rec in [r for r in new.get("records", [])
                if "/obs_overhead/" in r["name"]]:
        ratio = _parse_derived(rec["derived"]).get("ratio")
        if not isinstance(ratio, float):
            errors.append(f"{rec['name']}: no ratio in derived")
        elif ratio < min_obs_ratio:
            errors.append(
                f"{rec['name']}: metrics-on engine at {ratio:.2f}x the "
                f"recorder-less engine (< required {min_obs_ratio:.2f}x — "
                f"observability overhead above budget)")
    engine_recs = [r for r in new.get("records", [])
                   if r["name"].startswith("serve/")
                   and ("/paged/" in r["name"] or "/fixed/" in r["name"])]
    for rec in engine_recs:
        d = _parse_derived(rec["derived"])
        for key in ("occupancy", "ttft_ms"):
            v = d.get(key)
            if not isinstance(v, float) or v <= 0.0:
                errors.append(
                    f"{rec['name']}: {key} {v!r} is not a positive number "
                    f"— registry-backed cells missing")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", required=True)
    ap.add_argument("--new", required=True)
    ap.add_argument("--min-ratio", type=float, default=1.0,
                    help="required paged/fixed tokens-per-second ratio")
    ap.add_argument("--min-spec-ratio", type=float, default=1.0,
                    help="required speculative/plain tokens-per-second ratio")
    ap.add_argument("--min-prefix-ratio", type=float, default=1.0,
                    help="required cold/warm TTFT ratio for shared-prefix "
                         "admissions (prefix reuse must not slow TTFT)")
    ap.add_argument("--min-verify-ratio", type=float, default=1.1,
                    help="required fused/scan verify-window speed ratio "
                         "(the fused kernel must beat the per-token oracle)")
    ap.add_argument("--min-obs-ratio", type=float, default=0.95,
                    help="required metrics-on/recorder-less tokens-per-"
                         "second ratio (observability overhead budget)")
    args = ap.parse_args(argv)

    baseline = json.loads(Path(args.baseline).read_text())
    new = json.loads(Path(args.new).read_text())
    errors = check(baseline, new, args.min_ratio, args.min_spec_ratio,
                   args.min_prefix_ratio, args.min_verify_ratio,
                   args.min_obs_ratio)
    if errors:
        for e in errors:
            print(f"[trajectory] FAIL: {e}", file=sys.stderr)
        return 1
    n = len(new.get("records", []))
    print(f"[trajectory] OK: {n} records — coverage, paged>fixed and "
          "spec>plain hold")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
