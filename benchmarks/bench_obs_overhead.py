"""Observability overhead: metrics-recording on vs off, same workload.

The PR-7 contract (extended by PR 10) is that a live metrics recorder
costs a few percent at most — every engine hook is ``if obs:``-guarded
host bookkeeping, and the quality probe is sampling-based so its
*default-off* path adds nothing.
This bench pins the contract with a number: the same mixed-length
workload drains through the paged engine with no recorder and with a
metrics-only :class:`repro.serving.Recorder`, best-of-``REPEATS``
each, and the cell reports ``ratio = on_tok_s / off_tok_s``.
``benchmarks/check_trajectory.py`` gates every ``/obs_overhead/``
record at ``--min-obs-ratio`` (default 0.95, i.e. ≤5 % overhead).

Run:  PYTHONPATH=src python -m benchmarks.run --only obs_overhead
"""
import dataclasses
import time

from benchmarks.common import emit
from benchmarks.bench_serve_throughput import _prompts, _tiny_cfg

REPEATS = 5
MAX_NEW = 12
REQUESTS = 8


def _drain(engine, prompts, max_new):
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    t0 = time.perf_counter()
    done = engine.run_until_drained()
    dt = time.perf_counter() - t0
    return dt, sum(len(r.generated) for r in done)


def run() -> None:
    import jax

    from repro.models import model as MD
    from repro.serving import Recorder, ServeEngine

    # Wider than the throughput-bench config on purpose: the recorder's
    # cost is host bookkeeping per step/token, so a model that is *too*
    # small measures the bookkeeping against near-zero compute and
    # reports an overhead fraction no real deployment would see.
    cfg = dataclasses.replace(_tiny_cfg(), d_model=128, d_ff=256)
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg, REQUESTS)

    def mk(recorder=None):
        return ServeEngine(params, cfg, max_batch=4, max_len=64,
                           page_size=16, prefill_chunk=8, recorder=recorder)

    engines = {"off": mk(), "on": mk(Recorder(trace=False))}
    best = {"off": 0.0, "on": 0.0}
    for eng in engines.values():
        _drain(eng, prompts[:1], 2)  # warm the compiled programs
    # interleave the repeats so slow machine drift (thermal, noisy
    # neighbours) hits both variants equally instead of biasing the ratio
    for _ in range(REPEATS):
        for kind, eng in engines.items():
            dt, n_tok = _drain(eng, prompts, MAX_NEW)
            best[kind] = max(best[kind], n_tok / max(dt, 1e-9))
    ratio = best["on"] / max(best["off"], 1e-9)
    emit("serve/obs_overhead/paged", 0.0,
         f"ratio={ratio:.3f};on_tok_s={best['on']:.1f};"
         f"off_tok_s={best['off']:.1f};"
         f"requests={REQUESTS};max_new={MAX_NEW};repeats={REPEATS}")


if __name__ == "__main__":
    run()
