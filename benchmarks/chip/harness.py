"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

A run builds the cell's model from the seed (weights made on the device),
serves it through the program's own entry (``load_engine`` ->
``ServeEngine.submit`` / ``step``) under the cell's traffic, measures a
window of ``--seconds``, checks what the window served against the plain
reference, and prints one JSON line.  ``--trace 1`` also records a device
trace of a few seconds inside the window and reports the per-layer metrics
instead of the end-to-end ones.

Nothing here knows a cell: the configuration, the traffic mix and each
metric are files found by the names in ``BENCHMARK.json``
(``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from benchmarks.chip import check, spec as spec_mod, traffic, work

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_FRACTION = 1 / 3      # the traced stretch starts a third in ...
TRACE_MAX_S = 4.0           # ... and lasts at most this long


class NoChip(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------- records
@dataclasses.dataclass
class Served:
    """One request as the load generator saw it (host clock)."""
    prompt: List[int]
    max_new: int
    due: float                      # when it was due (open loop) or sent
    uid: int = -1
    handle: object = None
    seen: int = 0                   # tokens already stamped
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done_at: Optional[float] = None
    failed: bool = False


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    served: List[Served]
    steps: int
    late_s: List[float]             # how late each open-loop send was
    trace_dir: Optional[str] = None
    trace_span: tuple = (0.0, 0.0)


def _install_cache() -> str:
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    path = enable_compile_cache()
    # every program goes to the cache, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _program_config(s: spec_mod.ModelSpec):
    from repro.models.config import AMMConfig, ModelConfig

    return ModelConfig(
        name=s.name, family="dense", num_layers=s.layers, d_model=s.d_model,
        num_heads=s.n_heads, num_kv_heads=s.n_kv_heads, d_ff=s.d_ff,
        vocab_size=s.vocab, head_dim=s.head_dim, qk_norm=True,
        rope_theta=s.rope_theta, norm_eps=s.norm_eps,
        max_seq_len=s.max_positions,
        amm=AMMConfig(enabled=s.lutmu, d_sub=s.d_sub, depth=s.depth,
                      prune=s.prune, quantize_int8=True))


def step_recorder():
    """A ``Recorder`` of the program that also keeps, per engine step,
    the rows it decoded and the prefill chunk it ran (with host stamps),
    and each request's admission stamp."""
    from repro.serving.obs import Recorder

    class StepLog(Recorder):
        def __init__(self):
            super().__init__(trace=False)
            self.admitted: Dict[int, float] = {}
            self.decodes: List[tuple] = []     # (t0, t1, [contexts])
            self.prefills: List[tuple] = []    # (t0, t1, start, n_tokens)

        def on_admit(self, req) -> None:
            super().on_admit(req)
            self.admitted[req.uid] = self.now()

        def on_decode(self, rows_reqs, t0, t1, **kw) -> None:
            super().on_decode(rows_reqs, t0, t1, **kw)
            self.decodes.append((t0, t1, [r.next_pos + 1
                                          for _, r in rows_reqs]))

        def on_prefill(self, req, chunk_index, n_tokens, t0, t1) -> None:
            super().on_prefill(req, chunk_index, n_tokens, t0, t1)
            # the engine has already counted this chunk into pf_done
            self.prefills.append((t0, t1, req.pf_done - n_tokens, n_tokens))

    return StepLog()


def build_engine(s: spec_mod.ModelSpec, params, recorder=None):
    import jax.numpy as jnp

    from repro.serving import load_engine

    return load_engine(None, params, _program_config(s),
                       max_batch=s.max_batch, max_len=s.max_len,
                       page_size=s.page_size, prefill_chunk=s.prefill_chunk,
                       num_pages=s.kv_pool_tokens // s.page_size,
                       compute_dtype=jnp.bfloat16, recorder=recorder)


def warm_up(eng, s: spec_mod.ModelSpec, vocab: int) -> None:
    """Run every program the traffic will: a two-chunk prefill, the
    first token, and decode steps with a full and a partial batch."""
    rng = np.random.default_rng(0)
    long = rng.integers(0, vocab, s.prefill_chunk + 1).tolist()
    hs = [eng.submit(long, max_new_tokens=3)]
    hs += [eng.submit(rng.integers(0, vocab, 2).tolist(), max_new_tokens=3)
           for _ in range(s.max_batch)]
    eng.run_until_drained()
    for h in hs:
        if len(h.tokens()) != 3:
            raise RuntimeError("warm-up request did not finish")


# ------------------------------------------------------------ the window
def _stamp(live: List[Served], now: float, vocab: int) -> List[Served]:
    still = []
    for r in live:
        gen = r.handle.generated
        while r.seen < len(gen):
            tok = gen[r.seen]
            if not 0 <= tok < vocab:
                r.failed = True
            r.tokens.append(tok)
            r.stamps.append(now)
            r.seen += 1
        if r.handle.done:
            r.done_at = now
        else:
            still.append(r)
    return still


def serve_window(eng, mix: dict, seed: int, seconds: float, vocab: int,
                 clock=time.perf_counter, trace: bool = False) -> Window:
    """Drive the engine under the mix: lead-in, then ``seconds`` measured.
    Host spans (``bench.*``) mark what the load generator is doing."""
    import jax

    span = jax.profiler.TraceAnnotation if trace else (
        lambda name: contextlib.nullcontext())
    lead = float(mix["lead_in_s"])
    horizon = lead + seconds
    served: List[Served] = []
    live: List[Served] = []
    late: List[float] = []
    if mix["loop"] == "open":
        pending = traffic.open_loop(mix, seed, vocab, horizon)
        first, stream = [], []
    else:
        first, stream = traffic.closed_loop(
            mix, seed, vocab, n_requests=mix["clients"] * 64)
        pending = []
    nxt = 0
    t0 = clock()
    t_open, t_close = t0 + lead, t0 + horizon
    t_trace = t_open + seconds * TRACE_FRACTION
    trace_dir, trace_span, tracing = None, (0.0, 0.0), False

    def send(req, due: float) -> Served:
        r = Served(req.prompt, req.max_new, due)
        with span("bench.submit"):
            r.handle = eng.submit(req.prompt, max_new_tokens=req.max_new)
        r.uid = r.handle.request_id
        served.append(r)
        live.append(r)
        return r

    for req in first:
        send(req, t0)
    steps = 0
    while True:
        now = clock()
        if trace and not tracing and trace_dir is None and now >= t_trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(trace_dir)
            tracing = True
            trace_span = (clock(), 0.0)
        if tracing and now >= trace_span[0] + min(TRACE_MAX_S, seconds / 3):
            jax.profiler.stop_trace()
            tracing = False
            trace_span = (trace_span[0], clock())
        if now >= t_close:
            break
        while nxt < len(pending) and t0 + pending[nxt].due_s <= now:
            due = t0 + pending[nxt].due_s
            late.append(now - due)
            send(pending[nxt], due)
            nxt += 1
        if eng.has_work:
            with span("bench.step"):
                eng.step()
                steps += 1
            with span("bench.stamp"):
                now = clock()
                before = len(live)
                live[:] = _stamp(live, now, vocab)
                # closed loop: each finished client sends its next request
                for _ in range(before - len(live) if stream else 0):
                    send(stream.pop(0), now)
        else:
            wake = min(t_close, t0 + pending[nxt].due_s
                       if nxt < len(pending) else t_close)
            with span("bench.wait"):
                time.sleep(max(0.0, wake - clock()))
    if tracing:
        jax.profiler.stop_trace()
        trace_span = (trace_span[0], clock())
    return Window(t_open, t_close, served, steps, late, trace_dir,
                  trace_span)


# --------------------------------------------------------------- metrics
def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class RunContext:
    """What a metric reader may read."""
    spec: spec_mod.ModelSpec
    window: Window
    setup_s: float
    peaks: dict
    trace: object = None            # trace.Reduced, in a traced run
    log: object = None              # StepLog, in a traced run


def wanted_metrics(bench: dict, cell: dict, traced: bool) -> List[dict]:
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


# ------------------------------------------------------------------- run
def run(cell_name: str, seed: int, seconds: float, traced: bool,
        require_chip: bool = True, bench_path: Path = ROOT / "BENCHMARK.json",
        data_dir: Path = HERE, fault=None,
        keep_trace: Optional[str] = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    Tests pass ``require_chip=False``, a ``bench_path`` and ``data_dir`` of
    their own, and a ``fault``: called with the engine before the window,
    it may break the served path, to show that ``correct`` then reads
    false.  ``keep_trace`` names a directory that receives a copy of the
    traced run's profile."""
    t_start = time.perf_counter()
    bench = json.loads(bench_path.read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in {bench_path.name}")
    cell = cells[cell_name]
    s = spec_mod.load(cell["config"], data_dir / "configs")
    mix = traffic.load(cell["traffic"], data_dir / "traffic")
    readers = {m["name"]: (m, load_reader(m["name"]))
               for m in wanted_metrics(bench, cell, traced)}

    cache_dir = _install_cache()
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell["chips"]):
        raise NoChip(f"cell {cell_name} needs {cell['chips']} TPU chip(s); "
                     f"JAX has {len(devs)} {devs[0].platform} device(s)")
    dev = devs[0]
    pk = work.peaks(dev.device_kind) if require_chip else work.peaks(
        "TPU v5 lite")
    say(f"{cell_name}: {s.name} under {mix['name']} on {dev.device_kind} "
        f"x{len(devs)}, seed {seed}, {seconds} s, trace {int(traced)}, "
        f"compile cache {cache_dir}")

    from benchmarks.chip import weights

    params = weights.make_params(s, seed, dev)
    jax.block_until_ready(params)
    say(f"weights made in {time.perf_counter() - t_start:.2f} s")
    log = step_recorder() if traced else None
    eng = build_engine(s, params, recorder=log)
    warm_up(eng, s, s.vocab)
    programs = (eng._decode, eng._prefill)
    sizes = [p._cache_size() for p in programs]
    if fault is not None:
        fault(eng)
    t_ready = time.perf_counter()
    say(f"engine built and warmed in {t_ready - t_start:.2f} s since start")

    w = serve_window(eng, mix, seed, seconds, s.vocab, trace=traced)
    setup_s = w.t_open - t_start
    if [p._cache_size() for p in programs] != sizes:
        raise RuntimeError("a serving program compiled inside the window")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    late = np.asarray(w.late_s) if w.late_s else np.zeros(1)
    say(f"window: {w.steps} steps, {len(w.served)} requests sent; "
        f"generator late by p50 {np.median(late) * 1e3:.3f} ms, max "
        f"{late.max() * 1e3:.3f} ms; peak {peak / 2**30:.3f} GiB")

    ctx = RunContext(s, w, setup_s, pk)
    reduced = None
    if traced:
        from benchmarks.chip import trace as trace_mod

        if keep_trace:
            shutil.copytree(w.trace_dir, keep_trace, dirs_exist_ok=True)
        reduced = trace_mod.reduce(w.trace_dir)
        shutil.rmtree(w.trace_dir, ignore_errors=True)
        ctx.trace, ctx.log = reduced, log
    metrics = {}
    for name, (m, read) in readers.items():
        value = read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"]}

    # the check, on the program's state freed
    in_window = [r for r in w.served if r.done_at is not None
                 and w.t_open <= r.done_at <= w.t_close and not r.failed]
    attempted = [r for r in w.served if w.t_open <= r.due <= w.t_close]
    failed = sum(r.failed for r in attempted)
    del eng
    for r in w.served:
        r.handle = None
    gc.collect()
    numbers = check.compare(params, s, in_window, seed)
    ok = failed == 0 and all(v["value"] <= v["limit"]
                             for v in numbers.values())
    for name, v in numbers.items():
        say(f"check {name} {v['value']!r} limit {v['limit']!r}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(ok), "attempted": len(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    out["checks"] = numbers
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory for a copy of the traced run's profile")
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"[bench] no result: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0
