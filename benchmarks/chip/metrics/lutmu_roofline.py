"""LUT-MU kernels' share of their roofline: the least time the chip needs
for the work the algorithm asks of every LUT-MU kernel call in the traced
executions of the serving programs (``work.lutmu_call``: table, input,
thresholds and output bytes; encode compares and aggregate adds), over
the device time of those calls.  The gate, up and down products of each
layer are one kernel call each; the program's rows per call are the
decode batch or the prefill chunk."""
from benchmarks.chip import work

KERNEL = "fused_lutmu"
PROGRAMS = (("jit__decode", "max_batch"), ("jit__prefill", "prefill_chunk"))


def read(ctx):
    s = ctx.spec
    if not s.lutmu:
        return None
    need = took = 0.0
    for program, rows in PROGRAMS:
        sites = work.lutmu_layer_sites(s, getattr(s, rows))
        per_exec = s.layers * sum(work.roofline_s(o, b, ctx.peaks)
                                  for o, b in sites)
        for _, _, _, ops in ctx.trace.executions(program):
            calls = [(n, st, e) for n, st, e in ops if KERNEL in n]
            if len(calls) != s.layers * len(sites):
                raise ValueError(f"{program}: {len(calls)} {KERNEL} calls, "
                                 f"want {s.layers * len(sites)}")
            need += per_exec
            took += sum(e - st for _, st, e in calls) * 1e-9
    return 100.0 * need / took if took else None
