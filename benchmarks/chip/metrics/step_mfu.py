"""Model FLOPs of every prefill and decode token the engine processed in
the traced stretch, over its length times the chip's bf16 peak.  A LUT-MU
MLP counts as the dense product it stands for, so replacing a kernel
cannot move the yardstick (``work.token_flops``)."""
from benchmarks.chip import stats, work


def read(ctx):
    s, span = ctx.spec, ctx.window.trace_span
    flops = 0.0
    for _, _, contexts in stats.in_trace(ctx.log.decodes, span):
        flops += sum(work.token_flops(s, c, head=True) for c in contexts)
    for _, _, start, n in stats.in_trace(ctx.log.prefills, span):
        flops += sum(work.token_flops(s, start + i + 1, head=(i == n - 1))
                     for i in range(n))
    secs = span[1] - span[0]
    return 100.0 * flops / (secs * ctx.peaks["bf16_flop_s"]) if secs else None
