"""Mean device time of one execution of the prefill-chunk program (its
``XLA Modules`` events in the trace)."""

PROGRAM = "jit__prefill"


def read(ctx):
    ex = ctx.trace.executions(PROGRAM)
    return sum(e - s for _, s, e, _ in ex) / len(ex) * 1e-6 if ex else None
