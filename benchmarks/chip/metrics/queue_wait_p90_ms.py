"""90th percentile, over requests due in the window, of the wait from due
to admission (the scheduler's ``on_admit`` hook, host clock); one still
waiting at the close counts at its wait so far."""
from benchmarks.chip import stats


def read(ctx):
    w, log = ctx.window, ctx.log
    waits = [min(log.admitted.get(r.uid, w.t_close), w.t_close) - r.due
             for r in stats.due_in_window(w)]
    return stats.quantile_ms(waits, 0.90)
