"""95th percentile of every gap between consecutive tokens of a request,
both tokens inside the window."""
from benchmarks.chip import stats


def read(ctx):
    return stats.quantile_ms(stats.itl_in_window(ctx.window), 0.95)
