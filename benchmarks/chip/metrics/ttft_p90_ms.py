"""90th percentile, over requests due in the window, of the time from when the
request was due to its first token on the host; unanswered requests count
at their wait so far."""
from benchmarks.chip import stats


def read(ctx):
    return stats.quantile_ms(stats.ttft(ctx.window), 0.90)
