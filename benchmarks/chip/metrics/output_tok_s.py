"""Every output token that reached the host inside the window (first
tokens included), over the window's seconds."""
from benchmarks.chip import stats


def read(ctx):
    w = ctx.window
    return stats.tokens_in_window(w) / (w.t_close - w.t_open)
