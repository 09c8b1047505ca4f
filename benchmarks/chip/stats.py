"""Helpers the metric readers share: what lies in the window, quantiles."""
from __future__ import annotations

import numpy as np


def quantile_ms(values, q: float):
    """The ``q`` quantile of seconds, in milliseconds; None if empty."""
    return float(np.quantile(np.asarray(values), q) * 1e3) if values else None


def due_in_window(w) -> list:
    return [r for r in w.served if w.t_open <= r.due <= w.t_close]


def tokens_in_window(w) -> int:
    return sum(sum(w.t_open <= t <= w.t_close for t in r.stamps)
               for r in w.served)


def itl_in_window(w) -> list:
    """Every gap between consecutive tokens of a request, both inside."""
    out = []
    for r in w.served:
        st = [t for t in r.stamps if w.t_open <= t <= w.t_close]
        out += list(np.diff(st))
    return out


def ttft(w) -> list:
    """From due to first token, for requests due in the window; one still
    unanswered at the close counts at its wait so far."""
    return [(r.stamps[0] if r.stamps and r.stamps[0] <= w.t_close
             else w.t_close) - r.due for r in due_in_window(w)]


def in_trace(items, span) -> list:
    """Host-stamped ``(t0, ...)`` records whose start lies in ``span``."""
    return [x for x in items if span[0] <= x[0] <= span[1]]
