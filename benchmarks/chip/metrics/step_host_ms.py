"""Mean host time of an engine step in the window: each ``serve.step``
less its ``serve.tokens`` phases (the host's waits for sampled tokens),
from the program's own step records (``Recorder.steps``, on the window's
clock).  A step counts where its start falls in the window; nothing where
the program keeps no step records."""

WAIT = "serve.tokens"


def read(ctx):
    w = ctx.window
    host = [s.t1 - s.t0 - sum(t1 - t0 for name, t0, t1, _ in s.phases
                              if name == WAIT)
            for s in getattr(ctx.log, "steps", ())
            if w.t_open <= s.t0 <= w.t_close]
    return sum(host) / len(host) * 1e3 if host else None
