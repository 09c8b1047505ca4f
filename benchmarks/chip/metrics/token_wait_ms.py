"""Mean wait of the host for a decode step's sampled tokens: the
``serve.tokens`` phase that follows the decode dispatch, which covers
decode, sampler and transfer as the program waits for them.  From the
program's own step records (``Recorder.steps``, on the window's clock),
over steps whose start falls in the window; nothing where the program
keeps no step records."""

WAIT = "serve.tokens"


def read(ctx):
    w = ctx.window
    waits = [t1 - t0 for s in getattr(ctx.log, "steps", ())
             if w.t_open <= s.t0 <= w.t_close
             for name, t0, t1, after in s.phases
             if name == WAIT and after == "decode"]
    return sum(waits) / len(waits) * 1e3 if waits else None
