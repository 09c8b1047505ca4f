"""Mean rows per batched decode step in the window (the engine's
``on_decode`` hook, which also feeds ``serve_batch_occupancy``)."""


def read(ctx):
    w = ctx.window
    rows = [len(c) for t0, _, c in ctx.log.decodes
            if w.t_open <= t0 <= w.t_close]
    return sum(rows) / len(rows) if rows else None
