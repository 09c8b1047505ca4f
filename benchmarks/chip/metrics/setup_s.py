"""Set-up: from the start of the run to the window's opening (weights,
engine, compiles or cache loads, warm-up, and the traffic's lead-in)."""


def read(ctx):
    return ctx.setup_s
