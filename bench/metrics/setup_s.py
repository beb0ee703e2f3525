"""Process start to the end of the warm-up step: imports, the cell's
arguments, tracing, and compiling or loading every program the window
runs."""


def read(ctx):
    return ctx.setup_s
