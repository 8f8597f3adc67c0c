"""Process start until the timed window opens: JAX and chip start-up,
compile-cache loads, job set-up and warm-up."""


def read(ctx):
    return ctx.setup_s
