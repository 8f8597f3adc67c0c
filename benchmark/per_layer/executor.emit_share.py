"""Union of the step loop's emit spans over the window, as a share of
it."""


def read(ctx):
    return ctx.span_share(("emit",))
