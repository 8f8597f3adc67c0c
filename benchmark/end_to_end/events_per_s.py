"""Events the job took from the source and applied in the timed window,
over the window's length; all chips together."""


def read(ctx):
    return ctx.events_in_window() / ctx.window_s
