"""1 - (union of device op intervals) / window, from the profiler trace,
averaged over devices."""


def read(ctx):
    return ctx.idle_share()
