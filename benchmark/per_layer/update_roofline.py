"""The update step's device time against the least time its bytes and
operations need (benchmark/roofline.py update_cost), in %."""

from benchmark.roofline import update_cost


def read(ctx):
    if ctx.trace is None:
        return None
    steps = ctx.kernel_calls("update")
    if not steps:
        return None
    cost = update_cost(ctx.events_in_window(), steps,
                       ctx.cfg["job"]["keys"])
    return ctx.kernel_share("update", cost)
