"""The fire step's device time against the least time its fired (key,
window) pairs need (benchmark/roofline.py fire_cost), in %."""

from benchmark.roofline import fire_cost


def read(ctx):
    fired = ctx.fired_in_window()
    if ctx.trace is None or not fired:
        return None
    return ctx.kernel_share("fire", fire_cost(fired))
