"""Union of the executor's inflight_wait spans (blocked on the oldest
queued step, the device behind) over the window, as a share of it."""


def read(ctx):
    return ctx.span_share(("inflight_wait",))
