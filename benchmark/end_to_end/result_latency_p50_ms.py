"""Median over all (key, window) results emitted in the window of the
sink-call time minus the due time of the window's last event."""


def read(ctx):
    return ctx.latency_pct(50)
