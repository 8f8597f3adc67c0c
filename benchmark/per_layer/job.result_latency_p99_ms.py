"""99th percentile of result_latency_p50_ms's sample: over all (key,
window) results emitted in the window, sink-call time minus the due time
of the window's last event. Every result of one boundary leaves in one
sink call, so in a 30 s window this is the slowest of about 5
boundaries: too unsteady on the driver's machines to hold to a bound
(PERF.md, section 2), so it stands here, beside the median."""


def read(ctx):
    return ctx.latency_pct(99)
