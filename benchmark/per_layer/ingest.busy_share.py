"""Union of the ingest thread's route, stage and transfer spans over the
window, as a share of it."""


def read(ctx):
    return ctx.span_share(("route", "stage", "transfer"))
