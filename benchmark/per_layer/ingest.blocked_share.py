"""Union of the ingest thread's handoff spans (blocked on the full
prefetch queue, the executor behind) over the window, as a share of it;
0 where the program anchors its spans and never blocked."""

from benchmark.phases import has_anchor


def read(ctx):
    share = ctx.span_share(("handoff",))
    if share is None and has_anchor(ctx.rec["spans"]):
        return 0.0
    return share
