"""The longest garbage collection that overlaps the window (the program's
gc spans, any thread), in ms; 0 where the program anchors its spans and
collected nothing there."""

from benchmark.phases import has_anchor, named


def read(ctx):
    spans = ctx.rec["spans"]
    if not has_anchor(spans):
        return None
    w0, w1 = ctx.win.t_open, ctx.win.t_close
    return max((1e3 * s[3] for s in named(spans, "gc")
                if s[2] < w1 and s[2] + s[3] > w0), default=0.0)
