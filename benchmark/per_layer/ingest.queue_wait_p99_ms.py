"""How long a batch waited between its poll and its dispatch: over the
batches whose first dispatch began in the window, the 99th percentile of
that dispatch's start minus the end of the batch's poll span (both carry
the batch's poll sequence number)."""

import numpy as np

from benchmark.phases import batch_ids, named
from benchmark.traffic import nearest_rank


def read(ctx):
    spans = ctx.rec["spans"]
    poll_end = {b: s[2] + s[3] for s in named(spans, "poll")
                for b in batch_ids(s)}
    first = {}
    for s in named(spans, "dispatch", "drain"):
        for b in batch_ids(s):
            first[b] = min(first.get(b, s[2]), s[2])
    wait = [t - poll_end[b] for b, t in first.items()
            if b in poll_end and ctx.win.t_open <= t <= ctx.win.t_close]
    if not wait:
        return None
    return 1e3 * nearest_rank(np.asarray(wait), np.ones(len(wait)), 99)
