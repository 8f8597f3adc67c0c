"""At the harness source, the 99th percentile over the window's polls of
poll time minus the due time of the oldest event handed over."""

import numpy as np

from benchmark.traffic import nearest_rank


def read(ctx):
    lag = ctx.rec["lag_s"]
    if not lag:
        return None
    return 1e3 * nearest_rank(np.asarray(lag), np.ones(len(lag)), 99)
