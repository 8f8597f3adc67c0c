"""The program's own fire latency (watermark crossing to emission), the
99th percentile over the windows fired inside the timed window."""

import numpy as np

from benchmark.traffic import nearest_rank


def read(ctx):
    s = ctx.rec["fire_samples"]
    if not s:
        return None
    w, ms = np.asarray(s, np.float64).T
    return nearest_rank(ms, w, 99)
