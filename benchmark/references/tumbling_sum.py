"""The plain reference of the keyed tumbling-window sum, the comparison
that decides a run's ``correct``, and its control.

Copied from ``chip_smoke.py`` (``reference``, ``check``). The reference
and the comparison import nothing of the program; the control is the
reference put in the program's place, on the device, in bfloat16.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def n_windows(last_ts_ms: int, window_ms: int) -> int:
    return int(last_ts_ms) // window_ms + 1


def _size(job: dict, n_events: int, traffic) -> int:
    last = traffic.sched.event_ms(np.array([n_events - 1]))[0]
    return n_windows(last, job["window"]["size_ms"]) * job["keys"]


def _flat(idx: np.ndarray, seed: int, job: dict, traffic) -> np.ndarray:
    W, K = job["window"]["size_ms"], job["keys"]
    return traffic.sched.event_ms(idx) // W * K + traffic.keys(idx, seed)


def reference(seed: int, job: dict, n_events: int, traffic,
              chunk: int = 1 << 22, threads: int = 4) -> np.ndarray:
    """Exact per-(window, key) sums over events [0, n_events), int64
    ``[n_windows * n_keys]``, of the events ``traffic`` makes."""
    if n_events == 0:
        return np.zeros(0, np.int64)
    size = _size(job, n_events, traffic)

    def part(off):
        idx = np.arange(off, min(off + chunk, n_events), dtype=np.int64)
        return np.bincount(_flat(idx, seed, job, traffic),
                           weights=traffic.values(idx, seed),
                           minlength=size)

    out = np.zeros(size, np.float64)
    with ThreadPoolExecutor(threads) as pool:
        for p in pool.map(part, range(0, n_events, chunk)):
            out += p
    # integer-valued float64 partial sums below 2**53: exact
    return out.astype(np.int64)


def attempted(ref: np.ndarray) -> int:
    """The (key, window) results the run owes: every pair with events
    (every value is >= 1)."""
    return int(np.count_nonzero(ref))


def compare(cols: dict, ref: np.ndarray, job: dict) -> dict:
    """Every way the emitted (key_id, window_end_ms, value) rows differ
    from the reference, as counts; each has the limit 0."""
    n_keys, window_ms = job["keys"], job["window"]["size_ms"]
    key = np.asarray(cols["key_id"]).astype(np.int64)
    win = np.asarray(cols["window_end_ms"]).astype(np.int64) // window_ms - 1
    flat = win * n_keys + key
    outside = (key < 0) | (key >= n_keys) | (win < 0) | (flat >= len(ref))
    flat = flat[~outside]
    vals = np.asarray(cols["value"]).astype(np.float64)[~outside]
    got_n = np.bincount(flat, minlength=len(ref))
    got = np.bincount(flat, weights=vals, minlength=len(ref))
    has = ref > 0
    once = got_n == 1
    return {
        "rows_outside_input": int(outside.sum()),
        "pairs_fired_twice": int((got_n > 1).sum()),
        "pairs_never_fired": int((has & (got_n == 0)).sum()),
        "pairs_fired_without_events": int((~has & (got_n > 0)).sum()),
        "sums_differing": int((once & (got != ref)).sum()),
    }


def control_rows(seed: int, job: dict, n_events: int, traffic,
                 chunk: int = 1 << 22) -> dict:
    """(key_id, window_end_ms, value) rows of per-(key, window) sums
    accumulated on the device in bfloat16, the nearest precision below
    the configuration's float32."""
    import jax
    import jax.numpy as jnp

    K, W = job["keys"], job["window"]["size_ms"]
    size = _size(job, n_events, traffic)

    @jax.jit
    def add(acc, flat, vals):
        return acc.at[flat].add(vals.astype(jnp.bfloat16), mode="drop")

    acc = jnp.zeros(size, jnp.bfloat16)
    for off in range(0, n_events, chunk):
        idx = np.arange(off, off + chunk, dtype=np.int64)
        live = idx < n_events
        flat = np.where(live, _flat(idx, seed, job, traffic), size)
        vals = np.where(live, traffic.values(idx, seed), 0)
        acc = add(acc, jnp.asarray(flat, jnp.int32),
                  jnp.asarray(vals, jnp.float32))
    sums = np.asarray(acc.astype(jnp.float32)).astype(np.float64)
    flat = np.nonzero(sums)[0]
    return {"key_id": flat % K, "window_end_ms": (flat // K + 1) * W,
            "value": sums[flat]}
