"""Batched pre-aggregation: sort + segmented reduce.

The reference combines per record (HeapReducingState.add = HashMap get ->
user reduce -> put, SURVEY §3.2 "per-record scalar reduce"). TPU-native: a
whole micro-batch is pre-aggregated *per (slot, pane)* in one shot, then a
single scatter-combine touches state. For the built-in reducers this is a
native duplicate-index scatter (`.at[].add/.min/.max`); for arbitrary
associative combine functions we sort by segment id and run a segmented
associative scan (the classic "flagged scan" trick), which works for any
jnp-traceable associative op.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def segmented_reduce_sorted(values, seg_start, combine: Callable):
    """Reduce runs of a sorted array with an arbitrary associative combine.

    values:    [B, ...] sorted so equal segments are adjacent
    seg_start: bool [B], True where a new segment begins
    combine:   (a, b) -> c, associative, jnp-traceable

    Returns [B, ...] where the *last* element of each segment holds the
    segment's reduction (other lanes hold partial prefixes).
    """

    def seg_combine(a, b):
        a_flag, a_val = a
        b_flag, b_val = b
        merged = jax.tree_util.tree_map(
            lambda av, bv: jnp.where(
                _bshape(b_flag, bv), bv, combine(av, bv)
            ),
            a_val,
            b_val,
        )
        return a_flag | b_flag, merged

    _, out = jax.lax.associative_scan(seg_combine, (seg_start, values))
    return out


def _bshape(flag, val):
    """Broadcast a [B] bool against [B, ...] values."""
    extra = val.ndim - flag.ndim
    return flag.reshape(flag.shape + (1,) * extra)


# -- the ONE place device sorts live -----------------------------------
# Every jnp.sort/argsort in flink_tpu/ops goes through these wrappers:
# a sort is the single most expensive reordering primitive the kernels
# use, and the whole pre-combine design is "pay ONE sort, feed every
# consumer from it" (acc scatter, fire eligibility via touched, the
# kg_dirty changelog bits, kg_fill skew telemetry — see
# window_kernels.update). Centralizing the call sites makes that seam
# auditable: tools/check_segment_sort_seam.py (tier-1) fails the build
# when a sort appears anywhere else under ops/, so a future edit cannot
# quietly reintroduce a per-plane sort pass.

def sort_values(x):
    """Ascending sort of a 1-D array (the do_late window-id dedup in
    window_kernels and any future value sort)."""
    return jnp.sort(x)


def argsort_ids(ids, stable: bool = False):
    """Permutation ordering ``ids`` ascending. ``stable=True`` keeps
    equal ids in input order (the session-window chain relies on it)."""
    return jnp.argsort(ids, stable=stable) if stable else jnp.argsort(ids)


def invert_permutation(order):
    """Inverse of a permutation: out[order[i]] = i. One scatter instead
    of the argsort-of-argsort idiom (an O(B log B) sort to invert what a
    single O(B) scatter inverts exactly)."""
    B = order.shape[0]
    return (
        jnp.zeros(B, order.dtype)
        .at[order]
        .set(jnp.arange(B, dtype=order.dtype))
    )


def segment_sort(seg_ids, valid):
    """The ONE sort a batched pre-combine pays: order lanes by segment id
    with invalid lanes pushed to the end (id = INT32_MAX).

    Returns ``(order, ids_s, valid_s, seg_start, rep_mask)`` — the gather
    permutation, the sorted ids, the sorted validity, the new-segment
    flags, and the representative mask (last lane of each valid segment).
    Callers gather any number of per-lane columns through ``order`` and
    reduce them with ``reduce_sorted`` — the update kernel shares this
    sort between the accumulator scatter and the changelog dirty bits
    instead of sweeping the batch once per consumer.
    """
    big = jnp.int32(2**31 - 1)
    ids = jnp.where(valid, seg_ids, big)
    order = argsort_ids(ids)
    ids_s = ids[order]
    valid_s = valid[order]
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), ids_s[1:] != ids_s[:-1]]
    )
    # last lane of each segment = lane before the next segment start (or last)
    seg_end = jnp.concatenate([ids_s[1:] != ids_s[:-1], jnp.ones((1,), bool)])
    rep_mask = seg_end & (ids_s != big)
    return order, ids_s, valid_s, seg_start, rep_mask


def stable_partition(mask, *columns):
    """Stream compaction: move the rows where ``mask`` holds to the front,
    in input order, and zero every row past them.

    mask:    bool [N]
    columns: arrays with leading dimension N

    Returns ``(count, *packed)``: ``count`` is the int32 number of True
    rows, and ``packed[c][i] == columns[c][flatnonzero(mask)[i]]`` for
    ``i < count``, 0 beyond. The fire pack (window_kernels.
    ``_pack_fire_lanes``) compacts every fire lane through this.

    One stable ``lax.sort`` keyed on the inverted mask (0 = keep, 1 =
    drop), so there is no data-dependent loop. 1-D columns ride the sort
    as payload operands; wider columns ride as a row index and take one
    row gather (sort operands must share one shape). The cumsum +
    ``searchsorted`` form this replaces lowers to a scan of
    ``ceil(log2(N + 1))`` full-length gathers: 21 of them at N = 1M,
    about 957 ms per 4-lane fire on a TPU v5e.
    """
    n = mask.shape[0]
    flat = [c for c in columns if c.ndim == 1]
    wide = len(flat) < len(columns)
    rows = [jnp.arange(n, dtype=jnp.int32)] if wide else []
    out = jax.lax.sort(((~mask).astype(jnp.int32), *flat, *rows),
                       num_keys=1, is_stable=True)
    flat_s = iter(out[1:1 + len(flat)])
    moved = [next(flat_s) if c.ndim == 1 else c[out[-1]] for c in columns]
    count = jnp.sum(mask, dtype=jnp.int32)
    keep = jnp.arange(n, dtype=jnp.int32) < count
    return (count,) + tuple(
        jnp.where(_bshape(keep, m), m, jnp.zeros((), m.dtype)) for m in moved
    )


def reduce_sorted(order, valid_s, seg_start, values, combine: Callable,
                  neutral):
    """Gather a pytree of per-lane columns through a ``segment_sort``
    permutation and reduce each segment (neutral substituted in invalid
    lanes). Returns [B, ...] where the representative (last) lane of each
    segment holds the segment's full reduction."""
    vals_s = jax.tree_util.tree_map(
        lambda v, n: jnp.where(
            _bshape(valid_s, v[order]), v[order], jnp.asarray(n, v.dtype)
        ),
        values,
        neutral,
    )
    return segmented_reduce_sorted(vals_s, seg_start, combine)


def preaggregate(seg_ids, values, valid, combine: Callable, neutral):
    """Pre-aggregate a batch by segment id with a general associative combine.

    seg_ids: int32 [B]  (e.g. slot * num_panes + pane)
    values:  pytree of [B, ...]
    valid:   bool [B]
    combine: associative (a, b) -> c over the pytree leaves
    neutral: pytree of scalars — identity element, substituted in invalid lanes

    Returns (rep_ids int32[B], rep_mask bool[B], reduced values [B, ...]):
    one representative lane per distinct segment carries the full reduction;
    rep_mask selects it. Invalid lanes sort to the end (id = INT32_MAX).
    """
    order, ids_s, valid_s, seg_start, rep_mask = segment_sort(seg_ids, valid)
    reduced = reduce_sorted(order, valid_s, seg_start, values, combine,
                            neutral)
    return ids_s, rep_mask, reduced


def scatter_combine(target, idx, updates, mask, kind: str,
                    unique: bool = False):
    """Scatter a batch into state with a built-in reducer.

    kind: 'add' | 'min' | 'max' | 'set'. idx lanes with mask=False must be
    out of range already (or are forced out here); duplicates are fine for
    add/min/max (hardware-combined) and resolved arbitrarily for 'set'.

    ``unique=True`` asserts the masked-in indices are pairwise distinct
    (e.g. pre-combined segment representatives): XLA then lowers the
    scatter without the duplicate-collision serialization. Masked-out
    lanes get DISTINCT out-of-range indices (base + lane) so the promise
    holds for them too — a shared sentinel would itself be a duplicate.
    """
    n = target.shape[0]
    if unique:
        safe_idx = jnp.where(
            mask, idx, n + jnp.arange(idx.shape[0], dtype=idx.dtype)
        )
    else:
        safe_idx = jnp.where(mask, idx, n)
    at = target.at[safe_idx]
    if kind == "add":
        return at.add(updates, mode="drop", unique_indices=unique)
    if kind == "min":
        return at.min(updates, mode="drop", unique_indices=unique)
    if kind == "max":
        return at.max(updates, mode="drop", unique_indices=unique)
    if kind == "set":
        return at.set(updates, mode="drop", unique_indices=unique)
    raise ValueError(f"unknown scatter kind {kind!r}")


def grouped_reduce(kind: str, gid, vals, n_groups: int):
    """Dictionary-encoded grouped reduction: one XLA scatter-reduce per
    aggregate. Shared by the batch DataSet and Table aggregation paths
    (the device analog of the reference's ReduceCombineDriver).

    gid: [N] int group ids in [0, n_groups); vals: [N] float values
    (ignored for 'count'). Returns a numpy [n_groups] float32 array.
    """
    import numpy as np

    g = jnp.asarray(np.asarray(gid))
    if kind == "count":
        return np.asarray(jnp.zeros(n_groups, jnp.float32).at[g].add(1.0))
    v = jnp.asarray(np.asarray(vals, np.float32))
    if kind == "sum":
        return np.asarray(jnp.zeros(n_groups, jnp.float32).at[g].add(v))
    if kind == "min":
        return np.asarray(
            jnp.full(n_groups, jnp.inf, jnp.float32).at[g].min(v)
        )
    if kind == "max":
        return np.asarray(
            jnp.full(n_groups, -jnp.inf, jnp.float32).at[g].max(v)
        )
    if kind in ("avg", "mean"):
        s = jnp.zeros(n_groups, jnp.float32).at[g].add(v)
        c = jnp.zeros(n_groups, jnp.float32).at[g].add(1.0)
        return np.asarray(s / c)
    raise ValueError(f"unknown aggregate kind {kind!r}")
