"""Session windows: per-key gap-separated windows with merging.

The reference implements sessions via MergingWindowSet + mergeable window
state (SURVEY §2.5, EventTimeSessionWindows / MergingWindowSet.java): each
element opens a [ts, ts+gap) window which merges with overlapping ones.

TPU-native redesign (batch sessionization + open-session state):
  * Within a batch: lexsort by (key-slot, ts); a session boundary is a key
    change or a time gap > gap_ticks; segmented reduces give each batch
    session's (start, last, aggregate) in one pass.
  * Across batches: each key holds at most ONE open session in device state
    (start, last, acc, active). A batch session within `gap` of the open
    session merges into it; a batch session beyond the gap *supersedes* it —
    the superseded session fires immediately.
  * Watermark close: open sessions with last + gap <= wm fire and clear
    (whole-shard masked scan, gated on watermark advance).

Deviation from the reference (documented): a key cannot hold two
simultaneously open sessions. When out-of-orderness exceeds the session gap,
a superseded session fires at supersession time instead of at watermark
time, and a record older than the open session's span minus the gap counts
as late. For out-of-orderness <= gap (the normal configuration, since the
watermark bound is usually far below the session gap) the semantics match.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.ops import hashtable
from flink_tpu.ops.hashtable import SlotTable
from flink_tpu.ops import segment
from flink_tpu.ops.segment import _bshape, segmented_reduce_sorted
from flink_tpu.ops.window_kernels import ReduceSpec


@jax.tree_util.register_pytree_node_class
@dataclass
class SessionShardState:
    table: SlotTable
    start: jax.Array     # int32 [C] open-session first event ts
    last: jax.Array      # int32 [C] open-session latest event ts
    acc: jax.Array       # [C, *vs]
    active: jax.Array    # bool [C]
    watermark: jax.Array  # int32 scalar
    dropped_late: jax.Array
    dropped_capacity: jax.Array

    def tree_flatten(self):
        return (self.table, self.start, self.last, self.acc, self.active,
                self.watermark, self.dropped_late, self.dropped_capacity), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_state(capacity: int, probe_len: int, red: ReduceSpec) -> SessionShardState:
    neutral = red.neutral_value()
    acc = jnp.broadcast_to(neutral, (capacity,) + red.value_shape).astype(red.dtype)
    return SessionShardState(
        table=hashtable.create(capacity, probe_len),
        start=jnp.zeros(capacity, jnp.int32),
        last=jnp.zeros(capacity, jnp.int32),
        acc=acc + jnp.zeros_like(acc),
        active=jnp.zeros(capacity, bool),
        watermark=jnp.asarray(-(2**31) + 1, jnp.int32),
        dropped_late=jnp.zeros((), jnp.int32),
        dropped_capacity=jnp.zeros((), jnp.int32),
    )


def _lexsort_slot_ts(ids, ts):
    """Stable sort by (ids, ts): sort by ts first, then stable by ids."""
    o1 = segment.argsort_ids(ts, stable=True)
    o2 = segment.argsort_ids(ids[o1], stable=True)
    return o1[o2]


def update_and_fire(
    state: SessionShardState, red: ReduceSpec, gap: int,
    hi, lo, ts, values, valid, new_watermark,
):
    """One micro-batch + watermark advance.

    Returns (state', old_fire, mid_fire, wm_fire): two superseded-session
    fire sets in sorted-lane space [B] — each (khi, klo, start, end, vals,
    mask) — plus watermark-close fires in slot space [C] as (start, end,
    vals, mask) with keys from the table.
    Session window end = last + gap (ref TimeWindow semantics for sessions).
    """
    C = state.table.capacity
    G = jnp.int32(gap)
    combine = red.combine_fn()
    neutral = red.neutral_value()

    wm = jnp.maximum(state.watermark, jnp.asarray(new_watermark, jnp.int32))

    # -- late filter against the PRE-batch watermark (elements process
    #    before their own batch's watermark advances, ref operator order):
    #    a record older than wm - gap can never join a live session
    late = valid & (ts + G <= state.watermark)
    n_late = jnp.sum(late, dtype=jnp.int32)
    live = valid & ~late

    # 8 claim rounds: this stage has NO spill tier, so a cold-start claim
    # storm that fails to settle is a counted record LOSS (strict
    # capacity); the extra probe gathers are cheap insurance
    table, slot, ok = hashtable.upsert(state.table, hi, lo, live,
                                       max_rounds=8)
    n_nofit = jnp.sum(live & ~ok, dtype=jnp.int32)
    live = live & ok

    big = jnp.int32(2**31 - 1)
    ids = jnp.where(live, slot, big)
    order = _lexsort_slot_ts(ids, jnp.where(live, ts, big))
    ids_s = ids[order]
    ts_s = jnp.where(live[order], ts[order], big)
    khi_s, klo_s = hi[order], lo[order]
    vals = values.astype(red.dtype)[order]
    live_s = live[order]
    vals = jnp.where(_bshape(live_s, vals), vals, jnp.asarray(neutral, red.dtype))

    slot_change = jnp.concatenate(
        [jnp.ones((1,), bool), ids_s[1:] != ids_s[:-1]]
    )
    time_gap = jnp.concatenate(
        [jnp.ones((1,), bool), (ts_s[1:] - ts_s[:-1]) > G]
    )
    sess_start_flag = slot_change | time_gap

    agg = segmented_reduce_sorted(vals, sess_start_flag, combine)
    smin = segmented_reduce_sorted(ts_s, sess_start_flag, jnp.minimum)
    smax = segmented_reduce_sorted(ts_s, sess_start_flag, jnp.maximum)

    sess_end_flag = jnp.concatenate(
        [sess_start_flag[1:], jnp.ones((1,), bool)]
    )
    rep = sess_end_flag & live_s
    # is this the FIRST session of its slot in the batch?
    first_of_slot = segmented_reduce_sorted(
        slot_change.astype(jnp.int32), sess_start_flag, jnp.maximum
    )  # 1 where the session's lanes include a slot change
    # is this the LAST session of its slot? next session starts new slot
    next_slot_change = jnp.concatenate(
        [ids_s[1:] != ids_s[:-1], jnp.ones((1,), bool)]
    )
    last_of_slot = rep & next_slot_change

    safe = jnp.where(ids_s < C, ids_s, C - 1)
    o_active = state.active[safe] & (ids_s < C)
    o_start = state.start[safe]
    o_last = state.last[safe]
    o_acc = state.acc[safe]

    # merge condition for the first batch session of each slot
    is_first = rep & (first_of_slot > 0)
    merges = is_first & o_active & (smin <= o_last + G) & (smax + G >= o_start)
    merged_acc = jnp.where(
        _bshape(merges, agg), combine(o_acc, agg), agg
    )
    merged_start = jnp.where(merges, jnp.minimum(o_start, smin), smin)
    merged_last = jnp.where(merges, jnp.maximum(o_last, smax), smax)

    # superseded fires, in two independent lane-spaces (a lane can carry
    # both an old-session fire and its own mid-session fire):
    #  a) the previously-open session when the first batch session does NOT
    #     merge with it (fires with its stored values)
    sup_old = is_first & o_active & ~merges
    #  b) every non-last batch session (superseded by the next one)
    sup_mid = rep & ~last_of_slot
    old_fire = (khi_s, klo_s, o_start, o_last + G, o_acc, sup_old)
    mid_fire = (khi_s, klo_s, merged_start, merged_last + G, merged_acc, sup_mid)

    # -- state writeback: last session of each slot becomes the open one --
    wb = last_of_slot
    wb_idx = jnp.where(wb, ids_s, C)
    new_start = state.start.at[wb_idx].set(merged_start, mode="drop")
    new_last = state.last.at[wb_idx].set(merged_last, mode="drop")
    new_acc = state.acc.at[wb_idx].set(merged_acc.astype(red.dtype), mode="drop")
    new_active = state.active.at[wb_idx].set(True, mode="drop")

    # -- watermark close over all slots ----------------------------------
    w_mask = new_active & (new_last + G <= wm)
    w_start = new_start
    w_vals = new_acc
    w_end = new_last + G

    # unconditional masked close: the all-false where is a cheap sweep,
    # and keeps data-dependent control flow out of the step
    new_acc = jnp.where(
        _bshape(w_mask, new_acc), jnp.asarray(neutral, red.dtype), new_acc
    )
    new_active = new_active & ~w_mask

    new_state = SessionShardState(
        table=table, start=new_start, last=new_last, acc=new_acc,
        active=new_active, watermark=wm,
        dropped_late=state.dropped_late + n_late,
        dropped_capacity=state.dropped_capacity + n_nofit,
    )
    return (
        new_state,
        old_fire,
        mid_fire,
        (w_start, w_end, w_vals, w_mask),
    )
