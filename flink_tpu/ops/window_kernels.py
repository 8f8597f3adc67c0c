"""Keyed window aggregation as whole-shard device kernels.

The reference's WindowOperator (SURVEY §2.5, WindowOperator.java:222) handles
one record at a time: assign windows, HashMap-probe the pane accumulator,
apply the user reduce, maybe register a timer; window fire replays per-key
timer callbacks sequentially (§3.3). TPU-native redesign:

  * Time is divided into aligned *panes* of `slide` ticks. A tumbling window
    is one pane; a sliding window of size k*slide is the combine of k
    consecutive panes (pane composition — the reference's aligned-window
    fast path AbstractKeyedTimePanes has the same idea, per key on heap).
  * Each shard holds accumulators for ALL its keys × a ring of R recent
    panes: acc[C*R, ...]. A micro-batch updates them with one upsert +
    one scatter-combine (built-in reducers) or sort+segmented-scan (general
    associative combines). No per-record control flow.
  * Window fire is watermark-driven and evaluates the ENTIRE key population
    of up to F window-ends per step as masked whole-array reads — the
    vectorized analog of draining the timer queue.

Late records (all their windows already fired) are dropped and counted,
matching the reference's default allowed-lateness=0 behavior
(WindowOperator.isWindowLate). Ring overflow (data older than the R-pane
horizon evicted before firing) is counted separately — R is the configured
out-of-orderness budget.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.core.keygroups import assign_to_key_group
from flink_tpu.ops import hashtable
from flink_tpu.ops.hashing import route_hash
from flink_tpu.ops.hashtable import SlotTable
from flink_tpu.ops.segment import (
    preaggregate,
    reduce_sorted,
    scatter_combine,
    segment_sort,
    sort_values,
)

# np scalar, not jnp: a module-level jnp call would initialize the JAX
# backend at import time (hanging any process whose platform override
# comes after `import flink_tpu`); np.int32 behaves identically inside
# jnp expressions
PANE_NONE = np.int32(-(2**31) + 1)


@dataclass(frozen=True)
class ReduceSpec:
    """How window contents aggregate.

    kind: 'sum' | 'min' | 'max' | 'count' | 'generic' | 'sketch'
    For 'generic', combine must be associative and jnp-traceable and
    neutral its identity element. For 'sketch', `sketch` is a spec object
    (ops/sketches.py) whose register array is the accumulator: records
    scatter-expand into it and panes compose elementwise.
    Mirrors the role of ReduceFunction under ReducingStateDescriptor
    (ref flink-core state API, SURVEY §2.1); `finalize` mirrors the result
    extraction the reference performs in the window function at fire time
    (WindowOperator.fire -> InternalWindowFunction.apply).
    """

    kind: str = "sum"
    dtype: Any = jnp.float32
    value_shape: Tuple[int, ...] = ()
    combine: Optional[Callable] = None
    neutral: Any = None
    sketch: Any = None
    finalize: Optional[Callable] = None      # [..., *value_shape] -> [..., *result_shape]
    result_shape: Optional[Tuple[int, ...]] = None
    result_dtype: Any = None

    @property
    def out_shape(self) -> Tuple[int, ...]:
        return self.value_shape if self.finalize is None else self.result_shape

    @property
    def out_dtype(self):
        return self.dtype if self.result_dtype is None else self.result_dtype

    def neutral_value(self):
        if self.kind == "sketch":
            return jnp.asarray(self.sketch.neutral, self.dtype)
        if self.neutral is not None:
            return jnp.asarray(self.neutral, self.dtype)
        if self.kind in ("sum", "count"):
            return jnp.zeros((), self.dtype)
        if self.kind == "min":
            return jnp.asarray(jnp.finfo(self.dtype).max
                               if jnp.issubdtype(self.dtype, jnp.floating)
                               else jnp.iinfo(self.dtype).max, self.dtype)
        if self.kind == "max":
            return jnp.asarray(jnp.finfo(self.dtype).min
                               if jnp.issubdtype(self.dtype, jnp.floating)
                               else jnp.iinfo(self.dtype).min, self.dtype)
        raise ValueError(f"generic reduce needs an explicit neutral")

    def combine_fn(self) -> Callable:
        if self.kind == "sketch":
            return {"add": lambda a, b: a + b, "max": jnp.maximum}[
                self.sketch.op
            ]
        return {
            "sum": lambda a, b: a + b,
            "count": lambda a, b: a + b,
            "min": jnp.minimum,
            "max": jnp.maximum,
            "generic": self.combine,
        }[self.kind]


@dataclass(frozen=True)
class WindowSpec:
    """Aligned time windows via pane composition.

    size_ticks must be a multiple of slide_ticks; panes_per_window =
    size // slide (1 = tumbling). ring = R panes of history retained;
    fires_per_step = max window-ends emitted per step.
    """

    size_ticks: int
    slide_ticks: int
    ring: int = 8
    fires_per_step: int = 2
    lateness_ticks: int = 0  # allowedLateness: late updates re-fire windows
    # overflow ring lanes (0 = disabled): records whose key finds no table
    # slot append (key, pane, value) here instead of being dropped; the
    # host drains the ring into the spill-store tier at fire boundaries
    # (the RocksDB-analog seam, RocksDBKeyedStateBackend.java:82)
    overflow: int = 0
    # accumulator memory order: "pane" (ring-major, pane columns
    # contiguous — sweeps/fires/purges are sequential-bandwidth passes)
    # or "slot" (slot-major, each key's pane vector contiguous — the
    # scatter writes one cache line per key). The runtime always runs
    # pane-major (measured best for the sweep-dominated step); the
    # device_update_ceiling bench sweeps both so the choice stays
    # grounded per platform instead of asserted.
    acc_layout: str = "pane"

    def __post_init__(self):
        if self.size_ticks % self.slide_ticks:
            raise ValueError("window size must be a multiple of slide")
        if self.panes_per_window + 1 > self.ring:
            raise ValueError(
                f"ring={self.ring} too small for {self.panes_per_window} panes/window"
            )
        if self.acc_layout not in ("pane", "slot"):
            raise ValueError(
                f"acc_layout must be pane|slot, got {self.acc_layout!r}"
            )

    @property
    def panes_per_window(self) -> int:
        return self.size_ticks // self.slide_ticks


@jax.tree_util.register_pytree_node_class
@dataclass
class WindowShardState:
    """All device state of one key-group shard of a window operator."""

    table: SlotTable
    acc: jax.Array          # [C*R, *value_shape] pane accumulators
    touched: jax.Array      # bool [C*R]
    pane_ids: jax.Array     # int32 [R]: absolute pane id in each ring slot
    max_pane: jax.Array     # int32 scalar: newest registered pane
    min_pane: jax.Array     # int32 scalar: oldest pane ever seen (fire start)
    watermark: jax.Array    # int32 scalar
    fired_through: jax.Array  # int32 scalar: last window-end pane emitted
    purged_through: jax.Array  # int32 scalar: panes <= this are known clean
    dropped_late: jax.Array     # int32 counter
    dropped_capacity: jax.Array  # int32 counter (records genuinely lost)
    fresh: jax.Array            # bool [C*R]: late-updated, pending re-fire
    n_fresh: jax.Array          # int32 scalar: count of set fresh flags
    # overflow ring [O] (O = win.overflow, possibly 0): records whose key
    # found no table slot, appended for host drain into the spill tier
    ovf_hi: jax.Array           # uint32 [O]
    ovf_lo: jax.Array           # uint32 [O]
    ovf_pane: jax.Array         # int32 [O]
    ovf_val: jax.Array          # [O, *value_shape] red.dtype
    ovf_n: jax.Array            # int32 scalar: filled lanes
    # changelog dirty bits [n_key_groups] (size 0 = tracking off):
    # kg_dirty[g] is set when a record of key group g touched this shard's
    # state since the host last cleared it — the device half of
    # incremental checkpointing (flink_tpu/checkpointing/): fetched with
    # the scalars at the step-boundary barrier, it tells the snapshot
    # which key groups' entries must ride the next delta
    kg_dirty: jax.Array         # bool [n_key_groups]
    # STATIC plane descriptor (pytree aux data, not a leaf): -1 = split
    # planes (acc + touched are separate arrays, the layout above);
    # >= 0 = PACKED planes — ``acc`` carries a trailing touch column
    # ([C*R, W+1] for a W-wide value, [C*R, 2] for scalars) updated by
    # the SAME scatter/sweep as the values, and ``touched`` is a
    # zero-length placeholder. The int is the logical value ndim (0 for
    # scalar reduces), which disambiguates [*, 2] scalar-packed from a
    # width-1 vector. Self-describing so snapshot/restore/queryable
    # consumers unpack without threading a spec (wk.split_packed).
    packed: int = -1

    def tree_flatten(self):
        return (
            (self.table, self.acc, self.touched, self.pane_ids, self.max_pane,
             self.min_pane, self.watermark, self.fired_through,
             self.purged_through, self.dropped_late, self.dropped_capacity,
             self.fresh, self.n_fresh, self.ovf_hi, self.ovf_lo,
             self.ovf_pane, self.ovf_val, self.ovf_n, self.kg_dirty),
            self.packed,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, packed=aux)


def ring_append(ovf, mask, hi, lo, pane, vals, O: int):
    """Append masked lanes to the overflow ring (shared by the update hot
    path and compaction eviction so the lost-record accounting cannot
    diverge).

    ovf: (ovf_hi, ovf_lo, ovf_pane, ovf_val, ovf_n) current ring.
    Returns (new_ovf, n_lost) where n_lost counts lanes beyond capacity.
    """
    ovf_hi, ovf_lo, ovf_pane, ovf_val, ovf_n = ovf
    O = jnp.int32(O)
    pos = ovf_n + jnp.cumsum(mask.astype(jnp.int32)) - 1
    fits = mask & (pos < O)
    idx = jnp.where(fits, pos, O)
    ovf_hi = ovf_hi.at[idx].set(hi, mode="drop")
    ovf_lo = ovf_lo.at[idx].set(lo, mode="drop")
    ovf_pane = ovf_pane.at[idx].set(pane, mode="drop")
    ovf_val = ovf_val.at[idx].set(vals, mode="drop")
    n_total = jnp.sum(mask, dtype=jnp.int32)
    n_lost = n_total - jnp.sum(fits, dtype=jnp.int32)
    ovf_n = jnp.minimum(ovf_n + n_total, O)
    return (ovf_hi, ovf_lo, ovf_pane, ovf_val, ovf_n), n_lost


def overflow_supported(red: ReduceSpec) -> bool:
    """The overflow tier stores raw record contributions and merges them
    host-side, so it needs a host-computable builtin combine over plain
    scalar blocks and no kernel-side finalize."""
    return red.kind in ("sum", "count", "min", "max") and red.finalize is None


# ------------------------------------------------- packed state planes
# ISSUE 7: the pane-ring accumulator and the touched (fire-eligibility)
# plane can live in ONE wider array — acc[..., :W] holds the values and
# acc[..., -1] a touch column combined under the SAME reducer op — so
# every update issues one scatter over W+1 lanes instead of a value
# scatter plus a bool scatter, and every ring-reset/purge sweep clears
# one plane instead of two. The touch column's neutral IS the untouched
# marker (sweeps that write the packed neutral reset both planes at
# once); any update drives it away from neutral (add: +1 per lane,
# min/max: 0 against the +/-extreme default neutral), so
# ``column != neutral`` recovers the bool plane exactly.

def packed_eligible(red: ReduceSpec) -> bool:
    """Packing needs a builtin combine whose DEFAULT neutral the touch
    marker provably escapes (an explicit user neutral could collide with
    the marker), and an at-most-1-D value (the column rides axis -1)."""
    return (
        red.kind in ("sum", "count", "min", "max")
        and red.neutral is None
        and red.sketch is None
        and len(red.value_shape) <= 1
    )


def _touch_marker(red: ReduceSpec):
    """Per-lane touch-column update: combines to something != neutral."""
    if red.kind in ("sum", "count"):
        return jnp.ones((), red.dtype)     # neutral 0 -> count of touches
    return jnp.zeros((), red.dtype)        # min/max: 0 vs the +/-extreme


def make_packed(acc, touched, red: ReduceSpec):
    """Pack split (acc, touched) planes into the [..., W+1] packed array.
    Works on host numpy and device arrays alike (restore/splice pack on
    the host; the jnp scalars below are compile-time constants)."""
    xp = np if isinstance(acc, np.ndarray) else jnp
    neutral = red.neutral_value().astype(red.dtype)
    marker = _touch_marker(red)
    col = xp.where(touched, marker, neutral).astype(acc.dtype)
    if len(red.value_shape) == 0:
        return xp.stack([acc, col], axis=-1)
    return xp.concatenate([acc, col[..., None]], axis=-1)


def split_packed(acc_packed, vdims: int, red: ReduceSpec):
    """Unpack a packed plane into logical (acc, touched). ``vdims`` is
    the state's ``packed`` descriptor (logical value ndim)."""
    neutral = red.neutral_value().astype(red.dtype)
    if isinstance(acc_packed, np.ndarray):
        # host staging path (checkpoint SYNC phase): keep the compare in
        # numpy — a jnp scalar operand would bounce the whole plane
        # through the device. The scalar constant fetch is the only
        # device touch.
        neutral = np.asarray(neutral)  # host-sync-ok: compile-time scalar constant, snapshot staging runs host-side by contract
    touched = acc_packed[..., -1] != neutral
    acc = acc_packed[..., 0] if vdims == 0 else acc_packed[..., :-1]
    return acc, touched


def acc_view(state: "WindowShardState", red: ReduceSpec):
    """Logical value accumulator regardless of plane packing."""
    if state.packed < 0:
        return state.acc
    return split_packed(state.acc, state.packed, red)[0]


def touched_view(state: "WindowShardState", red: ReduceSpec):
    """Logical bool touched plane regardless of plane packing."""
    if state.packed < 0:
        return state.touched
    return split_packed(state.acc, state.packed, red)[1]


# ------------------------------------------------ accumulator layouts
# Logical shape is always [R, C, ...] (ring rows x key slots); the
# flat storage order is the WindowSpec.acc_layout choice. Every kernel
# goes through these three helpers so pane-major and slot-major cannot
# drift semantically — only the memory walk differs.

def _acc2d(flat_arr, C: int, R: int, slot_major: bool):
    """[C*R, ...] flat storage -> logical [R, C, ...] view."""
    tail = flat_arr.shape[1:]
    if slot_major:
        return flat_arr.reshape((C, R) + tail).swapaxes(0, 1)
    return flat_arr.reshape((R, C) + tail)


def _acc_flat(arr2d, C: int, R: int, slot_major: bool):
    """Logical [R, C, ...] -> [C*R, ...] flat storage order."""
    tail = arr2d.shape[2:]
    if slot_major:
        return arr2d.swapaxes(0, 1).reshape((C * R,) + tail)
    return arr2d.reshape((C * R,) + tail)


def _flat_index(ring, slot, C: int, R: int, slot_major: bool):
    """Per-lane flat scatter index for (ring row, slot)."""
    if slot_major:
        return slot.astype(jnp.int32) * jnp.int32(R) + ring
    return ring * jnp.int32(C) + slot.astype(jnp.int32)


def init_state(capacity: int, probe_len: int, win: WindowSpec,
               red: ReduceSpec, layout: str = "hash",
               n_key_groups: int = 0,
               packed: bool = False) -> WindowShardState:
    """layout="direct": the DIRECT-INDEX state backend. For keys that are
    bounded non-negative ints (identity hi==0, lo < capacity — see
    hashing.key_identity64), the key IS its slot: no probe gathers, no
    claim scatters, no insert phase at all. The table is prefilled with
    identity rows (0, slot), so every consumer of table.keys (fire
    packing, snapshots, queryable reads) works unchanged; keys outside
    the bound take the overflow ring -> spill tier like any other
    non-resident key. The reference has no analog — its HeapKeyedState-
    Backend always pays the HashMap probe (StateTable, SURVEY §2.4);
    array-indexed state is the layout a TPU wants."""
    R = win.ring
    n_elems = capacity * R * int(np.prod(red.value_shape, dtype=np.int64))
    if n_elems > 2**31 - 1:
        raise ValueError(
            f"accumulator of {n_elems} elements overflows int32 scatter "
            f"indices; lower capacity/ring or the sketch register count"
        )
    if win.overflow and not overflow_supported(red):
        raise ValueError(
            f"overflow ring requires a builtin scalar reduce without "
            f"finalize, got kind={red.kind!r}"
        )
    if packed and not packed_eligible(red):
        raise ValueError(
            f"packed state planes require a builtin reduce with the "
            f"default neutral and an at-most-1-D value, got "
            f"kind={red.kind!r}"
        )
    neutral = red.neutral_value()
    if packed:
        # acc + touched in one plane: W value lanes + 1 touch column,
        # all initialized to the neutral (== untouched marker)
        W = int(np.prod(red.value_shape, dtype=np.int64)) or 1
        acc = jnp.broadcast_to(
            neutral, (capacity * R, W + 1)
        ).astype(red.dtype)
    else:
        acc = jnp.broadcast_to(
            neutral, (capacity * R,) + red.value_shape
        ).astype(red.dtype)
    O = win.overflow
    if layout == "direct":
        iota = jnp.arange(capacity, dtype=jnp.uint32)
        table = hashtable.SlotTable(
            jnp.stack([jnp.zeros_like(iota), iota], axis=1), probe_len
        )
    elif layout == "hash":
        table = hashtable.create(capacity, probe_len)
    else:
        raise ValueError(f"unknown state layout {layout!r}")
    return WindowShardState(
        table=table,
        acc=acc + jnp.zeros_like(acc),  # materialize (broadcast_to is a view)
        touched=jnp.zeros(0 if packed else capacity * R, bool),
        pane_ids=jnp.full((R,), PANE_NONE, jnp.int32),
        max_pane=jnp.asarray(PANE_NONE),
        min_pane=jnp.asarray(2**31 - 1, jnp.int32),
        watermark=jnp.asarray(-(2**31) + 1, jnp.int32),
        fired_through=jnp.asarray(PANE_NONE),
        purged_through=jnp.asarray(PANE_NONE),
        dropped_late=jnp.zeros((), jnp.int32),
        dropped_capacity=jnp.zeros((), jnp.int32),
        fresh=jnp.zeros(capacity * R, bool),
        n_fresh=jnp.zeros((), jnp.int32),
        ovf_hi=jnp.zeros(O, jnp.uint32),
        ovf_lo=jnp.zeros(O, jnp.uint32),
        ovf_pane=jnp.full((O,), PANE_NONE, jnp.int32),
        ovf_val=jnp.zeros((O,) + red.value_shape, red.dtype),
        ovf_n=jnp.zeros((), jnp.int32),
        kg_dirty=jnp.zeros(n_key_groups, bool),
        packed=len(red.value_shape) if packed else -1,
    )


def kg_occupancy(state: WindowShardState, n_key_groups: int,
                 red: Optional[ReduceSpec] = None,
                 win: Optional[WindowSpec] = None):
    """Per-key-group live-key occupancy of one shard: how many table keys
    with at least one touched pane hash into each key group. int32
    [n_key_groups].

    The device half of the skew telemetry (ISSUE 2): the reference can
    walk its per-key-group StateTables on the heap, but here the key
    population lives in HBM — a host-side sweep would fetch the whole
    [C, 2] key table plus the touched plane every refresh. On device it
    is one route-hash over the table keys and one scatter-add, and only
    the [n_key_groups] counts cross the link at the existing step-
    boundary barrier (same pattern as the kg_dirty changelog bits).

    ``red`` is required for packed-plane state (the touch column derives
    through the neutral); ``win`` only for a non-default acc layout.
    """
    C = state.table.capacity
    slot_major = win is not None and win.acc_layout == "slot"
    t_flat = touched_view(state, red) if state.packed >= 0 else state.touched
    R = t_flat.shape[0] // C
    touched2 = _acc2d(t_flat, C, R, slot_major)          # [R, C]
    fresh2 = _acc2d(state.fresh, C, R, slot_major)
    alive = touched2.any(axis=0) | fresh2.any(axis=0)
    keys = state.table.keys                              # [C, 2]
    kg = assign_to_key_group(
        route_hash(keys[:, 0], keys[:, 1], jnp), n_key_groups, jnp
    )
    return kg_batch_fill(kg, alive, n_key_groups)


def kg_batch_fill(kg, mask, n_key_groups: int):
    """Per-key-group record counts of one micro-batch: int32
    [n_key_groups] with mask-selected lanes bincounted by their key
    group. O(B) scatter riding the update step (the cheap half of the
    skew telemetry — occupancy says who HOLDS state, fill says who is
    RECEIVING traffic right now). Shared by the mask and exchange step
    bodies so the two routes count identically."""
    idx = jnp.where(mask, kg.astype(jnp.int32), jnp.int32(n_key_groups))
    return jnp.zeros(n_key_groups, jnp.int32).at[idx].add(1, mode="drop")


def _floor_div_pane(ts, slide: int):
    # floor division for possibly-negative ticks
    return jnp.floor_divide(ts, jnp.int32(slide)).astype(jnp.int32)


def compact_table(state: WindowShardState, win: WindowSpec,
                  red: ReduceSpec) -> WindowShardState:
    """Rebuild the key table keeping only keys with live (touched) panes.

    The table never frees slots on purge (linear-probe chains must stay
    intact, hashtable.remove_slots), so long-running streams with key
    churn fill it with dead identities. This whole-shard rebuild is the
    batched analog of RocksDB compaction: re-upsert live keys into a
    fresh table and remap the pane accumulators to the new slots. Run by
    the host at fire boundaries when the overflow ring reported pressure.
    """
    C = state.table.capacity
    R = win.ring
    slot_major = win.acc_layout == "slot"
    packed = state.packed >= 0
    acc3 = _acc2d(state.acc, C, R, slot_major)           # [R, C, ...]
    if packed:
        touched2 = acc3[..., -1] != red.neutral_value().astype(red.dtype)
    else:
        touched2 = _acc2d(state.touched, C, R, slot_major)
    fresh2 = _acc2d(state.fresh, C, R, slot_major)
    alive = touched2.any(axis=0) | fresh2.any(axis=0)   # [C]

    keys = state.table.keys                              # [C, 2]
    fresh_table = hashtable.create(C, state.table.probe_len)
    # re-inserting a whole shard at once has far heavier claim-race
    # contention than incremental batches: probe_len rounds (not the step
    # path's 4) so every key that fit before fits again
    new_keys, slot, ok, _ = hashtable._upsert_impl(
        fresh_table.keys, keys[:, 0], keys[:, 1],
        (C, state.table.probe_len, state.table.probe_len), alive,
    )
    # Parallel re-insert resolves claim races in a different order than
    # the incremental inserts did, so a live key can fail to fit the new
    # arrangement even though it fit the old one. Its pane state must NOT
    # be lost: export (key, pane, acc) rows into the overflow ring — the
    # host drained it immediately before compacting — and only count a
    # drop if even the ring is full.
    failed = alive & ~ok                                 # [C]
    idx = jnp.where(alive & ok, slot, C)                 # old slot -> new

    neutral = red.neutral_value().astype(red.dtype)
    # overflow export needs LOGICAL values; the remap moves the physical
    # plane (packed: values + touch column together, one vmap scatter)
    acc3_logical = acc3[..., :-1] if packed else acc3
    if packed and state.packed == 0:
        acc3_logical = acc3[..., 0]
    tail = acc3.shape[2:]

    ovf = (state.ovf_hi, state.ovf_lo, state.ovf_pane, state.ovf_val,
           state.ovf_n)
    if win.overflow:
        ent = (touched2 & failed[None, :]).reshape(-1)   # [R*C]
        key_rc = jnp.broadcast_to(keys[None, :, :], (R, C, 2)).reshape(-1, 2)
        pane_rc = jnp.broadcast_to(
            state.pane_ids[:, None], (R, C)
        ).reshape(-1)
        ovf, lost = ring_append(
            ovf, ent, key_rc[:, 0], key_rc[:, 1], pane_rc,
            acc3_logical.reshape((R * C,) + red.value_shape), win.overflow,
        )
    else:
        lost = jnp.sum(
            jnp.where(failed[None, :], touched2, False), dtype=jnp.int32
        )
    ovf_hi, ovf_lo, ovf_pane, ovf_val, ovf_n = ovf

    def remap_row(row):
        base = jnp.broadcast_to(neutral, (C,) + tail).astype(
            red.dtype
        ) + jnp.zeros((), red.dtype)
        return base.at[idx].set(row, mode="drop")

    new_acc3 = jax.vmap(remap_row)(acc3)
    new_fresh2 = jax.vmap(
        lambda row: jnp.zeros(C, bool).at[idx].set(row, mode="drop")
    )(fresh2)
    if packed:
        new_touched_flat = state.touched       # [0] placeholder
    else:
        new_touched2 = jax.vmap(
            lambda row: jnp.zeros(C, bool).at[idx].set(row, mode="drop")
        )(touched2)
        new_touched_flat = _acc_flat(new_touched2, C, R, slot_major)

    import dataclasses as _dc

    return _dc.replace(
        state,
        table=hashtable.SlotTable(new_keys, state.table.probe_len),
        acc=_acc_flat(new_acc3, C, R, slot_major),
        touched=new_touched_flat,
        fresh=_acc_flat(new_fresh2, C, R, slot_major),
        dropped_capacity=state.dropped_capacity + lost,
        ovf_hi=ovf_hi,
        ovf_lo=ovf_lo,
        ovf_pane=ovf_pane,
        ovf_val=ovf_val,
        ovf_n=ovf_n,
    )


def update(
    state: WindowShardState,
    win: WindowSpec,
    red: ReduceSpec,
    hi, lo, ts, values, valid,
    insert: bool = True,
    direct: bool = False,
    kg=None,
    precombine: bool = False,
    kg_fill: int = 0,
    clear_rows=None,
    kg_res=None,
):
    """Apply one micro-batch of records to shard state (pure function).

    The caller has already routed records: `valid` is False for lanes not
    owned by this shard. Replaces WindowOperator.processElement +
    HeapReducingState.add for the whole batch at once.

    Returns ``(new_state, activity, kgf)``. ``activity`` (int32 scalar)
    counts lanes whose key was NOT already resident in the table: newly
    inserted keys plus overflowed lanes — ``activity == 0`` certifies the
    batch was a pure in-place update. ``kgf`` is the per-key-group record
    count of this batch (int32 ``[kg_fill]``; ``[0]`` when ``kg_fill=0``)
    counting the PRE-late-check ``valid`` lanes — the traffic half of the
    skew telemetry, computed here so it can ride the shared sort below.

    ``insert=False`` compiles the steady-state FAST path: the key table is
    never mutated — one probe gather instead of upsert's five, and no claim
    scatters (~6x cheaper on TPU, where the statically-unrolled claim
    rounds dominate the step even when every key is already resident).
    Records whose key is absent take the overflow ring -> host spill tier
    (win.overflow must be > 0; their contributions merge back into window
    emissions exactly like capacity overflow). The executor watches
    ``activity`` through the lagged monitoring channel and flips back to
    the insert step while new keys are arriving, so the fast path only
    ever runs when misses are rare (runtime/executor.py step tiering).

    ``precombine=True`` (built-in reducers only) pre-aggregates the batch
    per (slot, pane) BEFORE the state scatter: ONE shared sort by flat
    accumulator index + a segmented scan, and every consumer rides the
    same permutation — the accumulator scatter, the fire-eligibility
    (touched) plane, the changelog kg_dirty bits, and the kg_fill skew
    counts (segment lane-counts scattered at the representatives, plus a
    residual scatter for the rare late/too-old/nofit lanes the sort
    excludes). Duplicate scatter indices serialize on TPU, and a hot-key
    batch is exactly the duplicate-heavy case; the rep scatters carry
    ``unique_indices`` so XLA skips the collision handling entirely.
    tools/check_segment_sort_seam.py keeps this the only sort a batch
    pays.

    ``clear_rows`` (bool ``[R]`` in logical ring-row space) folds a
    DEFERRED purge from the fused-fire scan into this batch's ring-reset
    sweep: rows flagged by the previous sub-step's
    ``advance_and_fire_resident`` clear here for free instead of paying
    their own sweep (every containing window already fired, so nothing
    reads them in between — see the resident-pipeline invariant there).
    Only valid with ``win.lateness_ticks == 0``.

    With PACKED planes (``state.packed >= 0``) the touched bits live in
    the accumulator's trailing column, so the value scatter and the
    ring-reset/purge sweeps maintain both planes in one pass and the
    separate touched scatter disappears.

    ``kg_res`` (bool ``[max_parallelism]``, tiered key-group state —
    ``state.tiers.*``) is this shard's HBM-residency mask: lanes whose
    key group reads False never touch the table or accumulators — they
    fall straight down the overflow ring to the host spill tier, which
    owns cold-group state. The mask is a plain operand, so the compiled
    step is shape-stable as residency changes; diversion is NEVER lossy
    (only ring exhaustion drops, same as any overflow) and requires
    ``win.overflow > 0`` for exactly that reason.
    """
    if kg_res is not None and not win.overflow:
        raise ValueError(
            "kg_res (tiered residency) requires an overflow ring "
            "(win.overflow > 0): non-resident lanes divert to the "
            "host spill tier through it"
        )
    C = state.table.capacity
    R = win.ring
    k = win.panes_per_window
    slot_major = win.acc_layout == "slot"
    packed = state.packed >= 0
    mine = valid            # pre-late-check routing mask (kg_fill contract)

    pane = _floor_div_pane(ts, win.slide_ticks)
    L = win.lateness_ticks

    # -- late check (ref WindowOperator.isWindowLate): drop iff every window
    # containing this pane has passed end-1+allowedLateness at the PRE-batch
    # watermark, or the pane's storage was already purged.
    base = jnp.maximum(
        state.watermark,
        jnp.int32(-(2**31) + 1 + win.slide_ticks) + jnp.int32(L),
    ) - jnp.int32(L)
    wm_pane_l = _floor_div_pane(base + 1 - win.slide_ticks, win.slide_ticks)
    last_end = pane + jnp.int32(k - 1)  # newest window-end pane covering rec
    late = valid & (
        (last_end <= wm_pane_l) | (pane <= state.purged_through)
    )
    n_late = jnp.sum(late, dtype=jnp.int32)
    live = valid & ~late

    # -- register/advance the pane ring -----------------------------------
    batch_max = jnp.max(jnp.where(live, pane, PANE_NONE))
    new_max = jnp.maximum(state.max_pane, batch_max)
    batch_min = jnp.min(jnp.where(live, pane, jnp.int32(2**31 - 1)))
    new_min = jnp.minimum(state.min_pane, batch_min)
    r_idx = jnp.arange(R, dtype=jnp.int32)
    # newest pane with (p % R) == r, p <= new_max
    p_r = new_max - jnp.mod(new_max - r_idx, jnp.int32(R))
    have_data = new_max != PANE_NONE
    p_r = jnp.where(have_data, p_r, PANE_NONE)
    stale = (p_r != state.pane_ids)
    # unfired data being evicted from the ring = capacity drop
    evicted = stale & (state.pane_ids != PANE_NONE) & (
        state.pane_ids + jnp.int32(k - 1) > state.fired_through
    )
    neutral = red.neutral_value()
    # logical [R, C, ...] views of the flat planes (pane-major keeps pane
    # columns CONTIGUOUS so ring resets/fires/purges are sequential-
    # bandwidth sweeps — the difference between ~0.2ms and ~20ms per step
    # on TPU for a 4M-slot shard; slot-major is the bench-swept variant)
    acc2d = _acc2d(state.acc, C, R, slot_major)
    if packed:
        touched2d = acc2d[..., -1] != neutral.astype(red.dtype)
    else:
        touched2d = _acc2d(state.touched, C, R, slot_major)
    n_evicted = jnp.sum(
        jnp.where(evicted[:, None], touched2d, False), dtype=jnp.int32
    )

    # unconditional sweep: a fused full pass costs far less than the
    # operand copies a lax.cond forces on 100MB+ carried buffers.
    # clear_rows (the fused-fire deferred purge) rides the same pass.
    clear = stale if clear_rows is None else (stale | clear_rows)
    acc2d = jnp.where(_expand(clear[:, None], acc2d),
                      neutral.astype(red.dtype), acc2d)
    if not packed:
        touched2d = jnp.where(clear[:, None], False, touched2d)
    if L > 0:
        # with no allowed lateness the fresh plane is never set, so its
        # sweep (and reshape) is statically elided — one fewer full pass
        # per batch
        fresh2d = _acc2d(state.fresh, C, R, slot_major)
        fresh2d = jnp.where(clear[:, None], False, fresh2d)
    pane_ids = jnp.where(stale, p_r, state.pane_ids)
    acc = _acc_flat(acc2d, C, R, slot_major)
    touched = (
        state.touched if packed else _acc_flat(touched2d, C, R, slot_major)
    )

    # -- drop records older than the ring horizon --------------------------
    oldest = new_max - jnp.int32(R - 1)
    too_old = live & (pane < oldest)
    n_too_old = jnp.sum(too_old, dtype=jnp.int32)
    live = live & ~too_old

    # -- changelog dirty bits: every surviving lane is about to mutate
    # this shard's state for its key group (table/accumulator scatter OR
    # the overflow ring -> spill tier), so mark the group dirty BEFORE the
    # fit check — over-marking a spilled lane's group is safe (its delta
    # just covers a group that only changed host-side), under-marking
    # would silently drop its state from the next incremental checkpoint.
    # `kg`: the caller's precomputed per-lane key groups (the routing
    # bodies in runtime/step.py already have them — skip the re-hash).
    # With precombine the marking moves AFTER the upsert so it can ride
    # the shared sort: segment representatives cover every FITTING lane's
    # group (same slot => same key => same group), and the rare nofit
    # lanes get their own scatter below — together exactly the live set
    # this eager scatter covers.
    KG = state.kg_dirty.shape[0]
    if KG and kg_fill and kg_fill != KG:
        raise ValueError(
            f"kg_fill group count {kg_fill} != changelog group count {KG}"
        )
    pre = precombine and red.kind in ("sum", "min", "max", "count")
    n_groups = KG or kg_fill or (
        kg_res.shape[0] if kg_res is not None else 0
    )
    if kg_res is not None and (KG or kg_fill) and \
            kg_res.shape[0] != (KG or kg_fill):
        raise ValueError(
            f"kg_res group count {kg_res.shape[0]} != "
            f"changelog/kg_fill group count {KG or kg_fill}"
        )
    if n_groups and kg is None:
        kg = assign_to_key_group(route_hash(hi, lo, jnp), n_groups, jnp)
    if KG and not pre:
        kg_dirty = state.kg_dirty.at[
            jnp.where(live, kg.astype(jnp.int32), jnp.int32(KG))
        ].set(True, mode="drop")
    else:
        kg_dirty = state.kg_dirty

    # -- tiered residency (state.tiers.*): divert lanes whose key group
    # is cold BEFORE the upsert — they must not claim table slots, and
    # `activity` must stay a pure hot-tier signal (a cold-group burst
    # may not flip the executor's insert/fast step tiering). The dirty
    # marking above deliberately still covers them: their spill-side
    # state changes under the same group.
    if kg_res is not None:
        tier_nonres = live & ~kg_res[kg.astype(jnp.int32)]
        live = live & ~tier_nonres
    else:
        tier_nonres = None

    # -- key upsert / lookup ------------------------------------------------
    # activity = lanes the CURRENT mode failed to handle natively:
    #   insert mode -> newly PLACED keys (population still growing; lanes
    #     that exhaust their probe chain are excluded — re-running insert
    #     can never place them, they belong to the spill tier)
    #   fast mode   -> missing lanes (spilled; the host flips back to
    #     insert mode only when these exceed a churn threshold)
    if direct:
        # direct-index layout (init_state layout="direct"): the key IS the
        # slot. No probe, no table mutation; out-of-bound keys spill.
        table = state.table
        ok = live & (hi == jnp.uint32(0)) & (lo < jnp.uint32(C))
        slot = jnp.where(ok, lo, jnp.uint32(C)).astype(jnp.int32)
        nofit = live & ~ok
        activity = jnp.zeros((), jnp.int32)   # no insert phase to tier
    elif insert:
        table, slot, ok, activity = hashtable.upsert_counted(
            state.table, hi, lo, live
        )
        nofit = live & ~ok
    else:
        table = state.table
        slot, found = hashtable.lookup(state.table, hi, lo)
        ok = found & live
        nofit = live & ~ok
        activity = jnp.sum(nofit, dtype=jnp.int32)
    if tier_nonres is not None:
        # cold-group lanes ride the same overflow ring as capacity
        # overcommit: appended (key, pane, value), host-merged into the
        # spill tier, merged back into emissions at fire — lossless
        nofit = nofit | tier_nonres
    live = live & ok

    # -- overflow ring: nofit records append (key, pane, value) for the
    # host to drain into the spill tier; only ring exhaustion drops
    ovf = (state.ovf_hi, state.ovf_lo, state.ovf_pane, state.ovf_val,
           state.ovf_n)
    if win.overflow:
        contrib = (
            jnp.ones_like(values) if red.kind == "count" else values
        ).astype(red.dtype)
        ovf, n_nofit = ring_append(
            ovf, nofit, hi, lo, pane, contrib, win.overflow
        )
    else:
        n_nofit = jnp.sum(nofit, dtype=jnp.int32)
    ovf_hi, ovf_lo, ovf_pane, ovf_val, ovf_n = ovf

    # -- scatter-combine into (slot, pane-ring) accumulators ----------------
    ring = jnp.mod(pane, jnp.int32(R))
    # flat storage index (layout-aware); slot==C when !ok lands in
    # [0, C*R) only via the scatter mask, which drops those lanes
    flat = _flat_index(ring, slot, C, R, slot_major)
    kgf = jnp.zeros(0, jnp.int32)
    kgf_pending = bool(kg_fill)
    if red.kind == "sketch":
        # records expand to per-register updates in the flattened
        # [C*R * prod(value_shape)] register space; one hardware scatter
        eidx, upd, emask = red.sketch.expand(flat, values, live)
        acc = scatter_combine(
            acc.reshape(-1), eidx, upd.astype(red.dtype), emask,
            red.sketch.op,
        ).reshape((C * R,) + red.value_shape)
    elif red.kind in ("sum", "min", "max", "count"):
        upd = values if red.kind != "count" else jnp.ones_like(values)
        upd = upd.astype(red.dtype)
        if packed:
            # the touch column rides the SAME scatter: marker lanes
            # combine to != neutral under the reducer op
            marker = jnp.broadcast_to(
                _touch_marker(red), upd.shape[: upd.ndim - state.packed]
            ).astype(red.dtype)
            if state.packed == 0:
                upd = jnp.stack([upd, marker], axis=-1)
            else:
                upd = jnp.concatenate([upd, marker[..., None]], axis=-1)
        op = {"sum": "add", "count": "add",
              "min": "min", "max": "max"}[red.kind]
        if pre:
            # duplicate-key collapse: ONE sort by flat accumulator index,
            # a segmented-scan reduce, then unique-index rep scatters —
            # acc (+ its packed touch column), touched, kg_dirty, and the
            # kg_fill counts all consume this single permutation
            order, ids_s, valid_s, seg_start, rep_mask = segment_sort(
                flat, live
            )
            upd_s = reduce_sorted(order, valid_s, seg_start, upd,
                                  red.combine_fn(), neutral)
            acc = scatter_combine(acc, ids_s, upd_s, rep_mask, op,
                                  unique=True)
            if not packed:
                touched = scatter_combine(
                    touched, ids_s, jnp.ones_like(ids_s, bool), rep_mask,
                    "set", unique=True,
                )
            kg32 = kg.astype(jnp.int32) if (KG or kg_fill) else None
            if KG:
                kg_dirty = kg_dirty.at[
                    jnp.where(rep_mask, kg32[order], jnp.int32(KG))
                ].set(True, mode="drop")
                # nofit lanes never reached a slot but still dirtied
                # their group (they spill host-side); usually all-masked
                kg_dirty = kg_dirty.at[
                    jnp.where(nofit, kg32, jnp.int32(KG))
                ].set(True, mode="drop")
            if kg_fill:
                # 4th consumer of the shared sort: per-segment lane
                # counts land at the representatives (same slot => same
                # key => same group), residual pre-late-check traffic
                # (late / too-old / nofit lanes, outside the sort's
                # validity) adds its own mostly-masked scatter
                seg_n = reduce_sorted(
                    order, valid_s, seg_start,
                    jnp.ones_like(ids_s), lambda a, b: a + b,
                    jnp.zeros((), ids_s.dtype),
                )
                kgf = jnp.zeros(kg_fill, jnp.int32).at[
                    jnp.where(rep_mask, kg32[order], jnp.int32(kg_fill))
                ].add(seg_n.astype(jnp.int32), mode="drop")
                resid = mine & ~live
                kgf = kgf.at[
                    jnp.where(resid, kg32, jnp.int32(kg_fill))
                ].add(1, mode="drop")
                kgf_pending = False
        else:
            acc = scatter_combine(acc, flat, upd, live, op)
    else:
        ids, rep_mask, reduced = preaggregate(
            flat, values.astype(red.dtype), live,
            combine=red.combine_fn(), neutral=neutral,
        )
        safe = jnp.where(rep_mask, ids, C * R)
        old = acc.at[safe].get(mode="clip")
        old_touched = touched.at[safe].get(mode="clip") & rep_mask
        merged = jnp.where(
            _expand(old_touched, old), red.combine_fn()(old, reduced), reduced
        )
        acc = acc.at[safe].set(merged, mode="drop")
    if not pre and not packed:
        touched = scatter_combine(
            touched, flat, jnp.ones_like(flat, bool), live, "set"
        )
    if kgf_pending:
        # non-precombined paths: the plain one-scatter bincount
        kgf = kg_batch_fill(kg, mine, kg_fill)

    # -- allowed lateness: records landing in already-fired windows mark
    # their pane "fresh" so those windows re-fire (ref late-firing panes)
    n_fresh = state.n_fresh
    if L > 0:
        fresh = _acc_flat(fresh2d, C, R, slot_major)
        late_upd = live & (pane <= state.fired_through)
        fresh = scatter_combine(
            fresh, flat, jnp.ones_like(flat, bool), late_upd, "set"
        )
        n_fresh = n_fresh + jnp.sum(late_upd, dtype=jnp.int32)
    else:
        fresh = state.fresh

    import dataclasses as _dc

    return _dc.replace(
        state,
        table=table,
        acc=acc,
        touched=touched,
        pane_ids=pane_ids,
        max_pane=new_max,
        min_pane=new_min,
        dropped_late=state.dropped_late + n_late,
        dropped_capacity=state.dropped_capacity + n_too_old + n_nofit + n_evicted,
        fresh=fresh,
        n_fresh=n_fresh,
        ovf_hi=ovf_hi,
        ovf_lo=ovf_lo,
        ovf_pane=ovf_pane,
        ovf_val=ovf_val,
        ovf_n=ovf_n,
        kg_dirty=kg_dirty,
    ), activity, kgf


def _expand(flag, val):
    extra = val.ndim - flag.ndim
    return flag.reshape(flag.shape + (1,) * extra)


@jax.tree_util.register_pytree_node_class
@dataclass
class FireResult:
    """Window fires, whole-shard masked. With allowedLateness the lane count
    doubles: F on-time lanes then F late re-fire lanes.

    mask:     bool [Ft, C] — slot emitted for fire lane f
    values:   [Ft, C, *value_shape]
    window_end_ticks: int32 [Ft] (exclusive end; PANE_NONE when lane unused)
    n_fires:  int32 scalar — number of valid lanes
    lane_valid: bool [Ft]
    """

    mask: jax.Array
    values: jax.Array
    window_end_ticks: jax.Array
    n_fires: jax.Array
    lane_valid: jax.Array

    def tree_flatten(self):
        return (self.mask, self.values, self.window_end_ticks, self.n_fires,
                self.lane_valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclass
class CompactFires:
    """Fire output packed on device so the host never transfers the dense
    [Ft, C] mask/value planes or the [C, 2] key table: for lane f, entries
    j < counts[f] are (key_hi[f, j], key_lo[f, j], values[f, j]) and the
    whole lane shares window_end_ticks[f]. The host reads the small fields
    (counts/lane_valid/window_end/n_fires), then slices only [:counts[f]]
    of the packed arrays — O(actual fires) transferred instead of O(F*C).
    """

    key_hi: jax.Array           # uint32 [Ft, C]
    key_lo: jax.Array           # uint32 [Ft, C]
    values: jax.Array           # [Ft, C, *out_shape]
    counts: jax.Array           # int32 [Ft] emitted keys per lane
    window_end_ticks: jax.Array  # int32 [Ft]
    n_fires: jax.Array          # int32 scalar: valid lanes
    lane_valid: jax.Array       # bool [Ft]
    # per-lane scalar reduction of the packed values (sum over emitted
    # slots; unused lanes pack zeros so no mask is needed). Lets a
    # device_reduce sink consume a drain by reading ONLY the small fields
    # — no O(fires) device->host transfer (runtime/sinks.py Sink.
    # device_reduce).
    value_sums: jax.Array       # float32 [Ft]

    def tree_flatten(self):
        return (self.key_hi, self.key_lo, self.values, self.counts,
                self.window_end_ticks, self.n_fires, self.lane_valid,
                self.value_sums), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclass
class ReducedFires:
    """Fire output reduced ON DEVICE to per-lane scalars — the drain path
    for device_reduce-capable sinks (runtime/sinks.py). Nothing O(C) is
    packed or transferred: the host reads five [Ft]-sized fields and the
    drain is done. Compared to CompactFires this skips the 3 full-capacity
    pack scatters per lane that dominate the fire step's cost (the
    reference's timer drain materializes every (key, window, value) triple;
    a counting/aggregating sink never needs them —
    ref WindowOperator.java:222 emit path).
    """

    counts: jax.Array            # int32 [Ft] fired keys per lane
    window_end_ticks: jax.Array  # int32 [Ft]
    n_fires: jax.Array           # int32 scalar: valid lanes
    lane_valid: jax.Array        # bool [Ft]
    value_sums: jax.Array        # float32 [Ft]

    def tree_flatten(self):
        return (self.counts, self.window_end_ticks, self.n_fires,
                self.lane_valid, self.value_sums), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def reduce_fires(fr: FireResult) -> ReducedFires:
    """Reduce a dense FireResult to per-lane (count, value-sum) scalars."""
    counts = jnp.sum(fr.mask, axis=1, dtype=jnp.int32)          # [Ft]
    masked = jnp.where(_expand(fr.mask, fr.values), fr.values, 0)
    vsums = jnp.sum(
        masked.reshape(masked.shape[0], -1), axis=1
    ).astype(jnp.float32)                                        # [Ft]
    return ReducedFires(counts, fr.window_end_ticks, fr.n_fires,
                        fr.lane_valid, vsums)


def _pack_fire_lanes(table: SlotTable, mask, values):
    """The pack math of compact_fires: per fire lane, compact the dense
    (mask, values) planes into prefix buffers of (key_hi, key_lo, value)
    plus (count, value_sum) scalars. Shared by compact_fires and the
    fused-fire resident advance (the gated in-scan pack) so the payload
    bytes cannot diverge between the split and resident drains.

    Round 7: the stream compaction is GATHER-formulated — cumsum the
    mask, then ``searchsorted`` finds output position i's source lane
    (the first lane whose running count reaches i+1; a vectorized
    binary search, NOT a sort) and three gathers move the payload.
    The previous three row SCATTERS per lane serialized on XLA CPU
    (~60ns/element — the single biggest term of the firing-stream
    ceiling); the gather form is ~8x cheaper there and collision-free
    everywhere, with bit-identical output."""
    C = table.capacity
    tk = table.keys
    ar = jnp.arange(C, dtype=jnp.int32)

    def pack(mask_f, vals_f):
        cs = jnp.cumsum(mask_f.astype(jnp.int32))
        count = cs[-1]
        sel = jnp.searchsorted(cs, ar + 1, side="left")
        ok = ar < count
        selc = jnp.minimum(sel, jnp.int32(C - 1))
        khi = jnp.where(ok, tk[selc, 0], jnp.uint32(0))
        klo = jnp.where(ok, tk[selc, 1], jnp.uint32(0))
        v = jnp.where(_expand(ok, vals_f), vals_f[selc],
                      jnp.zeros((), vals_f.dtype))
        vsum = jnp.sum(
            jnp.where(_expand(mask_f, vals_f), vals_f, 0.0)
        ).astype(jnp.float32)
        return khi, klo, v, count, vsum

    return jax.vmap(pack)(mask, values)


def compact_fires(table: SlotTable, fr: FireResult) -> CompactFires:
    """Pack a dense FireResult into per-lane prefix buffers on device.

    Delegates the compaction to ``_pack_fire_lanes`` (cumsum +
    searchsorted + gathers — see there). Replaces the host-side
    np.nonzero sweep over [Ft, C] masks and the full table.keys transfer
    the round-1 emit path paid every step.
    """
    khi, klo, v, counts, vsums = _pack_fire_lanes(table, fr.mask, fr.values)
    return CompactFires(khi, klo, v, counts, fr.window_end_ticks,
                        fr.n_fires, fr.lane_valid, vsums)


def _fire_plan(state: WindowShardState, win: WindowSpec, new_watermark):
    """Scalar half of a watermark advance: which window-ends are due.

    Shared by the split-dispatch fire step (advance_and_fire) and the
    fused-fire resident advance so the two drains cannot disagree about
    lane scheduling. Pure scalar/[F] math — nothing O(C)."""
    R = win.ring
    k = win.panes_per_window
    F = win.fires_per_step

    wm = jnp.maximum(state.watermark, jnp.asarray(new_watermark, jnp.int32))
    # window ending at pane p covers ticks [(p-k+1)*slide, (p+1)*slide);
    # fires when wm >= end-1. Clamp before the subtraction so the MIN
    # sentinel watermark cannot wrap int32 and spuriously fire everything.
    wm_c = jnp.maximum(wm, jnp.int32(-(2**31) + 1 + win.slide_ticks))
    wm_pane = _floor_div_pane(wm_c + 1 - win.slide_ticks, win.slide_ticks)

    have = state.max_pane != PANE_NONE
    oldest_registered = jnp.maximum(
        state.max_pane - jnp.int32(R - 1), state.min_pane
    )
    start = jnp.maximum(state.fired_through + 1, oldest_registered)
    start = jnp.where(state.fired_through == PANE_NONE,
                      oldest_registered, start)
    # Sliding windows ending up to k-1 panes past max_pane still contain
    # registered panes; only ends beyond max_pane+k-1 are certainly empty.
    end = jnp.where(
        have, jnp.minimum(wm_pane, state.max_pane + jnp.int32(k - 1)),
        start - 1,
    )
    n_due = jnp.maximum(end - start + 1, 0)
    n_now = jnp.minimum(n_due, F)

    f_idx = jnp.arange(F, dtype=jnp.int32)
    p_f = start + f_idx                      # window-end pane per fire lane
    lane_ok = f_idx < n_now
    window_end = jnp.where(
        lane_ok, (p_f + 1) * jnp.int32(win.slide_ticks), PANE_NONE
    )

    new_fired_through = jnp.where(
        n_due > F, start + n_now - 1, jnp.maximum(wm_pane, state.fired_through)
    )
    # Empty shards track wm_pane too, so fired_through stays consistent
    # across shards and a snapshot min() reflects the true global cut.
    new_fired_through = jnp.where(
        have, new_fired_through,
        jnp.maximum(state.fired_through, wm_pane),
    )
    return {
        "wm": wm, "wm_pane": wm_pane, "have": have, "start": start,
        "n_due": n_due, "n_now": n_now, "p_f": p_f, "lane_ok": lane_ok,
        "window_end": window_end, "new_fired_through": new_fired_through,
    }


def _state_fire_views(state: WindowShardState, win: WindowSpec,
                      red: ReduceSpec):
    """(acc3 logical, touched2) read views [R, C(, ...)] of the pane
    planes, regardless of plane packing and accumulator layout."""
    C = state.table.capacity
    R = win.ring
    slot_major = win.acc_layout == "slot"
    accp3 = _acc2d(state.acc, C, R, slot_major)
    if state.packed >= 0:
        neutral = red.neutral_value().astype(red.dtype)
        touched2 = accp3[..., -1] != neutral
        acc3 = accp3[..., 0] if state.packed == 0 else accp3[..., :-1]
    else:
        touched2 = _acc2d(state.touched, C, R, slot_major)
        acc3 = accp3
    return acc3, touched2


def _eval_fire_lanes(acc3, touched2, pane_ids, win: WindowSpec,
                     red: ReduceSpec, p_f, lane_ok, mask2):
    """Evaluate the windows ending at panes ``p_f`` for ALL keys.

    The emission mask comes from ``mask2`` (touched for on-time fires,
    fresh for late re-fires); values always combine every touched pane
    of the window. PANE-INDEXED (round 7): the window ending at pane p
    is the combine of panes p-k+1..p, and pane q can only live in ring
    row q % R — so each lane reads its k rows by direct (dynamic) row
    index, O(k*C) instead of the old O(R*C) sweep over every ring row.
    For a tumbling window (k=1, the throughput topology) that is a
    1/R-th of the old fire-evaluation cost — the single biggest term of
    the firing-stream ceiling (device_update_ceiling fire_grid). A row
    only contributes when its registered id equals q (an unrotated ring
    row still holding an older pane stays masked out)."""
    C = acc3.shape[1]
    R = win.ring
    k = win.panes_per_window
    combine = red.combine_fn()
    neutral = red.neutral_value()

    def fire_one(p, ok):
        vals = jnp.broadcast_to(
            neutral, (C,) + red.value_shape
        ).astype(red.dtype)
        emit = jnp.zeros(C, bool)
        for j in range(k):
            q = p - jnp.int32(k - 1) + jnp.int32(j)
            row = jnp.mod(q, jnp.int32(R))
            present = ok & (pane_ids[row] == q)
            col = acc3[row]
            col_t = touched2[row] & present
            vals = jnp.where(_expand(col_t, vals), combine(vals, col), vals)
            # combine(neutral, col) == col for first touch
            emit = emit | (mask2[row] & present)
        if red.finalize is not None:
            vals = red.finalize(vals)
        return emit, vals

    return jax.vmap(fire_one)(p_f, lane_ok)


def _purge_plan(state: WindowShardState, win: WindowSpec, wm,
                new_fired_through, fresh2=None):
    """Which ring rows purge at this advance, and the purged_through
    scalar. A pane leaves state only once BOTH every containing window
    has fired AND the lateness horizon has passed (and no re-fire is
    pending on it). Clamps before subtracting so the MIN sentinel cannot
    wrap int32."""
    k = win.panes_per_window
    base_l = jnp.maximum(
        wm,
        jnp.int32(-(2**31) + 1 + win.slide_ticks)
        + jnp.int32(win.lateness_ticks),
    ) - jnp.int32(win.lateness_ticks)
    wm_pane_l = _floor_div_pane(base_l + 1 - win.slide_ticks, win.slide_ticks)
    cutoff = jnp.minimum(new_fired_through, wm_pane_l)
    purgeable = (
        (state.pane_ids != PANE_NONE)
        & (state.pane_ids + jnp.int32(k - 1) <= cutoff)
        & (state.pane_ids > state.purged_through)
    )
    if fresh2 is not None:
        purgeable = purgeable & ~jnp.any(fresh2, axis=1)
    new_purged = jnp.where(
        cutoff == PANE_NONE,
        state.purged_through,
        jnp.maximum(
            state.purged_through,
            jnp.maximum(cutoff, PANE_NONE + jnp.int32(k)) - jnp.int32(k - 1),
        ),
    )
    return cutoff, purgeable, new_purged


def _clear_rows_planes(state: WindowShardState, win: WindowSpec,
                       red: ReduceSpec, rows):
    """Clear the flagged ring rows in the acc/touched planes (one sweep
    when packed). Returns (acc_flat, touched_flat)."""
    C = state.table.capacity
    R = win.ring
    slot_major = win.acc_layout == "slot"
    neutral = red.neutral_value().astype(red.dtype)
    accp = _acc2d(state.acc, C, R, slot_major)
    accp = jnp.where(_expand(rows[:, None], accp), neutral, accp)
    if state.packed >= 0:
        return _acc_flat(accp, C, R, slot_major), state.touched
    t2 = _acc2d(state.touched, C, R, slot_major)
    t2 = jnp.where(rows[:, None], False, t2)
    return (_acc_flat(accp, C, R, slot_major),
            _acc_flat(t2, C, R, slot_major))


def apply_pending_purge(state: WindowShardState, win: WindowSpec,
                        red: ReduceSpec, rows) -> WindowShardState:
    """Post-scan fixup of the fused-fire resident pipeline: clear ring
    rows whose purge was deferred into "the next update's ring-reset
    sweep" but whose megastep ended first. After this the state is
    bit-identical to the sequential update/advance_and_fire interleaving
    (the purged_through scalar already advanced at defer time)."""
    import dataclasses as _dc

    acc, touched = _clear_rows_planes(state, win, red, rows)
    return _dc.replace(state, acc=acc, touched=touched)


def advance_and_fire(
    state: WindowShardState,
    win: WindowSpec,
    red: ReduceSpec,
    new_watermark,
) -> Tuple[WindowShardState, FireResult]:
    """Advance the shard watermark and emit due window fires.

    Vectorized analog of HeapInternalTimerService.advanceWatermark +
    WindowOperator.onEventTime per key (ref §3.3): instead of per-key timer
    callbacks, each due window-end is evaluated for ALL keys at once; a
    sliding window combines its panes_per_window ring columns.
    """
    import dataclasses as _dc

    C = state.table.capacity
    R = win.ring
    k = win.panes_per_window
    F = win.fires_per_step
    slot_major = win.acc_layout == "slot"

    plan = _fire_plan(state, win, new_watermark)
    wm = plan["wm"]
    lane_ok = plan["lane_ok"]
    window_end = plan["window_end"]
    new_fired_through = plan["new_fired_through"]
    n_now = plan["n_now"]

    acc3, touched2 = _state_fire_views(state, win, red)
    big = jnp.int32(2**31 - 1)

    mask, values = _eval_fire_lanes(
        acc3, touched2, state.pane_ids, win, red, plan["p_f"], lane_ok,
        touched2,
    )

    # -- late re-fires (allowedLateness): windows <= fired_through whose
    # panes got late updates re-fire with their corrected full value.
    if win.lateness_ticks > 0:
        fresh2 = _acc2d(state.fresh, C, R, slot_major)

        def do_late(fresh2):
            fresh_any = jnp.any(fresh2, axis=1)  # [R]
            j_idx = jnp.arange(k, dtype=jnp.int32)
            wc = state.pane_ids[:, None] + j_idx[None, :]  # [R, k]
            need = (
                fresh_any[:, None]
                & (state.pane_ids != PANE_NONE)[:, None]
                & (wc <= new_fired_through)
            )
            wflat = jnp.where(need.reshape(-1), wc.reshape(-1), big)
            wsort = sort_values(wflat)
            first = jnp.concatenate(
                [jnp.ones((1,), bool), wsort[1:] != wsort[:-1]]
            ) & (wsort < big)
            rank = jnp.cumsum(first) - 1
            sel = jnp.full((F,), big)
            sel = sel.at[jnp.where(first, rank, F)].set(wsort, mode="drop")
            sel_ok = sel < big
            lmask, lvals = _eval_fire_lanes(
                acc3, touched2, state.pane_ids, win, red, sel, sel_ok,
                fresh2,
            )
            # clear fresh panes whose due windows were all covered this pass
            covered_c = (~need) | (wc[:, :, None] == sel[None, None, :]).any(-1)
            pane_done = covered_c.all(axis=1) & fresh_any
            fresh2b = jnp.where(pane_done[:, None], False, fresh2)
            return (lmask, lvals, sel, sel_ok, fresh2b,
                    jnp.sum(fresh2b, dtype=jnp.int32))

        # unconditionally evaluated: with no fresh panes every selection
        # comes back empty and the state is unchanged; no lax.cond, so
        # the step keeps no data-dependent control flow.
        lmask, lvals, lsel, lsel_ok, fresh2, n_fresh = do_late(fresh2)
        mask = jnp.concatenate([mask, lmask])
        values = jnp.concatenate([values, lvals])
        window_end = jnp.concatenate(
            [window_end,
             jnp.where(lsel_ok, (lsel + 1) * jnp.int32(win.slide_ticks),
                       PANE_NONE)]
        )
        lane_valid = jnp.concatenate([lane_ok, lsel_ok])
        n_fires = n_now + jnp.sum(lsel_ok, dtype=jnp.int32)
    else:
        fresh2 = None
        lane_valid = lane_ok
        n_fires = n_now
        n_fresh = state.n_fresh

    # -- purge (unconditional sweep — see update(): conds copy the big
    # carried buffers)
    _cutoff, purgeable, new_purged = _purge_plan(
        state, win, wm, new_fired_through, fresh2=fresh2
    )
    acc, touched = _clear_rows_planes(state, win, red, purgeable)

    new_state = _dc.replace(
        state,
        acc=acc,
        touched=touched,
        watermark=wm,
        fired_through=new_fired_through,
        purged_through=new_purged,
        fresh=(
            _acc_flat(fresh2, C, R, slot_major)
            if win.lateness_ticks > 0 else state.fresh
        ),
        n_fresh=n_fresh,
        # fires/purges are NOT marked dirty: they are global sweeps fully
        # determined by the scalars (fired_through/watermark), and chain
        # recovery re-applies the same purge cutoff to merged entries
        # (checkpointing/recovery.py), so per-group bits stay precise
    )
    return new_state, FireResult(mask, values, window_end, n_fires, lane_valid)


def advance_and_fire_resident(
    state: WindowShardState,
    win: WindowSpec,
    red: ReduceSpec,
    new_watermark,
    reduced: bool = False,
) -> Tuple[WindowShardState, jax.Array, "CompactFires | ReducedFires"]:
    """Fused-fire advance for the RESIDENT megastep scan (ISSUE 7).

    The split path dispatches fire as its own device step and breaks
    every K-group at a pane boundary; here the whole advance runs inside
    the scan body after each sub-batch's update, with two cost moves
    that make a per-sub-step advance affordable:

    * the O(F*R*C) fire evaluation + payload pack runs under ``lax.cond``
      on ``n_now > 0`` — sub-steps that cross no pane boundary (the
      overwhelming steady-state majority) pay only the scalar plan. The
      cond is READ-ONLY over the big state (its outputs are just the
      packed fire buffers), so no identity-branch state copies arise,
      and the skip branch's all-zero payload is bit-identical to packing
      an empty fire.
    * the purge plane-clears are DEFERRED: this call advances the
      ``purged_through`` scalar immediately but returns the purgeable
      row mask for the NEXT sub-step's update to fold into its ring-
      reset sweep (wk.update ``clear_rows``) — or for
      ``apply_pending_purge`` after the scan. Safe because a deferred
      row's every window already fired: no in-scan reader revisits it
      (fire lanes start past it, late-dropped records cannot scatter
      into it) until a sweep clears it.

    Returns ``(state', purge_rows, fires)`` with ``fires`` a
    CompactFires for THIS sub-step — or, with ``reduced=True``, a
    ReducedFires: per-lane (count, value_sum) scalars only, NO payload
    planes at all. The reduced mode exists because the scan must stack
    a payload slot for EVERY sub-step (crossing or not), and those
    [F, C] zero-writes are the resident pipeline's whole overhead on a
    quiet stream; device_reduce sink topologies (runtime/sinks.py)
    never read the payload, so they skip it — the in-scan analog of
    build_window_fire_reduced_step. With allowed lateness the fresh/
    re-fire machinery is needed every sub-step anyway, so that cold
    path delegates to the classic advance (no gate, no deferral).
    """
    import dataclasses as _dc

    R = win.ring
    if win.lateness_ticks > 0:
        st, fr = advance_and_fire(state, win, red, new_watermark)
        packed_fr = (
            reduce_fires(fr) if reduced else compact_fires(st.table, fr)
        )
        return st, jnp.zeros(R, bool), packed_fr

    C = state.table.capacity
    F = win.fires_per_step

    plan = _fire_plan(state, win, new_watermark)
    wm = plan["wm"]
    n_now = plan["n_now"]
    lane_ok = plan["lane_ok"]

    _cutoff, purgeable, new_purged = _purge_plan(
        state, win, wm, plan["new_fired_through"]
    )

    def _eval_compact():
        acc3, touched2 = _state_fire_views(state, win, red)
        mask, values = _eval_fire_lanes(
            acc3, touched2, state.pane_ids, win, red, plan["p_f"],
            lane_ok, touched2,
        )
        return _pack_fire_lanes(state.table, mask, values)

    def _skip_compact():
        return (
            jnp.zeros((F, C), jnp.uint32),
            jnp.zeros((F, C), jnp.uint32),
            jnp.zeros((F, C) + red.out_shape, red.out_dtype),
            jnp.zeros(F, jnp.int32),
            jnp.zeros(F, jnp.float32),
        )

    def _eval_reduced():
        acc3, touched2 = _state_fire_views(state, win, red)
        mask, values = _eval_fire_lanes(
            acc3, touched2, state.pane_ids, win, red, plan["p_f"],
            lane_ok, touched2,
        )
        # == reduce_fires over this lane set (bit-parity with the
        # split drain's on-chip reduction)
        counts = jnp.sum(mask, axis=1, dtype=jnp.int32)
        masked = jnp.where(_expand(mask, values), values, 0)
        vsums = jnp.sum(
            masked.reshape(masked.shape[0], -1), axis=1
        ).astype(jnp.float32)
        return counts, vsums

    def _skip_reduced():
        return jnp.zeros(F, jnp.int32), jnp.zeros(F, jnp.float32)

    if reduced:
        counts, vsums = jax.lax.cond(n_now > 0, _eval_reduced,
                                     _skip_reduced)
        fires = ReducedFires(counts, plan["window_end"], n_now, lane_ok,
                             vsums)
    else:
        khi, klo, v, counts, vsums = jax.lax.cond(
            n_now > 0, _eval_compact, _skip_compact
        )
        fires = CompactFires(khi, klo, v, counts, plan["window_end"],
                             n_now, lane_ok, vsums)
    new_state = _dc.replace(
        state,
        watermark=wm,
        fired_through=plan["new_fired_through"],
        purged_through=new_purged,
    )
    return new_state, purgeable, fires


# --------------------------------------------- canonical kernel families

def kernel_family_grid(capacity: int = 64, probe_len: int = 4,
                       batch: int = 8):
    """Raw-kernel half of the canonical audit grid (the step-builder
    half lives in runtime/step.py kernel_family_grid, next to the
    builders): ``[(name, fn, example_args)]`` for every public kernel in
    this module, one entry per layout/plane variant the runtime
    dispatches. The compiled-graph auditor (tools/lint trace tier)
    make_jaxprs each entry and holds its primitive counts against the
    checked-in op-budget ledger — the one-sort precombine seam and the
    packed single-scatter plane are contracts here, not prose. None of
    these are jitted or donated: the jit/donation story is the step
    builders'; this grid pins the kernel bodies themselves."""
    win = WindowSpec(4, 2, ring=4, fires_per_step=2, overflow=4)
    red = ReduceSpec("sum", jnp.float32)
    B = batch
    hi = jnp.arange(B, dtype=jnp.uint32) * jnp.uint32(2654435761)
    lo = jnp.arange(B, dtype=jnp.uint32)
    hi_d = jnp.zeros(B, jnp.uint32)
    lo_d = jnp.arange(B, dtype=jnp.uint32) % jnp.uint32(capacity)
    ts = jnp.zeros(B, jnp.int32)
    values = jnp.ones(B, jnp.float32)
    valid = jnp.ones(B, bool)
    wm = jnp.zeros((), jnp.int32)
    st = init_state(capacity, probe_len, win, red)
    st_d = init_state(capacity, probe_len, win, red, layout="direct")
    st_p = init_state(capacity, probe_len, win, red, packed=True)

    def mk_update(direct=False, insert=True, precombine=False):
        def kernel(state, k_hi, k_lo, k_ts, k_values, k_valid):
            return update(state, win, red, k_hi, k_lo, k_ts, k_values,
                          k_valid, insert=insert, direct=direct,
                          precombine=precombine)
        return kernel

    def fire_compact(state, k_wm):
        state, fr = advance_and_fire(state, win, red, k_wm)
        return state, compact_fires(state.table, fr)

    def fire_reduced(state, k_wm):
        state, fr = advance_and_fire(state, win, red, k_wm)
        return state, reduce_fires(fr)

    def fire_resident(state, k_wm):
        return advance_and_fire_resident(state, win, red, k_wm)

    def fire_resident_reduced(state, k_wm):
        return advance_and_fire_resident(state, win, red, k_wm,
                                         reduced=True)

    def compact(state):
        return compact_table(state, win, red)

    def occupancy(state):
        return kg_occupancy(state, 8, red=red, win=win)

    upd = (hi, lo, ts, values, valid)
    upd_d = (hi_d, lo_d, ts, values, valid)
    return [
        ("wk.update.hash", mk_update(), (st,) + upd),
        ("wk.update.direct", mk_update(direct=True), (st_d,) + upd_d),
        ("wk.update.hash.precombine", mk_update(precombine=True),
         (st,) + upd),
        ("wk.update.hash.packed", mk_update(), (st_p,) + upd),
        ("wk.update_fast.hash", mk_update(insert=False), (st,) + upd),
        ("wk.fire.compact", fire_compact, (st, wm)),
        ("wk.fire.reduced", fire_reduced, (st, wm)),
        ("wk.fire.resident", fire_resident, (st, wm)),
        ("wk.fire.resident_reduced", fire_resident_reduced, (st, wm)),
        ("wk.compact_table", compact, (st,)),
        ("wk.occupancy", occupancy, (st,)),
    ]
