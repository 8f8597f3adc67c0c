"""Compiled SPMD step functions.

A Flink job runs thousands of task threads pulling records through Netty
(SURVEY §3.2). Here a pipeline stage compiles to ONE jitted SPMD function:

    step(state, batch, watermark) -> (state', fires)

executed over the mesh with `shard_map`: every device applies the stage's
stateless chain, masks the lanes whose key group it owns (replicate-and-mask
exchange, see parallel/mesh.py), updates its shard of windowed state, and
evaluates due window fires. The checkpoint barrier of the reference
(BarrierBuffer alignment) is simply the step boundary: between two step
invocations ALL state is consistent and snapshottable.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from flink_tpu.core.keygroups import assign_to_key_group
from flink_tpu.ops import window_kernels as wk
from flink_tpu.ops.hashing import route_hash
from flink_tpu.parallel.mesh import SHARD_AXIS, MeshContext


@dataclass
class WindowStageSpec:
    """Static config of one keyed-window pipeline stage."""

    win: wk.WindowSpec
    red: wk.ReduceSpec
    capacity_per_shard: int = 1 << 16
    probe_len: int = 16
    # jnp-traceable pre-keyed chain: (values_dict, ts, valid) -> (value, ts, valid)
    # applied on-device before keying (fused maps/filters).
    pre: Optional[Callable] = None
    # "hash" (open-addressing SlotTable) or "direct" (key == slot for
    # bounded non-negative int keys; see wk.init_state layout="direct")
    layout: str = "hash"
    # duplicate-key collapse before the state scatter (wk.update
    # precombine): sort + segmented-scan per (slot, pane), unique-index
    # rep scatters. Only built-in reducers take it; resolved from
    # pipeline.update-precombine by the executor.
    precombine: bool = False
    # packed state planes (wk.init_state packed): touched bits ride a
    # trailing accumulator column — one scatter/sweep maintains both.
    # Resolved from state.packed-planes by the executor (platform-gated
    # auto); only wk.packed_eligible reduce specs take it.
    packed: bool = False


def init_sharded_state(ctx: MeshContext, spec: WindowStageSpec):
    """Per-shard window state stacked on a leading [n_shards] axis.

    Changelog tracking (kg_dirty, sized to the key-group space) is always
    on: the per-batch cost is one route-hash + one bool scatter, and the
    bits are what lets an incremental checkpoint fetch/serialize only the
    key groups that changed (flink_tpu/checkpointing/)."""
    def one(_):
        return wk.init_state(spec.capacity_per_shard, spec.probe_len,
                             spec.win, spec.red, layout=spec.layout,
                             n_key_groups=ctx.max_parallelism,
                             packed=spec.packed)

    states = [one(i) for i in range(ctx.n_shards)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    return jax.device_put(stacked, ctx.state_sharding)


def build_window_step(ctx: MeshContext, spec: WindowStageSpec):
    """Compile the stage into a jitted SPMD step over the mesh."""
    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh

    def shard_body(state, kg_start, kg_end, hi, lo, ts, values, valid, wm):
        # state leaves arrive with their leading [1] shard axis; drop it.
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        if spec.pre is not None:
            values, ts, valid = spec.pre(values, ts, valid)
        kg = assign_to_key_group(route_hash(hi, lo, jnp), maxp, jnp)
        mine = valid & (kg >= kg_start.astype(jnp.uint32)) & (
            kg <= kg_end.astype(jnp.uint32)
        )
        state, _, _ = wk.update(state, spec.win, spec.red, hi, lo, ts,
                                values, mine,
                                direct=spec.layout == "direct", kg=kg,
                                precombine=spec.precombine)
        state, fires = wk.advance_and_fire(state, spec.win, spec.red, wm[0])
        state = jax.tree_util.tree_map(lambda x: x[None], state)
        fires = jax.tree_util.tree_map(lambda x: x[None], fires)
        return state, fires

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS),  # state (leading shard axis)
            P(SHARD_AXIS),  # kg_start
            P(SHARD_AXIS),  # kg_end
            P(), P(), P(), P(), P(),  # batch replicated
            P(SHARD_AXIS),  # per-shard watermark
        ),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def step(state, hi, lo, ts, values, valid, wm):
        """wm: int32[n_shards] watermark per shard (usually identical).
        State is DONATED: XLA updates the 100MB+ shard arrays in place
        instead of copy-on-write; callers must not reuse the old state."""
        return sharded(state, starts, ends, hi, lo, ts, values, valid, wm)

    return step


def mask_update_shard(state, spec: WindowStageSpec, kg_start, kg_end,
                      hi, lo, ts, values, valid, wm, maxp: int,
                      insert: bool = True, kg_fill: bool = False,
                      clear_rows=None, kg_res=None):
    """Shared per-shard body for the mask (replicated-batch) route: hash
    to key groups, mask to owned groups, apply the window update, and
    advance the shard watermark. Used by the single step AND the K-fused
    megastep scan bodies so the mask semantics cannot diverge (the
    exchange route shares exchange_update_shard the same way). ``wm`` is
    this batch's watermark scalar. Returns (state', activity,
    kg_fill_counts); the kg_fill counts (observability.kg-stats skew
    telemetry) are computed INSIDE wk.update so they ride the shared
    pre-combine sort with the other scatter consumers, statically
    compiled out to a zero-length array when off. ``clear_rows`` folds
    the fused-fire scan's deferred purge into the update's ring-reset
    sweep (wk.update). ``kg_res`` (bool ``[maxp]``, tiered state) is the
    replicated HBM-residency mask wk.update diverts cold-group lanes
    around the table with."""
    import dataclasses as _dc

    if spec.pre is not None:
        values, ts, valid = spec.pre(values, ts, valid)
    kg = assign_to_key_group(route_hash(hi, lo, jnp), maxp, jnp)
    mine = valid & (kg >= kg_start.astype(jnp.uint32)) & (
        kg <= kg_end.astype(jnp.uint32)
    )
    state, activity, kgf = wk.update(
        state, spec.win, spec.red, hi, lo, ts, values, mine,
        insert=insert, direct=spec.layout == "direct", kg=kg,
        precombine=spec.precombine, kg_fill=maxp if kg_fill else 0,
        clear_rows=clear_rows, kg_res=kg_res,
    )
    state = _dc.replace(state, watermark=jnp.maximum(state.watermark, wm))
    return state, activity, kgf


def build_window_update_step(ctx: MeshContext, spec: WindowStageSpec,
                             insert: bool = True,
                             kg_fill: bool = False,
                             tiered: bool = False):
    """Update-only half of the window step: apply a micro-batch and advance
    the shard watermark, but do NOT evaluate fires. The reference evaluates
    timers on every watermark advance (HeapInternalTimerService), but a
    window only becomes due when the watermark crosses a pane boundary —
    once per slide interval, i.e. once in ~hundreds of micro-batches. The
    host computes the watermark, so it knows exactly when that happens and
    calls the fire step (build_window_fire_step) only then. Between
    boundaries every step is sync-free: state is donated, nothing is read
    back, and dispatch overlaps device compute.

    ``insert=False`` builds the lookup-only FAST variant (wk.update's
    insert flag): same state layout, so the executor switches between the
    two compiled steps per micro-batch at zero cost, driven by the lagged
    activity signal in the monitoring output.

    ``tiered=True`` appends one trailing ``kg_res`` operand (replicated
    bool ``[max_parallelism]`` HBM-residency mask, state.tiers.*): cold-
    group lanes divert to the overflow ring inside wk.update. The mask
    is data, not structure — residency changes never recompile."""
    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh

    def shard_body(state, kg_start, kg_end, hi, lo, ts, values, valid,
                   wm, *rest):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        state, activity, kgf = mask_update_shard(
            state, spec, kg_start[0], kg_end[0], hi, lo, ts, values,
            valid, wm[0], maxp, insert=insert, kg_fill=kg_fill,
            kg_res=rest[0] if tiered else None,
        )
        ovf_n = state.ovf_n
        return (
            jax.tree_util.tree_map(lambda x: x[None], state),
            ovf_n[None], activity[None], kgf[None],
        )

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(), P(), P(), P(), P(),
            P(SHARD_AXIS),
        ) + ((P(),) if tiered else ()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS)),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def update_step(state, hi, lo, ts, values, valid, wm, *rest):
        """Returns (state', (ovf_n, activity, kg_fill)). The second
        element is a tiny NON-donated monitoring tuple: overflow-ring
        fill level, not-already-resident lane count, and per-key-group
        record counts of this batch ([n_shards, max_parallelism] — the
        traffic half of the skew telemetry; [n_shards, 0] when the
        builder's kg_fill flag is off). The host queues the handles
        and inspects them a few steps later — by then the values have
        materialized, so the read never stalls the step pipeline (lagged
        monitoring). `activity` drives the insert<->fast step tiering.
        """
        st, ovf_n, act, kgf = sharded(state, starts, ends, hi, lo, ts,
                                      values, valid, wm, *rest)
        return st, (ovf_n, act, kgf)

    update_step.tiered = tiered
    return update_step


def exchange_update_shard(state, spec: WindowStageSpec, kg_start, kg_end,
                          hi, lo, ts, values, valid, n: int, maxp: int,
                          cap: int, insert: bool = True, clear_rows=None,
                          kg_res=None):
    """Shared per-shard body: route this device's lane slice to owning
    shards over the mesh all_to_all, mask to owned key groups, and apply
    the window update. Used by the single-host exchange step and the
    cross-host DCN runner (runtime/dcn.py) so the shuffle semantics
    cannot diverge. Returns (state', activity) with bucket overflow
    already counted into dropped_capacity. (kg_fill telemetry stays a
    route-level concern here: the contract counts each record at its
    PRE-exchange source device, which update cannot see.)"""
    import dataclasses as _dc

    from flink_tpu.parallel.exchange import exchange_owned

    if spec.pre is not None:
        values, ts, valid = spec.pre(values, ts, valid)
    cols, r_hi, r_lo, mine, n_over = exchange_owned(
        {"ts": ts, "values": values}, hi, lo, valid, n, maxp, cap,
        kg_start, kg_end,
    )
    state, activity, _ = wk.update(state, spec.win, spec.red, r_hi, r_lo,
                                   cols["ts"], cols["values"], mine,
                                   insert=insert,
                                   direct=spec.layout == "direct",
                                   precombine=spec.precombine,
                                   clear_rows=clear_rows, kg_res=kg_res)
    state = _dc.replace(
        state, dropped_capacity=state.dropped_capacity + n_over
    )
    return state, activity


def build_window_update_step_exchange(ctx: MeshContext, spec: WindowStageSpec,
                                      batch_per_device: int,
                                      capacity_factor: float = 2.0,
                                      insert: bool = True,
                                      kg_fill: bool = False,
                                      tiered: bool = False):
    """Update step with a real ICI record exchange instead of
    replicate-and-mask: the host splits the batch over devices (each holds
    B/n lanes), each device buckets its lanes by owning shard and ONE
    jax.lax.all_to_all routes them (parallel/exchange.py). Per-device
    update work is O(B/n) — ingest throughput scales with chips, matching
    the reference's KeyGroupStreamPartitioner+RecordWriter shuffle
    (KeyGroupStreamPartitioner.java:53, RecordWriter.java:82).

    Bucket overflow (hash skew beyond capacity_factor x expected) is
    counted into dropped_capacity — surfaced, never silent."""
    import dataclasses as _dc

    from flink_tpu.parallel.exchange import bucket_capacity

    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    n = ctx.n_shards
    cap = bucket_capacity(batch_per_device, n, capacity_factor)

    def shard_body(state, kg_start, kg_end, hi, lo, ts, values, valid,
                   wm, *rest):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        state, activity = exchange_update_shard(
            state, spec, kg_start, kg_end, hi, lo, ts, values, valid,
            n, maxp, cap, insert=insert,
            kg_res=rest[0] if tiered else None,
        )
        state = _dc.replace(
            state, watermark=jnp.maximum(state.watermark, wm[0])
        )
        ovf_n = state.ovf_n
        # skew telemetry over THIS device's pre-exchange lane slice: each
        # record is counted once at its source device, so the host-side
        # shard sum equals the mask route's per-owner counts; compiled
        # out when the builder's kg_fill flag is off
        if kg_fill:
            kg_local = assign_to_key_group(
                route_hash(hi, lo, jnp), maxp, jnp
            )
            kgf = wk.kg_batch_fill(kg_local, valid, maxp)
        else:
            kgf = jnp.zeros(0, jnp.int32)
        return (
            jax.tree_util.tree_map(lambda x: x[None], state),
            ovf_n[None], activity[None], kgf[None],
        )

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            # batch arrays are SPLIT over devices on the batch axis
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(SHARD_AXIS),
            P(SHARD_AXIS),  # per-shard watermark
        ) + ((P(),) if tiered else ()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS)),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def _jit_step(state, hi, lo, ts, values, valid, wm, *rest):
        st, ovf_n, act, kgf = sharded(state, starts, ends, hi, lo, ts,
                                      values, valid, wm, *rest)
        return st, (ovf_n, act, kgf)

    def update_step(state, hi, lo, ts, values, valid, wm, *rest):
        return _jit_step(state, hi, lo, ts, values, valid, wm, *rest)

    update_step.recv_lanes = n * cap
    update_step.bucket_cap = cap
    update_step.tiered = tiered
    # the jitted inner step, for AOT consumers (cost_analysis needs
    # .lower(), which the plain wrapper doesn't have)
    update_step.jit = _jit_step
    return update_step


def _fused_batch_stack(K: int, flat):
    """Stack the flat per-batch megastep operands back into [K, B] arrays.

    ``flat`` is (hi_0, lo_0, ticks_0, values_0, valid_0, hi_1, ...): K
    groups of 5. The stack happens INSIDE the jit so the executor can
    hand over K individually device-staged batches (the ingest ring
    stages them one poll at a time) without a host-side concat."""
    return [
        jnp.stack([flat[5 * i + j] for i in range(K)]) for j in range(5)
    ]


def build_window_megastep(ctx: MeshContext, spec: WindowStageSpec,
                          k_steps: int, insert: bool = True,
                          kg_fill: bool = False,
                          tiered: bool = False):
    """K-step dispatch fusion (pipeline.steps-per-dispatch): ONE jitted
    ``lax.scan`` applies a stack of K staged micro-batches against
    donated state in a single dispatch. Every fused group divides the
    fixed per-dispatch cost — Python ``run_update`` overhead, tracing,
    watchdog arming and the dispatch itself — by K, while the per-batch
    semantics (late checks against the pre-batch watermark, per-batch
    watermark advance) are byte-for-byte the sequential single steps':
    the scan body IS the single-step body.

    Signature: ``megastep(state, hi_0, lo_0, ticks_0, values_0, valid_0,
    ..., wmv)`` with wmv int32 [n_shards, K] (column i = batch i's
    watermark vector). Returns ``(state', (ovf_n, activity, kg_fill))``
    with the SAME monitoring shapes as the single step — ovf_n is the
    post-scan fill (monotone within a dispatch, so final == max),
    activity and kg_fill are summed over the K sub-steps — so the
    executor's lagged-monitoring consumer needs no fused-path variant.
    """
    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    K = int(k_steps)

    def shard_body(state, kg_start, kg_end, hi, lo, ts, values, valid,
                   wm, *rest):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg_res = rest[0] if tiered else None   # scan-invariant, closed over

        def sub(st, xs):
            s_hi, s_lo, s_ts, s_vals, s_valid, s_wm = xs
            st, act, kgf = mask_update_shard(
                st, spec, kg_start, kg_end, s_hi, s_lo, s_ts, s_vals,
                s_valid, s_wm, maxp, insert=insert, kg_fill=kg_fill,
                kg_res=kg_res,
            )
            return st, (act, kgf)

        state, (acts, kgfs) = jax.lax.scan(
            sub, state, (hi, lo, ts, values, valid, wm[0])
        )
        ovf_n = state.ovf_n
        act = jnp.sum(acts)
        kgf = kgfs.sum(axis=0) if kg_fill else jnp.zeros(0, jnp.int32)
        return (
            jax.tree_util.tree_map(lambda x: x[None], state),
            ovf_n[None], act[None], kgf[None],
        )

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(), P(), P(), P(), P(),   # [K, B] batch stacks, replicated
            P(SHARD_AXIS),             # wmv [n_shards, K]
        ) + ((P(),) if tiered else ()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS)),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def megastep(state, *flat):
        if tiered:
            *batches, wmv, kg_res = flat
            tail = (wmv, kg_res)
        else:
            *batches, wmv = flat
            tail = (wmv,)
        stacks = _fused_batch_stack(K, batches)
        st, ovf_n, act, kgf = sharded(state, starts, ends, *stacks, *tail)
        return st, (ovf_n, act, kgf)

    megastep.k_steps = K
    megastep.tiered = tiered
    return megastep


def build_window_megastep_exchange(ctx: MeshContext, spec: WindowStageSpec,
                                   batch_per_device: int, k_steps: int,
                                   capacity_factor: float = 2.0,
                                   insert: bool = True,
                                   kg_fill: bool = False,
                                   tiered: bool = False):
    """Exchange-route megastep: the K-fused analog of
    build_window_update_step_exchange — each scan sub-step runs the
    shared ``exchange_update_shard`` body (bucket + all_to_all + masked
    update), so the fused shuffle semantics cannot diverge from the
    single-step route. Batch stacks arrive [K, B] SPLIT over devices on
    the batch (second) axis."""
    import dataclasses as _dc

    from flink_tpu.parallel.exchange import bucket_capacity

    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    n = ctx.n_shards
    cap = bucket_capacity(batch_per_device, n, capacity_factor)
    K = int(k_steps)

    def shard_body(state, kg_start, kg_end, hi, lo, ts, values, valid,
                   wm, *rest):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg_res = rest[0] if tiered else None

        def sub(st, xs):
            s_hi, s_lo, s_ts, s_vals, s_valid, s_wm = xs
            st, act = exchange_update_shard(
                st, spec, kg_start, kg_end, s_hi, s_lo, s_ts, s_vals,
                s_valid, n, maxp, cap, insert=insert, kg_res=kg_res,
            )
            st = _dc.replace(st, watermark=jnp.maximum(st.watermark, s_wm))
            if kg_fill:
                kg_local = assign_to_key_group(
                    route_hash(s_hi, s_lo, jnp), maxp, jnp
                )
                kgf = wk.kg_batch_fill(kg_local, s_valid, maxp)
            else:
                kgf = jnp.zeros(0, jnp.int32)
            return st, (act, kgf)

        state, (acts, kgfs) = jax.lax.scan(
            sub, state, (hi, lo, ts, values, valid, wm[0])
        )
        ovf_n = state.ovf_n
        act = jnp.sum(acts)
        kgf = kgfs.sum(axis=0) if kg_fill else jnp.zeros(0, jnp.int32)
        return (
            jax.tree_util.tree_map(lambda x: x[None], state),
            ovf_n[None], act[None], kgf[None],
        )

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            # [K, B] stacks SPLIT over devices on the batch axis
            P(None, SHARD_AXIS), P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(SHARD_AXIS),
        ) + ((P(),) if tiered else ()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS)),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def megastep(state, *flat):
        if tiered:
            *batches, wmv, kg_res = flat
            tail = (wmv, kg_res)
        else:
            *batches, wmv = flat
            tail = (wmv,)
        stacks = _fused_batch_stack(K, batches)
        st, ovf_n, act, kgf = sharded(state, starts, ends, *stacks, *tail)
        return st, (ovf_n, act, kgf)

    megastep.k_steps = K
    megastep.recv_lanes = n * cap
    megastep.bucket_cap = cap
    megastep.tiered = tiered
    return megastep


def build_window_megastep_fired(ctx: MeshContext, spec: WindowStageSpec,
                                k_steps: int, insert: bool = True,
                                kg_fill: bool = False,
                                reduced: bool = False,
                                tiered: bool = False):
    """Resident-pipeline megastep (pipeline.fused-fire, ISSUE 7): the
    K-fused ``lax.scan`` with the FIRE SWEEP folded into the scan body.
    Each sub-step applies its micro-batch (the shared mask_update_shard
    body, so the routing semantics cannot diverge from the single step)
    and then runs ``wk.advance_and_fire_resident`` against its own
    watermark: a pane-boundary crossing inside the K-group fires WITHIN
    the scan instead of breaking the group into single dispatches plus a
    separate fire dispatch (the split path this replaces serialized
    update and fire at every boundary).

    The per-sub-step advance is affordable because the fire evaluation
    is lax.cond-gated on "anything due" and the purge plane-clears
    DEFER into the next sub-step's ring-reset sweep (carried ``pending``
    rows; ``apply_pending_purge`` reconciles after the scan so the
    returned state is bit-identical to the sequential interleaving).

    Returns ``(state', (ovf_n, activity, kg_fill), fires)`` where
    ``fires`` is a CompactFires pytree with a leading [n_shards, K] axis
    — sub-step i's payload under sub-step i's watermark. The executor
    consumes the handles LAGGED (runtime/executor.py consume_fires), so
    surfacing fires costs no step-loop sync.

    ``reduced=True`` surfaces ReducedFires instead — per-lane scalars,
    no payload planes. The scan stacks a payload slot for every
    sub-step whether it fired or not, so device_reduce sink topologies
    (which never read payloads) skip the [K, F, C] zero traffic that
    otherwise dominates the resident overhead on quiet streams."""
    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    K = int(k_steps)

    def shard_body(state, kg_start, kg_end, hi, lo, ts, values, valid,
                   wm, *rest):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg_res = rest[0] if tiered else None
        pend0 = jnp.zeros(spec.win.ring, bool)

        def sub(carry, xs):
            st, pend = carry
            s_hi, s_lo, s_ts, s_vals, s_valid, s_wm = xs
            st, act, kgf = mask_update_shard(
                st, spec, kg_start, kg_end, s_hi, s_lo, s_ts, s_vals,
                s_valid, s_wm, maxp, insert=insert, kg_fill=kg_fill,
                clear_rows=pend, kg_res=kg_res,
            )
            st, pend, cf = wk.advance_and_fire_resident(
                st, spec.win, spec.red, s_wm, reduced=reduced
            )
            return (st, pend), (act, kgf, cf)

        (state, pend), (acts, kgfs, fires) = jax.lax.scan(
            sub, (state, pend0), (hi, lo, ts, values, valid, wm[0])
        )
        state = wk.apply_pending_purge(state, spec.win, spec.red, pend)
        ovf_n = state.ovf_n
        act = jnp.sum(acts)
        kgf = kgfs.sum(axis=0) if kg_fill else jnp.zeros(0, jnp.int32)
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        return (
            pack(state), ovf_n[None], act[None], kgf[None], pack(fires),
        )

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(), P(), P(), P(), P(),   # [K, B] batch stacks, replicated
            P(SHARD_AXIS),             # wmv [n_shards, K]
        ) + ((P(),) if tiered else ()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def megastep(state, *flat):
        if tiered:
            *batches, wmv, kg_res = flat
            tail = (wmv, kg_res)
        else:
            *batches, wmv = flat
            tail = (wmv,)
        stacks = _fused_batch_stack(K, batches)
        st, ovf_n, act, kgf, fires = sharded(
            state, starts, ends, *stacks, *tail
        )
        return st, (ovf_n, act, kgf), fires

    megastep.k_steps = K
    megastep.fused_fire = True
    megastep.fused_fire_reduced = reduced
    megastep.tiered = tiered
    return megastep


def build_window_megastep_fired_exchange(ctx: MeshContext,
                                         spec: WindowStageSpec,
                                         batch_per_device: int,
                                         k_steps: int,
                                         capacity_factor: float = 2.0,
                                         insert: bool = True,
                                         kg_fill: bool = False,
                                         reduced: bool = False,
                                         tiered: bool = False):
    """Exchange-route resident megastep: the fused-fire analog of
    build_window_megastep_exchange — each scan sub-step runs the shared
    ``exchange_update_shard`` body (bucket + all_to_all + masked update)
    followed by the gated resident advance, so neither the shuffle nor
    the fire semantics can diverge from the split-dispatch route. Batch
    stacks arrive [K, B] SPLIT over devices on the batch (second) axis;
    fires come back per shard like the mask variant."""
    import dataclasses as _dc

    from flink_tpu.parallel.exchange import bucket_capacity

    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    n = ctx.n_shards
    cap = bucket_capacity(batch_per_device, n, capacity_factor)
    K = int(k_steps)

    def shard_body(state, kg_start, kg_end, hi, lo, ts, values, valid,
                   wm, *rest):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg_res = rest[0] if tiered else None
        pend0 = jnp.zeros(spec.win.ring, bool)

        def sub(carry, xs):
            st, pend = carry
            s_hi, s_lo, s_ts, s_vals, s_valid, s_wm = xs
            st, act = exchange_update_shard(
                st, spec, kg_start, kg_end, s_hi, s_lo, s_ts, s_vals,
                s_valid, n, maxp, cap, insert=insert, clear_rows=pend,
                kg_res=kg_res,
            )
            st = _dc.replace(st, watermark=jnp.maximum(st.watermark, s_wm))
            if kg_fill:
                kg_local = assign_to_key_group(
                    route_hash(s_hi, s_lo, jnp), maxp, jnp
                )
                kgf = wk.kg_batch_fill(kg_local, s_valid, maxp)
            else:
                kgf = jnp.zeros(0, jnp.int32)
            st, pend, cf = wk.advance_and_fire_resident(
                st, spec.win, spec.red, s_wm, reduced=reduced
            )
            return (st, pend), (act, kgf, cf)

        (state, pend), (acts, kgfs, fires) = jax.lax.scan(
            sub, (state, pend0), (hi, lo, ts, values, valid, wm[0])
        )
        state = wk.apply_pending_purge(state, spec.win, spec.red, pend)
        ovf_n = state.ovf_n
        act = jnp.sum(acts)
        kgf = kgfs.sum(axis=0) if kg_fill else jnp.zeros(0, jnp.int32)
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        return (
            pack(state), ovf_n[None], act[None], kgf[None], pack(fires),
        )

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            # [K, B] stacks SPLIT over devices on the batch axis
            P(None, SHARD_AXIS), P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(SHARD_AXIS),
        ) + ((P(),) if tiered else ()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def megastep(state, *flat):
        if tiered:
            *batches, wmv, kg_res = flat
            tail = (wmv, kg_res)
        else:
            *batches, wmv = flat
            tail = (wmv,)
        stacks = _fused_batch_stack(K, batches)
        st, ovf_n, act, kgf, fires = sharded(
            state, starts, ends, *stacks, *tail
        )
        return st, (ovf_n, act, kgf), fires

    megastep.k_steps = K
    megastep.fused_fire = True
    megastep.fused_fire_reduced = reduced
    megastep.recv_lanes = n * cap
    megastep.bucket_cap = cap
    megastep.tiered = tiered
    return megastep


def _zero_slot_fires(spec: WindowStageSpec, reduced: bool):
    """Zero-shaped per-sub-step fire payload for the resident drain's
    skip branch: field-for-field the shapes/dtypes a live sub-step's
    ``wk.advance_and_fire_resident`` emits (its own internal skip branch
    packs the same zeros), so both ``lax.cond`` branches of the drain
    body stack identically and an unconsumed ring slot is bit-identical
    to packing an empty fire — the executor's lagged consume_fires sees
    counts == 0 and emits nothing."""
    F = spec.win.fires_per_step
    C = spec.capacity_per_shard
    zi = jnp.zeros(F, jnp.int32)
    zf = jnp.zeros(F, jnp.float32)
    zb = jnp.zeros(F, bool)
    n0 = jnp.zeros((), jnp.int32)
    if reduced:
        return wk.ReducedFires(zi, zi, n0, zb, zf)
    return wk.CompactFires(
        jnp.zeros((F, C), jnp.uint32),
        jnp.zeros((F, C), jnp.uint32),
        jnp.zeros((F, C) + spec.red.out_shape, spec.red.out_dtype),
        zi, zi, n0, zb, zf,
    )


# per-slot drain-interior counters (observability.drain-stats, ISSUE 14):
# index order of the int32 stats vector each live drain slot emits. The
# scan stacks them [D, N]; shard_map packs [n_shards, D, N] — the
# "flight recorder" payload the executor unpacks LAGGED alongside fires.
# The tuple lives with the host-side unpacker so packer and unpacker
# cannot drift (flink_tpu/metrics/drain_stats.py documents each field).
from flink_tpu.metrics.drain_stats import (  # noqa: E402
    DRAIN_STAT_FIELDS, STAGE_STAT_FIELDS,
)


def _slot_drain_stats(st, spec: WindowStageSpec, s_valid, act, kgf, cf,
                      wm_before, late0, cap0, defer_fires=False):
    """One live slot's DRAIN_STAT_FIELDS vector — element ops and tiny
    reductions over fields the fused body already materialized, so the
    telemetry-ON kernels add zero sort/scatter/gather passes (the
    op-budget ledger pins the OFF variants byte-identical).

    ``defer_fires`` zeroes the two fire-plane reductions (fire_lanes,
    fired_keys): in the CHAINED drain the per-slot CompactFires are
    stacked for the stage tail rather than consumed in the slot body,
    and reducing them inside the scan forces XLA to materialize the
    fire pack twice per slot (~25% on the chained body). The builder
    fills the columns after the scan with one vectorized pass over the
    stacked fires (_deferred_fire_columns) — same numbers, one read."""
    slide = jnp.int32(spec.win.slide_ticks)
    # clamp the pre-advance watermark so a fresh job's MIN sentinel
    # cannot overflow the int32 pane subtraction, and report the very
    # first advance (no meaningful baseline) as zero panes crossed
    wb = jnp.maximum(wm_before, st.watermark - jnp.int32(1 << 20))
    panes = jnp.maximum(
        jnp.int32(0), st.watermark // slide - wb // slide
    )
    panes = jnp.where(
        wm_before < jnp.int32(-(2 ** 30)), jnp.int32(0), panes
    )
    kg_max = (
        jnp.max(kgf) if kgf.shape[0] else jnp.zeros((), jnp.int32)
    )
    zero = jnp.zeros((), jnp.int32)
    return jnp.stack([
        jnp.sum(s_valid, dtype=jnp.int32),
        act,
        zero if defer_fires else jnp.sum(cf.lane_valid, dtype=jnp.int32),
        zero if defer_fires else jnp.sum(cf.counts, dtype=jnp.int32),
        st.dropped_late - late0,
        st.dropped_capacity - cap0,
        st.ovf_n,
        kg_max,
        panes,
    ])


def _deferred_fire_columns(ds_stack, cf_stack):
    """Fill the deferred fire_lanes / fired_keys columns of a [D, N]
    per-slot stats stack from the scan's STACKED CompactFires — one
    vectorized reduction per drain instead of one per slot inside the
    scan (see _slot_drain_stats defer_fires). Skip slots stacked zero
    fires, so their columns stay zero exactly as the inline path."""
    lv, cnt = cf_stack.lane_valid, cf_stack.counts
    fire_lanes = jnp.sum(
        lv, dtype=jnp.int32, axis=tuple(range(1, lv.ndim))
    )
    fired_keys = jnp.sum(
        cnt, dtype=jnp.int32, axis=tuple(range(1, cnt.ndim))
    )
    return jnp.concatenate([
        ds_stack[:, :2], fire_lanes[:, None], fired_keys[:, None],
        ds_stack[:, 4:],
    ], axis=1)


def build_window_resident_drain(ctx: MeshContext, spec: WindowStageSpec,
                                depth: int, insert: bool = True,
                                kg_fill: bool = False,
                                reduced: bool = False,
                                drain_stats: bool = False,
                                tiered: bool = False):
    """Device-resident ring-drain loop (pipeline.resident-loop, ISSUE
    12): ONE jitted dispatch consumes up to ``depth`` staged ring slots
    against donated state, running the PR 7 fused update+fire body per
    slot — steady state costs one host round trip per ring DRAIN instead
    of one per megastep.

    Lowering choice (the ISSUE allows ``lax.while_loop`` or a long-K
    scan with a read-only early-exit cond): a fixed-depth ``lax.scan``
    whose body is gated by ``lax.cond(i < count, live, skip)``. The scan
    stacks the per-slot fire payloads for free (the while_loop form
    needs a dynamic_update_slice per payload field per iteration — more
    ops under the PR 10 op-budget ledger and a worse scatter count), the
    carry threading is identical to the proven megastep_fired scan, and
    XLA's conditional executes only the taken branch, so slots past the
    write cursor cost the scalar predicate, not an update pass. ``count``
    is a TRACED int32 operand — one compile per (route, tier) serves
    every fill level, so the loop never recompiles as ring occupancy
    varies (the compile-signature ledger pins this).

    The host-side exit conditions (ring-empty, fire-buffer high water,
    monitoring cadence, checkpoint-cut request) all resolve to the
    ``count`` the executor passes: it caps the drain at whichever
    boundary comes first, and slots past the cut stay in the ring for
    the next drain — the exactly-once cut is the ring-drain boundary.

    Signature: ``drain(state, hi_0, lo_0, ticks_0, values_0, valid_0,
    ..., wmv, count)`` — ``depth`` staged batch 5-tuples (slots past
    ``count`` repeat an already-staged slot; the skip branch never reads
    them), wmv int32 [n_shards, depth] (sentinel past count), count
    int32 scalar. Returns ``(state', (ovf_n, activity, kg_fill),
    fires)`` with fires stacked [n_shards, depth] exactly like
    ``build_window_megastep_fired`` at K=depth, so the executor's lagged
    fire consumption and monitoring paths need no drain-specific
    variant. With ``drain_stats`` (observability.drain-stats) a fourth
    return element rides along: an int32 [n_shards, depth,
    len(DRAIN_STAT_FIELDS)] per-slot flight-recorder stack, consumed
    lagged with the fires; off, the kernel and its return contract are
    byte-identical to pre-telemetry (the op-budget ledger asserts it)."""
    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    D = int(depth)

    def shard_body(state, kg_start, kg_end, count, hi, lo, ts, values,
                   valid, wm, *rest):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg_res = rest[0] if tiered else None   # scan-invariant residency
        pend0 = jnp.zeros(spec.win.ring, bool)

        def sub(carry, xs):
            i, s_hi, s_lo, s_ts, s_vals, s_valid, s_wm = xs

            def live(op):
                st, pend = op
                wm_b = st.watermark
                late0, cap0 = st.dropped_late, st.dropped_capacity
                st, act, kgf = mask_update_shard(
                    st, spec, kg_start, kg_end, s_hi, s_lo, s_ts,
                    s_vals, s_valid, s_wm, maxp, insert=insert,
                    kg_fill=kg_fill, clear_rows=pend, kg_res=kg_res,
                )
                st, pend, cf = wk.advance_and_fire_resident(
                    st, spec.win, spec.red, s_wm, reduced=reduced
                )
                if drain_stats:
                    ds = _slot_drain_stats(st, spec, s_valid, act, kgf,
                                           cf, wm_b, late0, cap0)
                    return (st, pend), (act, kgf, cf, ds)
                return (st, pend), (act, kgf, cf)

            def skip(op):
                kgf = jnp.zeros(maxp if kg_fill else 0, jnp.int32)
                ys = (jnp.zeros((), jnp.int32), kgf,
                      _zero_slot_fires(spec, reduced))
                if drain_stats:
                    ys += (jnp.zeros(len(DRAIN_STAT_FIELDS), jnp.int32),)
                return op, ys

            return jax.lax.cond(i < count, live, skip, carry)

        (state, pend), ys = jax.lax.scan(
            sub, (state, pend0),
            (jnp.arange(D, dtype=jnp.int32), hi, lo, ts, values, valid,
             wm[0]),
        )
        acts, kgfs, fires = ys[:3]
        state = wk.apply_pending_purge(state, spec.win, spec.red, pend)
        ovf_n = state.ovf_n
        act = jnp.sum(acts)
        kgf = kgfs.sum(axis=0) if kg_fill else jnp.zeros(0, jnp.int32)
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        out = (
            pack(state), ovf_n[None], act[None], kgf[None], pack(fires),
        )
        if drain_stats:
            out += (ys[3][None],)      # [1, D, N] flight-recorder stack
        return out

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(),                       # count: replicated scalar cursor
            P(), P(), P(), P(), P(),   # [D, B] batch stacks, replicated
            P(SHARD_AXIS),             # wmv [n_shards, D]
        ) + ((P(),) if tiered else ()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P(SHARD_AXIS))
        + ((P(SHARD_AXIS),) if drain_stats else ()),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def drain(state, *flat):
        if tiered:
            *batches, wmv, count, kg_res = flat
            tail = (wmv, kg_res)
        else:
            *batches, wmv, count = flat
            tail = (wmv,)
        stacks = _fused_batch_stack(D, batches)
        res = sharded(
            state, starts, ends, jnp.asarray(count, jnp.int32),
            *stacks, *tail,
        )
        st, ovf_n, act, kgf, fires = res[:5]
        if drain_stats:
            return st, (ovf_n, act, kgf), fires, res[5]
        return st, (ovf_n, act, kgf), fires

    drain.k_steps = D
    drain.ring_depth = D
    drain.resident_drain = True
    drain.fused_fire = True
    drain.fused_fire_reduced = reduced
    drain.drain_stats = drain_stats
    drain.tiered = tiered
    return drain


def build_window_resident_drain_exchange(ctx: MeshContext,
                                         spec: WindowStageSpec,
                                         batch_per_device: int,
                                         depth: int,
                                         capacity_factor: float = 2.0,
                                         insert: bool = True,
                                         kg_fill: bool = False,
                                         reduced: bool = False,
                                         drain_stats: bool = False,
                                         tiered: bool = False):
    """Exchange-route resident drain: the ring-drain analog of
    build_window_megastep_fired_exchange — each live slot runs the
    shared ``exchange_update_shard`` body (bucket + all_to_all + masked
    update) followed by the gated resident advance, under the same
    ``lax.cond(i < count)`` gate as the mask-route drain, so neither the
    shuffle nor the fire semantics can diverge between routes or fill
    levels. Batch stacks arrive [D, B] SPLIT over devices on the batch
    (second) axis; ``count`` is replicated. Note the all_to_all runs
    only in the live branch: every device takes the same branch because
    ``count`` is replicated, so the collective stays globally
    consistent."""
    import dataclasses as _dc

    from flink_tpu.parallel.exchange import bucket_capacity

    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    n = ctx.n_shards
    cap = bucket_capacity(batch_per_device, n, capacity_factor)
    D = int(depth)

    def shard_body(state, kg_start, kg_end, count, hi, lo, ts, values,
                   valid, wm, *rest):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg_res = rest[0] if tiered else None
        pend0 = jnp.zeros(spec.win.ring, bool)

        def sub(carry, xs):
            i, s_hi, s_lo, s_ts, s_vals, s_valid, s_wm = xs

            def live(op):
                st, pend = op
                wm_b = st.watermark
                late0, cap0 = st.dropped_late, st.dropped_capacity
                st, act = exchange_update_shard(
                    st, spec, kg_start, kg_end, s_hi, s_lo, s_ts,
                    s_vals, s_valid, n, maxp, cap, insert=insert,
                    clear_rows=pend, kg_res=kg_res,
                )
                st = _dc.replace(
                    st, watermark=jnp.maximum(st.watermark, s_wm)
                )
                if kg_fill:
                    kg_local = assign_to_key_group(
                        route_hash(s_hi, s_lo, jnp), maxp, jnp
                    )
                    kgf = wk.kg_batch_fill(kg_local, s_valid, maxp)
                else:
                    kgf = jnp.zeros(0, jnp.int32)
                st, pend, cf = wk.advance_and_fire_resident(
                    st, spec.win, spec.red, s_wm, reduced=reduced
                )
                if drain_stats:
                    ds = _slot_drain_stats(st, spec, s_valid, act, kgf,
                                           cf, wm_b, late0, cap0)
                    return (st, pend), (act, kgf, cf, ds)
                return (st, pend), (act, kgf, cf)

            def skip(op):
                kgf = jnp.zeros(maxp if kg_fill else 0, jnp.int32)
                ys = (jnp.zeros((), jnp.int32), kgf,
                      _zero_slot_fires(spec, reduced))
                if drain_stats:
                    ys += (jnp.zeros(len(DRAIN_STAT_FIELDS), jnp.int32),)
                return op, ys

            return jax.lax.cond(i < count, live, skip, carry)

        (state, pend), ys = jax.lax.scan(
            sub, (state, pend0),
            (jnp.arange(D, dtype=jnp.int32), hi, lo, ts, values, valid,
             wm[0]),
        )
        acts, kgfs, fires = ys[:3]
        state = wk.apply_pending_purge(state, spec.win, spec.red, pend)
        ovf_n = state.ovf_n
        act = jnp.sum(acts)
        kgf = kgfs.sum(axis=0) if kg_fill else jnp.zeros(0, jnp.int32)
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        out = (
            pack(state), ovf_n[None], act[None], kgf[None], pack(fires),
        )
        if drain_stats:
            out += (ys[3][None],)
        return out

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(),                       # count: replicated scalar cursor
            # [D, B] stacks SPLIT over devices on the batch axis
            P(None, SHARD_AXIS), P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(SHARD_AXIS),
        ) + ((P(),) if tiered else ()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P(SHARD_AXIS))
        + ((P(SHARD_AXIS),) if drain_stats else ()),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def drain(state, *flat):
        if tiered:
            *batches, wmv, count, kg_res = flat
            tail = (wmv, kg_res)
        else:
            *batches, wmv, count = flat
            tail = (wmv,)
        stacks = _fused_batch_stack(D, batches)
        res = sharded(
            state, starts, ends, jnp.asarray(count, jnp.int32),
            *stacks, *tail,
        )
        st, ovf_n, act, kgf, fires = res[:5]
        if drain_stats:
            return st, (ovf_n, act, kgf), fires, res[5]
        return st, (ovf_n, act, kgf), fires

    drain.k_steps = D
    drain.ring_depth = D
    drain.resident_drain = True
    drain.fused_fire = True
    drain.fused_fire_reduced = reduced
    drain.recv_lanes = n * cap
    drain.bucket_cap = cap
    drain.drain_stats = drain_stats
    drain.tiered = tiered
    return drain


def build_window_sharded_drain(ctx: MeshContext, spec: WindowStageSpec,
                               depth: int, insert: bool = True,
                               kg_fill: bool = False,
                               reduced: bool = False,
                               drain_stats: bool = False,
                               tiered: bool = False):
    """Data-parallel resident drain (pipeline.data-parallel, ISSUE 13):
    the ring-drain scan lowered shard-LOCALLY — the ingest side already
    partitioned each batch by owning key-group slice and published the
    per-shard lane slices into the owning shard's ring slot, so the
    keyed body here is mask_update_shard over lanes that are ALL local
    by construction. Zero cross-chip collectives on the hot path: no
    all_to_all (records arrived pre-routed), no replicated full-batch
    broadcast (each chip touches only its own cap lanes, O(cap) work
    per chip instead of the mask route's O(B)).

    The per-shard independence is what buys the third delta: ``counts``
    is an int32 [n_shards] VECTOR under P(SHARD_AXIS), so each shard
    gates its scan on its OWN fill level. The exchange drain must keep
    ``count`` replicated (its all_to_all would deadlock if shards took
    different branches); with no collective in this body, divergent
    counts are safe — one slow shard's shallow ring never forces the
    others to under-drain.

    Signature: ``drain(state, hi_0, lo_0, ticks_0, values_0, valid_0,
    ..., wmv, counts)`` — ``depth`` staged 5-tuples of [n_shards, cap]
    arrays split over devices on the LEADING axis (slots past a shard's
    count repeat stale lanes; the skip branch never reads them), wmv
    int32 [n_shards, depth], counts int32 [n_shards]. Returns the same
    ``(state', (ovf_n, activity, kg_fill), fires)`` contract as
    build_window_resident_drain, fires stacked [n_shards, depth] — the
    executor's lagged consume_fires merges the per-shard packs host-
    side unchanged."""
    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    D = int(depth)

    def shard_body(state, kg_start, kg_end, counts, hi, lo, ts, values,
                   valid, wm, *rest):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg_res = rest[0] if tiered else None
        count = counts[0]          # this shard's OWN fill level
        pend0 = jnp.zeros(spec.win.ring, bool)

        def sub(carry, xs):
            i, s_hi, s_lo, s_ts, s_vals, s_valid, s_wm = xs

            def live(op):
                st, pend = op
                wm_b = st.watermark
                late0, cap0 = st.dropped_late, st.dropped_capacity
                st, act, kgf = mask_update_shard(
                    st, spec, kg_start, kg_end, s_hi, s_lo, s_ts,
                    s_vals, s_valid, s_wm, maxp, insert=insert,
                    kg_fill=kg_fill, clear_rows=pend, kg_res=kg_res,
                )
                st, pend, cf = wk.advance_and_fire_resident(
                    st, spec.win, spec.red, s_wm, reduced=reduced
                )
                if drain_stats:
                    ds = _slot_drain_stats(st, spec, s_valid, act, kgf,
                                           cf, wm_b, late0, cap0)
                    return (st, pend), (act, kgf, cf, ds)
                return (st, pend), (act, kgf, cf)

            def skip(op):
                kgf = jnp.zeros(maxp if kg_fill else 0, jnp.int32)
                ys = (jnp.zeros((), jnp.int32), kgf,
                      _zero_slot_fires(spec, reduced))
                if drain_stats:
                    ys += (jnp.zeros(len(DRAIN_STAT_FIELDS), jnp.int32),)
                return op, ys

            return jax.lax.cond(i < count, live, skip, carry)

        (state, pend), ys = jax.lax.scan(
            sub, (state, pend0),
            # [D, 1, cap] per-shard batch stacks squeeze the split axis
            (jnp.arange(D, dtype=jnp.int32), hi[:, 0], lo[:, 0],
             ts[:, 0], values[:, 0], valid[:, 0], wm[0]),
        )
        acts, kgfs, fires = ys[:3]
        state = wk.apply_pending_purge(state, spec.win, spec.red, pend)
        ovf_n = state.ovf_n
        act = jnp.sum(acts)
        kgf = kgfs.sum(axis=0) if kg_fill else jnp.zeros(0, jnp.int32)
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        out = (
            pack(state), ovf_n[None], act[None], kgf[None], pack(fires),
        )
        if drain_stats:
            out += (ys[3][None],)
        return out

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(SHARD_AXIS),             # counts: per-shard fill levels
            # [D, n_shards, cap] stacks SPLIT on the shard axis: each
            # chip receives only its own pre-routed lane slices
            P(None, SHARD_AXIS), P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(SHARD_AXIS),             # wmv [n_shards, D]
        ) + ((P(),) if tiered else ()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P(SHARD_AXIS))
        + ((P(SHARD_AXIS),) if drain_stats else ()),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def drain(state, *flat):
        if tiered:
            *batches, wmv, counts, kg_res = flat
            tail = (wmv, kg_res)
        else:
            *batches, wmv, counts = flat
            tail = (wmv,)
        stacks = _fused_batch_stack(D, batches)
        res = sharded(
            state, starts, ends, jnp.asarray(counts, jnp.int32),
            *stacks, *tail,
        )
        st, ovf_n, act, kgf, fires = res[:5]
        if drain_stats:
            return st, (ovf_n, act, kgf), fires, res[5]
        return st, (ovf_n, act, kgf), fires

    drain.k_steps = D
    drain.ring_depth = D
    drain.resident_drain = True
    drain.sharded_drain = True
    drain.fused_fire = True
    drain.fused_fire_reduced = reduced
    drain.drain_stats = drain_stats
    drain.tiered = tiered
    return drain


def _zero_fires_stack(spec: WindowStageSpec, reduced: bool, depth: int):
    """[depth]-stacked zero fire payload — the while-drain's accumulation
    buffer. Row ``i`` is written by dynamic_update_index_in_dim when slot
    ``i`` retires; unconsumed rows stay bit-identical to the scan drain's
    skip-branch zeros, so the executor's lagged consume_fires treats both
    lowering forms identically."""
    z = _zero_slot_fires(spec, reduced)
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros((depth,) + x.shape, x.dtype), z
    )


def _while_drain_limit(cursor, base, staged, max_slots):
    """The live trip bound of one while-drain dispatch: slots the publish
    cursor has committed past this drain's base, clamped to what the host
    actually staged into the operand stacks and the configured per-
    dispatch bound. Re-evaluated in the loop CONDITION each iteration so
    a cursor store landing mid-drain (the DeviceBatchRing's HBM cursor
    slot, donated alongside the payloads on an aliasing runtime) extends
    the trip count of the dispatch already in flight."""
    return jnp.minimum(
        jnp.minimum(
            jnp.maximum(cursor - base, jnp.int32(0)), staged
        ),
        jnp.int32(max_slots),
    )


def build_window_while_drain(ctx: MeshContext, spec: WindowStageSpec,
                             max_slots: int, insert: bool = True,
                             kg_fill: bool = False,
                             reduced: bool = False,
                             drain_stats: bool = False,
                             tiered: bool = False):
    """Early-exit live ring drain (pipeline.resident-loop=while, ISSUE
    20): the resident drain lowered as a ``lax.while_loop`` whose
    condition re-reads a device-visible PUBLISH CURSOR instead of a
    host-frozen count — a batch the ingest thread commits while the
    drain is running is retired *inside the same dispatch*, so the
    structural one-dispatch-per-publish-burst cost of the count-gated
    scan disappears under sustained ingest.

    Contract vs the scan drain (build_window_resident_drain):

    * the ``count`` operand is replaced by ``(cursor, base, staged)`` —
      cursor int32 [1] is the ring's device cursor slot (absolute
      publish seq, stored by the ingest thread after each commit; the
      executor donates it so an aliasing runtime lets the in-flight
      loop observe the store), base is the drain group's first ring
      seq, staged is how many slot payloads the host bound into THIS
      dispatch's operand stacks. The trip bound is
      ``clamp(cursor - base, 0, min(staged, max_slots))``: on a
      runtime without host->HBM stores into dispatched buffers the
      cursor term freezes at its dispatch-time value and the kernel
      degrades exactly to the scan drain's count gating — never reads
      a slot the host didn't stage.
    * ``max_slots`` (pipeline.while-drain.max-slots) bounds ONE
      dispatch, so the exactly-once cut, the watchdog deadline
      (``Watchdog.arm`` scale = the bound) and the flight-recorder
      payload ([n_shards, max_slots, N] with zeroed dead rows) stay
      well-defined however long the publisher keeps the cursor ahead.
    * a fourth return element, ``consumed`` int32 [1], reports the live
      slot count this dispatch actually retired — the host's release /
      telemetry boundary (it matches the cursor slot's shape+dtype, so
      the donated cursor buffer is reused for it).

    Fires cannot ride a scan stack here: each retired slot's payload is
    written into a preallocated [max_slots, ...] buffer with one
    dynamic_update_slice per field per iteration — a deliberately
    different op profile from the scan drain, pinned by its own
    ``step.while_drain.*`` op-budget/signature ledger entries."""
    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    D = int(max_slots)
    n_ds = len(DRAIN_STAT_FIELDS)

    def shard_body(state, kg_start, kg_end, cursor, base, staged, hi,
                   lo, ts, values, valid, wm, *rest):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg_res = rest[0] if tiered else None
        wm_l = wm[0]                       # [D] per-shard watermarks
        pend0 = jnp.zeros(spec.win.ring, bool)
        kgf0 = jnp.zeros(maxp if kg_fill else 0, jnp.int32)
        fires0 = _zero_fires_stack(spec, reduced, D)
        ds0 = jnp.zeros((D, n_ds), jnp.int32)

        def cond(carry):
            i, cur = carry[0], carry[1]
            # the live re-read: cur is carried so the bound check sits
            # INSIDE the loop, not hoisted as a dispatch-time constant
            return i < _while_drain_limit(cur[0], base, staged, D)

        def body(carry):
            i, cur, st, pend, act, kgf, fires, ds = carry
            pick = lambda a: jax.lax.dynamic_index_in_dim(
                a, i, keepdims=False
            )
            s_hi, s_lo, s_ts = pick(hi), pick(lo), pick(ts)
            s_vals, s_valid, s_wm = pick(values), pick(valid), pick(wm_l)
            wm_b = st.watermark
            late0, cap0 = st.dropped_late, st.dropped_capacity
            st, a, kg = mask_update_shard(
                st, spec, kg_start, kg_end, s_hi, s_lo, s_ts, s_vals,
                s_valid, s_wm, maxp, insert=insert, kg_fill=kg_fill,
                clear_rows=pend, kg_res=kg_res,
            )
            st, pend, cf = wk.advance_and_fire_resident(
                st, spec.win, spec.red, s_wm, reduced=reduced
            )
            fires = jax.tree_util.tree_map(
                lambda buf, v: jax.lax.dynamic_update_index_in_dim(
                    buf, v, i, 0
                ),
                fires, cf,
            )
            if drain_stats:
                row = _slot_drain_stats(st, spec, s_valid, a, kg, cf,
                                        wm_b, late0, cap0)
                ds = jax.lax.dynamic_update_index_in_dim(ds, row, i, 0)
            return (i + 1, cur, st, pend, act + a,
                    kgf + kg if kg_fill else kgf, fires, ds)

        i_fin, _cur, state, pend, act, kgf, fires, ds = \
            jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), cursor, state, pend0,
                 jnp.zeros((), jnp.int32), kgf0, fires0, ds0),
            )
        state = wk.apply_pending_purge(state, spec.win, spec.red, pend)
        ovf_n = state.ovf_n
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        out = (
            pack(state), ovf_n[None], act[None], kgf[None], pack(fires),
            i_fin[None],               # consumed: live retired-slot count
        )
        if drain_stats:
            out += (ds[None],)         # [1, max_slots, N] recorder stack
        return out

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(), P(), P(),             # cursor [1], base, staged: all
            #                            replicated so every shard takes
            #                            the same trip count
            P(), P(), P(), P(), P(),   # [D, B] batch stacks, replicated
            P(SHARD_AXIS),             # wmv [n_shards, D]
        ) + ((P(),) if tiered else ()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P(SHARD_AXIS), P())
        + ((P(SHARD_AXIS),) if drain_stats else ()),
        check_vma=False,
    )

    # donate the state AND the cursor slot: consumed [1] int32 reuses the
    # cursor's buffer, and on an aliasing runtime the donation is what
    # lets the ingest thread's commit store land in the dispatched slot
    @partial(jax.jit, donate_argnums=(0, 5 * D + 2))
    def drain(state, *flat):
        if tiered:
            *batches, wmv, cursor, base, staged, kg_res = flat
            tail = (kg_res,)
        else:
            *batches, wmv, cursor, base, staged = flat
            tail = ()
        stacks = _fused_batch_stack(D, batches)
        res = sharded(
            state, starts, ends, jnp.asarray(cursor, jnp.int32),
            jnp.asarray(base, jnp.int32), jnp.asarray(staged, jnp.int32),
            *stacks, wmv, *tail,
        )
        st, ovf_n, act, kgf, fires, consumed = res[:6]
        if drain_stats:
            return st, (ovf_n, act, kgf), fires, consumed, res[6]
        return st, (ovf_n, act, kgf), fires, consumed

    drain.k_steps = D
    drain.ring_depth = D
    drain.max_slots = D
    drain.resident_drain = True
    drain.while_drain = True
    drain.fused_fire = True
    drain.fused_fire_reduced = reduced
    drain.drain_stats = drain_stats
    drain.tiered = tiered
    return drain


def build_window_while_drain_sharded(ctx: MeshContext,
                                     spec: WindowStageSpec,
                                     max_slots: int, insert: bool = True,
                                     kg_fill: bool = False,
                                     reduced: bool = False,
                                     drain_stats: bool = False,
                                     tiered: bool = False):
    """Data-parallel early-exit drain: build_window_while_drain lowered
    shard-LOCALLY over pre-routed per-shard lane slices (the
    build_window_sharded_drain layout). ``cursor``/``base``/``staged``
    are int32 [n_shards] VECTORS under P(SHARD_AXIS): each shard's
    while_loop trips on its OWN publish cursor, and — with zero
    collectives in the keyed body — divergent trip counts are safe, so
    one shard's quiet ring never under-drains a hot one mid-dispatch.
    ``consumed`` returns [n_shards]: each shard's live retired count,
    the per-shard release boundary (and the donated cursor vector's
    buffer)."""
    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    D = int(max_slots)
    n_ds = len(DRAIN_STAT_FIELDS)

    def shard_body(state, kg_start, kg_end, cursor, base, staged, hi,
                   lo, ts, values, valid, wm, *rest):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg_res = rest[0] if tiered else None
        s_base, s_staged = base[0], staged[0]
        # [D, 1, cap] per-shard batch stacks squeeze the split axis
        b_hi, b_lo, b_ts = hi[:, 0], lo[:, 0], ts[:, 0]
        b_vals, b_valid = values[:, 0], valid[:, 0]
        wm_l = wm[0]
        pend0 = jnp.zeros(spec.win.ring, bool)
        kgf0 = jnp.zeros(maxp if kg_fill else 0, jnp.int32)
        fires0 = _zero_fires_stack(spec, reduced, D)
        ds0 = jnp.zeros((D, n_ds), jnp.int32)

        def cond(carry):
            i, cur = carry[0], carry[1]
            return i < _while_drain_limit(cur[0], s_base, s_staged, D)

        def body(carry):
            i, cur, st, pend, act, kgf, fires, ds = carry
            pick = lambda a: jax.lax.dynamic_index_in_dim(
                a, i, keepdims=False
            )
            s_hi, s_lo, s_ts = pick(b_hi), pick(b_lo), pick(b_ts)
            s_vals, s_valid = pick(b_vals), pick(b_valid)
            s_wm = pick(wm_l)
            wm_b = st.watermark
            late0, cap0 = st.dropped_late, st.dropped_capacity
            st, a, kg = mask_update_shard(
                st, spec, kg_start, kg_end, s_hi, s_lo, s_ts, s_vals,
                s_valid, s_wm, maxp, insert=insert, kg_fill=kg_fill,
                clear_rows=pend, kg_res=kg_res,
            )
            st, pend, cf = wk.advance_and_fire_resident(
                st, spec.win, spec.red, s_wm, reduced=reduced
            )
            fires = jax.tree_util.tree_map(
                lambda buf, v: jax.lax.dynamic_update_index_in_dim(
                    buf, v, i, 0
                ),
                fires, cf,
            )
            if drain_stats:
                row = _slot_drain_stats(st, spec, s_valid, a, kg, cf,
                                        wm_b, late0, cap0)
                ds = jax.lax.dynamic_update_index_in_dim(ds, row, i, 0)
            return (i + 1, cur, st, pend, act + a,
                    kgf + kg if kg_fill else kgf, fires, ds)

        i_fin, _cur, state, pend, act, kgf, fires, ds = \
            jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), cursor, state, pend0,
                 jnp.zeros((), jnp.int32), kgf0, fires0, ds0),
            )
        state = wk.apply_pending_purge(state, spec.win, spec.red, pend)
        ovf_n = state.ovf_n
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        out = (
            pack(state), ovf_n[None], act[None], kgf[None], pack(fires),
            i_fin[None],
        )
        if drain_stats:
            out += (ds[None],)
        return out

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            # per-shard cursor/base/staged vectors: each shard trips on
            # its OWN publish frontier (no collectives in the body, so
            # divergent trip counts cannot deadlock anything)
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            # [D, n_shards, cap] stacks SPLIT on the shard axis
            P(None, SHARD_AXIS), P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(SHARD_AXIS),             # wmv [n_shards, D]
        ) + ((P(),) if tiered else ()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS))
        + ((P(SHARD_AXIS),) if drain_stats else ()),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0, 5 * D + 2))
    def drain(state, *flat):
        if tiered:
            *batches, wmv, cursor, base, staged, kg_res = flat
            tail = (kg_res,)
        else:
            *batches, wmv, cursor, base, staged = flat
            tail = ()
        stacks = _fused_batch_stack(D, batches)
        res = sharded(
            state, starts, ends, jnp.asarray(cursor, jnp.int32),
            jnp.asarray(base, jnp.int32), jnp.asarray(staged, jnp.int32),
            *stacks, wmv, *tail,
        )
        st, ovf_n, act, kgf, fires, consumed = res[:6]
        if drain_stats:
            return st, (ovf_n, act, kgf), fires, consumed, res[6]
        return st, (ovf_n, act, kgf), fires, consumed

    drain.k_steps = D
    drain.ring_depth = D
    drain.max_slots = D
    drain.resident_drain = True
    drain.sharded_drain = True
    drain.while_drain = True
    drain.fused_fire = True
    drain.fused_fire_reduced = reduced
    drain.drain_stats = drain_stats
    drain.tiered = tiered
    return drain


def build_window_dcn_resident_drain(ctx: MeshContext,
                                    spec: WindowStageSpec,
                                    batch_per_device: int,
                                    depth: int,
                                    capacity_factor: float = 2.0,
                                    insert: bool = True,
                                    drain_stats: bool = False):
    """Per-host DCN-resident drain (ISSUE 20 tentpole b): the lockstep
    DCN step (runtime/dcn.py DCNWindowRunner._build_step) promoted to a
    count-gated multi-slot drain — each lockstep ROUND retires up to
    ``depth`` locally-polled batches in ONE dispatch, with the keyed
    all_to_all still running per slot and the cross-host control plane
    (global watermark / done / fire backlog pmin-pmax) evaluated at the
    DRAIN BOUNDARY.

    The trip count is agreed ON DEVICE: every host passes its own local
    fill in ``fills`` and the kernel takes ``pmax`` over the shard axis
    before the slot loop, so all hosts enter the same number of
    all_to_all rounds (a host with a shallower ring pads empty-valid
    slots) without any host-side count exchange — the collective fabric
    that moves the records also synchronizes the drain shape.

    Signature: ``drain(state, hi, lo, ts, values, valid, wm, done,
    fills)`` with [depth, B] batch stacks SPLIT over the global mesh on
    the lane axis, wm int32 [depth, n_shards] split on the shard axis,
    done/fills int32 [n_shards]. Returns ``(state', fires, stop,
    drained)``: fires stacked [n_shards, depth] for the runner's
    per-slot ``_emit_local``, stop the lockstep termination conjunction
    (gdone and no fire backlog in any live slot), drained the agreed
    slot count — the host scales the NEXT boundary's peer-exchange
    frame deadline by it. With ``drain_stats`` a fifth element rides
    along: the [n_shards, depth, N] per-slot recorder stack."""
    from flink_tpu.parallel.exchange import bucket_capacity

    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    n = ctx.n_shards
    cap = bucket_capacity(batch_per_device, n, capacity_factor)
    D = int(depth)
    F = spec.win.fires_per_step

    def shard_body(state, kg_start, kg_end, fills, done, hi, lo, ts,
                   values, valid, wm):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        # the drain shape is a GLOBAL agreement: deepest local ring
        # wins, shallower hosts run empty-valid pad slots — replicated
        # by construction, so every host's all_to_all count matches
        count = jax.lax.pmax(fills[0], SHARD_AXIS)
        gdone = jax.lax.pmin(done[0], SHARD_AXIS)
        wm_l = wm[:, 0]                    # [D] this shard's wm column

        def sub(carry, xs):
            i, s_hi, s_lo, s_ts, s_vals, s_valid, s_wm = xs

            def live(st):
                # per-slot global low watermark: decisions ride the
                # same fabric as the records (lockstep invariant)
                gwm = jax.lax.pmin(s_wm, SHARD_AXIS)
                wm_b = st.watermark
                late0, cap0 = st.dropped_late, st.dropped_capacity
                st, act = exchange_update_shard(
                    st, spec, kg_start, kg_end, s_hi, s_lo, s_ts,
                    s_vals, s_valid, n, maxp, cap, insert=insert,
                )
                st, fr = wk.advance_and_fire(st, spec.win, spec.red,
                                             gwm)
                cf = wk.compact_fires(st.table, fr)
                # fire backlog: full on-time lanes mean more window
                # ends may be due — the ensemble must keep cycling
                pending = (
                    jnp.sum(fr.lane_valid[:F], dtype=jnp.int32)
                    >= jnp.int32(F)
                ).astype(jnp.int32)
                if drain_stats:
                    kgf = jnp.zeros(0, jnp.int32)
                    ds = _slot_drain_stats(st, spec, s_valid, act, kgf,
                                           cf, wm_b, late0, cap0)
                    return st, (cf, pending, ds)
                return st, (cf, pending)

            def skip(st):
                ys = (_zero_slot_fires(spec, False),
                      jnp.zeros((), jnp.int32))
                if drain_stats:
                    ys += (jnp.zeros(len(DRAIN_STAT_FIELDS), jnp.int32),)
                return st, ys

            return jax.lax.cond(i < count, live, skip, carry)

        state, ys = jax.lax.scan(
            sub, state,
            (jnp.arange(D, dtype=jnp.int32), hi, lo, ts, values, valid,
             wm_l),
        )
        cfs, pendings = ys[0], ys[1]
        # any live slot with a full fire-lane set keeps the ensemble
        # stepping (conservative: terminates once fires run dry)
        gpending = jax.lax.pmax(jnp.max(pendings), SHARD_AXIS)
        stop = gdone * (1 - gpending)
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        out = (pack(state), pack(cfs), stop, count)
        if drain_stats:
            out += (ys[2][None],)
        return out

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(SHARD_AXIS),             # fills: per-host ring occupancy
            P(SHARD_AXIS),             # done flags
            # [D, B] batch stacks SPLIT over the global mesh on the
            # lane axis: each host's records sit on its local devices
            P(None, SHARD_AXIS), P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(None, SHARD_AXIS),       # wm [D, n_shards]
        ),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P())
        + ((P(SHARD_AXIS),) if drain_stats else ()),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def drain(state, hi, lo, ts, values, valid, wm, done, fills):
        res = sharded(
            state, starts, ends, jnp.asarray(fills, jnp.int32),
            jnp.asarray(done, jnp.int32), hi, lo, ts, values, valid,
            wm,
        )
        if drain_stats:
            return res[0], res[1], res[2], res[3], res[4]
        return res[:4]

    drain.k_steps = D
    drain.ring_depth = D
    drain.resident_drain = True
    drain.dcn_resident = True
    drain.recv_lanes = n * cap
    drain.bucket_cap = cap
    drain.drain_stats = drain_stats
    return drain


def _chain_fires_to_lanes(cf, n_lanes: int):
    """Re-key CompactFires into the NEXT stage's input lanes (the
    inter-stage edge of the chained drain, ISSUE 16): every fired
    (key, window) pair becomes one record keyed by the SAME key with
    event time ``window_end - 1`` — the newest instant the fired window
    covers, so a multi-level rollup lands each upstream result in
    exactly the downstream pane its window closed in (the reference's
    re-keyed DataStream between two WindowOperators).

    Accepts one slot's fires ([F, C] planes) or a whole drain's STACKED
    fires ([D, F, C]): leading axes flatten into a single plane list,
    so one pass packs an entire drain's upstream output — the shape the
    per-drain stage tail (_chained_stage_tail) feeds it.

    Compaction exploits that CompactFires lanes are already PREFIX-
    packed per plane (live lanes are the first ``counts[f]`` of plane
    ``f``), so the edge never touches the payload wholesale: a cumsum
    over the per-plane counts gives plane offsets, a searchsorted over
    those offsets maps each of the ``n_lanes`` output slots to its
    (plane, lane) source, and three O(E) gathers pull the rows — no
    sort, no scatter, nothing proportional to F*C, so the edge adds
    nothing to the op-budget ledger's scatter/sort counts and stays
    cheap at large capacities. Lanes beyond ``n_lanes`` (an over-full
    edge) are counted in ``dropped`` so the executor's strict-capacity
    accounting sees them; identity re-keying keeps every fired key in
    its owning shard's key-group range, so the packed lanes feed the
    local next-stage update with ZERO collectives."""
    C = int(cf.key_hi.shape[-1])
    Pn = 1
    for d in cf.counts.shape:
        Pn *= int(d)
    counts = cf.counts.reshape(Pn)
    lane_valid = cf.lane_valid.reshape(Pn)
    ends = cf.window_end_ticks.reshape(Pn)
    key_hi = cf.key_hi.reshape(Pn, C)
    key_lo = cf.key_lo.reshape(Pn, C)
    out_shape = tuple(cf.values.shape[cf.counts.ndim + 1:])
    values = cf.values.reshape((Pn, C) + out_shape)
    E = int(n_lanes)
    live_counts = jnp.where(
        lane_valid, jnp.minimum(counts, jnp.int32(C)), jnp.int32(0)
    )
    offs = jnp.cumsum(live_counts)
    total = offs[-1]
    starts = offs - live_counts
    ar = jnp.arange(E, dtype=jnp.int32)
    f_sel = jnp.clip(jnp.searchsorted(offs, ar + 1), 0, Pn - 1)
    idx = jnp.clip(ar - starts[f_sel], 0, C - 1)
    ok = ar < total
    hi = jnp.where(ok, key_hi[f_sel, idx], jnp.uint32(0))
    lo = jnp.where(ok, key_lo[f_sel, idx], jnp.uint32(0))
    ts = jnp.where(ok, ends[f_sel] - jnp.int32(1), jnp.int32(0))
    okv = ok.reshape((E,) + (1,) * len(out_shape))
    vals = jnp.where(okv, values[f_sel, idx], jnp.zeros((), values.dtype))
    dropped = jnp.maximum(total - jnp.int32(E), 0)
    # ``total`` is the edge DEMAND (upstream fire lanes offered,
    # pre-clamp) — the stage flight recorder reports it against the
    # exchange-lanes budget so a near-overflow edge is visible before
    # it drops (ISSUE 17)
    return hi, lo, ts, vals, ok, dropped, total


def _chain_stage_watermark(up_wm, up_state, up_spec: WindowStageSpec):
    """Downstream watermark for the stage fed by ``up_state``'s fires.

    The upstream stage has fired panes through ``fired_through``; every
    FUTURE fire comes from a pane > fired_through, whose re-keyed record
    carries ts = (pane + 1) * slide - 1 >= (fired_through + 2) * slide
    - 1. Capping the downstream watermark at that horizon minus one
    guarantees no inter-stage record is ever late at the next stage —
    the stage tail inserts the whole drain's edge records BEFORE its
    single advance, and the cap is monotone in ``fired_through``, so
    records arriving in a LATER drain also beat this drain's cap. The
    outer min keeps the job watermark contract: a downstream window
    never closes past what the source watermark allows."""
    slide = int(up_spec.win.slide_ticks)
    # fired_through jumps to the WATERMARK pane once the upstream
    # backlog clears (end-of-stream flush: ~2^31/slide), so the
    # horizon multiply must clamp first or it wraps int32 negative and
    # pins the downstream watermark below the final windows forever
    ft_cap = (2**31 - 4) // slide - 2
    ft = jnp.clip(up_state.fired_through, jnp.int32(-1), jnp.int32(ft_cap))
    horizon = (ft + 2) * jnp.int32(slide) - 2
    return jnp.minimum(up_wm, horizon)


def _chained_slot_body(stage0, spec0, kg_start, kg_end, maxp, s_hi, s_lo,
                       s_ts, s_vals, s_valid, s_wm, insert, kg_fill,
                       drain_stats=False):
    """One live slot of the chained drain's stage-0 scan: consume the
    staged batch exactly like the single-stage resident body and emit
    this slot's CompactFires for the scan to stack. Downstream stages
    deliberately do NOT run here — they run ONCE per drain over the
    stacked fires (_chained_stage_tail), which is the chained drain's
    whole cost model. With ``drain_stats`` the slot also emits its
    DRAIN_STAT_FIELDS vector — the stage-0 half of the stage-aware
    flight recorder (ISSUE 17), identical to the single-stage payload."""
    st, pend = stage0
    wm_b = st.watermark
    late0, cap0 = st.dropped_late, st.dropped_capacity
    st, act, kgf = mask_update_shard(
        st, spec0, kg_start, kg_end, s_hi, s_lo, s_ts, s_vals,
        s_valid, s_wm, maxp, insert=insert, kg_fill=kg_fill,
        clear_rows=pend,
    )
    st, pend, cf = wk.advance_and_fire_resident(
        st, spec0.win, spec0.red, s_wm
    )
    if drain_stats:
        ds = _slot_drain_stats(st, spec0, s_valid, act, kgf, cf,
                               wm_b, late0, cap0, defer_fires=True)
        return (st, pend), (act, kgf, cf, ds)
    return (st, pend), (act, kgf, cf)


def _chained_stage_tail(down_states, specs, st0, cf_stack, wm_last,
                        kg_start, kg_end, maxp, exchange_lanes,
                        drain_stats=False):
    """Downstream stages of the chained drain, ONCE per drain — not
    once per slot. The whole drain's stacked stage-0 fires pack into a
    single ``exchange_lanes``-wide edge (_chain_fires_to_lanes over the
    [D, F, C] stack), feed ONE update and ONE advance-and-fire at the
    coupled watermark, and each further stage repeats the pattern on
    its upstream's single fire set.

    Correct because every insert precedes the stage's single advance
    (no window can close before receiving all of this drain's records
    for it), and the watermark coupling (_chain_stage_watermark) still
    guarantees across drains that no future upstream fire is late
    downstream. Fires only become host-visible after the dispatch
    returns, so deferring the downstream advance to the drain boundary
    changes no observable timing — but it changes the cost model
    completely: a second stage adds one E-lane update + one advance
    per D-slot drain instead of D of each (plus D per-slot state
    copies through the fire gate). The <15%-overhead acceptance
    criterion of ISSUE 16 lives here.

    Returns ``(down_states', final_fires)`` with ``final_fires`` a
    1-slot stacked CompactFires ([1, F, C] leaves) when the chain has
    a downstream stage — the executor's consume path reads the slot
    dimension from the payload shape, so the narrower stack needs no
    host-side change. With ``drain_stats`` a third element rides
    along: a ``[n_stages-1, len(STAGE_STAT_FIELDS)]`` int32 stack, one
    per-drain record per downstream stage (the tail runs once per
    drain, so each row IS this drain's edge/watermark story) — element
    ops and tiny reductions only, same ledger discipline as the
    per-slot payload."""
    import dataclasses as _dc

    out = []
    stage_recs = []
    up_state, up_fires, wm_up = st0, cf_stack, wm_last
    for j in range(1, len(specs)):
        wm_j = _chain_stage_watermark(wm_up, up_state, specs[j - 1])
        (c_hi, c_lo, c_ts, c_vals, c_ok, c_drop,
         c_demand) = _chain_fires_to_lanes(up_fires, exchange_lanes)
        st_j = down_states[j - 1]
        wm_b_j = st_j.watermark
        # downstream stages always insert: their key population arrives
        # through the edge, never through the ingest-staged batch the
        # fast (lookup-only) tier models
        st_j, _act_j, _kgf_j = mask_update_shard(
            st_j, specs[j], kg_start, kg_end, c_hi, c_lo, c_ts,
            c_vals, c_ok, wm_j, maxp, insert=True, kg_fill=False,
        )
        # an over-full edge drops the overflow lanes; fold them into
        # the receiving stage's capacity-drop counter so the executor's
        # strict-capacity accounting (and the drop metrics) see them
        st_j = _dc.replace(
            st_j, dropped_capacity=st_j.dropped_capacity + c_drop
        )
        st_j, pend_j, cf_j = wk.advance_and_fire_resident(
            st_j, specs[j].win, specs[j].red, wm_j
        )
        # one purge sweep per drain (instead of deferring into a next
        # update's ring reset — there is no next update this dispatch)
        st_j = wk.apply_pending_purge(
            st_j, specs[j].win, specs[j].red, pend_j
        )
        if drain_stats:
            slide_j = jnp.int32(specs[j].win.slide_ticks)
            # coupled-watermark lag behind upstream, in downstream pane
            # widths; max-0 first so an end-of-stream flush (wm near
            # int32 max) wrapping the subtraction reads 0, never junk
            lag_panes = jnp.maximum(wm_up - wm_j, jnp.int32(0)) // slide_j
            # downstream panes this advance crossed, sentinel-clamped
            # exactly like the per-slot payload (_slot_drain_stats)
            wb_j = jnp.maximum(
                wm_b_j, st_j.watermark - jnp.int32(1 << 20)
            )
            panes_j = jnp.maximum(
                jnp.int32(0),
                st_j.watermark // slide_j - wb_j // slide_j,
            )
            panes_j = jnp.where(
                wm_b_j < jnp.int32(-(2 ** 30)), jnp.int32(0), panes_j
            )
            stage_recs.append(jnp.stack([       # STAGE_STAT_FIELDS order
                c_demand,
                jnp.minimum(c_demand, jnp.int32(exchange_lanes)),
                jnp.sum(cf_j.lane_valid, dtype=jnp.int32),
                c_drop,
                lag_panes,
                panes_j,
            ]))
        out.append(st_j)
        up_state, wm_up = st_j, wm_j
        up_fires = jax.tree_util.tree_map(lambda x: x[None], cf_j)
    if drain_stats:
        ss = jnp.stack(stage_recs)      # [n_stages-1, N_STAGE_FIELDS]
        return tuple(out), up_fires, ss
    return tuple(out), up_fires


def build_window_chained_drain(ctx: MeshContext,
                               specs: Sequence[WindowStageSpec],
                               depth: int, insert: bool = True,
                               kg_fill: bool = False,
                               exchange_lanes: int = 1024,
                               drain_stats: bool = False):
    """Multi-stage resident ring drain (stage-graph subsystem, ISSUE
    16): ONE jitted dispatch consumes up to ``depth`` staged ring slots
    through a CHAIN of keyed window stages — stage 0 applies the staged
    batch exactly like build_window_resident_drain's body (the same
    count-gated slot scan), stacking each slot's CompactFires; then
    each downstream stage runs ONCE per drain (_chained_stage_tail):
    the whole stack of upstream fires is re-keyed on device
    (_chain_fires_to_lanes: a cumsum+searchsorted+gather pack over the
    stacked fire planes) and applied in one update + one
    advance-and-fire at the coupled watermark. A keyBy→window→keyBy→
    window pipeline (sessionize→aggregate, multi-level rollup)
    therefore still costs one host dispatch per ring drain — the
    Hazelcast-Jet saturation criterion the ISSUE names: chaining must
    not reintroduce per-stage host round trips — and the second stage
    adds one edge pack + E-lane update + advance per DRAIN, not per
    slot (fires only become host-visible when the dispatch returns, so
    the deferral changes no observable timing).

    Inter-stage edge: identity re-key. A fired key keeps its key bits,
    so it hashes to the same key group and stays on its owning shard —
    the per-shard exchange is a local pack, no all_to_all, and the
    sharded variant keeps its zero-collective body. ``exchange_lanes``
    bounds the PER-DRAIN edge width (pipeline.stages.exchange-lanes —
    size it at distinct keys x panes closing per drain); overflow
    lanes count into the downstream stage's dropped_capacity so a
    too-narrow edge is loudly visible, never silent.

    Watermark coupling: stage j+1 advances to ``min(upstream wm,
    (fired_through_j + 2) * slide_j - 2)`` (_chain_stage_watermark) so
    no future upstream fire can be late downstream — the exactly-once
    cut at a drain boundary then needs no in-flight edge payload: every
    fire the upstream state counts as fired has been folded into the
    downstream state within the same dispatch.

    Signature: ``drain(states, hi_0, lo_0, ticks_0, values_0, valid_0,
    ..., wmv, count)`` — ``states`` a TUPLE of per-stage stacked window
    states (donated as one buffer set), batch operands exactly as
    build_window_resident_drain. Returns ``(states', (ovf_n, activity,
    kg_fill), fires)`` with ``fires`` the FINAL stage's CompactFires
    stacked [n_shards, 1] (one tail advance per drain) — the
    executor's lagged consume_fires path reads the slot dimension from
    the payload shape, so the chain's output needs no host change.
    With ``drain_stats`` (observability.drain-stats, ISSUE 17) a
    fourth return element rides along: the PAIR ``(ds0, ss)`` — the
    stage-0 per-slot [n_shards, depth, len(DRAIN_STAT_FIELDS)] flight-
    recorder stack exactly as the single-stage drain emits it, plus a
    per-downstream-stage [n_stages-1, n_shards,
    len(STAGE_STAT_FIELDS)] record of this drain's edge/watermark
    story; off, arity and op budgets are byte-identical to pre-
    telemetry (op_budget_pre_stage_stats.json pins it)."""
    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    D = int(depth)
    specs = tuple(specs)

    def shard_body(states, kg_start, kg_end, count, hi, lo, ts, values,
                   valid, wm):
        states = jax.tree_util.tree_map(lambda x: x[0], states)
        kg_start, kg_end = kg_start[0], kg_end[0]
        carry0 = (states[0], jnp.zeros(specs[0].win.ring, bool))

        def sub(carry, xs):
            i, s_hi, s_lo, s_ts, s_vals, s_valid, s_wm = xs

            def live(op):
                return _chained_slot_body(
                    op, specs[0], kg_start, kg_end, maxp, s_hi, s_lo,
                    s_ts, s_vals, s_valid, s_wm, insert, kg_fill,
                    drain_stats=drain_stats,
                )

            def skip(op):
                kgf = jnp.zeros(maxp if kg_fill else 0, jnp.int32)
                ys = (jnp.zeros((), jnp.int32), kgf,
                      _zero_slot_fires(specs[0], False))
                if drain_stats:
                    ys += (jnp.zeros(len(DRAIN_STAT_FIELDS), jnp.int32),)
                return op, ys

            return jax.lax.cond(i < count, live, skip, carry)

        wm_vec = wm[0]
        carry, ys = jax.lax.scan(
            sub, carry0,
            (jnp.arange(D, dtype=jnp.int32), hi, lo, ts, values, valid,
             wm_vec),
        )
        acts, kgfs, cf_stack = ys[:3]
        st0 = wk.apply_pending_purge(
            carry[0], specs[0].win, specs[0].red, carry[1]
        )
        # effective drain watermark: MAX over LIVE slots — update-only
        # slots (and the dispatch pad) carry the MIN-int "no watermark"
        # sentinel, so the last slot is not necessarily the target
        live_mask = jnp.arange(D, dtype=jnp.int32) < count
        wm_last = jnp.max(jnp.where(
            live_mask, wm_vec, jnp.int32(-(2**31) + 1)
        ))
        tail = _chained_stage_tail(
            states[1:], specs, st0, cf_stack, wm_last, kg_start,
            kg_end, maxp, exchange_lanes, drain_stats=drain_stats,
        )
        down, fires = tail[0], tail[1]
        states = (st0,) + down
        ovf_n = states[0].ovf_n
        act = jnp.sum(acts)
        kgf = kgfs.sum(axis=0) if kg_fill else jnp.zeros(0, jnp.int32)
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        out = (
            pack(states), ovf_n[None], act[None], kgf[None], pack(fires),
        )
        if drain_stats:
            # [1, D, N] per-slot stack (deferred fire columns filled
            # from the stacked fires) + [1, S-1, K] per-stage records
            ds0 = _deferred_fire_columns(ys[3], cf_stack)
            out += (ds0[None], tail[2][None])
        return out

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(),                       # count: replicated scalar cursor
            P(), P(), P(), P(), P(),   # [D, B] batch stacks, replicated
            P(SHARD_AXIS),             # wmv [n_shards, D]
        ),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P(SHARD_AXIS))
        + ((P(SHARD_AXIS), P(SHARD_AXIS)) if drain_stats else ()),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def drain(states, *flat):
        *batches, wmv, count = flat
        stacks = _fused_batch_stack(D, batches)
        res = sharded(
            states, starts, ends, jnp.asarray(count, jnp.int32),
            *stacks, wmv,
        )
        st, ovf_n, act, kgf, fires = res[:5]
        if drain_stats:
            # stage records transpose to the documented
            # [n_stages-1, n_shards, K] block (element op only)
            return st, (ovf_n, act, kgf), fires, (
                res[5], jnp.swapaxes(res[6], 0, 1)
            )
        return st, (ovf_n, act, kgf), fires

    drain.k_steps = D
    drain.ring_depth = D
    drain.resident_drain = True
    drain.chained_drain = True
    drain.n_stages = len(specs)
    drain.exchange_lanes = int(exchange_lanes)
    drain.fused_fire = True
    drain.fused_fire_reduced = False
    drain.drain_stats = drain_stats
    return drain


def build_window_chained_drain_sharded(ctx: MeshContext,
                                       specs: Sequence[WindowStageSpec],
                                       depth: int, insert: bool = True,
                                       kg_fill: bool = False,
                                       exchange_lanes: int = 1024,
                                       drain_stats: bool = False):
    """Data-parallel chained drain: the multi-stage chain of
    build_window_chained_drain lowered over build_window_sharded_drain's
    shard-local geometry — per-shard pre-routed lane slices, per-shard
    count VECTOR, and still ZERO cross-chip collectives in the body:
    the identity re-key keeps every inter-stage record on the shard
    that fired it (same key → same key group → same owner), so the
    chained edge is a local pack and divergent per-shard counts stay
    safe exactly as in the single-stage sharded drain."""
    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh
    D = int(depth)
    specs = tuple(specs)

    def shard_body(states, kg_start, kg_end, counts, hi, lo, ts, values,
                   valid, wm):
        states = jax.tree_util.tree_map(lambda x: x[0], states)
        kg_start, kg_end = kg_start[0], kg_end[0]
        count = counts[0]          # this shard's OWN fill level
        carry0 = (states[0], jnp.zeros(specs[0].win.ring, bool))

        def sub(carry, xs):
            i, s_hi, s_lo, s_ts, s_vals, s_valid, s_wm = xs

            def live(op):
                return _chained_slot_body(
                    op, specs[0], kg_start, kg_end, maxp, s_hi, s_lo,
                    s_ts, s_vals, s_valid, s_wm, insert, kg_fill,
                    drain_stats=drain_stats,
                )

            def skip(op):
                kgf = jnp.zeros(maxp if kg_fill else 0, jnp.int32)
                ys = (jnp.zeros((), jnp.int32), kgf,
                      _zero_slot_fires(specs[0], False))
                if drain_stats:
                    ys += (jnp.zeros(len(DRAIN_STAT_FIELDS), jnp.int32),)
                return op, ys

            return jax.lax.cond(i < count, live, skip, carry)

        wm_vec = wm[0]
        carry, ys = jax.lax.scan(
            sub, carry0,
            # [D, 1, cap] per-shard batch stacks squeeze the split axis
            (jnp.arange(D, dtype=jnp.int32), hi[:, 0], lo[:, 0],
             ts[:, 0], values[:, 0], valid[:, 0], wm_vec),
        )
        acts, kgfs, cf_stack = ys[:3]
        st0 = wk.apply_pending_purge(
            carry[0], specs[0].win, specs[0].red, carry[1]
        )
        # per-shard effective drain watermark: MAX over this shard's
        # LIVE slots (divergent counts are safe — each shard's tail
        # advances under its own target, same as the per-slot scan)
        live_mask = jnp.arange(D, dtype=jnp.int32) < count
        wm_last = jnp.max(jnp.where(
            live_mask, wm_vec, jnp.int32(-(2**31) + 1)
        ))
        tail = _chained_stage_tail(
            states[1:], specs, st0, cf_stack, wm_last, kg_start,
            kg_end, maxp, exchange_lanes, drain_stats=drain_stats,
        )
        down, fires = tail[0], tail[1]
        states = (st0,) + down
        ovf_n = states[0].ovf_n
        act = jnp.sum(acts)
        kgf = kgfs.sum(axis=0) if kg_fill else jnp.zeros(0, jnp.int32)
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        out = (
            pack(states), ovf_n[None], act[None], kgf[None], pack(fires),
        )
        if drain_stats:
            ds0 = _deferred_fire_columns(ys[3], cf_stack)
            out += (ds0[None], tail[2][None])
        return out

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(SHARD_AXIS),             # counts: per-shard fill levels
            P(None, SHARD_AXIS), P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(None, SHARD_AXIS), P(None, SHARD_AXIS),
            P(SHARD_AXIS),             # wmv [n_shards, D]
        ),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P(SHARD_AXIS))
        + ((P(SHARD_AXIS), P(SHARD_AXIS)) if drain_stats else ()),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def drain(states, *flat):
        *batches, wmv, counts = flat
        stacks = _fused_batch_stack(D, batches)
        res = sharded(
            states, starts, ends, jnp.asarray(counts, jnp.int32),
            *stacks, wmv,
        )
        st, ovf_n, act, kgf, fires = res[:5]
        if drain_stats:
            return st, (ovf_n, act, kgf), fires, (
                res[5], jnp.swapaxes(res[6], 0, 1)
            )
        return st, (ovf_n, act, kgf), fires

    drain.k_steps = D
    drain.ring_depth = D
    drain.resident_drain = True
    drain.sharded_drain = True
    drain.chained_drain = True
    drain.n_stages = len(specs)
    drain.exchange_lanes = int(exchange_lanes)
    drain.fused_fire = True
    drain.fused_fire_reduced = False
    drain.drain_stats = drain_stats
    return drain


def build_window_fire_step(ctx: MeshContext, spec: WindowStageSpec):
    """Fire-only half: advance the watermark, evaluate due window ends for
    the whole key population, and return device-compacted fires
    (wk.CompactFires). Called by the host only at pane-boundary crossings
    (or to drain at checkpoints / end of stream)."""
    mesh = ctx.mesh

    def shard_body(state, wm):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        state, fr = wk.advance_and_fire(state, spec.win, spec.red, wm[0])
        cf = wk.compact_fires(state.table, fr)
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        return pack(state), pack(cf)

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def fire_step(state, wm):
        return sharded(state, wm)

    return fire_step


def build_window_fire_reduced_step(ctx: MeshContext, spec: WindowStageSpec):
    """Fire step whose output is reduced on device to per-lane scalars
    (wk.ReducedFires): no key/value packing at all. Used by the executor
    when every sink is device_reduce-capable and the spill tier is empty —
    the common high-throughput analytics topology. The pack scatters this
    avoids are ~4x the cost of the whole watermark advance on a 1M-slot
    shard, and the drain's device->host traffic drops to five [Ft] fields."""
    mesh = ctx.mesh

    def shard_body(state, wm):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        state, fr = wk.advance_and_fire(state, spec.win, spec.red, wm[0])
        rf = wk.reduce_fires(fr)
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        return pack(state), pack(rf)

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def fire_step(state, wm):
        return sharded(state, wm)

    return fire_step


def build_kg_occupancy_step(ctx: MeshContext, spec: WindowStageSpec):
    """Per-key-group live-key occupancy over the mesh (wk.kg_occupancy):
    int32 [n_shards, max_parallelism], shards own disjoint groups so the
    host's per-group view is the sum over the shard axis. State is NOT
    donated — the telemetry read must never invalidate the live buffers.
    Compiled lazily by the executor and run at fire boundaries on a wall-
    clock budget (observability.kg-stats-interval-ms), where the barrier
    fetch already syncs the loop."""
    mesh = ctx.mesh
    maxp = ctx.max_parallelism

    def shard_body(state):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        return wk.kg_occupancy(state, maxp, red=spec.red,
                               win=spec.win)[None]

    sharded = shard_map(
        shard_body, mesh=mesh, in_specs=(P(SHARD_AXIS),),
        out_specs=P(SHARD_AXIS), check_vma=False,
    )

    @jax.jit
    def occupancy_step(state):
        return sharded(state)

    return occupancy_step


def build_compact_step(ctx: MeshContext, spec: WindowStageSpec):
    """Whole-shard table compaction (wk.compact_table) over the mesh; run
    by the host at fire boundaries when the overflow ring reported
    pressure (the RocksDB-compaction analog)."""
    mesh = ctx.mesh

    def shard_body(state):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        state = wk.compact_table(state, spec.win, spec.red)
        return jax.tree_util.tree_map(lambda x: x[None], state)

    sharded = shard_map(
        shard_body, mesh=mesh, in_specs=(P(SHARD_AXIS),),
        out_specs=P(SHARD_AXIS), check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def compact_step(state):
        return sharded(state)

    return compact_step


def clear_overflow(state):
    """Host-side: zero the overflow counter after draining the ring (the
    entry arrays may keep stale rows — only [:ovf_n] is ever read)."""
    import dataclasses as _dc

    return _dc.replace(
        state,
        ovf_n=jax.device_put(
            np.zeros(state.ovf_n.shape, np.int32), state.ovf_n.sharding
        ),
    )


def clear_dirty(state):
    """Host-side: reset the changelog dirty bits after a checkpoint staged
    its device fetch — everything mutated from here on belongs to the NEXT
    delta. Cheap device_put of a tiny bool plane (cf. clear_overflow)."""
    import dataclasses as _dc

    if state.kg_dirty.shape[-1] == 0:
        return state
    return _dc.replace(
        state,
        kg_dirty=jax.device_put(
            np.zeros(state.kg_dirty.shape, bool), state.kg_dirty.sharding
        ),
    )


def watermark_vector(ctx: MeshContext, wm: int):
    return jnp.full((ctx.n_shards,), np.int32(wm))


# -------------------------------------------------------- session windows

@dataclass
class SessionStageSpec:
    red: "object"
    gap_ticks: int = 1000
    capacity_per_shard: int = 1 << 16
    probe_len: int = 16


def init_session_state(ctx: MeshContext, spec: SessionStageSpec):
    from flink_tpu.ops import session_windows as sw

    states = [
        sw.init_state(spec.capacity_per_shard, spec.probe_len, spec.red)
        for _ in range(ctx.n_shards)
    ]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    return jax.device_put(stacked, ctx.state_sharding)


def build_session_step(ctx: MeshContext, spec: SessionStageSpec):
    from flink_tpu.ops import session_windows as sw

    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh

    def shard_body(state, kg_start, kg_end, hi, lo, ts, values, valid, wm):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg = assign_to_key_group(route_hash(hi, lo, jnp), maxp, jnp)
        mine = valid & (kg >= kg_start.astype(jnp.uint32)) & (
            kg <= kg_end.astype(jnp.uint32)
        )
        state, old_f, mid_f, wm_f = sw.update_and_fire(
            state, spec.red, spec.gap_ticks, hi, lo, ts, values, mine, wm[0]
        )
        pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        return pack(state), pack(old_f), pack(mid_f), pack(wm_f)

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(), P(), P(), P(), P(), P(SHARD_AXIS),
        ),
        out_specs=(P(SHARD_AXIS),) * 4,
        check_vma=False,
    )

    @jax.jit
    def step(state, hi, lo, ts, values, valid, wm):
        return sharded(state, starts, ends, hi, lo, ts, values, valid, wm)

    return step


# ---------------------------------------------------------- count windows

@dataclass
class CountStageSpec:
    red: "object"
    n_per_window: int = 100
    capacity_per_shard: int = 1 << 16
    probe_len: int = 16


def init_count_state(ctx: MeshContext, spec: CountStageSpec):
    from flink_tpu.ops import count_windows as cw

    states = [
        cw.init_state(spec.capacity_per_shard, spec.probe_len, spec.red)
        for _ in range(ctx.n_shards)
    ]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    return jax.device_put(stacked, ctx.state_sharding)


def build_count_step(ctx: MeshContext, spec: CountStageSpec):
    from flink_tpu.ops import count_windows as cw

    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh

    def shard_body(state, kg_start, kg_end, hi, lo, values, valid):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg = assign_to_key_group(route_hash(hi, lo, jnp), maxp, jnp)
        mine = valid & (kg >= kg_start.astype(jnp.uint32)) & (
            kg <= kg_end.astype(jnp.uint32)
        )
        state, khi, klo, w, vals, mask = cw.update(
            state, spec.red, spec.n_per_window, hi, lo, values, mine
        )
        pack = lambda x: x[None]
        state = jax.tree_util.tree_map(pack, state)
        return state, pack(khi), pack(klo), pack(w), pack(vals), pack(mask)

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(), P(), P(), P(),
        ),
        out_specs=tuple([P(SHARD_AXIS)] * 6),
        check_vma=False,
    )

    @jax.jit
    def step(state, hi, lo, values, valid):
        return sharded(state, starts, ends, hi, lo, values, valid)

    return step


# --------------------------------------------------------------- rolling

@dataclass
class RollingStageSpec:
    red: "object"  # wk.ReduceSpec
    capacity_per_shard: int = 1 << 16
    probe_len: int = 16


def init_rolling_state(ctx: MeshContext, spec: RollingStageSpec):
    from flink_tpu.ops import rolling

    states = [
        rolling.init_state(spec.capacity_per_shard, spec.probe_len, spec.red)
        for _ in range(ctx.n_shards)
    ]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    return jax.device_put(stacked, ctx.state_sharding)


def build_rolling_step(ctx: MeshContext, spec: RollingStageSpec):
    """Rolling keyed reduce over the mesh: per-record outputs are psum-merged
    across shards (each lane is owned by exactly one shard)."""
    from flink_tpu.ops import rolling
    from flink_tpu.ops.segment import _bshape

    starts, ends = ctx.kg_bounds()
    starts = jnp.asarray(starts)
    ends = jnp.asarray(ends)
    maxp = ctx.max_parallelism
    mesh = ctx.mesh

    def shard_body(state, kg_start, kg_end, hi, lo, values, valid):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        kg_start, kg_end = kg_start[0], kg_end[0]
        kg = assign_to_key_group(route_hash(hi, lo, jnp), maxp, jnp)
        mine = valid & (kg >= kg_start.astype(jnp.uint32)) & (
            kg <= kg_end.astype(jnp.uint32)
        )
        state, outputs, out_valid = rolling.update(
            state, spec.red, hi, lo, values, mine
        )
        outputs = jax.lax.psum(
            jnp.where(_bshape(out_valid, outputs), outputs,
                      jnp.zeros((), outputs.dtype)),
            SHARD_AXIS,
        )
        out_valid = jax.lax.psum(out_valid.astype(jnp.int32), SHARD_AXIS) > 0
        state = jax.tree_util.tree_map(lambda x: x[None], state)
        return state, outputs, out_valid

    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(), P(), P(), P(),
        ),
        out_specs=(P(SHARD_AXIS), P(), P()),
        check_vma=False,
    )

    @jax.jit
    def step(state, hi, lo, values, valid):
        return sharded(state, starts, ends, hi, lo, values, valid)

    return step


# --------------------------------------------- canonical kernel families

# Canonical "tiny but structurally real" dims for auditing: big enough
# that every code path (probe rounds, ring panes, overflow lanes, kg
# telemetry) is live in the traced program, small enough that tracing
# the whole grid stays inside the lint tier's wall-time budget.
AUDIT_CAPACITY = 64
AUDIT_PROBE_LEN = 4
AUDIT_BATCH = 8
AUDIT_K_STEPS = 2
# resident-drain ring depth for the audit grid: deep enough that the
# cond gate is structurally live (the canonical count operand is
# depth - 1, so BOTH branches appear in the traced program), small
# enough to stay inside the lint tier's wall-time budget
AUDIT_RING_DEPTH = 4
# per-slot inter-stage edge width for the audited chained-drain chain
AUDIT_EXCHANGE_LANES = 16


@dataclass(frozen=True)
class KernelFamily:
    """One canonical hot-path kernel family.

    The compiled-graph auditor (tools/lint trace tier, ISSUE 11) and the
    bench harness both need the same enumeration of "which step builders
    exist, along which spec axes" — this descriptor and
    :func:`kernel_family_grid` ARE that enumeration, kept next to the
    builders so the audited grid and the executor's dispatch surface
    cannot drift. ``donated`` mirrors the builder's donate_argnums
    contract (argnum 0 = state); the donation-effective rule verifies it
    against the lowered/compiled alias tables. ``deep`` marks the
    families the auditor fully compiles (executable alias table + memory
    stats) rather than just lowers — one representative per kind keeps
    the audit under its wall-time budget.
    """

    name: str
    builder: Callable
    kind: str            # update | megastep | megastep_fired |
    #                      resident_drain | fire | fire_reduced |
    #                      compact | occupancy | session | count |
    #                      rolling
    #                      (resident_drain reuses ``k_steps`` for its
    #                      ring depth — the scan length axis is the same
    #                      ledger currency either way)
    route: str = "mask"      # mask | exchange | sharded
    layout: str = "hash"     # hash | direct
    donated: bool = True
    insert: bool = True
    precombine: bool = False
    packed: bool = False
    reduced: bool = False
    k_steps: int = 0
    deep: bool = False
    # observability.drain-stats telemetry-ON variant (ISSUE 14): the
    # drain emits the per-slot DRAIN_STAT_FIELDS stack. OFF families
    # keep their pre-telemetry names AND ledger entries — the byte-
    # identity test proves the payload compiles out.
    drain_stats: bool = False
    # tiered-residency variant (ISSUE 18): the kernel takes a trailing
    # replicated kg_res bool[max_parallelism] mask and diverts lanes of
    # non-resident key-groups down the overflow ring. OFF families keep
    # their pre-tier ledger entries byte-identical — residency is data,
    # not structure.
    tiered: bool = False


def kernel_family_grid():
    """THE canonical kernel-family grid: every window step builder, along
    the spec axes the executor actually dispatches (routes x layouts x
    packed/precombine planes x fused depths), plus the auxiliary
    session/count/rolling window steps. tests/test_lint_trace.py asserts
    every ``build_*`` step factory in this module is represented, so
    adding a builder without extending the grid fails tier-1."""
    F = KernelFamily
    K = AUDIT_K_STEPS
    return [
        F("step.combined.mask.hash", build_window_step, "combined"),
        F("step.update.mask.hash", build_window_update_step,
          "update", deep=True),
        F("step.update.mask.direct", build_window_update_step,
          "update", layout="direct"),
        F("step.update.mask.hash.precombine", build_window_update_step,
          "update", precombine=True),
        F("step.update.mask.hash.packed", build_window_update_step,
          "update", packed=True),
        F("step.update_fast.mask.hash", build_window_update_step,
          "update", insert=False),
        F("step.update.exchange.hash", build_window_update_step_exchange,
          "update", route="exchange"),
        F("step.megastep.mask.hash.k2", build_window_megastep,
          "megastep", k_steps=K),
        F("step.megastep.exchange.hash.k2", build_window_megastep_exchange,
          "megastep", route="exchange", k_steps=K),
        F("step.megastep_fired.mask.hash.k2", build_window_megastep_fired,
          "megastep_fired", k_steps=K, deep=True),
        F("step.megastep_fired.mask.direct.k2", build_window_megastep_fired,
          "megastep_fired", layout="direct", k_steps=K),
        F("step.megastep_fired.mask.hash.k2.packed",
          build_window_megastep_fired,
          "megastep_fired", packed=True, k_steps=K),
        F("step.megastep_fired.mask.hash.k2.reduced",
          build_window_megastep_fired,
          "megastep_fired", reduced=True, k_steps=K),
        F("step.megastep_fired.exchange.hash.k2",
          build_window_megastep_fired_exchange,
          "megastep_fired", route="exchange", k_steps=K),
        # the device-resident ring drain (ISSUE 12): the executor
        # dispatches it along the same layout/plane/route axes as the
        # fired megastep it supersedes in steady state
        F("step.resident_drain.mask.hash.d4", build_window_resident_drain,
          "resident_drain", k_steps=AUDIT_RING_DEPTH, deep=True),
        F("step.resident_drain.mask.direct.d4",
          build_window_resident_drain,
          "resident_drain", layout="direct", k_steps=AUDIT_RING_DEPTH),
        F("step.resident_drain.mask.hash.d4.packed",
          build_window_resident_drain,
          "resident_drain", packed=True, k_steps=AUDIT_RING_DEPTH),
        F("step.resident_drain.mask.hash.d4.reduced",
          build_window_resident_drain,
          "resident_drain", reduced=True, k_steps=AUDIT_RING_DEPTH),
        F("step.resident_drain.exchange.hash.d4",
          build_window_resident_drain_exchange,
          "resident_drain", route="exchange", k_steps=AUDIT_RING_DEPTH),
        # the data-parallel shard-local drain (ISSUE 13): per-shard
        # pre-routed lane slices, per-shard count gating, ZERO
        # collectives in the keyed body (the no-host-crossing rule and
        # the op-budget ledger pin that — an all_to_all sneaking in
        # here would break divergent-count safety)
        F("step.sharded_drain.hash.d4", build_window_sharded_drain,
          "sharded_drain", route="sharded", k_steps=AUDIT_RING_DEPTH,
          deep=True),
        F("step.sharded_drain.direct.d4", build_window_sharded_drain,
          "sharded_drain", route="sharded", layout="direct",
          k_steps=AUDIT_RING_DEPTH),
        F("step.sharded_drain.hash.d4.packed", build_window_sharded_drain,
          "sharded_drain", route="sharded", packed=True,
          k_steps=AUDIT_RING_DEPTH),
        # telemetry-ON drain variants (observability.drain-stats, ISSUE
        # 14): one per drain builder. Ledgered like any family — the
        # flight recorder must stay element-ops-only, so an ON variant
        # whose sort/scatter/gather counts drift from its OFF twin is a
        # telemetry regression the op-budget rule catches
        F("step.resident_drain.mask.hash.d4.dstats",
          build_window_resident_drain,
          "resident_drain", k_steps=AUDIT_RING_DEPTH, drain_stats=True),
        F("step.resident_drain.exchange.hash.d4.dstats",
          build_window_resident_drain_exchange,
          "resident_drain", route="exchange", k_steps=AUDIT_RING_DEPTH,
          drain_stats=True),
        F("step.sharded_drain.hash.d4.dstats", build_window_sharded_drain,
          "sharded_drain", route="sharded", k_steps=AUDIT_RING_DEPTH,
          drain_stats=True),
        # tiered-residency variants (ISSUE 18): one per dispatchable
        # route through the tiered executor path. Ledgered like any
        # family — the residency mask must stay a pure element-wise
        # divert (gather + and/or), so a sort/scatter creeping into the
        # tier gate is structural drift the op-budget rule catches; OFF
        # twins stay byte-identical to the frozen ledger
        F("step.update.mask.hash.tiered", build_window_update_step,
          "update", tiered=True),
        F("step.update.exchange.hash.tiered",
          build_window_update_step_exchange,
          "update", route="exchange", tiered=True),
        F("step.megastep_fired.mask.hash.k2.tiered",
          build_window_megastep_fired,
          "megastep_fired", k_steps=K, tiered=True),
        F("step.resident_drain.mask.hash.d4.tiered",
          build_window_resident_drain,
          "resident_drain", k_steps=AUDIT_RING_DEPTH, tiered=True),
        F("step.resident_drain.exchange.hash.d4.tiered",
          build_window_resident_drain_exchange,
          "resident_drain", route="exchange", k_steps=AUDIT_RING_DEPTH,
          tiered=True),
        F("step.sharded_drain.hash.d4.tiered", build_window_sharded_drain,
          "sharded_drain", route="sharded", k_steps=AUDIT_RING_DEPTH,
          tiered=True),
        # the multi-stage chained drain (ISSUE 16): stage-N fires
        # re-keyed on device into stage-N+1's update inside the same
        # count-gated scan. The edge is gather-only (_chain_fires_to
        # _lanes) — a sort or scatter creeping into it is exactly the
        # structural drift the op-budget ledger exists to catch, and
        # the sharded variant stays collective-free (no-host-crossing)
        F("step.chained_drain.mask.hash.d4.s2",
          build_window_chained_drain,
          "chained_drain", k_steps=AUDIT_RING_DEPTH, deep=True),
        F("step.chained_drain.mask.direct.d4.s2",
          build_window_chained_drain,
          "chained_drain", layout="direct", k_steps=AUDIT_RING_DEPTH),
        F("step.chained_drain.sharded.hash.d4.s2",
          build_window_chained_drain_sharded,
          "chained_drain_sharded", route="sharded",
          k_steps=AUDIT_RING_DEPTH),
        # stage-aware flight recorder (ISSUE 17): the chained drains'
        # telemetry-ON twins — stage-0 per-slot payload + per-stage
        # edge/watermark records, all element ops, so the OFF twins
        # stay byte-identical (op_budget_pre_stage_stats.json) and the
        # ON twins match their OFF twin per op group
        F("step.chained_drain.mask.hash.d4.s2.dstats",
          build_window_chained_drain,
          "chained_drain", k_steps=AUDIT_RING_DEPTH, drain_stats=True),
        F("step.chained_drain.sharded.hash.d4.s2.dstats",
          build_window_chained_drain_sharded,
          "chained_drain_sharded", route="sharded",
          k_steps=AUDIT_RING_DEPTH, drain_stats=True),
        # the early-exit live drains (ISSUE 20a): the count-gated scan
        # lowered as a while_loop tripping on the device-visible publish
        # cursor. The body is the SAME exchange/advance/fire sequence —
        # the op-budget ledger pins that the lowering change costs no
        # sorts/scatters — and the sharded variant keeps the keyed body
        # collective-free (divergent per-shard trip counts stay safe)
        F("step.while_drain.mask.hash.d4", build_window_while_drain,
          "while_drain", k_steps=AUDIT_RING_DEPTH, deep=True),
        F("step.while_drain.sharded.hash.d4",
          build_window_while_drain_sharded,
          "while_drain_sharded", route="sharded",
          k_steps=AUDIT_RING_DEPTH),
        F("step.while_drain.mask.hash.d4.dstats", build_window_while_drain,
          "while_drain", k_steps=AUDIT_RING_DEPTH, drain_stats=True),
        # the per-host DCN-resident drain (ISSUE 20b): the lockstep DCN
        # body run depth times per dispatch with the trip count
        # pmax-agreed on device — the all_to_all count per slot is the
        # structural invariant the signature ledger pins
        F("step.dcn_resident.hash.d4", build_window_dcn_resident_drain,
          "dcn_resident", route="exchange", k_steps=AUDIT_RING_DEPTH),
        F("step.dcn_resident.hash.d4.dstats",
          build_window_dcn_resident_drain,
          "dcn_resident", route="exchange", k_steps=AUDIT_RING_DEPTH,
          drain_stats=True),
        F("step.fire.hash", build_window_fire_step, "fire", deep=True),
        F("step.fire_reduced.hash", build_window_fire_reduced_step,
          "fire_reduced"),
        F("step.compact.hash", build_compact_step, "compact", deep=True),
        F("step.occupancy.hash", build_kg_occupancy_step,
          "occupancy", donated=False),
        # auxiliary window kinds: their steps do not donate today (the
        # audit mirrors the builders' real contracts, it does not wish)
        F("step.session.mask.hash", build_session_step,
          "session", donated=False),
        F("step.count.mask.hash", build_count_step,
          "count", donated=False),
        F("step.rolling.mask.hash", build_rolling_step,
          "rolling", donated=False),
    ]


def audit_stage_spec(fam: KernelFamily):
    """The canonical stage spec for one family: fixed tiny dims,
    family-specific layout/precombine/packed axes (spec class chosen by
    the family's window kind)."""
    red = wk.ReduceSpec("sum", jnp.float32)
    if fam.kind == "session":
        return SessionStageSpec(
            red=red, gap_ticks=16,
            capacity_per_shard=AUDIT_CAPACITY, probe_len=AUDIT_PROBE_LEN,
        )
    if fam.kind == "count":
        return CountStageSpec(
            red=red, n_per_window=4,
            capacity_per_shard=AUDIT_CAPACITY, probe_len=AUDIT_PROBE_LEN,
        )
    if fam.kind == "rolling":
        return RollingStageSpec(
            red=red,
            capacity_per_shard=AUDIT_CAPACITY, probe_len=AUDIT_PROBE_LEN,
        )
    if fam.kind in ("chained_drain", "chained_drain_sharded"):
        # a 2-stage rollup chain: stage 0 at the canonical tiny dims,
        # stage 1 a coarser tumbling window over the re-keyed fires.
        # The identity re-key preserves the key bits, so the direct-
        # index contract (hi == 0, lo < capacity) holds downstream
        # whenever it holds at ingest — both stages share the layout
        s0 = WindowStageSpec(
            win=wk.WindowSpec(4, 2, ring=4, fires_per_step=2),
            red=red,
            capacity_per_shard=AUDIT_CAPACITY, probe_len=AUDIT_PROBE_LEN,
            layout=fam.layout, precombine=fam.precombine,
            packed=fam.packed,
        )
        s1 = WindowStageSpec(
            win=wk.WindowSpec(8, 4, ring=4, fires_per_step=2),
            red=wk.ReduceSpec("sum", jnp.float32),
            capacity_per_shard=AUDIT_CAPACITY, probe_len=AUDIT_PROBE_LEN,
            layout=fam.layout,
        )
        return (s0, s1)
    win = wk.WindowSpec(4, 2, ring=4, fires_per_step=2, overflow=4)
    return WindowStageSpec(
        win=win, red=red,
        capacity_per_shard=AUDIT_CAPACITY, probe_len=AUDIT_PROBE_LEN,
        layout=fam.layout, precombine=fam.precombine, packed=fam.packed,
    )


def _family_example_args(fam: KernelFamily, ctx: MeshContext, state,
                         batch: int):
    """A canonical concrete call for ``fam``: batch operands with the
    exact dtypes the executor stages (uint32 keys, int32 ticks, f32
    values, bool valid, int32 watermark vectors). Direct layout keeps
    the identity-key contract (hi == 0, lo < capacity)."""
    B = batch
    if fam.layout == "direct":
        hi = jnp.zeros(B, jnp.uint32)
        lo = jnp.arange(B, dtype=jnp.uint32) % jnp.uint32(AUDIT_CAPACITY)
    else:
        hi = jnp.arange(B, dtype=jnp.uint32) * jnp.uint32(2654435761)
        lo = jnp.arange(B, dtype=jnp.uint32)
    per = (hi, lo, jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32),
           jnp.ones(B, bool))
    # tiered families take a trailing replicated residency mask; the
    # canonical call marks every key-group resident (the mask is data,
    # so the all-resident trace covers the divert path structurally)
    tier = ((jnp.ones(ctx.max_parallelism, bool),) if fam.tiered else ())
    if fam.kind in ("update", "combined"):
        return (state,) + per + (watermark_vector(ctx, 0),) + tier
    if fam.kind in ("megastep", "megastep_fired"):
        wmv = jnp.zeros((ctx.n_shards, fam.k_steps), jnp.int32)
        return (state,) + per * fam.k_steps + (wmv,) + tier
    if fam.kind == "resident_drain":
        # partially-filled ring (count = depth - 1): both cond branches
        # are live in the traced program, so the audit sees the gate
        wmv = jnp.zeros((ctx.n_shards, fam.k_steps), jnp.int32)
        count = jnp.asarray(fam.k_steps - 1, jnp.int32)
        return (state,) + per * fam.k_steps + (wmv, count) + tier
    if fam.kind == "chained_drain":
        # same operand shape as the single-stage resident drain: the
        # chained edge is internal to the kernel (state is the tuple)
        wmv = jnp.zeros((ctx.n_shards, fam.k_steps), jnp.int32)
        count = jnp.asarray(fam.k_steps - 1, jnp.int32)
        return (state,) + per * fam.k_steps + (wmv, count)
    if fam.kind in ("sharded_drain", "chained_drain_sharded"):
        # per-shard [n_shards, cap] lane slices (cap = the audit batch)
        # and a per-shard count VECTOR at depth - 1 — both cond
        # branches live, per-shard gating in the traced signature
        n = ctx.n_shards
        per2 = tuple(jnp.broadcast_to(a, (n,) + a.shape) for a in per)
        wmv = jnp.zeros((n, fam.k_steps), jnp.int32)
        counts = jnp.full((n,), fam.k_steps - 1, jnp.int32)
        return (state,) + per2 * fam.k_steps + (wmv, counts) + tier
    if fam.kind == "while_drain":
        # cursor = base + (depth - 1) staged slots: the while_loop's
        # bound is live (not the static depth), so the traced program
        # keeps the cursor re-read in its condition
        wmv = jnp.zeros((ctx.n_shards, fam.k_steps), jnp.int32)
        cursor = jnp.full((1,), fam.k_steps - 1, jnp.int32)
        base = jnp.asarray(0, jnp.int32)
        staged = jnp.asarray(fam.k_steps - 1, jnp.int32)
        return ((state,) + per * fam.k_steps
                + (wmv, cursor, base, staged) + tier)
    if fam.kind == "while_drain_sharded":
        # per-shard cursor/base/staged VECTORS — each shard trips its
        # own while_loop on its own publish cursor
        n = ctx.n_shards
        per2 = tuple(jnp.broadcast_to(a, (n,) + a.shape) for a in per)
        wmv = jnp.zeros((n, fam.k_steps), jnp.int32)
        cursor = jnp.full((n,), fam.k_steps - 1, jnp.int32)
        base = jnp.zeros((n,), jnp.int32)
        staged = jnp.full((n,), fam.k_steps - 1, jnp.int32)
        return ((state,) + per2 * fam.k_steps
                + (wmv, cursor, base, staged) + tier)
    if fam.kind == "dcn_resident":
        # [depth, B] slot-major stacks + per-shard wm columns / done /
        # fills (fills = depth - 1: both cond branches live)
        D = fam.k_steps
        n = ctx.n_shards
        stack = tuple(jnp.broadcast_to(a, (D,) + a.shape) for a in per)
        wm = jnp.zeros((D, n), jnp.int32)
        done = jnp.zeros((n,), jnp.int32)
        fills = jnp.full((n,), D - 1, jnp.int32)
        return (state,) + stack + (wm, done, fills)
    if fam.kind in ("fire", "fire_reduced"):
        return (state, watermark_vector(ctx, 0))
    if fam.kind == "session":
        # (state, hi, lo, ts, values, valid, per-shard watermark)
        return (state,) + per + (jnp.zeros(ctx.n_shards, jnp.int32),)
    if fam.kind in ("count", "rolling"):
        # (state, hi, lo, values, valid) — no event-time operands
        return (state, per[0], per[1], per[3], per[4])
    return (state,)


def build_family(fam: KernelFamily, ctx: MeshContext,
                 batch: int = AUDIT_BATCH):
    """Instantiate one canonical family: ``(fn, example_args,
    donate_argnums)``. ``fn`` is exactly what the executor would hold
    (the exchange route's plain wrapper keeps its jitted inner on
    ``.jit`` for AOT consumers); ``example_args`` is a concrete call the
    auditor can make_jaxpr / lower / compile against."""
    spec = audit_stage_spec(fam)
    kw = {}
    if fam.kind in ("update", "megastep", "megastep_fired",
                    "resident_drain", "sharded_drain", "chained_drain",
                    "chained_drain_sharded", "while_drain",
                    "while_drain_sharded"):
        kw["insert"] = fam.insert
        kw["kg_fill"] = True
    if fam.route == "exchange":
        kw["batch_per_device"] = batch
    if fam.kind in ("megastep", "megastep_fired"):
        kw["k_steps"] = fam.k_steps
    if fam.kind in ("megastep_fired", "resident_drain", "sharded_drain"):
        kw["reduced"] = fam.reduced
    if fam.kind in ("resident_drain", "sharded_drain"):
        kw["depth"] = fam.k_steps
        kw["drain_stats"] = fam.drain_stats
    if fam.kind in ("update", "megastep", "megastep_fired",
                    "resident_drain", "sharded_drain"):
        kw["tiered"] = fam.tiered
    if fam.kind in ("chained_drain", "chained_drain_sharded"):
        kw["depth"] = fam.k_steps
        kw["exchange_lanes"] = AUDIT_EXCHANGE_LANES
        kw["drain_stats"] = fam.drain_stats
    if fam.kind in ("while_drain", "while_drain_sharded"):
        kw["max_slots"] = fam.k_steps
        kw["reduced"] = fam.reduced
        kw["drain_stats"] = fam.drain_stats
        kw["tiered"] = fam.tiered
    if fam.kind == "dcn_resident":
        kw["depth"] = fam.k_steps
        kw["insert"] = fam.insert
        kw["drain_stats"] = fam.drain_stats
    fn = fam.builder(ctx, spec, **kw)
    init = {
        "session": init_session_state,
        "count": init_count_state,
        "rolling": init_rolling_state,
    }.get(fam.kind, init_sharded_state)
    if fam.kind in ("chained_drain", "chained_drain_sharded"):
        state = tuple(init_sharded_state(ctx, s) for s in spec)
    else:
        state = init(ctx, spec)
    args = _family_example_args(fam, ctx, state, batch)
    return fn, args, ((0,) if fam.donated else ())
