"""Checkpoint-compatible pipelined ingest (the front half of the hot loop).

The windowed step loop decomposes into a *prep* half (source poll, host
chain, key/value/timestamp encode — pure host numpy) and an *apply* half
(watermark advance, device step dispatch, fires). Historically the prep
half could run ahead on a prefetch thread ONLY when no snapshot could
ever be taken: offsets were captured live at the consume point, so a
polled-ahead batch would make a checkpoint skip records on restore. The
production configuration — checkpointing on — therefore serialized
source poll + encode with device compute.

This module makes the overlap checkpoint-compatible and pushes two more
stages of the cycle off the step-loop thread:

* **Epoch-tagged prefetch.** Every prepped batch carries the source
  offsets captured immediately after ITS poll (``Source.
  poll_with_offsets``) plus the pipeline epoch it was prepped under.
  The executor records the offsets of the last *applied* batch; a
  checkpoint/savepoint snapshots those applied offsets, so the cut is
  exactly the state the device has absorbed — in-flight prefetched
  batches are simply dropped on restore (the epoch bump invalidates
  them) and replayed from the rewound source.

* **Async device staging.** With a plan installed (``IngestPlan``, built
  once the stage's compiled steps exist), the prefetch thread pads the
  batch into a preallocated staging ring and ``jax.device_put``s the
  ``hi/lo/ticks/values/valid`` arrays with the route's sharding
  (replicated for the mask route, shard-split on the batch axis for the
  exchange route). The H2D transfer of batch k+1 completes on the
  ingest thread while the device runs the step for batch k; the step
  loop dispatches committed arrays and never pays the pad-copy or the
  transfer enqueue.

* **Off-thread route planning.** The exchange-feasibility check
  (``plan_route`` — the same murmur key-group math the device uses,
  ~2-4 ms of numpy per 262k batch) runs at prep time, reusing its
  key-group computation for the per-(src,dst) bucket fit check, so the
  step loop reads a precomputed route instead of hashing the batch
  again.

Threading contract: ONE producer (the prefetch thread — or the step-loop
thread itself when ``pipeline.prefetch=off``), one consumer (the step
loop). ``pause()``/``resume()`` bracket every source mutation (restore):
pause parks the producer, resume bumps the epoch so queued batches from
the old stream position are discarded by the consumer.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from collections import deque
from typing import Any, Callable, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from flink_tpu.core.keygroups import assign_to_key_group
from flink_tpu.ops.hashing import route_hash
from flink_tpu.parallel.mesh import SHARD_AXIS
from flink_tpu.testing import faults


class IngestThreadDied(RuntimeError):
    """The prefetch producer thread died without delivering a batch or
    an error (hard death — e.g. an injected ``kill`` rule or a native
    crash in the prep path). Classified TRANSIENT at the restart
    boundary: the thread is respawned by the next ``next()`` after a
    restore, so a warm in-process restart fully recovers it."""


# ---------------------------------------------------------------- masks

def make_prefix_mask_template(size: int) -> np.ndarray:
    """One bool template of length 2*size: [True]*size + [False]*size.
    ``prefix_mask(tmpl, n)`` slices a VIEW whose first n lanes are True —
    the per-batch ``np.ones(n) + pad`` allocation becomes one allocation
    per stage. The template is frozen so a view handed to an async
    transfer can never be corrupted by later batches."""
    tmpl = np.zeros(2 * size, bool)
    tmpl[:size] = True
    tmpl.flags.writeable = False
    return tmpl


def prefix_mask(tmpl: np.ndarray, n: int) -> np.ndarray:
    """bool[size] view of `tmpl` with lanes [0, n) True; 0 <= n <= size."""
    size = len(tmpl) >> 1
    return tmpl[size - n: 2 * size - n]


# ------------------------------------------------------------- batches

@dataclasses.dataclass
class PreppedBatch:
    """One prepped micro-batch flowing from the ingest side to the step
    loop. ``offsets`` is the source position captured right after this
    batch's poll — the epoch-tagged replay point; ``epoch`` stamps which
    pipeline incarnation prepped it (batches from a pre-restore epoch
    are dropped by the consumer)."""

    end: bool
    n: int
    now_ms: int
    t_src: float
    offsets: Any = None
    epoch: int = -1
    # host-side encoded arrays (None once staged to device, or when n=0)
    hi: Any = None
    lo: Any = None
    values: Any = None
    ts_ms: Any = None
    # filled by the ingest plan for single-group batches
    ticks: Any = None            # host int32, planned-but-unstaged batches
    ticks_min: Optional[int] = None
    ticks_max: Optional[int] = None
    ts_max: Optional[int] = None
    # "mask" | "exchange" | "sharded" | None (unplanned)
    route: Optional[str] = None
    # device-staged (hi, lo, ticks, values, valid) committed arrays
    staged: Optional[Tuple] = None
    # device batch ring slot sequence (pipeline.resident-loop): set when
    # ``staged`` lives in a DeviceBatchRing slot; the consumer releases
    # the slot once the batch's ring drain retired it. None = staged
    # outside the ring (ring full, or resident loop off).
    ring_seq: Optional[int] = None
    # per-shard slot sequences (pipeline.data-parallel): one entry per
    # shard when ``staged`` lives in a ShardedDeviceBatchRing — a None
    # entry means THAT shard's lane ring was full and its slice was
    # staged fresh (the shard-local backpressure seam); the consumer
    # releases per shard at the drain boundary (release_shards)
    ring_seqs: Optional[list] = None
    # poll sequence number, given at the poll: the `batch` attribute of
    # every span this batch passes through
    seq: int = -1


@dataclasses.dataclass
class IngestPlan:
    """Everything the prep side needs once the stage is set up: the time
    domain, the step lane geometry, the exchange capacity, and the
    shardings each route's compiled step expects its batch arrays in.
    Installed via ``IngestPipeline.set_plan`` after ``setup()`` builds
    the compiled steps (and re-installed on restore — the time-domain
    origin can change); batches prepped before that arrive unplanned and
    take the executor's legacy host-array path."""

    td: Any                      # core.time.TimeDomain
    slide_ticks: int
    span_limit: int              # catch-up slicing threshold (panes)
    B: int                       # micro-batch lane count
    B_step: int                  # step lane count (B padded to shards)
    n_shards: int
    max_parallelism: int
    kg_ends: Any                 # np int32 [n_shards] key-group range ends
    exchange_cap: int            # per-(src,dst) bucket lanes, 0 = no exchange
    routes: Tuple[str, ...]      # available compiled routes
    staging: bool                # device-stage on the ingest thread?
    mask_sharding: Any = None    # replicated batch arrays (mask route)
    split_sharding: Any = None   # batch-axis split (exchange route)
    value_shape: Tuple = ()
    value_dtype: Any = np.float32
    # device batch ring depth (pipeline.resident-loop / ring-depth):
    # > 0 promotes the staging ring to a DeviceBatchRing of this many
    # committed HBM slots; 0 keeps the plain PR 3 staging ring
    ring_depth: int = 0
    # per-shard lane capacity of the data-parallel route (pipeline.
    # data-parallel): > 0 (with "sharded" in ``routes``) promotes the
    # device ring to a ShardedDeviceBatchRing — each batch is host-
    # partitioned by owning key-group slice and published as [n_shards,
    # shard_cap] per-chip lane slices; 0 keeps the global-slot ring
    shard_cap: int = 0

    @staticmethod
    def shardings_for(mesh):
        return NamedSharding(mesh, P()), NamedSharding(mesh, P(SHARD_AXIS))


def plan_route(plan: IngestPlan, hi: np.ndarray, lo: np.ndarray,
               kg: Optional[np.ndarray] = None) -> str:
    """Exact per-batch feasibility of the ICI exchange, at prep time.

    Computes every lane's owning shard (the same murmur key-group math
    the device uses) and picks the O(B/n)-per-device all_to_all step
    only when each (source device, dest shard) bucket provably fits its
    static capacity — skew falls back to replicate-and-mask, so the
    adaptive route is never lossy. Runs on the UNPADDED arrays: padding
    lanes are invalid on device and lane i's source device is i//bpd
    either way, so the counts match the padded check exactly. ``kg``
    lets a caller that already computed the key groups (the sharded-
    route planner) skip the second murmur pass."""
    if "exchange" not in plan.routes:
        return "mask"
    if "mask" not in plan.routes:
        return "exchange"        # exchange.mode=all_to_all forced
    n = plan.n_shards
    if kg is None:
        kg = assign_to_key_group(route_hash(hi, lo, np),
                                 plan.max_parallelism, np)
    shard = np.searchsorted(plan.kg_ends, kg)
    bpd = plan.B_step // n
    src = np.arange(len(hi)) // bpd
    counts = np.bincount(src * n + shard, minlength=n * n)
    return (
        "exchange" if counts.max(initial=0) <= plan.exchange_cap
        else "mask"
    )


def plan_route_and_shards(
    plan: IngestPlan, hi: np.ndarray, lo: np.ndarray
) -> Tuple[str, Optional[np.ndarray]]:
    """Data-parallel route plan (pipeline.data-parallel): ONE key-group
    pass decides the route AND hands back every lane's owning shard.

    The sharded route is feasible when each shard's slice of the batch
    fits its static per-shard lane capacity (``plan.shard_cap``) — the
    host then partitions the batch and each chip receives only its own
    O(cap) lanes. A batch too skewed to fit falls back to the ordinary
    ``plan_route`` choice (reusing the computed key groups), so the
    adaptive ladder is sharded -> exchange -> mask and never lossy."""
    kg = assign_to_key_group(route_hash(hi, lo, np), plan.max_parallelism,
                             np)
    if "sharded" in plan.routes and plan.shard_cap > 0:
        shard = np.searchsorted(plan.kg_ends, kg)
        counts = np.bincount(shard, minlength=plan.n_shards)
        if counts.max(initial=0) <= plan.shard_cap:
            return "sharded", shard
    return plan_route(plan, hi, lo, kg=kg), None


def _route_sharding(plan: IngestPlan, route: str):
    # sharded batches are [n_shards, cap] arrays split on the leading
    # (shard) axis — the same P(SHARD_AXIS) sharding the exchange route
    # uses on its batch axis
    return (
        plan.split_sharding if route in ("exchange", "sharded")
        else plan.mask_sharding
    )


def stage_batch_arrays(plan: IngestPlan, route: str, hi, lo, ticks,
                       values, valid) -> Tuple:
    """Step-loop-thread staging of already-padded FRESH arrays (the
    executor's fallback call sites: warmup, catch-up slices, chunked
    polls). Non-blocking — the transfer is enqueued and the arrays are
    never reused by the caller, so there is no buffer-recycle hazard.
    Exists so every update dispatch feeds the compiled step committed
    arrays of the SAME sharding: mixing committed and uncommitted inputs
    would recompile the step mid-stream."""
    sh = _route_sharding(plan, route)
    return tuple(
        jax.device_put(x, sh) for x in (hi, lo, ticks, values, valid)
    )


def _host_probe_put_aliases(buf: np.ndarray, sharding) -> bool:
    """One-time ring-init probe (host-side by contract): does
    ``jax.device_put`` of THIS buffer alias its memory instead of
    copying?  XLA's CPU client zero-copies suitably-aligned host
    buffers — the "staged" array then IS the buffer, and recycling the
    slot would corrupt every batch still referencing it. Aliasing is
    decided per allocation (alignment), so each slot buffer is probed
    individually. Mutates one lane and restores it."""
    flat = buf.reshape(-1)
    d = jax.device_put(flat[:1], sharding)
    jax.block_until_ready(d)
    old = flat[0]
    flat[0] = 1 if old == 0 else 0
    aliased = bool(np.asarray(d)[0] != old)
    flat[0] = old
    return aliased


# per-PROCESS zero-copy aliasing verdicts (ISSUE 12 small fix): the
# probe used to run per ring init — per JOB — so bench sweeps and test
# suites that build dozens of pipelines in one process paid the device
# round trips over and over. The verdict is a property of the backend's
# device_put path, not of the job, so it is cached process-wide:
#
#   * non-CPU platforms skip the probe entirely — an accelerator
#     device_put is architecturally an H2D copy into HBM; host memory
#     can never alias it.
#   * on CPU only the ALIASED verdict is sticky: one observed zero-copy
#     proves the client takes that path, and disabling slot reuse (the
#     consequence) is the safe direction for every later ring. An
#     all-False probe is NOT cached — aliasing is decided per
#     allocation (alignment), so a later ring's differently-aligned
#     buffers could still alias, and caching False there is exactly the
#     silent-corruption direction the probe exists to prevent.
_put_alias_sticky: dict = {}


def _host_put_aliases_cached(bufs, sharding) -> bool:
    platform = jax.default_backend()
    if platform != "cpu":
        return False
    if _put_alias_sticky.get(platform):
        return True
    aliased = any(_host_probe_put_aliases(b, sharding) for b in bufs)
    if aliased:
        _put_alias_sticky[platform] = True
    return aliased


class StagingRing:
    """Preallocated host padding buffers for the prefetch thread's
    device staging — the per-batch ``np.zeros`` padding in ``_pad``
    becomes a write into a recycled slot. A slot is reused only after
    its transfer COMPLETED: ``stage()`` blocks on the put, on the ingest
    thread, so the step loop never waits and the recycled bytes can
    never race an in-flight H2D copy. Depth 2 double-buffers (one slot
    being written while the previous one finishes transferring).

    Backends whose ``device_put`` ZERO-COPIES host memory (XLA CPU with
    aligned buffers) make recycling impossible: the staged array aliases
    the slot forever, so ``stage()`` detects that at init (per-buffer
    probe) and falls back to fresh per-batch buffers there — on such
    backends there is no H2D copy to overlap anyway, so the ring's only
    job is correctness."""

    def __init__(self, plan: IngestPlan, depth: int = 2):
        Bs = plan.B_step
        vshape = (Bs,) + tuple(plan.value_shape)

        def one_slot():
            return {
                "hi": np.zeros(Bs, np.uint32),
                "lo": np.zeros(Bs, np.uint32),
                "ticks": np.zeros(Bs, np.int32),
                "values": np.zeros(vshape, plan.value_dtype),
            }

        self._make_slot = one_slot
        self._slots = [one_slot() for _ in range(max(2, int(depth)))]
        self._i = 0
        self._mask_tmpl = make_prefix_mask_template(Bs)
        self._reuse = not _host_put_aliases_cached(
            [buf for slot in self._slots for buf in slot.values()],
            plan.mask_sharding,
        )

    @staticmethod
    def _fill(buf: np.ndarray, arr: np.ndarray, n: int) -> np.ndarray:
        if len(arr) == len(buf):
            return arr           # full batch: fresh array, ship directly
        buf[:n] = arr
        buf[n:] = 0
        return buf

    def stage(self, plan: IngestPlan, hi, lo, ticks, values, n: int,
              route: str, tracer=None, batch: int = -1) -> Tuple:
        """Pad into the next ring slot and device_put with the route's
        sharding; returns committed (hi, lo, ticks, values, valid)."""
        if self._reuse:
            slot = self._slots[self._i]
            self._i = (self._i + 1) % len(self._slots)
        else:
            # zero-copy backend: the staged array will alias whatever we
            # hand it — hand it single-use buffers, never the ring's
            slot = self._make_slot()
        t0 = time.perf_counter()
        srcs = (
            self._fill(slot["hi"], hi, n),
            self._fill(slot["lo"], lo, n),
            self._fill(slot["ticks"], ticks, n),
            self._fill(slot["values"], values, n),
            prefix_mask(self._mask_tmpl, n),
        )
        t_pad = time.perf_counter()
        sh = _route_sharding(plan, route)
        staged = tuple(jax.device_put(x, sh) for x in srcs)
        # transfer completion ON THE INGEST THREAD: the slot may be
        # recycled the moment the device owns the bytes, and the step
        # loop receives arrays it can dispatch without ever waiting
        jax.block_until_ready(staged)  # host-sync-ok: ingest-thread transfer completion, off the step loop
        if tracer is not None and tracer.active:
            tracer.rec("stage", t0, t_pad, n=n, batch=batch)
            tracer.rec("transfer", t_pad, route=route, batch=batch)
        return staged


class DeviceBatchRing:
    """Device-resident batch ring (pipeline.resident-loop, ISSUE 12):
    PR 3's staging ring promoted to a bounded ring of COMMITTED device
    batch slots with a host-side write cursor, so the step loop can see
    "how many staged batches are ready right now" and retire them all
    with one resident-drain dispatch (runtime/step.py
    build_window_resident_drain) instead of one megastep each.

    Layout: ``depth`` slots, each pairing one preallocated host padding
    buffer set (the embedded StagingRing, sized to the ring so every
    in-flight slot has its own pad buffers) with the committed device
    arrays staged through it. A slot is (seq, epoch, staged 5-tuple);
    the staged arrays are the slot's HBM residency — publishing bounds
    the device footprint to ``depth`` batches, and releasing a slot
    drops the last reference so the arrays free as soon as the drain
    that consumed them retires. (JAX owns physical allocation; the ring
    owns the lifetime, which is the half a host-side cursor can pin.)

    Threading contract (SPSC, same as the pipeline): ONE producer — the
    prefetch thread — publishes; ONE consumer — the step loop — reads
    occupancy and releases. ``try_publish`` is the producer's whole
    surface: it stages into the next slot and advances the write cursor,
    or returns None when the ring is full (the caller falls back to
    plain staging, so a slow drain never blocks the source poll). The
    write cursor is advanced AFTER the slot contents are in place, so
    the consumer can never observe a half-published slot; cursors are
    plain ints mutated under one lock (the critical sections are
    pointer-sized — the cursor-race property test drives this seam).

    Epoch discard: every slot carries the pipeline epoch it was staged
    under. ``clear()`` (called from the pipeline's restore ``resume``,
    after ``pause`` parked the producer) retires every in-flight slot —
    the epoch bump already invalidates the queued PreppedBatches that
    reference them, and the rewound source replays those records."""

    sharded = False    # ShardedDeviceBatchRing overrides

    def __init__(self, plan: IngestPlan, depth: int):
        self.depth = max(2, int(depth))
        self._staging = StagingRing(plan, self.depth)
        self._slots: list = [None] * self.depth
        self._write = 0          # seq of the next slot to publish
        self._read = 0           # seq of the oldest unreleased slot
        self._refusals = 0       # full-ring publish refusals (backpressure)
        # drain flight recorder (observability.drain-stats): publish-time
        # stamps (shard, seq, fill, max_tick, t) appended in the locked
        # commit below and drained by the executor's consume path; the
        # executor flips stats_enabled so the default path appends nothing
        self.stats_enabled = False
        self._pub_samples: deque = deque(maxlen=4096)
        # device publish cursor (pipeline.resident-loop=while, ISSUE 20):
        # a tiny HBM int32 slot mirroring the host write cursor. The
        # ingest thread refreshes it after every commit; the while-drain
        # dispatch takes the freshest copy (donated, so an aliasing
        # runtime reuses the same HBM slot) and its loop condition
        # re-reads it — a batch published mid-drain retires in the same
        # dispatch. Disabled (None) unless the executor opts in.
        self._cursor_sharding = None
        self._cursor = None
        self._lock = threading.Lock()

    def enable_device_cursor(self, sharding) -> None:
        """Opt in to the HBM publish cursor (while-drain mode). The
        sharding is the replicated scalar-slot sharding the while-drain
        kernel expects for its ``cursor`` operand."""
        with self._lock:
            self._cursor_sharding = sharding
            self._cursor = jax.device_put(
                np.full(1, self._write, np.int32), sharding)

    def device_cursor(self):
        """``(cursor, write_snapshot)`` — the freshest device-resident
        publish cursor (int32[1]) plus the host write seq it encodes
        (read under the same lock, so the pair is consistent) — or None
        when the cursor slot is disabled. The caller passes the array
        straight into the while-drain dispatch and derives the drain
        base from the snapshot; the array is replaced (never mutated)
        on every commit, so a grabbed reference is a stable snapshot
        lower-bounding the live value."""
        with self._lock:
            if self._cursor is None:
                return None
            return self._cursor, self._write

    def refresh_device_cursor(self) -> None:
        """Re-stage the cursor slot (the consumer calls this right after
        a while-drain dispatch donated the grabbed array, so a quiet
        stream's NEXT drain never re-passes a deleted buffer)."""
        with self._lock:
            if self._cursor_sharding is not None:
                self._cursor = jax.device_put(
                    np.full(1, self._write, np.int32),
                    self._cursor_sharding)

    # -- producer (prefetch thread) --------------------------------------
    def try_publish(self, plan: IngestPlan, hi, lo, ticks, values,
                    n: int, route: str, epoch: int, tracer=None,
                    batch: int = -1) -> Optional[Tuple[int, Tuple]]:
        """Stage one batch into the next ring slot; returns (seq,
        staged) or None when the ring is full. The stage itself blocks
        for transfer completion on THIS thread (StagingRing.stage), so a
        published slot's arrays are always dispatch-ready."""
        with self._lock:
            if self._write - self._read >= self.depth:
                # counted, not silent: the ring_publish_refusals gauge
                # makes a stalled drain observable as backpressure
                # instead of an unexplained throughput dip
                self._refusals += 1
                return None
            seq = self._write
        staged = self._staging.stage(plan, hi, lo, ticks, values, n,
                                     route, tracer=tracer, batch=batch)
        max_tick = int(ticks[:n].max()) if n else None
        with self._lock:
            self._slots[seq % self.depth] = (seq, epoch, staged)
            self._write = seq + 1
            if self._cursor_sharding is not None:
                # refresh the HBM cursor slot AFTER the commit so the
                # device can never see a cursor covering a slot whose
                # payload isn't resident yet (the while-drain's staged
                # clamp guards the packed-operand side)
                self._cursor = jax.device_put(
                    np.full(1, self._write, np.int32),
                    self._cursor_sharding)
            if self.stats_enabled:
                self._pub_samples.append((
                    0, seq, self._write - self._read, max_tick,
                    time.perf_counter(),
                ))
        return seq, staged

    # -- consumer (step loop) --------------------------------------------
    def occupancy(self) -> int:
        """Committed-but-unreleased slots: write cursor - read cursor."""
        with self._lock:
            return self._write - self._read

    def release_through(self, seq: int) -> int:
        """Retire every slot up to and including ``seq`` (a drain
        returned for them — the ring-drain exactly-once boundary).
        Returns the number of slots released. Out-of-window seqs are a
        no-op: a restore's ``clear`` may already have retired them."""
        with self._lock:
            if seq < self._read:
                return 0
            upto = min(seq, self._write - 1)
            n = upto - self._read + 1
            for s in range(self._read, upto + 1):
                self._slots[s % self.depth] = None
            self._read = upto + 1
            return n

    def clear(self) -> int:
        """Restore path: discard every in-flight slot (their epoch is
        pre-bump; the queued batches referencing them are dropped by the
        consumer's epoch check and replay from the rewound source)."""
        with self._lock:
            n = self._write - self._read
            self._slots = [None] * self.depth
            self._read = self._write
            return n

    def refusals(self) -> list:
        """Per-shard full-ring publish refusal counts (one entry here —
        the global-slot ring has a single lane); the executor surfaces
        the sum and the per-shard breakdown as gauges."""
        with self._lock:
            return [self._refusals]

    def occupancy_shards(self) -> list:
        """Per-lane committed-but-unreleased counts (one lane here)."""
        with self._lock:
            return [self._write - self._read]

    def publish_samples(self) -> list:
        """Drain the publish-time stamp buffer (drain flight recorder);
        empty unless the executor enabled ``stats_enabled``."""
        with self._lock:
            out = list(self._pub_samples)
            self._pub_samples.clear()
        return out


class ShardedDeviceBatchRing:
    """Per-shard device batch ring (pipeline.data-parallel, ISSUE 13):
    the DeviceBatchRing split into ``n_shards`` independent lanes. The
    prefetch thread partitions each planned batch by owning key-group
    slice (one stable-sort pass — stable so a key's records keep their
    arrival order and float accumulation is bit-exact vs the single-chip
    oracle), pads each shard's slice into that shard's ring slot, and
    device_puts the (1, cap) row DIRECTLY onto the owning chip. The
    per-slot global [n_shards, cap] arrays are then assembled ZERO-COPY
    from the committed rows (jax.make_array_from_single_device_arrays)
    under the split sharding the sharded drain kernel expects — no chip
    ever receives another chip's lanes, on the wire or in HBM.

    Per-shard write/read cursors are the "one slow shard never blocks
    the others" seam: a full lane refuses ONLY its own shard's row
    (counted in that shard's refusal counter; the row is staged fresh,
    unringed, and its ``ring_seqs`` entry is None), while every other
    shard's row still publishes into its recycled slot. The consumer
    releases per shard at ring-drain boundaries (``release_shards``
    with the drained per-shard sequence vector).

    Threading contract is the DeviceBatchRing's: one producer (prefetch
    thread) publishes, one consumer (step loop) releases; cursors are
    plain ints under one lock."""

    sharded = True

    def __init__(self, plan: IngestPlan, depth: int):
        self.depth = max(2, int(depth))
        self.n_shards = plan.n_shards
        self.cap = int(plan.shard_cap)
        vshape = (self.cap,) + tuple(plan.value_shape)
        mesh = plan.split_sharding.mesh
        self._devices = list(mesh.devices.flat)
        self._split = plan.split_sharding
        self._vdtype = plan.value_dtype

        def one_slot():
            return {
                "hi": np.zeros(self.cap, np.uint32),
                "lo": np.zeros(self.cap, np.uint32),
                "ticks": np.zeros(self.cap, np.int32),
                "values": np.zeros(vshape, plan.value_dtype),
            }

        self._make_slot = one_slot
        # per-shard slot buffer pools + cursors; a slot pins its rows'
        # lifetime (the committed global array holds the same buffers)
        self._bufs = [
            [one_slot() for _ in range(self.depth)]
            for _ in range(self.n_shards)
        ]
        self._slots = [[None] * self.depth for _ in range(self.n_shards)]
        self._write = [0] * self.n_shards
        self._read = [0] * self.n_shards
        self._refusals = [0] * self.n_shards
        # drain flight recorder stamps — see DeviceBatchRing
        self.stats_enabled = False
        self._pub_samples: deque = deque(maxlen=4096)
        # per-shard device publish cursor (while-drain mode) — int32
        # [n_shards] under the shard axis; see DeviceBatchRing
        self._cursor_sharding = None
        self._cursor = None
        self._lock = threading.Lock()
        self._mask_tmpl = make_prefix_mask_template(self.cap)
        self._reuse = not _host_put_aliases_cached(
            [b for pool in self._bufs for slot in pool
             for b in slot.values()],
            plan.mask_sharding,
        )

    def enable_device_cursor(self, sharding) -> None:
        """Opt in to the per-shard HBM publish cursor (while-drain
        mode); ``sharding`` places int32[n_shards] one entry per owning
        chip (shard axis)."""
        with self._lock:
            self._cursor_sharding = sharding
            self._cursor = jax.device_put(
                np.fromiter(self._write, np.int32, self.n_shards),
                sharding)

    def device_cursor(self):
        """``(cursor, write_snapshots)`` — the freshest per-shard
        publish cursor (int32[n_shards]) plus the per-shard host write
        seqs it encodes — or None; see DeviceBatchRing.device_cursor."""
        with self._lock:
            if self._cursor is None:
                return None
            return self._cursor, tuple(self._write)

    def refresh_device_cursor(self) -> None:
        """Re-stage the per-shard cursor after a while-drain dispatch
        donated the grabbed array; see DeviceBatchRing."""
        with self._lock:
            if self._cursor_sharding is not None:
                self._cursor = jax.device_put(
                    np.fromiter(self._write, np.int32, self.n_shards),
                    self._cursor_sharding)

    @staticmethod
    def _fill(buf: np.ndarray, arr: np.ndarray, c: int) -> np.ndarray:
        buf[:c] = arr
        buf[c:] = 0
        return buf

    # -- producer (prefetch thread) --------------------------------------
    def publish_batch(self, plan: IngestPlan, hi, lo, ticks, values,
                      shard: np.ndarray, n: int, epoch: int,
                      tracer=None, batch: int = -1) -> Tuple[list, Tuple]:
        """Partition one planned batch by owning shard and publish each
        slice into that shard's ring lane. Returns ``(ring_seqs,
        staged)``: per-shard slot sequences (None where that lane was
        full and the row went out fresh) and the committed global
        [n_shards, cap] 5-tuple the sharded drain consumes. Never
        refuses the whole batch — the global-array contract needs every
        shard's row either way, so a full lane costs one fresh
        allocation, not a stall."""
        t0 = time.perf_counter()
        order = np.argsort(shard[:n], kind="stable")
        counts = np.bincount(shard[:n], minlength=self.n_shards)
        srcs = (hi[order], lo[order], ticks[order], values[order])
        seqs: list = [None] * self.n_shards
        rows = ([], [], [], [], [])
        pos = 0
        for s in range(self.n_shards):
            c = int(counts[s])
            with self._lock:
                if self._write[s] - self._read[s] < self.depth:
                    seqs[s] = self._write[s]
                else:
                    self._refusals[s] += 1
            if self._reuse and seqs[s] is not None:
                bufs = self._bufs[s][seqs[s] % self.depth]
            else:
                # zero-copy backend or full lane: single-use buffers
                bufs = self._make_slot()
            filled = (
                self._fill(bufs["hi"], srcs[0][pos:pos + c], c),
                self._fill(bufs["lo"], srcs[1][pos:pos + c], c),
                self._fill(bufs["ticks"], srcs[2][pos:pos + c], c),
                self._fill(bufs["values"], srcs[3][pos:pos + c], c),
                prefix_mask(self._mask_tmpl, c),
            )
            pos += c
            dev = self._devices[s]
            for j, x in enumerate(filled):
                # (1, cap) row committed onto the OWNING chip only
                rows[j].append(jax.device_put(x[None], dev))
        t_pad = time.perf_counter()
        staged = tuple(
            jax.make_array_from_single_device_arrays(
                (self.n_shards,) + r[0].shape[1:], self._split, r,
            )
            for r in rows
        )
        # transfer completion ON THE INGEST THREAD (StagingRing.stage
        # contract): a published slot's rows are dispatch-ready
        jax.block_until_ready(staged)  # host-sync-ok: ingest-thread transfer completion, off the step loop
        max_tick = int(ticks[:n].max()) if n else None
        t_pub = time.perf_counter()
        with self._lock:
            for s in range(self.n_shards):
                if seqs[s] is not None:
                    self._slots[s][seqs[s] % self.depth] = (
                        seqs[s], epoch, tuple(r[s] for r in rows),
                    )
                    self._write[s] = seqs[s] + 1
                if self.stats_enabled:
                    self._pub_samples.append((
                        s, seqs[s], self._write[s] - self._read[s],
                        max_tick, t_pub,
                    ))
            if self._cursor_sharding is not None:
                # post-commit refresh; see DeviceBatchRing.try_publish
                self._cursor = jax.device_put(
                    np.fromiter(self._write, np.int32, self.n_shards),
                    self._cursor_sharding)
        if tracer is not None and tracer.active:
            tracer.rec("stage", t0, t_pad, n=n, batch=batch)
            tracer.rec("transfer", t_pad, route="sharded", batch=batch)
        return seqs, staged

    # -- consumer (step loop) --------------------------------------------
    def occupancy(self) -> int:
        """Deepest lane's committed-but-unreleased slot count."""
        with self._lock:
            return max(
                self._write[s] - self._read[s]
                for s in range(self.n_shards)
            )

    def release_shards(self, seqs) -> int:
        """Retire each shard's slots up to and including ``seqs[s]`` (a
        drain returned for them — the per-shard exactly-once boundary).
        None entries (that shard published nothing ringed in the
        drained group) and out-of-window seqs are no-ops. Returns total
        slots released."""
        total = 0
        with self._lock:
            for s, seq in enumerate(seqs):
                if seq is None or seq < self._read[s]:
                    continue
                upto = min(int(seq), self._write[s] - 1)
                for q in range(self._read[s], upto + 1):
                    self._slots[s][q % self.depth] = None
                total += upto - self._read[s] + 1
                self._read[s] = upto + 1
        return total

    def release_through(self, seq: int) -> int:
        """Uniform release — every shard through ``seq`` (fallback call
        sites that only track a scalar frontier)."""
        return self.release_shards([seq] * self.n_shards)

    def clear(self) -> int:
        """Restore path: discard every lane's in-flight slots (epoch
        bump invalidated the batches referencing them)."""
        with self._lock:
            n = sum(
                self._write[s] - self._read[s]
                for s in range(self.n_shards)
            )
            self._slots = [
                [None] * self.depth for _ in range(self.n_shards)
            ]
            self._read = list(self._write)
            return n

    def refusals(self) -> list:
        """Per-shard full-lane publish refusal counts."""
        with self._lock:
            return list(self._refusals)

    def occupancy_shards(self) -> list:
        """Per-shard committed-but-unreleased slot counts."""
        with self._lock:
            return [
                self._write[s] - self._read[s]
                for s in range(self.n_shards)
            ]

    def publish_samples(self) -> list:
        """Drain the publish-time stamp buffer (drain flight recorder);
        empty unless the executor enabled ``stats_enabled``."""
        with self._lock:
            out = list(self._pub_samples)
            self._pub_samples.clear()
        return out


# ------------------------------------------------------- fused dispatch

class FusedBatchAccumulator:
    """Fused-dispatch slot for ``pipeline.steps-per-dispatch=K``: collects
    up to K consecutive planned micro-batches that share a route and a
    staging mode, which the executor then hands to ONE compiled lax.scan
    megastep (runtime/step.py build_window_megastep*). The flush triggers
    — route change, checkpoint/savepoint cut, idle poll, end of stream,
    restore, and (split-dispatch mode only) fire boundary — are all
    step-loop state, so the executor drives; this class owns the slot
    bookkeeping so the grouping contract is unit-testable.

    ``hold_fires`` records the resident-pipeline mode
    (pipeline.fused-fire): the fire sweep is folded into the megastep
    scan, so a pane-boundary crossing inside the group no longer breaks
    it — groups stay K-full across fire boundaries and the in-scan
    advance fires each sub-batch under its own watermark. With it off
    (the PR-5 split-dispatch behavior, still the partial-group and DCN
    fallback) the executor flushes early at every fire boundary so the
    separate fire dispatch sees every pending update.

    Exactly-once contract: a batch sitting in the slot has NOT been
    dispatched, so its offsets must not become the applied cut until the
    flush — the executor marks the LAST flushed batch applied, which is
    the megastep-boundary snapshot cut."""

    def __init__(self, k: int, hold_fires: bool = False):
        self.k = max(1, int(k))
        self.hold_fires = bool(hold_fires)
        self.items: list = []      # [(args 5-tuple, wm_ms | None, pb)]
        self.route: Optional[str] = None
        self.staged: Optional[bool] = None

    def __len__(self):
        return len(self.items)

    def compatible(self, route: str, staged: bool) -> bool:
        """Can a batch of this route/staging mode join the open group?"""
        return not self.items or (
            route == self.route and staged == self.staged
        )

    def push(self, args: Tuple, wm_ms, pb, route: str, staged: bool):
        if not self.items:
            self.route, self.staged = route, staged
        self.items.append((args, wm_ms, pb))

    def full(self) -> bool:
        return len(self.items) >= self.k

    def drain(self):
        """Take the group: (route, staged, items). Resets the slot."""
        items, self.items = self.items, []
        route, staged = self.route, self.staged
        self.route = self.staged = None
        return route, staged, items

    def clear(self):
        """Restore path: pending batches belong to the pre-restore epoch
        — they are discarded and replay from the rewound source."""
        self.items = []
        self.route = self.staged = None


# ------------------------------------------------------------- pipeline

class IngestPipeline:
    """Single-producer single-consumer prep pipeline with restore-safe
    epochs.

    * ``next()`` — the step loop's batch intake. With prefetch on it
      drains the bounded queue (stale-epoch batches are skipped,
      producer errors re-raise on the consumer); with prefetch off it
      runs the prep function inline. Either way the batch is finished
      against the current plan (route planned, optionally staged).
    * ``mark_applied(pb)`` — the step loop calls this once a batch's
      updates are dispatched; ``applied_offsets()`` then names the cut a
      checkpoint/savepoint must snapshot.
    * ``pause()`` / ``resume(offsets)`` — bracket source mutation
      (restore). Pause parks the producer (waits until it is off the
      source); resume bumps the epoch, drops queued batches, re-arms the
      applied cut, and unparks.

    The producer parks itself after delivering an end-of-stream batch or
    an error instead of exiting: a restore may rewind the source past
    either, and ``resume`` simply continues the same thread.
    """

    def __init__(self, prep_fn: Callable[[], PreppedBatch], *,
                 prefetch: bool, initial_offsets: Any = None,
                 depth: int = 2, ring_depth: int = 2, tracer=None):
        self.prep_fn = prep_fn
        self.prefetch = bool(prefetch)
        self.tracer = tracer
        # serializes SOURCE WIRE interactions: the producer holds it
        # across each poll, and the executor takes it around checkpoint-
        # complete notifications (offset commits may share the poll's
        # connection — e.g. the partitioned socket consumers — and an
        # interleaved commit mid-fetch would corrupt the protocol)
        self.source_lock = threading.RLock()
        self._plan: Optional[IngestPlan] = None
        self._ring: Optional[StagingRing] = None
        self._device_ring: Optional[DeviceBatchRing] = None
        self._ring_depth = max(2, int(ring_depth))
        self._applied = initial_offsets
        self._epoch = 0
        self._q: queue.Queue = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._gate = threading.Event()   # producer runs while set
        self._pause_req = threading.Event()  # consumer-requested pause
        self._parked = threading.Event()
        self._gate.set()
        self._thread: Optional[threading.Thread] = None
        # epoch the live thread was spawned under: a DEAD thread is only
        # respawned after a restore bumped the epoch (see _ensure_thread)
        self._thread_epoch = -1
        self._seq = 0            # poll sequence number of the next batch

    # -- plan ------------------------------------------------------------
    @property
    def plan(self) -> Optional[IngestPlan]:
        return self._plan

    def set_plan(self, plan: IngestPlan):
        """Install/replace the prep plan (attribute publish is atomic;
        batches mid-prep finish under whichever plan they started —
        the consumer handles both planned and unplanned batches). With
        ``plan.ring_depth > 0`` the plan also stands up the device batch
        ring; the plain staging ring stays as the ring-full fallback."""
        if plan.staging:
            self._ring = StagingRing(plan, self._ring_depth)
            if plan.ring_depth > 0:
                # data-parallel mode: per-shard lane rings (re-sliced on
                # every set_plan — the elastic re-plan installs a plan
                # at the new n_shards and gets fresh lanes for free)
                ring_cls = (
                    ShardedDeviceBatchRing
                    if plan.shard_cap > 0 and "sharded" in plan.routes
                    else DeviceBatchRing
                )
                self._device_ring = ring_cls(plan, plan.ring_depth)
            else:
                self._device_ring = None
        else:
            self._ring = None
            self._device_ring = None
        self._plan = plan

    @property
    def device_ring(self) -> Optional[DeviceBatchRing]:
        return self._device_ring

    def _finish(self, pb: PreppedBatch) -> PreppedBatch:
        """Apply the plan to a freshly prepped batch: time-domain ticks,
        pane-span eligibility, route choice, optional device staging.
        Ineligible batches (catch-up spans, host-chain expansion beyond
        B, foreign value dtype) pass through unplanned and take the
        executor's general path."""
        plan = self._plan
        if plan is None or pb.n == 0:
            return pb
        pb.ts_max = int(pb.ts_ms.max())
        ticks = plan.td.to_ticks(pb.ts_ms)
        t_min, t_max = int(ticks.min()), int(ticks.max())
        values = pb.values
        eligible = (
            pb.n <= plan.B
            and (t_max // plan.slide_ticks) - (t_min // plan.slide_ticks)
            < plan.span_limit
            and isinstance(values, np.ndarray)
            and values.dtype == plan.value_dtype
            and values.shape[1:] == tuple(plan.value_shape)
        )
        if not eligible:
            return pb
        pb.ticks_min, pb.ticks_max = t_min, t_max
        t_r0 = time.perf_counter()
        dr = self._device_ring
        shard_of = None
        if dr is not None and dr.sharded:
            # ONE key-group pass plans the route and the partition
            pb.route, shard_of = plan_route_and_shards(plan, pb.hi, pb.lo)
        else:
            pb.route = plan_route(plan, pb.hi, pb.lo)
        tracer = self.tracer
        if tracer is not None and tracer.active:
            tracer.rec("route", t_r0, route=pb.route, planned=True,
                       batch=pb.seq)
        if self._ring is not None:
            pub = None
            if shard_of is not None:
                # data-parallel publish: per-shard slices into per-shard
                # lanes (never refuses the batch — a full lane only
                # costs its own shard a fresh row)
                pb.ring_seqs, pb.staged = dr.publish_batch(
                    plan, pb.hi, pb.lo, ticks, values, shard_of, pb.n,
                    pb.epoch, tracer=tracer, batch=pb.seq,
                )
                pub = (None, pb.staged)
            elif dr is not None and not dr.sharded:
                pub = dr.try_publish(
                    plan, pb.hi, pb.lo, ticks, values, pb.n, pb.route,
                    pb.epoch, tracer=tracer, batch=pb.seq,
                )
                if pub is not None:
                    pb.ring_seq, pb.staged = pub
            if pub is None:
                # device ring full (or resident loop off): plain staging
                # — the batch still flows in order through the queue,
                # and the drain dispatcher applies it as an unringed
                # staged batch, so a slow drain backpressures HBM
                # residency without ever blocking the source poll
                pb.staged = self._ring.stage(
                    plan, pb.hi, pb.lo, ticks, values, pb.n, pb.route,
                    tracer=tracer, batch=pb.seq,
                )
            # the ring slot owns the padded copies; drop the host arrays
            # so nothing can alias a recycled slot
            pb.hi = pb.lo = pb.values = None
            pb.ticks = None
        else:
            pb.ticks = ticks
        return pb

    # -- producer --------------------------------------------------------
    def _producer(self):
        while not self._stop.is_set():
            if not self._gate.is_set():
                self._parked.set()
                self._gate.wait(0.1)
                continue
            self._parked.clear()
            # chaos seam, OUTSIDE the delivery try: an injected raise
            # kills the thread WITHOUT handing the consumer an error —
            # the "prefetch thread died" detection path in next() (and
            # the ensure-thread respawn) is exactly what it exercises
            faults.inject("ingest.producer", epoch=self._epoch)
            epoch = self._epoch
            park_after = False
            try:
                pb = self._poll()
                pb.epoch = epoch
                self._finish(pb)
                item = ("ok", epoch, pb)
                park_after = pb.end
            except Exception as e:   # deliver to the consumer
                # BaseException (ThreadKilled, interpreter teardown) is
                # NOT delivered: it kills the producer hard, which is
                # the dead-thread detection path next() covers
                item = ("err", epoch, e)
                park_after = True
            if park_after:
                # park BEFORE publishing: the consumer may pause+resume
                # (restore) the instant it sees the item, and resume
                # must find the producer already off the source
                self._gate.clear()
            self._put(item)
        self._parked.set()

    def _poll(self) -> PreppedBatch:
        """One prep (source poll + encode), numbered in poll order."""
        with self.source_lock:
            t0 = time.perf_counter()
            pb = self.prep_fn()
            pb.seq = self._seq
            self._seq += 1
        tracer = self.tracer
        if tracer is not None and tracer.active:
            tracer.rec("poll", t0, batch=pb.seq)
        return pb

    def _put(self, item):
        t_full = None            # when the queue was first found full
        try:
            while not self._stop.is_set():
                if self._pause_req.is_set():
                    # consumer is pausing: the epoch is being invalidated
                    # and the consumer would skip this item anyway — drop
                    # rather than deadlock on a full queue while pause()
                    # waits
                    return
                try:
                    if t_full is None:
                        self._q.put(item, block=False)
                    else:
                        self._q.put(item, timeout=0.05)
                    return
                except queue.Full:
                    if t_full is None:
                        t_full = time.perf_counter()
        finally:
            tracer = self.tracer
            if t_full is not None and tracer is not None and tracer.active:
                tracer.rec("handoff", t_full,
                           batch=item[2].seq if item[0] == "ok" else None)

    def _ensure_thread(self):
        if self._thread is not None and not self._thread.is_alive():
            if self._thread_epoch == self._epoch:
                # hard death (not a restore respawn): the thread may have
                # died MID-POLL, advancing the source past records it
                # never delivered — silently respawning would turn that
                # into data loss. Surface it; the restart machinery
                # restores to the applied-offset cut and the epoch bump
                # below then legitimizes a fresh producer.
                raise IngestThreadDied(
                    "ingest prefetch thread died without delivering a "
                    "batch or an error"
                )
            self._thread = None
        if self._thread is None:
            t = threading.Thread(
                target=self._producer, daemon=True,
                name="flink-tpu-ingest",
            )
            self._thread = t
            self._thread_epoch = self._epoch
            t.start()

    # -- consumer --------------------------------------------------------
    def next(self) -> PreppedBatch:
        if not self.prefetch:
            pb = self._poll()
            pb.epoch = self._epoch
            return self._finish(pb)
        self._ensure_thread()
        while True:
            try:
                kind, epoch, item = self._q.get(timeout=1.0)
            except queue.Empty:
                if not self._thread.is_alive() and self._q.empty():
                    raise IngestThreadDied(
                        "ingest prefetch thread died without delivering "
                        "a batch or an error"
                    )
                continue
            if epoch != self._epoch:
                continue     # pre-restore batch: dropped, source rewound
            if kind == "err":
                raise item
            return item

    def try_next(self) -> Optional[PreppedBatch]:
        """Non-blocking ``next()`` for the resident drain's greedy ring
        fill: a ready batch, or None when the queue is empty RIGHT NOW
        (the caller dispatches what it already holds instead of
        waiting). Inline (prefetch-off) pipelines always return None —
        there is no queue to be ahead in, and polling the source here
        would turn the greedy accumulate into an unbounded synchronous
        poll loop. A dead producer also returns None: the next blocking
        ``next()`` surfaces IngestThreadDied with its full context."""
        if not self.prefetch:
            return None
        self._ensure_thread()
        while True:
            try:
                kind, epoch, item = self._q.get_nowait()
            except queue.Empty:
                return None
            if epoch != self._epoch:
                continue     # pre-restore batch: dropped, source rewound
            if kind == "err":
                raise item
            return item

    def mark_applied(self, pb: PreppedBatch):
        """Record pb's offsets as the applied cut — everything up to and
        including this batch has been dispatched to device state, so a
        snapshot taken from here restores without skipping or
        double-applying records."""
        self._applied = pb.offsets

    def applied_offsets(self):
        return self._applied

    # -- restore protocol ------------------------------------------------
    def pause(self):
        """Park the producer; returns only when it is off the source (or
        was never started / prefetch is off)."""
        self._pause_req.set()
        self._gate.clear()
        if not self.prefetch or self._thread is None:
            return
        while self._thread.is_alive() and not self._parked.is_set():
            self._parked.wait(0.1)

    def resume(self, applied_offsets: Any):
        """Invalidate every batch prepped before the pause and restart
        production from the (rewound) source position. ``applied_offsets``
        re-arms the cut — it IS the restored snapshot's offsets."""
        self._epoch += 1
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._device_ring is not None:
            # the epoch bump above already invalidates every queued
            # batch referencing these slots; retiring them re-opens the
            # full ring to the post-restore epoch's producer
            self._device_ring.clear()
        self._applied = applied_offsets
        if self._thread is not None and self._thread.is_alive():
            # the surviving (parked) producer serves the new epoch from
            # here on — re-stamp it so a LATER hard death is surfaced as
            # IngestThreadDied rather than mistaken for a restore respawn
            # (a stale stamp would silently respawn past lost records);
            # a thread already dead here keeps its old stamp so
            # _ensure_thread treats the post-restore spawn as legitimate
            self._thread_epoch = self._epoch
        self._pause_req.clear()
        self._gate.set()

    def close(self):
        self._stop.set()
        self._gate.set()
