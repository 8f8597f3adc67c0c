"""LocalExecutor: drives a job's micro-batch loop on the device mesh.

The role of StreamTask.invoke + StreamInputProcessor.processInput
(SURVEY §3.2) collapsed into a host loop around ONE compiled SPMD step per
keyed stage:

    poll source -> host chain (fused stateless ops) -> key/encode ->
    device step(state, batch, watermark) -> decode fires -> sinks

Checkpoint barriers are step boundaries (no BarrierBuffer needed: between
steps, device state + source offsets form a consistent cut — the
Chandy-Lamport cut is structural).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import pickle
import queue
import sys
import threading
import time
from collections import deque, namedtuple
from functools import partial
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.core.time import TimeDomain
from flink_tpu.core.types import KeyCodec
from flink_tpu.graph import stream_graph as sg
from flink_tpu.ops import window_kernels as wk
from flink_tpu.parallel.exchange import bucket_capacity
from flink_tpu.parallel.mesh import MeshContext
from flink_tpu.checkpointing import changelog as cklog
from flink_tpu.checkpointing import manifest as ckmf
from flink_tpu.checkpointing.materializer import (
    Materializer,
    MaterializerError,
)
from flink_tpu.checkpointing.local import local_cache_from_config
from flink_tpu.checkpointing.policy import (
    CheckpointFailureBudgetExceeded,
    policy_from_config,
)
from flink_tpu.metrics.drain_stats import DrainTelemetry
from flink_tpu.metrics.recovery import RecoveryTracker
from flink_tpu.metrics.tracing import (
    CompileEvents,
    cost_analysis_of,
    tracer_from_config,
)
from flink_tpu.runtime import controller as controller_mod
from flink_tpu.runtime import elastic
from flink_tpu.runtime import ingest as ingest_mod
from flink_tpu.runtime import stages as stages_mod
from flink_tpu.runtime.step import (
    WindowStageSpec,
    build_compact_step,
    build_kg_occupancy_step,
    build_window_chained_drain,
    build_window_chained_drain_sharded,
    build_window_fire_reduced_step,
    build_window_fire_step,
    build_window_megastep,
    build_window_megastep_exchange,
    build_window_megastep_fired,
    build_window_megastep_fired_exchange,
    build_window_resident_drain,
    build_window_resident_drain_exchange,
    build_window_sharded_drain,
    build_window_while_drain,
    build_window_while_drain_sharded,
    build_window_update_step,
    build_window_update_step_exchange,
    clear_dirty,
    clear_overflow,
    init_sharded_state,
)
from flink_tpu.runtime import checkpoint as ckpt
from flink_tpu.runtime import tiers as tiers_mod
from flink_tpu.runtime.cluster import JobCancelledException
from flink_tpu.runtime.union import to_elements
from flink_tpu.runtime.watchdog import WatchdogError, watchdog_from_config
from flink_tpu.runtime.watermarks import WatermarkStrategy
from flink_tpu.testing import faults

WindowResult = namedtuple("WindowResult", ["key", "window_end_ms", "value"])
SessionResult = namedtuple(
    "SessionResult", ["key", "window_start_ms", "window_end_ms", "value"]
)


class _LaggedEmitter:
    """Pipelined emission for per-step output handles: reading a step's
    outputs immediately blocks on it (a cold d2h costs ~70ms fixed on
    this runtime), so up to ``lag`` steps' handles are retained and read
    only when they fall off the window — the read then overlaps the
    subsequent dispatches. FIFO order is preserved; ``idle()`` drains
    everything the moment the source has nothing new (computed results
    must never be withheld behind an idle stream); ``lag == 0`` is fully
    synchronous (the pre-pipelining behavior). Shared by the rolling and
    session runners."""

    CONFIG_KEY = "pipeline.max-inflight-steps"

    def __init__(self, env, emit_fn):
        self.lag = max(0, env.config.get_int(self.CONFIG_KEY, 4))
        self.emit_fn = emit_fn
        self._q = deque()

    def push(self, item):
        self._q.append(item)
        while len(self._q) > self.lag:
            self.emit_fn(self._q.popleft())

    def idle(self):
        self.drain()

    def drain(self):
        while self._q:
            self.emit_fn(self._q.popleft())

    def discard(self):
        """Drop retained handles WITHOUT emitting — restore rewinds the
        sink to the checkpoint cut, and replay re-fires everything after
        it; emitting the stale handles would double-count."""
        self._q.clear()


def classify_failure(exc: BaseException) -> str:
    """Failure classification at the restart boundary (ref the
    coarse-grained recovery split in RestartPipelinedRegionFailover-
    Strategy — here the regions are "the host-side plumbing" vs "the
    state itself"). TRANSIENT host-side failures — a watchdog trip, an
    exhausted checkpoint failure budget, a DCN peer stall/loss, the
    ingest thread dying, a connection/timeout blip — say nothing about
    the integrity of the live device state or the compiled kernels, so
    recovery may restart warm in-process: keep the jitted steps, re-stage
    only what diverged from the restored cut. DEVICE LOSS (a mesh
    shard's chip gone — runtime/elastic.py) is its own kind: the
    checkpoint is fine but the mesh is wrong, so recovery re-plans the
    job over the survivors instead of restoring onto a dead device.
    Anything else (arithmetic/assertion/XLA errors, unknown exceptions)
    is treated as STATE-CORRUPTING and takes the full restore path,
    rebuilding every shard from the checkpoint."""
    from flink_tpu.runtime import dcn

    if isinstance(exc, elastic.DeviceLostError):
        # checked FIRST: DCNPeerLostError is both a DCNPeerError (in
        # the transient tuple) and a DeviceLostError — the dead peer's
        # mesh segment is gone, which no warm restart survives
        return "device-loss"
    transient = (
        WatchdogError,
        CheckpointFailureBudgetExceeded,
        MaterializerError,
        ingest_mod.IngestThreadDied,
        dcn.DCNPeerError,
        ConnectionError,
        TimeoutError,
    )
    return "transient" if isinstance(exc, transient) else "state-corrupting"


def _storage_for_restore_path(live_storage, path_or_storage):
    """Resolve a restore target: an own-directory path rides the live
    storage object (and its task-local snapshot cache); a foreign path
    gets a plain reader; a storage object passes through."""
    if not isinstance(path_or_storage, str):
        return path_or_storage
    if live_storage is not None and os.path.abspath(
        path_or_storage
    ) == os.path.abspath(live_storage.dir):
        return live_storage
    return ckpt.CheckpointStorage(path_or_storage)


def _pad(arr, size, dtype):
    arr = np.asarray(arr, dtype)
    if len(arr) == size:
        return arr
    out = np.zeros((size,) + arr.shape[1:], dtype)
    out[: len(arr)] = arr
    return out


class _GenericCheckpointIO:
    """Async write machinery shared by every generic (pickled-payload)
    checkpoint path — flat-stage, keyed-process, and device-CEP. Owns
    the optional Materializer, the completion-notification queue, and
    the drain/flush/recover/close protocol, so the three paths cannot
    diverge. (The windowed path has its own staged delta pipeline.)

    checkpoint.async defaults on when checkpoint.mode=incremental —
    the same rule as the windowed path, so /checkpoints/config reports
    what actually runs. The generic payloads themselves are always full
    snapshots (one small pytree/dict — nothing to delta)."""

    def __init__(self, env, storage, pipe, policy=None):
        self.storage = storage
        self.pipe = pipe
        # optional CheckpointFailurePolicy: completions reset its
        # consecutive-failure count AT PUBLISH TIME (sync inline, async
        # on the materializer thread — the policy is thread-safe)
        self.policy = policy
        # serializes source wire interactions against a pipelined-ingest
        # producer (runtime/ingest.py): the windowed runner points this
        # at its pipeline's source_lock — an offset commit may share the
        # poll's connection, and an interleaved commit mid-fetch would
        # corrupt the protocol. Runners that poll inline have no
        # concurrent producer, so the no-op default costs nothing.
        self.source_lock = contextlib.nullcontext()
        self.materializer = None
        if storage is not None and env.config.get_bool(
            "checkpoint.async",
            env.config.get_str("checkpoint.mode", "full") == "incremental",
        ):
            self.materializer = Materializer(
                slots=env.config.get_int("checkpoint.staging-slots", 2)
            )
        # (cid, offsets) of durable checkpoints awaiting completion
        # fan-out: the materializer thread only QUEUES here — the step
        # loop delivers, because notify_checkpoint_complete mutates
        # connector state the hot path touches concurrently
        self._notify_q = deque()

    def queue_notification(self, cid, offsets):
        """Record a now-durable checkpoint for fan-out at the next
        drain. Called from the materializer thread by write paths that
        serialize their own files (the windowed staged-delta pipeline)."""
        self._notify_q.append((cid, offsets))

    def drain(self):
        """Deliver queued checkpoint-complete fan-outs ON THIS (the
        step loop's) thread."""
        while self._notify_q:
            cid, offsets = self._notify_q.popleft()
            with self.source_lock:
                self.pipe.source.notify_checkpoint_complete(cid, offsets)
            for s in self.pipe.all_sinks:
                s.notify_checkpoint_complete(cid)

    def write(self, cid, payload):
        """Write a generic checkpoint + schedule its completion fan-out.
        Async mode pickles NOW (the live payload keeps mutating once the
        step loop resumes) and ships frozen bytes to the materializer."""
        self.drain()
        if self.materializer is None:
            self.storage.write_generic(cid, payload)
            if self.policy is not None:
                self.policy.on_completed(cid)
            with self.source_lock:
                self.pipe.source.notify_checkpoint_complete(
                    cid, payload["offsets"]
                )
            for s in self.pipe.all_sinks:
                s.notify_checkpoint_complete(cid)
            return
        self.materializer.check()
        blob = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
        offsets = payload["offsets"]

        def task():
            self.storage.write_generic(cid, payload_bytes=blob)
            if self.policy is not None:
                self.policy.on_completed(cid)
            self._notify_q.append((cid, offsets))

        self.materializer.submit(f"chk-{cid}", task)

    def _drain_timeout(self):
        """Bound on recovery/teardown drains: a WEDGED write must not
        turn the escalation path into the very hang the containment
        layer exists to eliminate. checkpoint.timeout when configured;
        a generous fallback otherwise (0/unset timeout = the operator
        chose unbounded publishes, but recovery still terminates)."""
        t = getattr(self.policy, "timeout_s", 0) if self.policy else 0
        return t if t and t > 0 else 600.0

    def recover(self):
        """Restore-time drain: in-flight async writes land (each is a
        valid cut the restore may pick up), stored failures drop."""
        if self.materializer is not None:
            self.materializer.recover(timeout=self._drain_timeout())
            self.drain()

    def flush(self):
        """Success-path barrier: a still-failing async write IS a
        checkpoint failure — raises inside the caller's restart scope."""
        if self.materializer is not None:
            self.materializer.flush()
            self.drain()

    def settle(self):
        """Failure-path barrier: let pending cuts become durable before
        the caller checks whether a restartable checkpoint exists —
        bounded, so a wedged write cannot stall the restart decision."""
        if self.materializer is not None:
            self.materializer.flush(raise_errors=False,
                                    timeout=self._drain_timeout())

    def close(self):
        if self.materializer is not None:
            self.materializer.close(flush=True,
                                    timeout=self._drain_timeout())


def _guarded_generic_write(ck_io, policy, storage, metrics, cid,
                           payload_fn):
    """Abort-and-count containment for the generic checkpoint paths
    (docs/fault-tolerance.md): a failed attempt — including an async
    failure surfacing at this barrier via the materializer check — is
    GC'd and recorded, and the job keeps running until the consecutive-
    failure budget is exhausted. ``payload_fn`` builds the payload
    INSIDE the guard, so a snapshot-time failure is contained too."""
    t0 = time.perf_counter()
    trigger_ms = time.time() * 1000
    try:
        ck_io.write(cid, payload_fn())
    except (JobCancelledException, WatchdogError,
            CheckpointFailureBudgetExceeded):
        raise
    except Exception as e:
        storage.discard_tmp(cid)
        metrics.checkpoints_aborted += 1
        metrics.record_checkpoint_abort(
            cid, trigger_ms, (time.perf_counter() - t0) * 1e3,
            reason=f"{type(e).__name__}: {e}", kind="generic",
        )
        if policy.on_aborted(cid, str(e)):
            raise policy.exhausted_error(cid, e) from e


class _FlatStageCheckpointer:
    """Step-boundary checkpoint/savepoint/restore for keyed stage kinds
    whose device state is ONE flat pytree of per-shard arrays (rolling
    reduce, count windows). The reference snapshots EVERY operator's
    state (AbstractStreamOperator.java:367; rolling aggregates live in
    ValueState via StreamGroupedReduce), so these stage kinds must
    participate in the same fault-tolerance story as the windowed paths.

    Mirrors the session runner's inline machinery: a raw device_get of
    the state leaves at the step boundary (the structural barrier,
    SURVEY §3.4) + source offsets + sink states + the codec reverse map
    riding the append-only keymap log. Pending lagged fires are DRAINED
    before a cut (their sink effects belong to it) and DISCARDED on
    restore (source replay re-fires them). Stage-shape scalars that the
    compiled step bakes into its masks (capacity, count-window N, reduce
    kind) are validated at restore — mismatched arrays would corrupt
    silently via clamped gathers, so fail fast instead."""

    def __init__(self, executor, pipe, ctx, codec, keep_rev, emitter,
                 metrics, get_state, set_state, stage_kind, meta,
                 extra_payload=None, apply_extra=None):
        env = executor.env
        self.executor = executor
        self.env = env
        self.pipe = pipe
        self.ctx = ctx
        self.codec = codec
        self.keep_rev = keep_rev
        self.emitter = emitter
        self.metrics = metrics
        self.get_state = get_state
        self.set_state = set_state
        self.stage_kind = stage_kind
        self.meta = dict(meta)
        # stage-specific non-array state riding the payload (e.g. the
        # session path's watermark + time-domain origin)
        self.extra_payload = extra_payload
        self.apply_extra = apply_extra
        self.storage = None
        if env.checkpoint_dir:
            self.storage = ckpt.CheckpointStorage(
                env.checkpoint_dir,
                retain=env.config.get_int("checkpoint.retain", 2),
                local=local_cache_from_config(
                    env.config, env.checkpoint_dir
                ),
            )
        self.next_cid = (
            (self.storage.latest() or 0) + 1 if self.storage else 1
        )
        # failure budget (checkpointing/policy.py): generic stages get
        # the same abort-and-count containment as the windowed path
        self.policy = (
            policy_from_config(env.config)
            if self.storage is not None else None
        )
        # the live policy object: the web monitor snapshots .state()
        metrics.failure_budget = self.policy
        self._pause_declined = False
        self.io = _GenericCheckpointIO(
            env, self.storage, pipe, policy=self.policy
        )
        self.steps_at_ckpt = 0
        self.n_keys_logged = 0
        executor._savepoint_writer = self.write_savepoint

    def _payload(self, store):
        # codec reverse map rides the APPEND-ONLY keymap log: each
        # checkpoint writes only the keys seen since the last one
        if self.keep_rev:
            items, self.n_keys_logged = self.codec.rev_slice(
                self.n_keys_logged
            )
            store.append_keymap(items)
        leaves, _ = jax.tree_util.tree_flatten(self.get_state())
        return {
            "stage_state": [np.asarray(jax.device_get(x)) for x in leaves],
            "offsets": self.pipe.source.snapshot_offsets(),
            "codec_rev_count": self.n_keys_logged if self.keep_rev else 0,
            "sink_states": [
                s.snapshot_state() for s in self.pipe.all_sinks
            ],
            "max_parallelism": self.env.max_parallelism,
            "n_shards": self.ctx.n_shards,
            "stage_kind": self.stage_kind,
            "stage_meta": dict(self.meta),
            "stage_extra": (
                self.extra_payload() if self.extra_payload else {}
            ),
        }

    def maybe_checkpoint(self):
        self.io.drain()
        if (
            self.storage is not None
            and self.env.checkpoint_interval_steps > 0
            and self.metrics.steps - self.steps_at_ckpt
            >= self.env.checkpoint_interval_steps
        ):
            # min-pause gate (checkpoint.min-pause): a due trigger
            # defers until the pause elapses; ONE decline is counted per
            # deferred trigger, not one per polled cycle
            if self.policy is not None and not self.policy.can_trigger():
                if not self._pause_declined:
                    self._pause_declined = True
                    self.metrics.checkpoints_declined += 1
                return
            self._pause_declined = False
            self.write_checkpoint()

    def write_checkpoint(self):
        self.emitter.drain()
        _guarded_generic_write(
            self.io, self.policy, self.storage, self.metrics,
            self.next_cid, lambda: self._payload(self.storage),
        )
        self.next_cid += 1
        self.steps_at_ckpt = self.metrics.steps

    def restore(self, path_or_storage, cid=None):
        self.io.recover()             # durable cuts still notify
        st = _storage_for_restore_path(self.storage, path_or_storage)
        cid = cid if cid is not None else st.latest()
        if cid is None:
            raise FileNotFoundError(f"no checkpoint in {st.dir}")
        payload = st.read_generic(cid)
        if payload.get("session_window") and "stage_kind" not in payload:
            # round-4 inline session format: adapt to the unified shape
            # so retained checkpoints/savepoints stay restorable
            payload = {
                **payload,
                "stage_kind": "session-window",
                "stage_state": payload["session_state"],
                "stage_meta": {
                    "gap_ms": payload["gap_ms"],
                    "capacity_per_shard": payload["capacity_per_shard"],
                },
                "stage_extra": {
                    "wm_current": payload["wm_current"],
                    "origin_ms": payload["origin_ms"],
                },
            }
        if payload.get("stage_kind") != self.stage_kind:
            raise ValueError(
                f"checkpoint was not written by a {self.stage_kind} "
                f"stage (found {payload.get('stage_kind')!r})"
            )
        if payload["max_parallelism"] != self.env.max_parallelism:
            raise ValueError("checkpoint max-parallelism mismatch")
        if payload["n_shards"] != self.ctx.n_shards:
            raise ValueError(
                f"checkpoint has {payload['n_shards']} shard(s), job "
                f"configured for {self.ctx.n_shards}"
            )
        snap_meta = payload.get("stage_meta", {})
        for k, v in self.meta.items():
            if snap_meta.get(k) != v:
                raise ValueError(
                    f"checkpoint {k} {snap_meta.get(k)!r} != "
                    f"configured {v!r}"
                )
        self.emitter.discard()
        _leaves, treedef = jax.tree_util.tree_flatten(self.get_state())
        self.set_state(jax.tree_util.tree_unflatten(treedef, [
            jax.device_put(x, self.ctx.state_sharding)
            for x in payload["stage_state"]
        ]))
        self.pipe.source.restore_offsets(payload["offsets"])
        sink_states = payload.get("sink_states")
        if sink_states:
            if len(sink_states) != len(self.pipe.all_sinks):
                raise ValueError(
                    f"checkpoint has {len(sink_states)} sink states "
                    f"but the job topology has {len(self.pipe.all_sinks)} "
                    f"sinks — restore with the matching pipeline"
                )
            for s, ss in zip(self.pipe.all_sinks, sink_states):
                s.restore_state(ss)
        count = payload.get("codec_rev_count", 0)
        if self.keep_rev and count:
            self.codec._rev = st.read_keymap(count)
            # restoring from a FOREIGN directory (savepoint): the job's
            # own keymap log has none of these keys, so the next
            # checkpoint must append them all (n_keys_logged=0);
            # same-dir restores resume the append-only log where it is
            same_dir = self.storage is not None and (
                os.path.abspath(st.dir)
                == os.path.abspath(self.storage.dir)
            )
            self.n_keys_logged = len(self.codec._rev) if same_dir else 0
        if self.apply_extra is not None:
            self.apply_extra(payload.get("stage_extra", {}))
        self.steps_at_ckpt = self.metrics.steps

    def write_savepoint(self, path: str) -> str:
        self.emitter.drain()
        sp = ckpt.CheckpointStorage(path, retain=10**9)
        cid = (sp.latest() or 0) + 1
        # self-contained savepoint: full keymap into ITS directory
        logged = self.n_keys_logged
        self.n_keys_logged = 0
        try:
            return sp.write_generic(cid, self._payload(sp))
        finally:
            self.n_keys_logged = logged

    def run_with_restarts(self, batch_loop, restore_from):
        """Restore + restart protection around the stage's batch loop
        (ref ExecutionGraph.restart)."""
        if restore_from:
            self.restore(restore_from)
        restart = self.executor._restart_strategy()
        try:
            while True:
                try:
                    batch_loop()
                    self.io.flush()
                    break
                except JobCancelledException:
                    raise
                except Exception:
                    self.io.settle()
                    can = (
                        self.storage is not None
                        and self.storage.latest() is not None
                        and restart.should_restart()
                    )
                    if not can:
                        raise
                    self.metrics.restarts += 1
                    self.executor._notify_restart()
                    self.restore(self.storage)
        finally:
            self.io.close()


@dataclasses.dataclass
class JobMetrics:
    records_in: int = 0
    records_out: int = 0
    fires: int = 0
    steps: int = 0
    steps_fast: int = 0   # steps run on the lookup-only fast tier
    steps_exchanged: int = 0  # steps routed through the ICI all_to_all
    # steps drained through the shard_map'd data-parallel ring
    # (pipeline.data-parallel): records pre-routed to the owning
    # shard's slice, zero collectives in the keyed body
    steps_sharded: int = 0
    # K-fused lax.scan dispatches (pipeline.steps-per-dispatch > 1);
    # each one carries k_steps micro-batches of the `steps` counter
    fused_dispatches: int = 0
    # ...of which resident-pipeline dispatches (pipeline.fused-fire):
    # the fire sweep ran inside the scan and payloads surfaced lagged
    fused_fire_dispatches: int = 0
    # device-resident ring-drain dispatches (pipeline.resident-loop);
    # each carries up to ring-depth micro-batches of `steps` in ONE
    # count-gated scan — THE steady-state host-round-trip divisor
    resident_drains: int = 0
    state_layout: str = ""  # "hash" | "direct" once the stage is set up
    # packed acc+touched planes in effect (state.packed-planes)
    state_packed_planes: bool = False
    # "mask" | "all_to_all" | "adaptive" once the stage is set up
    exchange_mode: str = ""
    dropped_late: int = 0
    dropped_capacity: int = 0
    restarts: int = 0
    # failure containment (docs/fault-tolerance.md): aborted-and-counted
    # checkpoints, min-pause trigger declines, watchdog deadline trips
    checkpoints_aborted: int = 0
    checkpoints_declined: int = 0
    watchdog_trips: int = 0
    # the live CheckpointFailurePolicy (checkpointing/policy.py); the
    # web monitor serves its .state() snapshot on
    # /jobs/<jid>/checkpoints. None when checkpointing is off.
    failure_budget: Any = None
    # DCN path: records THIS host's lanes carried (post ingest
    # partitioning — shows rebalance/shuffle/global routing physically)
    dcn_ingested_local: int = 0
    wall_time_s: float = 0.0
    # CEP: which engine actually ran ("device" | "host"; VERDICT r3 —
    # a user must be able to tell without diffing step counters), plus
    # device count-NFA detections vs host-replay extractions — the
    # two must agree (honesty cross-check for the accelerated path)
    cep_engine: str = ""
    cep_device_steps: int = 0
    cep_matches_detected: int = 0
    cep_matches_extracted: int = 0
    # fire latency: bounded weighted samples — latency is watermark-
    # crossing -> sink invoke for every window in one emission
    # (ref LatencyMarker / the p99 half of the north-star metric)
    fire_latency: Any = None
    # checkpoint history (ref CheckpointStatsTracker): bounded list of
    # {"id", "trigger_ms", "duration_ms", "bytes", "entries"} dicts,
    # newest last — served by the web monitor's /checkpoints handler
    checkpoint_stats: Any = None

    def record_checkpoint(self, cid: int, trigger_ms: float,
                          duration_ms: float, nbytes: int, entries: int,
                          kind: str = "full", sync_ms: float = None,
                          async_ms: float = None, coverage: int = None,
                          staging_wait_ms: float = 0.0,
                          staging_occupancy: int = 0):
        """kind: "full" | "delta". sync_ms is the step-loop stall (drain +
        staging fetch + offset capture + staging-slot wait); async_ms the
        background materialization (extract/serialize/publish). Sync-mode
        checkpoints report the whole duration as sync_ms."""
        if self.checkpoint_stats is None:
            self.checkpoint_stats = []
        row = {
            "id": cid,
            "status": "completed",
            "trigger_ms": round(trigger_ms, 1),
            "duration_ms": round(duration_ms, 2),
            "bytes": nbytes,
            "entries": entries,
            "kind": kind,
            "sync_ms": round(
                duration_ms if sync_ms is None else sync_ms, 2
            ),
            "async_ms": round(async_ms or 0.0, 2),
            "staging_wait_ms": round(staging_wait_ms, 2),
            "staging_occupancy": staging_occupancy,
        }
        if coverage is not None:
            row["coverage"] = coverage
        self.checkpoint_stats.append(row)
        del self.checkpoint_stats[:-200]      # bounded history

    def record_checkpoint_abort(self, cid: int, trigger_ms: float,
                                duration_ms: float, reason: str,
                                kind: str = "full"):
        """An aborted-and-counted checkpoint (failure-budget path): the
        attempt rides the same history the web monitor serves, with
        status "aborted" and the failure reason, so an operator sees the
        contained fault instead of a silent gap in the ids."""
        if self.checkpoint_stats is None:
            self.checkpoint_stats = []
        self.checkpoint_stats.append({
            "id": cid,
            "status": "aborted",
            "trigger_ms": round(trigger_ms, 1),
            "duration_ms": round(duration_ms, 2),
            "bytes": 0,
            "entries": 0,
            "kind": kind,
            "sync_ms": 0.0,
            "async_ms": 0.0,
            "staging_wait_ms": 0.0,
            "staging_occupancy": 0,
            "failure_reason": reason[:500],
        })
        del self.checkpoint_stats[:-200]

    def record_fire_latency(self, n_windows: int, ms: float):
        from flink_tpu.metrics.latency import LatencySamples

        if self.fire_latency is None:
            self.fire_latency = LatencySamples()
        self.fire_latency.record(n_windows, ms)

    def fire_latency_pct(self, q: float):
        """Weighted percentile (0..100) over emitted windows; None if none."""
        if not self.fire_latency:
            return None
        return self.fire_latency.percentile(q)

    # the counter fields exported as live gauges (also consumed by the
    # MiniCluster's job detail endpoint)
    GAUGE_FIELDS = (
        "records_in", "records_out", "fires", "steps", "steps_fast",
        "steps_sharded",
        "fused_dispatches", "fused_fire_dispatches", "resident_drains",
        "dropped_late", "dropped_capacity", "restarts",
        "checkpoints_aborted", "checkpoints_declined", "watchdog_trips",
    )


@dataclasses.dataclass
class JobHandle:
    name: str
    metrics: JobMetrics
    state: Any = None      # final device state (windowed stages)
    ctx: Any = None
    # merged accumulator values (ref JobExecutionResult.getAllAccumulator-
    # Results); empty when no rich function registered any
    accumulator_results: Any = None

    def accumulator_result(self, name: str):
        return (self.accumulator_results or {})[name]


@dataclasses.dataclass
class _Pipeline:
    source: Any
    pre_chain: List[sg.OneInputTransformation]
    ts_transform: Optional[sg.TimestampsWatermarksTransformation]
    key_by: Optional[sg.KeyByTransformation]
    window_agg: Optional[sg.WindowAggTransformation]
    rolling: Optional[sg.KeyedProcessTransformation]
    # post-stage fan-out: each branch is (chain_ops, [sinks]); divergent
    # sink lineages after the last stateful stage become separate branches
    # (the role of the reference's Output broadcasting to multiple edges)
    branches: List[Any]
    process: Optional[sg.ProcessTransformation] = None
    # explicit exchange annotation upstream of key_by (rebalance /
    # shuffle / global / rescale / forward); physical on the DCN path's
    # ingestion edge, a recorded no-op single-host (see
    # PartitionTransformation)
    ingest_partition: Optional[str] = None
    # downstream keyed windowed stages beyond (key_by, window_agg):
    # ordered [key_by, window_agg] pairs collected by _translate, turned
    # into a validated StageGraph (runtime/stages.py) at dispatch
    stages: List[Any] = dataclasses.field(default_factory=list)

    @property
    def all_sinks(self):
        return [s for _, sinks in self.branches for s in sinks]


def _emit_batch(pipe: _Pipeline, elements, metrics: JobMetrics) -> int:
    """Run each post-stage branch chain over `elements` and invoke sinks."""
    total = 0
    for chain, sinks in pipe.branches:
        out = _apply_chain(chain, elements) if chain else elements
        total += len(out)
        for s in sinks:
            s.invoke_batch(out)
    metrics.records_out += total
    return total


def _translate_branch(parent: sg.Transformation):
    """Translate one union input into (source, pre_ts_ops, ts, post_ts_ops).

    Ops are split around the timestamp assigner so the timestamp_fn sees the
    element exactly as it was at the assigner's position in the chain."""
    pre_ops, post_ops, source, ts = [], [], None, None
    for t in sg.lineage(parent):
        if isinstance(t, sg.SourceTransformation):
            source = t.source
        elif isinstance(t, sg.TimestampsWatermarksTransformation):
            ts = t
        elif isinstance(t, sg.OneInputTransformation):
            (post_ops if ts is not None else pre_ops).append(t)
        elif isinstance(t, sg.PartitionTransformation):
            pass
        else:
            raise NotImplementedError(
                f"{type(t).__name__} upstream of a union/connect is not "
                f"supported yet (only source -> stateless chain)"
            )
    if source is None:
        raise ValueError("union input has no source")
    return source, pre_ops, ts, post_ops


def _merge_sources(u: sg.UnionTransformation):
    """Build a MergedSource + synthesized ts transform for a union head."""
    from flink_tpu.runtime import union as un

    branches, have_ts = [], []
    for i, parent in enumerate(u.parents):
        source, pre_ops, ts, post_ops = _translate_branch(parent)
        branches.append(un.Branch(
            source, pre_ops,
            ts_fn=ts.timestamp_fn if ts is not None else None,
            post_ops=post_ops,
            strategy=ts.strategy if ts is not None else None,
            tag=i if u.tagged else None,
        ))
        have_ts.append(ts is not None)
    merged = un.MergedSource(branches)
    ts_transform = None
    if any(have_ts):
        if not u.tagged:
            raise NotImplementedError(
                "assign timestamps AFTER union() (per-input assigners need "
                "the tagged connect/join path)"
            )
        if not all(have_ts):
            raise ValueError(
                "either all or none of the connected/joined inputs must "
                "assign timestamps"
            )
        strategy = un.MergedWatermarkStrategy(
            out_of_orderness_ms=max(
                b.strategy.out_of_orderness_ms for b in branches
            ),
            branches=branches,
        )
        ts_transform = sg.TimestampsWatermarksTransformation(
            "merged-ts", None,
            timestamp_fn=lambda e: e.ts,
            strategy=strategy,
        )
    return merged, ts_transform


def _translate(sink_transforms: List[sg.SinkTransformation]) -> _Pipeline:
    if not sink_transforms:
        raise ValueError("job has no sinks")
    spines, tails = [], []
    for st in sink_transforms:
        body = sg.lineage(st)[:-1]
        i = len(body)
        while i > 0 and isinstance(
            body[i - 1],
            (sg.OneInputTransformation, sg.PartitionTransformation),
        ):
            i -= 1
        spines.append(body[:i])
        tails.append(body[i:])
    # stateless jobs have an empty spine except the source; normalize so the
    # source is always on the spine
    ref = spines[0]
    for sp in spines[1:]:
        if [t.id for t in sp] != [t.id for t in ref]:
            raise NotImplementedError(
                "sinks must share the pipeline up to the last stateful "
                "stage; divergence is supported only in trailing "
                "stateless chains"
            )
    # group identical tails into branches
    branches, by_key = [], {}
    for tail, st in zip(tails, sink_transforms):
        key = tuple(t.id for t in tail)
        if key not in by_key:
            entry = (
                [t for t in tail if isinstance(t, sg.OneInputTransformation)],
                [],
            )
            by_key[key] = entry
            branches.append(entry)
        by_key[key][1].append(st.sink)

    pipe = _Pipeline(None, [], None, None, None, None, branches)
    for t in ref:
        if isinstance(t, sg.SourceTransformation):
            pipe.source = t.source
        elif isinstance(t, sg.UnionTransformation):
            pipe.source, pipe.ts_transform = _merge_sources(t)
        elif isinstance(t, sg.IterateTransformation):
            from flink_tpu.runtime.union import IterationSource

            pipe.source = IterationSource(
                pipe.source, pipe.pre_chain, t.queue
            )
            pipe.pre_chain = []
        elif isinstance(t, sg.TimestampsWatermarksTransformation):
            pipe.ts_transform = t
        elif isinstance(t, sg.KeyByTransformation):
            if pipe.key_by is None:
                pipe.key_by = t
            else:
                # a SECOND keyed boundary: collect it for the StageGraph
                # (runtime/stages.py) instead of silently overwriting the
                # first — the chain validates at dispatch, where every
                # unsupported shape raises naming its edge
                pipe.stages.append([t, None])
        elif isinstance(t, sg.WindowAggTransformation):
            if pipe.stages:
                if pipe.stages[-1][1] is not None:
                    from flink_tpu.runtime.stages import StageGraphError

                    raise StageGraphError(
                        f"two window aggregations with no keyBy between "
                        f"them after stage[{len(pipe.stages)}] — every "
                        f"chained stage is a keyBy→window pair"
                    )
                pipe.stages[-1][1] = t
            elif pipe.window_agg is not None:
                from flink_tpu.runtime.stages import StageGraphError

                raise StageGraphError(
                    "two window aggregations with no keyBy between them "
                    "— a downstream window must re-key the upstream "
                    "stage's results (.key_by(lambda r: r.key))"
                )
            else:
                pipe.window_agg = t
        elif isinstance(t, sg.KeyedProcessTransformation):
            pipe.rolling = t
        elif isinstance(t, sg.ProcessTransformation):
            pipe.process = t
        elif isinstance(t, sg.OneInputTransformation):
            pipe.pre_chain.append(t)
        elif isinstance(t, sg.PartitionTransformation):
            if t.mode not in ("broadcast", "forward"):
                pipe.ingest_partition = t.mode
        else:
            raise NotImplementedError(f"transformation {type(t).__name__}")
    if pipe.source is None:
        raise ValueError("pipeline has no source")
    if (pipe.key_by is not None and pipe.window_agg is None
            and pipe.rolling is None and pipe.process is None):
        raise NotImplementedError(
            "keyed stream must currently end in a window agg, rolling "
            "reduce, or process function"
        )
    if pipe.stages and (
        pipe.stages[-1][1] is None
        or pipe.rolling is not None or pipe.process is not None
    ):
        from flink_tpu.runtime.stages import StageGraphError

        raise StageGraphError(
            f"stage[{len(pipe.stages)}] does not end in a window "
            f"aggregation — a chained keyed stage must be a keyBy→window "
            f"pair (rolling reduces and process functions cannot chain "
            f"after a windowed stage)"
        )
    return pipe


def _apply_chain(chain, elements):
    for t in chain:
        if t.kind == "map":
            elements = [t.fn(e) for e in elements]
        elif t.kind == "filter":
            elements = [e for e in elements if t.fn(e)]
        elif t.kind == "flat_map":
            out = []
            for e in elements:
                out.extend(t.fn(e))
            elements = out
        else:
            raise NotImplementedError(t.kind)
    return elements


# builtin reduce kinds the spill tier can merge host-side:
# kind -> (accumulating numpy ufunc, neutral element)
_HOST_REDUCE = {
    "sum": (np.add, 0.0),
    "count": (np.add, 0.0),
    "min": (np.minimum, np.inf),
    "max": (np.maximum, -np.inf),
}


class CycleAttribution:
    """Per-cycle phase timing + back-pressure cause classification.

    The reference samples task-thread stack traces and classifies threads
    blocked on network buffers (BackPressureStatsTracker.java:64); in the
    micro-batch design each cycle decomposes exactly into phases, so the
    cause is measured, not sampled:

      source   — waiting on / reading the source
      host     — encode, key hashing, host chains
      dispatch — queueing device steps; BLOCKS when the device pipeline is
                 full (donated buffers unavailable) => device-bound
      emit     — fire readback + sink invocation => sink-bound

    Cycles with no records are source-starved. EWMAs + per-phase
    histograms feed /jobs/<jid>/backpressure.

    Resident-loop regimes (ISSUE 14): host-dispatch phases cannot see
    inside the ring drain, so when the drain flight recorder is live the
    executor plugs its duty-cycle estimator in as ``resident_fn`` and
    classification consults it FIRST — ``ring-starved`` (drains keep
    finding empty rings: publish side can't feed the device) and
    ``device-saturated`` (drains keep retiring full-depth ring groups:
    the device is the bottleneck) are more specific verdicts than the
    phase dominance rules below them.
    """

    PHASES = ("source", "host", "dispatch", "emit")
    RING_STARVED_ABOVE = 0.5      # mean empty-ring drain fraction
    DEVICE_SATURATED_ABOVE = 0.85  # mean drain duty cycle (count/depth)

    def __init__(self, group=None, alpha: float = 0.05):
        self.alpha = alpha
        self.ewma = {p: 0.0 for p in self.PHASES}
        self.idle = 0
        self.busy = 0
        # decaying idle fraction: classification must reflect the RECENT
        # regime, not the job's lifetime (a job idle overnight then
        # saturated must flip to device-bound, not stay source-starved)
        self.idle_ewma = 0.0
        # () -> (duty, starved) from metrics.drain_stats.DrainTelemetry
        # .regime(); None outside the resident loop
        self.resident_fn = None
        self.hists = (
            {p: group.histogram(f"phase_{p}_ms") for p in self.PHASES}
            if group is not None else None
        )

    def record(self, idle: bool, **phase_ms):
        self.idle_ewma += self.alpha * ((1.0 if idle else 0.0) - self.idle_ewma)
        if idle:
            self.idle += 1
            return
        self.busy += 1
        for p in self.PHASES:
            ms = phase_ms.get(p, 0.0)
            self.ewma[p] += self.alpha * (ms - self.ewma[p])
            if self.hists:
                self.hists[p].update(ms)

    def classify(self) -> str:
        total = self.idle + self.busy
        if total == 0:
            return "ok"
        if self.resident_fn is not None:
            duty, starved = self.resident_fn()
            if starved > self.RING_STARVED_ABOVE:
                return "ring-starved"
            if duty > self.DEVICE_SATURATED_ABOVE:
                return "device-saturated"
        if self.idle_ewma > 0.5:
            return "source-starved"
        dominant = max(self.ewma, key=self.ewma.get)
        cycle = sum(self.ewma.values()) or 1e-9
        if self.ewma[dominant] / cycle < 0.5:
            return "ok"
        return {
            "source": "source-starved",
            "host": "host-bound",
            "dispatch": "device-bound",
            "emit": "sink-bound",
        }[dominant]

    def report(self) -> dict:
        out = {
            "classification": self.classify(),
            "phase-ewma-ms": {p: round(v, 3) for p, v in self.ewma.items()},
            "idle-cycles": self.idle,
            "busy-cycles": self.busy,
        }
        if self.resident_fn is not None:
            duty, starved = self.resident_fn()
            out["drain-duty-cycle"] = round(duty, 4)
            out["ring-starved-fraction"] = round(starved, 4)
        return out


class LocalExecutor:
    def __init__(self, env):
        self.env = env
        # set per-stage once a snapshotting path exists (savepoint target)
        self._savepoint_writer = None
        self._job_group = None
        self._cycle_hist = None
        self._last_cycle_t = None
        self._attribution = None
        self._latency_hist = None
        # step-loop span tracer (metrics/tracing.py); None unless
        # observability.tracing is on — the off path carries no tracer
        self._tracer = None
        self._compile_sink = None

    def _poll_control(self):
        """Observe cancel/savepoint requests at the micro-batch boundary
        (the reference's Task cancellation + barrier injection cadence);
        also records the cycle-time histogram (back-pressure signal)."""
        if self._cycle_hist is not None:
            now = time.perf_counter()
            if self._last_cycle_t is not None:
                self._cycle_hist.update((now - self._last_cycle_t) * 1e3)
            self._last_cycle_t = now
        ctl = getattr(self.env, "_control", None)
        if ctl is None:
            return
        if ctl.cancel_event.is_set():
            req = ctl.take_savepoint_request()
            if req is not None:
                req.set_error(RuntimeError("job canceled"))
            raise JobCancelledException("job canceled")
        req = ctl.take_savepoint_request()
        if req is not None:
            if self._savepoint_writer is None:
                req.set_error(NotImplementedError(
                    "savepoints are not supported for this stage type"
                ))
            else:
                try:
                    req.set_result(self._savepoint_writer(req.path))
                except Exception as e:
                    req.set_error(e)

    def _init_metrics(self, job_name: str, metrics: JobMetrics):
        registry = getattr(self.env, "metric_registry", None)
        if registry is None:
            return
        grp = registry.group("jobs", job_name)
        self._job_group = grp
        for fname in JobMetrics.GAUGE_FIELDS:
            grp.gauge(fname, lambda m=metrics, n=fname: getattr(m, n))
        self._cycle_hist = grp.histogram("cycle_time_ms")
        self._attribution = CycleAttribution(grp)
        # LatencyMarker analog: ingest-to-sink latency of the youngest
        # records in each emission (markers are batch timestamps here)
        self._latency_hist = grp.histogram("record_latency_ms")
        self.env._backpressure_report = (
            lambda: self._attribution.report() if self._attribution else {}
        )
        # XLA compile visibility: process-global event counters snapshotted
        # at job start so the gauges report THIS job's compiles — a
        # recompile storm mid-stream moves a named metric instead of
        # presenting as a mystery stall (ISSUE 2 tentpole part 3)
        CompileEvents.install()
        mark = CompileEvents.mark()
        grp.gauge(
            "xla_compile_count", lambda: CompileEvents.since(mark)[0]
        )
        grp.gauge(
            "xla_compile_time_ms",
            lambda: round(CompileEvents.since(mark)[1] * 1e3, 2),
        )
        hist = grp.histogram("xla_compile_ms")
        self._compile_sink = CompileEvents.add_sink(
            lambda d, h=hist: h.update(d * 1e3)
        )
        self.env._compile_report = CompileEvents.report

    def _notify_restart(self):
        """ExecutionGraph hook: a restart creates new execution attempts
        (ref ExecutionGraph.restart). Called inside the restart `except`
        block, so the ACTIVE exception is the failure cause the attempt
        history records. Listener installed by MiniCluster."""
        listener = getattr(self.env, "_execution_listener", None)
        if listener is not None:
            exc = sys.exc_info()[1]
            cause = (
                f"{type(exc).__name__}: {exc}" if exc is not None
                else "restart"
            )
            try:
                listener("restart", cause)
            except Exception:
                pass      # observability must never kill the job

    def _restart_strategy(self) -> ckpt.RestartStrategy:
        """Reads go through the declared ConfigOptions so conf-file
        strings coerce strictly and parse errors name the key."""
        from flink_tpu.core.config import CoreOptions as CO

        cfg = self.env.config
        kind = cfg.get(CO.RESTART_STRATEGY)
        if kind == "fixed-delay":
            return ckpt.RestartStrategy.fixed_delay(
                cfg.get(CO.RESTART_ATTEMPTS),
                cfg.get(CO.RESTART_DELAY_S),
            )
        if kind == "failure-rate":
            return ckpt.RestartStrategy.failure_rate(
                cfg.get(CO.RESTART_FAILURE_RATE_MAX),
                cfg.get(CO.RESTART_FAILURE_RATE_INTERVAL),
                cfg.get(CO.RESTART_FAILURE_RATE_DELAY),
            )
        if kind == "exponential-backoff":
            return ckpt.RestartStrategy.exponential_backoff(
                cfg.get(CO.RESTART_EXP_INITIAL_DELAY),
                cfg.get(CO.RESTART_EXP_MAX_DELAY),
                cfg.get(CO.RESTART_EXP_MULTIPLIER),
                cfg.get(CO.RESTART_EXP_JITTER),
                cfg.get(CO.RESTART_EXP_RESET_AFTER),
            )
        if kind != "none":
            raise ValueError(
                f"restart-strategy must be none|fixed-delay|failure-rate|"
                f"exponential-backoff, got {kind!r}"
            )
        return ckpt.RestartStrategy.none()

    def run(self, job_name: str, sink_transforms, restore_from=None) -> JobHandle:
        from flink_tpu.core.time import TimeCharacteristic

        pipe = _translate(sink_transforms)
        metrics = JobMetrics()
        # live handle for web monitors (checkpoint stats are structured
        # history, not gauges — the registry only carries scalars)
        self.env._live_metrics = metrics
        self._init_metrics(job_name, metrics)
        # step-loop span tracing (observability.tracing; metrics/tracing):
        # attached to the env so /jobs/<jid>/traces can serve it live AND
        # after the job finishes
        self._tracer = tracer_from_config(
            getattr(self.env, "config", None), stage=job_name
        )
        self.env._span_tracer = self._tracer
        t_start = time.perf_counter()
        for s in pipe.all_sinks:
            s.open()
        pipe.source.open()
        try:
            if self._tracer is not None:
                self._tracer.watch_process()
            from flink_tpu.datastream.window.assigners import (
                CountWindowAssigner, GlobalWindows,
            )

            if self.env.config.get_str("dcn.coordinator", ""):
                if pipe.stages:
                    raise stages_mod.StageGraphError(
                        "multi-stage keyed chains are single-host for now "
                        "— the DCN lockstep plane runs one keyed stage"
                    )
                handle = self._run_dcn(pipe, metrics, job_name,
                                       restore_from)
            elif pipe.stages:
                # chained keyed windowed stages: StageGraph.from_pipeline
                # validates every edge up front (loud setup-time errors
                # naming the unsupported edge) before any compile work
                handle = self._run_windowed(
                    pipe, metrics, job_name, restore_from,
                    graph=stages_mod.StageGraph.from_pipeline(pipe),
                )
            elif pipe.window_agg is not None and (
                pipe.window_agg.trigger is not None
                or pipe.window_agg.evictor is not None
                or pipe.window_agg.window_fn is not None
                or isinstance(pipe.window_agg.assigner, GlobalWindows)
            ):
                handle = self._run_generic_window(pipe, metrics, job_name,
                                                  restore_from)
            elif pipe.window_agg is not None and getattr(
                pipe.window_agg.assigner, "is_session", False
            ):
                handle = self._run_session(pipe, metrics, job_name,
                                           restore_from)
            elif pipe.window_agg is not None and isinstance(
                pipe.window_agg.assigner, CountWindowAssigner
            ):
                handle = self._run_count(pipe, metrics, job_name, restore_from)
            elif pipe.window_agg is not None:
                handle = self._run_windowed(pipe, metrics, job_name,
                                            restore_from)
            elif pipe.process is not None:
                if self._cep_device_eligible(pipe, restore_from):
                    handle = self._run_cep_device(pipe, metrics, job_name,
                                                  restore_from)
                else:
                    handle = self._run_process(pipe, metrics, job_name,
                                               restore_from)
            elif pipe.rolling is not None:
                handle = self._run_rolling(pipe, metrics, job_name, restore_from)
            else:
                self._run_stateless(pipe, metrics)
                handle = JobHandle(job_name, metrics)
        finally:
            pipe.source.close()
            for s in pipe.all_sinks:
                s.close()
            if self._compile_sink is not None:
                CompileEvents.remove_sink(self._compile_sink)
                self._compile_sink = None
            if self._tracer is not None:
                self._tracer.unwatch_process()
                dump = self.env.config.get_str(
                    "observability.trace-dump", ""
                )
                if dump:
                    try:
                        self._tracer.dump(dump)
                    except OSError:
                        pass   # observability must never kill the job
        metrics.wall_time_s = time.perf_counter() - t_start
        return handle

    # ------------------------------------------------------------------
    def _run_dcn(self, pipe: _Pipeline, metrics: JobMetrics, job_name,
                 restore_from=None):
        """Multi-host execution over the DCN global mesh: the SAME
        program runs in every worker process (ref TaskManager.scala:296
        deployment model); ``dcn.coordinator`` + ``dcn.num-processes`` +
        ``dcn.process-id`` select this path from the standard
        ``env.execute()``. The pipeline's windowed keyed stage lowers to
        a DCNJobSpec; each process ingests ITS source's records and the
        keyed shuffle rides the global-mesh collectives (runtime/dcn.py).

        Supported: event-time tumbling/sliding/session windows over
        integer keys with built-in reduces — the stage kinds the
        cross-host kernels implement. Everything else raises rather than
        silently running single-host."""
        import jax

        from flink_tpu.core.time import TimeCharacteristic
        from flink_tpu.datastream.window.assigners import WindowAssigner
        from flink_tpu.runtime import dcn

        env = self.env
        coord = env.config.get_str("dcn.coordinator")
        nproc = env.config.get_int("dcn.num-processes", 1)
        pid = env.config.get_int("dcn.process-id", 0)
        res_dcn = env.config.get_str("pipeline.resident-loop", "auto")
        # Round 20 (was a config ERROR through round 19): resident-loop
        # on the DCN plane now COMPOSES — each host drains up to
        # ring-depth locally-polled batches per lockstep round in one
        # dispatch, the trip count pmax-agreed ON DEVICE so every
        # process still enters the same all_to_all sequence
        # (runtime/dcn.py _run_resident + step.py
        # build_window_dcn_resident_drain). "on" and "while" both select
        # it; "auto" keeps the single-step lockstep dispatch.
        dcn_resident = res_dcn in ("on", "while")
        if res_dcn == "auto":
            print(
                "flink-tpu: pipeline.resident-loop auto resolves to OFF "
                "on the DCN lockstep plane; multi-host execution keeps "
                "the single-step dispatch fallback",
                file=sys.stderr,
            )
        if env.config.get_str("pipeline.data-parallel", "auto") == "on":
            raise ValueError(
                "pipeline.data-parallel=on is incompatible with the DCN "
                "lockstep plane: the sharded ring drain rides the "
                "resident loop, which the lockstep plane cannot run; "
                "unset it or use pipeline.data-parallel=auto"
            )
        wagg = pipe.window_agg
        if wagg is None or pipe.key_by is None:
            raise NotImplementedError(
                "dcn execution covers windowed keyed stages "
                "(tumbling/sliding/session); run other stage kinds "
                "single-host or restructure the job"
            )
        if env.time_characteristic != TimeCharacteristic.EventTime or (
            pipe.ts_transform is None and not pipe.source.columnar
        ):
            raise NotImplementedError(
                "dcn execution requires event time, with an "
                "assign_timestamps_and_watermarks stage or a columnar "
                "source carrying a timestamp array (the lockstep "
                "watermark is the pmin of per-host event-time watermarks)"
            )
        if (wagg.trigger is not None or wagg.evictor is not None
                or wagg.window_fn is not None
                or wagg.allowed_lateness_ms):
            raise NotImplementedError(
                "dcn execution does not cover custom triggers/evictors/"
                "window functions or allowed lateness — these stage "
                "shapes run single-host (the generic window operator)"
            )
        if wagg.reduce_spec_factory is None:
            raise NotImplementedError(
                "dcn execution requires a reduce aggregation "
                "(sum/min/max/count)"
            )
        red = wagg.reduce_spec_factory()
        if red.kind not in ("sum", "min", "max", "count") or \
                getattr(red, "finalize", None) is not None or \
                tuple(getattr(red, "value_shape", ()) or ()) not in (
                    (), (1,)):
            raise NotImplementedError(
                f"dcn execution supports scalar built-in reduces, not "
                f"{red.kind!r} with value shape "
                f"{getattr(red, 'value_shape', ())!r} (e.g. mean() "
                f"needs the composite-accumulator fire path)"
            )
        if wagg.result_fn is not None:
            raise NotImplementedError(
                "dcn execution does not apply result_fn finalization; "
                "use a plain sum/min/max/count reduce"
            )
        assigner = wagg.assigner
        spec_kw = dict(
            capacity_per_shard=env.state_capacity_per_shard,
            max_parallelism=env.max_parallelism,
            batch_per_host=env.batch_size,
            reduce_kind=red.kind,
            out_of_orderness_ms=(
                getattr(pipe.ts_transform.strategy,
                        "out_of_orderness_ms", 0)
                if pipe.ts_transform is not None else 0
            ),
            origin_ms=env.config.get_int("dcn.origin-ms", 0),
            steps_per_dispatch=env.config.get_int(
                "pipeline.steps-per-dispatch", 1
            ),
            resident=dcn_resident,
            resident_ring_depth=env.config.get_int(
                "pipeline.ring-depth", 16
            ),
        )
        # physical ingest partitioner: the API annotation (.shuffle(),
        # .global_(), .rebalance(), .rescale() before key_by) wins, the
        # dcn.ingest-partitioner config is the fallback; the ring/router
        # side channel gets one host:port per process from
        # dcn.rebalance-addrs
        part = pipe.ingest_partition or env.config.get_str(
            "dcn.ingest-partitioner", "forward")
        if part == "rebalance":
            spec_kw.update(rebalance=True)
        elif part != "forward":
            spec_kw.update(ingest_partitioner=part)
        if part not in ("forward", "rescale") and nproc > 1:
            addrs = env.config.get_str("dcn.rebalance-addrs", "")
            if not addrs:
                raise ValueError(
                    f"ingest partitioner {part!r} needs "
                    f"dcn.rebalance-addrs (one host:port per process)")
            spec_kw.update(rebalance_addrs=addrs.split(","))
        if getattr(assigner, "is_session", False):
            if not assigner.is_event_time:
                raise NotImplementedError(
                    "dcn execution covers event-time sessions only "
                    "(processing-time sessions would close on the host "
                    "clock, not the lockstep watermark)"
                )
            spec_kw.update(window_kind="session",
                           gap_ms=assigner.gap_ms)
        elif isinstance(assigner, WindowAssigner) and \
                assigner.is_event_time:
            spec_kw.update(
                size_ms=assigner.size_ms,
                slide_ms=assigner.slide_ms,
                fires_per_step=env.config.get_int(
                    "window.fires-per-step", 4
                ),
            )
        else:
            raise NotImplementedError(
                f"dcn execution does not cover "
                f"{type(assigner).__name__} windows"
            )

        key_sel = pipe.key_by.key_selector
        extractor = wagg.extractor
        ts_fn = (pipe.ts_transform.timestamp_fn
                 if pipe.ts_transform is not None else None)

        class _PipeSource:
            """Adapts this process's pipeline source to the per-host
            partition contract (poll/snapshot/restore)."""

            def poll(self_, max_records):
                polled, end = pipe.source.poll(max_records)
                if pipe.source.columnar and isinstance(polled, tuple):
                    cols, src_ts = polled
                    if not cols:
                        z = np.zeros(0, np.int64)
                        return z, z, np.zeros(0, np.float32), end
                    for t in pipe.pre_chain:
                        if t.kind != "map":
                            raise NotImplementedError(
                                "columnar sources support only 'map' "
                                "before key_by"
                            )
                        cols = t.fn(cols)
                    keys = np.asarray(key_sel(cols))
                    vals = np.asarray(extractor(cols), np.float32)
                    ts = np.asarray(
                        ts_fn(cols) if ts_fn is not None else src_ts,
                        np.int64,
                    )
                else:
                    elements = _apply_chain(pipe.pre_chain,
                                            self._to_elements(polled))
                    if not elements:
                        z = np.zeros(0, np.int64)
                        return z, z, np.zeros(0, np.float32), end
                    keys = np.asarray([key_sel(e) for e in elements])
                    vals = np.asarray([extractor(e) for e in elements],
                                      np.float32)
                    ts = np.asarray([ts_fn(e) for e in elements],
                                    np.int64)
                if not np.issubdtype(keys.dtype, np.integer):
                    raise NotImplementedError(
                        "dcn execution requires integer keys (the key "
                        "id IS the 64-bit routing identity across "
                        "processes; string keys would need a "
                        "coordinated codec)"
                    )
                metrics.records_in += len(keys)
                return keys.astype(np.int64), ts, vals, end

            def snapshot(self_):
                return pipe.source.snapshot_offsets()

            def restore(self_, state):
                pipe.source.restore_offsets(state)

        spec = dcn.DCNJobSpec(
            source_factory=lambda _pid, _nproc: _PipeSource(),
            **spec_kw,
        )
        if not getattr(jax.distributed, "is_initialized", lambda: False)():
            jax.distributed.initialize(
                coordinator_address=coord, num_processes=nproc,
                process_id=pid,
            )
        if restore_from and (
            not env.checkpoint_dir
            or os.path.abspath(str(restore_from))
            != os.path.abspath(env.checkpoint_dir)
        ):
            # the DCN runner restores the latest GLOBAL cut from the
            # job's own lockstep checkpoint dir; silently substituting it
            # for a named savepoint would resume from different state
            raise NotImplementedError(
                "dcn execution restores from the job's configured "
                "checkpoint directory (the lockstep global cut); pass "
                "restore_from equal to the checkpoint directory, or "
                "point enable_checkpointing at the savepoint"
            )
        ckpt_every = env.checkpoint_interval_steps or 0
        runner = dcn.runner_for_spec(
            spec, pid, nproc,
            checkpoint_dir=env.checkpoint_dir or None,
            ckpt_every=ckpt_every,
            restore=bool(restore_from),
        )
        out = runner.run()
        metrics.steps = out["cycles"]
        metrics.dcn_ingested_local = int(out.get("ingested_local", 0))
        is_session = spec_kw.get("window_kind") == "session"
        rows = []
        for k64, st_, en_, v in zip(
                out["key_id"], out["window_start_ms"],
                out["window_end_ms"], out["value"]):
            key = int(np.int64(np.uint64(k64)))
            if is_session:
                rows.append(SessionResult(key, int(st_), int(en_),
                                          float(v)))
            else:
                rows.append(WindowResult(key, int(en_), float(v)))
        metrics.fires += len(rows)
        _emit_batch(pipe, rows, metrics)
        return JobHandle(job_name, metrics)

    # ------------------------------------------------------------------
    def _run_stateless(self, pipe: _Pipeline, metrics: JobMetrics):
        B = self.env.batch_size
        while True:
            self._poll_control()
            polled, end = pipe.source.poll(B)
            elements = self._to_elements(polled)
            metrics.records_in += len(elements)
            elements = _apply_chain(pipe.pre_chain, elements)
            _emit_batch(pipe, elements, metrics)
            metrics.steps += 1
            if end:
                break

    _to_elements = staticmethod(to_elements)

    # ------------------------------------------------------------------
    def _run_windowed(self, pipe: _Pipeline, metrics: JobMetrics, job_name,
                      restore_from=None, graph=None):
        from flink_tpu.core.time import TimeCharacteristic

        env = self.env
        wagg = pipe.window_agg
        assigner = wagg.assigner
        # -- chained stage graph (runtime/stages.py, round 16): when the
        # pipeline carries downstream keyBy→window stages, `graph` is the
        # validated StageGraph and the resident drain becomes the chained
        # variant (step.build_window_chained_drain*): stage-N fires are
        # re-keyed on device and applied to stage-N+1 inside the same
        # count-gated scan, so a 2-stage pipeline still costs one host
        # dispatch per ring drain. Sinks observe the FINAL stage's fires;
        # emit_wagg carries that stage's result_fn/codec semantics.
        emit_wagg = graph.stages[-1].wagg if graph is not None else wagg
        chain_specs: List[Any] = []   # downstream WindowStageSpecs (setup)
        chain_states: List[Any] = []  # downstream device states
        event_time = assigner.is_event_time and (
            env.time_characteristic == TimeCharacteristic.EventTime
        )

        n_dev = len(jax.devices())
        n_shards = max(1, min(env.parallelism, n_dev))
        ctx = MeshContext.create(n_shards, env.max_parallelism)
        # controller-chosen heat-balanced key-group slicing (ISSUE 19):
        # holds the (start, end) pairs the NEXT _replan_mesh installs,
        # persisting a rebalance across subsequent setups; None = the
        # uniform slicing. A shard-COUNT change (elastic loss/scale-up)
        # drops it — the heat evidence it encoded was per-shard.
        kg_slices_hold = [None]
        # -- elastic survival (runtime/elastic.py; ISSUE 8): device loss
        # re-plans the job over the surviving shards instead of crash-
        # looping at a parallelism the mesh no longer has. The
        # controller is the operator/web surface: degraded-state ledger
        # + the scale-back-up request box the step loop polls.
        from flink_tpu.core.config import CoreOptions as _ECO

        elastic_enabled = env.config.get(_ECO.RECOVERY_ELASTIC)
        elastic_min_shards = max(1, env.config.get(_ECO.RECOVERY_MIN_SHARDS))
        elastic_ctl = elastic.ElasticityController(
            list(np.asarray(ctx.mesh.devices).flat)
        )
        env._elasticity_report = elastic_ctl.report
        env._elastic_controller = elastic_ctl

        red = wagg.reduce_spec_factory()
        # time domain: 1 tick = 1 ms until first batch fixes the origin
        td: Optional[TimeDomain] = None
        size_ms, slide_ms = assigner.size_ms, assigner.slide_ms

        win = None
        spec = None
        # compiled update-step variants: steps_by_route[route][tier] with
        # route in {"mask", "exchange"} (record routing to owning shards)
        # and tier in {"insert", "fast"} (adaptive step tiering); the host
        # picks a variant per micro-batch at zero switch cost (shared
        # state layout)
        steps_by_route = {}
        # -- dispatch fusion (pipeline.steps-per-dispatch=K): the fused
        # slot collects K consecutive same-route planned batches and ONE
        # lax.scan megastep applies them in a single dispatch, dividing
        # the fixed dispatch/tracing/watchdog overhead by K. K=1 keeps
        # the single-step path untouched. megasteps_by_route mirrors
        # steps_by_route's [route][tier] shape.
        k_fuse = max(1, env.config.get_int("pipeline.steps-per-dispatch", 1))
        megasteps_by_route = {}
        # -- resident pipeline (pipeline.fused-fire): fold the fire sweep
        # into the megastep scan so a pane-boundary crossing inside a
        # K-group fires WITHIN the scan — the fused slot no longer breaks
        # groups at fire boundaries, and fire payloads surface as LAGGED
        # megastep outputs (fire_watch) instead of a separate serialized
        # fire dispatch. off = the PR-5 split-dispatch path, which always
        # remains the fallback for partial groups and the DCN lockstep
        # plane. Read through the declared ConfigOption (strict coercion).
        from flink_tpu.core.config import CoreOptions as _CoreOpts

        ff_cfg = str(env.config.get(_CoreOpts.PIPELINE_FUSED_FIRE))
        if ff_cfg not in ("auto", "on", "off"):
            raise ValueError(
                f"pipeline.fused-fire must be auto|on|off, got {ff_cfg!r}"
            )
        use_fused_fire = k_fuse > 1 and ff_cfg != "off"
        fire_watch = deque()   # lagged fused-fire payload handles
        FIRE_LAG = 1           # dispatches a payload may stay unread
        fused = ingest_mod.FusedBatchAccumulator(
            k_fuse, hold_fires=use_fused_fire
        )
        fuse_gauge = [None]    # settable steps_per_dispatch gauge
        # -- device-resident steady-state loop (pipeline.resident-loop,
        # round 12): the prefetch thread publishes staged batches into a
        # DeviceBatchRing (runtime/ingest.py) and the accumulated drain
        # group — capacity = ring depth — dispatches as ONE count-gated
        # resident-drain scan (runtime/step.py), so steady state costs
        # one host round trip per ring drain instead of one per
        # megastep. Config validated here; `use_resident` is FINALIZED
        # where prefetch/staging resolve (just before the ingest
        # pipeline is built) because the drain consumes ring-published
        # staged batches. The DCN lockstep plane runs a separate
        # executor entirely (_run_dcn) and keeps its loud single-step
        # fallback there.
        res_cfg = str(env.config.get(_CoreOpts.PIPELINE_RESIDENT_LOOP))
        if res_cfg not in ("auto", "on", "while", "off"):
            raise ValueError(
                f"pipeline.resident-loop must be auto|on|while|off, "
                f"got {res_cfg!r}"
            )
        ring_depth = max(2, env.config.get_int("pipeline.ring-depth", 16))
        # early-exit while-drain (pipeline.resident-loop=while, ISSUE
        # 20): the drain's trip count re-reads the ring's HBM publish
        # cursor inside the loop condition, bounded per dispatch by
        # while-drain.max-slots — the bound (not the observed fill) is
        # what the watchdog arms and the flight recorder sizes to, and
        # the drain GROUP capacity grows to the bound so publishes
        # landing while the previous drain was in flight join the
        # current dispatch instead of forcing a new one. 0 sizes the
        # bound to 2x ring depth (never below ring depth).
        wd_max_slots = env.config.get_int(
            "pipeline.while-drain.max-slots", 0)
        if wd_max_slots <= 0:
            wd_max_slots = 2 * ring_depth
        wd_max_slots = max(ring_depth, wd_max_slots)
        wd_cpu_override = env.config.get_str(
            "pipeline.while-drain.cpu-override", "off") == "on"
        use_while = False          # finalized with use_resident
        use_resident = False       # finalized at ingest construction
        residents_by_route = {}    # [route][tier] resident-drain kernels
        pending_batch = [None]     # greedy ring fill's non-drain leftover
        drain_warmup = [False]     # warmup drains skip the chaos seam
        # -- mesh-resident data parallelism (pipeline.data-parallel,
        # round 13): each chip owns a contiguous key-group slice, the
        # prefetch thread routes records to the owning shard and
        # publishes into that shard's slice of a ShardedDeviceBatchRing,
        # and one shard_map'd drain advances every shard's ring with
        # zero cross-chip collectives in the keyed body (fires pack
        # per-shard and merge host-side on the lagged consume path).
        # Validated here; `use_dp` is FINALIZED with use_resident.
        dp_cfg = str(env.config.get(_CoreOpts.PIPELINE_DATA_PARALLEL))
        if dp_cfg not in ("auto", "on", "off"):
            raise ValueError(
                f"pipeline.data-parallel must be auto|on|off, "
                f"got {dp_cfg!r}"
            )
        dp_capf = env.config.get_float("pipeline.shard-capacity-factor", 2.0)
        if dp_capf < 1.0:
            raise ValueError(
                f"pipeline.shard-capacity-factor must be >= 1.0, "
                f"got {dp_capf}"
            )
        use_dp = False             # finalized at ingest construction
        shard_cap = [0]            # per-shard ring-slice rows (dp only)
        # -- update-kernel pre-combine (pipeline.update-precombine):
        # duplicate-key collapse before the state scatter (wk.update);
        # generic reduces already pre-aggregate, sketches expand per
        # register. auto is PLATFORM-gated: on accelerators a scatter
        # with duplicate indices serializes (the win), but XLA's CPU
        # sort costs ~4.5ms per 16k lanes (measured, device_update_
        # ceiling bench) — far more than the CPU scatter it would save —
        # so auto keeps the CPU path bit-identical to the unsorted
        # scatter
        pc_cfg = env.config.get_str("pipeline.update-precombine", "auto")
        if pc_cfg not in ("auto", "on", "off"):
            raise ValueError(
                f"pipeline.update-precombine must be auto|on|off, "
                f"got {pc_cfg!r}"
            )
        use_precombine = pc_cfg == "on" or (
            pc_cfg == "auto" and jax.default_backend() != "cpu"
        )
        # -- packed state planes (state.packed-planes): touched bits ride
        # a trailing accumulator column — one scatter/sweep maintains
        # both planes (wk.init_state packed). auto is PLATFORM-gated
        # like precombine: on accelerators the saved scatter pass wins;
        # on CPU the wider sweep bytes cost more than the serial scatter
        # they replace (measured, device_update_ceiling state-plane
        # sweep). Snapshots stay logical, so checkpoints move freely
        # between plane layouts.
        pp_cfg = str(env.config.get(_CoreOpts.STATE_PACKED_PLANES))
        if pp_cfg not in ("auto", "on", "off"):
            raise ValueError(
                f"state.packed-planes must be auto|on|off, got {pp_cfg!r}"
            )
        if pp_cfg == "on" and not wk.packed_eligible(red):
            raise ValueError(
                "state.packed-planes=on requires a builtin sum/count/"
                "min/max reduce with the default neutral and an "
                "at-most-1-D value; unset it for this stage"
            )
        use_packed = pp_cfg == "on" or (
            pp_cfg == "auto" and jax.default_backend() != "cpu"
            and wk.packed_eligible(red)
        )
        # -- tiered key-group state (state.tiers.resident-key-groups):
        # a per-shard budget caps how many key-groups keep device slot
        # rows; the rest live in the host pane stores and ride the
        # overflow ring until promoted. The manager is created in
        # setup() (key-group ranges come from the mesh) and SURVIVES
        # re-plans via rescale() so fault/churn counters span the job.
        tier_budget_cfg = int(
            env.config.get(_CoreOpts.STATE_TIERS_RESIDENT_KEY_GROUPS)
        )
        use_tiers = [False]
        tier_mgr = [None]
        tier_mask_dev = [None]    # device replica of the residency mask
        exchange_cap = [0]        # per-(src,dst) bucket lanes of the exchange
        force_route = [None]      # warmup override
        fire_step = None
        fire_reduced_step = None   # ReducedFires variant (device_reduce sinks)
        state = None
        # key-state layout, decided ONCE (the compiled steps bake it in):
        # "hash" | "direct" | "auto" (resolved from the first batch's key
        # identities in setup(); see wk.init_state layout="direct")
        layout_cfg = env.config.get_str("state.backend.layout", "auto")
        if layout_cfg not in ("auto", "hash", "direct"):
            raise ValueError(
                f"state.backend.layout must be auto|hash|direct, "
                f"got {layout_cfg!r}"
            )
        layout = [None]
        # set by poll_cycle from the first batch's key identities; setup()
        # combines it with spillability to resolve layout "auto"
        auto_direct_hint = [False]
        # adaptive step tiering (see wk.update insert flag): holders are
        # 1-element lists so nested closures can flip them
        step_mode = ["insert"]
        tier_quiet = [0]          # consecutive zero-activity lagged checks
        # checks are SAMPLED every MON_EVERY steps, so 2 quiet checks span
        # ~2*MON_EVERY steps of genuinely quiet stream before the switch
        TIER_QUIET_CHECKS = 2
        # futile-bounce damping: when a fast->insert bounce places NOTHING
        # (the misses were chain-exhausted keys insert can never place),
        # tolerate that miss level in fast mode instead of bouncing
        # forever; reset when compaction/restore may change placeability
        miss_tolerance = [0]
        bounce_miss = [0]         # miss count that triggered current bounce
        bounce_placed = [False]   # did the bounce place any key?
        # step lane count: == B, or B rounded up to a multiple of the
        # shard count when the ICI exchange splits the batch over devices
        B_step = [None]
        # reused prefix-mask template (ingest.make_prefix_mask_template):
        # the per-batch np.ones+pad valid mask becomes a view slice —
        # one allocation per stage, immutable, safe under async transfer
        valid_tmpl = [None]
        codec = KeyCodec()
        # reverse key map costs a python dict insert per record; benchmarks
        # and columnar sinks that accept 64-bit key ids can turn it off
        keep_rev = env.config.get_bool("keys.reverse-map", True)
        B = env.batch_size
        wm_strategy = (
            pipe.ts_transform.strategy if pipe.ts_transform is not None
            else WatermarkStrategy.for_monotonous_timestamps()
        )
        # doctor recompile baseline: steady-bucket snapshot re-pinned at
        # the end of every setup() so only post-build compiles count
        _doctor_steady0 = [{"count": 0, "time_ms": 0.0}]

        def setup(origin_ms: int, fresh_state: bool = True):
            nonlocal td, win, spec, fire_step, fire_reduced_step, state
            td = TimeDomain(origin_ms=origin_ms, ms_per_tick=1)
            ppw = size_ms // slide_ms
            ring_cfg = env.config.get_int("window.ring-panes", 0)
            if ring_cfg and ring_cfg < ppw + 3:
                # the catch-up slicer's span bound is
                # ring - max(2, panes_per_window + 1); below 1 its
                # grouping loop can never advance (each group would be
                # empty forever) — fail loudly at setup instead of
                # hanging the job on the first replay burst
                raise ValueError(
                    f"window.ring-panes={ring_cfg} leaves no catch-up "
                    f"headroom for a {ppw}-pane window (need ring >= "
                    f"panes_per_window + 3 = {ppw + 3}); raise it or "
                    f"unset it to use the auto-sized ring"
                )
            ring = ring_cfg or max(
                8,
                2 * ppw
                + (wm_strategy.out_of_orderness_ms + wagg.allowed_lateness_ms)
                // slide_ms
                + 2,
            )
            # overflow ring: spill-tier support for builtin float32 scalar
            # reduces (kill the hard over-capacity failure; VERDICT item 7)
            ovf = 0
            spillable = (
                wk.overflow_supported(red)
                and jnp.zeros((), red.dtype).dtype == jnp.float32
                and len(red.value_shape) <= 1
                # the spill tier cannot replay late re-fires for evicted
                # keys (host stores carry no freshness); with allowed
                # lateness the job keeps strict-capacity semantics instead
                # of being silently wrong for that corner
                and wagg.allowed_lateness_ms == 0
                # chained stage graphs keep strict capacity: a spill-tier
                # eviction on stage 0 would have to replay through every
                # downstream stage (host stores carry no edge lineage)
                and graph is None
            )
            # -1/unset = auto: absorbs the full sampled-lagged detection
            # window of full-batch overflow (MON_EVERY*(OVF_LAG+1) steps
            # between a miss and its drain, plus dispatch slack) with no
            # loss; 0 disables; an explicit positive value wins (and may
            # lose under sustained pressure, surfaced by the
            # strict-capacity error)
            ovf_cfg = env.config.get_int("state.backend.overflow-ring", -1)
            if ovf_cfg > 0 and not spillable:
                raise ValueError(
                    "state.backend.overflow-ring is set but this window "
                    "stage cannot use the spill tier (requires a builtin "
                    "float32 sum/count/min/max reduce without finalize and "
                    "allowed lateness 0); unset it to run with strict "
                    "capacity"
                )
            if spillable:
                # + k_fuse: a fused group's misses can only drain at the
                # megastep boundary, so the detection window stretches by
                # up to one group of batches. The sample stride is
                # ceil(MON_EVERY / K) * K batches, not MON_EVERY: the
                # skip counter advances K at a time and resets on
                # crossing, so samples land only on dispatch boundaries
                # (K=7 with MON_EVERY=8 samples every 14 batches)
                # with the resident loop on the dispatch group is the
                # RING, so the detection window stretches by up to one
                # ring of batches, not one K-group
                grp_k = ring_depth if use_resident else k_fuse
                stride = -(-MON_EVERY // grp_k) * grp_k
                auto = (stride * (OVF_LAG + 1) + 4 + grp_k) * B + 8192
                ovf = ovf_cfg if ovf_cfg >= 0 else auto
            # tiered state rides the spill tier: a non-resident lane
            # diverts to the overflow ring and folds into the same host
            # pane stores, so every spill-tier precondition is a tier
            # precondition too (and the ring must actually exist)
            if tier_budget_cfg > 0 and not (spillable and ovf):
                raise ValueError(
                    "state.tiers.resident-key-groups is set but this "
                    "window stage cannot run tiered state (requires the "
                    "spill tier: a builtin float32 sum/count/min/max "
                    "reduce without finalize, allowed lateness 0, no "
                    "chained stage graph, and a non-zero overflow "
                    "ring); unset it to keep every key-group resident"
                )
            use_tiers[0] = tier_budget_cfg > 0
            win = wk.WindowSpec(
                size_ticks=size_ms, slide_ticks=slide_ms,
                ring=ring,
                # F window-ends evaluated per fire step: each lane costs 3
                # full-capacity pack scatters, so fewer lanes = cheaper
                # boundary drains; catch-up replay just loops more drains
                fires_per_step=env.config.get_int("window.fires-per-step", 4),
                lateness_ticks=wagg.allowed_lateness_ms,
                overflow=ovf,
            )
            if layout[0] is None:
                if layout_cfg != "auto":
                    layout[0] = layout_cfg
                else:
                    # auto picks direct only when the spill tier exists to
                    # absorb later out-of-bound keys; a non-spillable
                    # stage (e.g. allowed lateness > 0, generic reduce)
                    # would DROP them where the hash layout would simply
                    # insert them
                    layout[0] = (
                        "direct" if auto_direct_hint[0] and spillable
                        else "hash"
                    )
            spec = WindowStageSpec(
                win=win, red=red,
                capacity_per_shard=env.state_capacity_per_shard,
                probe_len=env.config.get_int("state.probe-len", 16),
                layout=layout[0],
                precombine=use_precombine,
                packed=use_packed,
            )
            metrics.state_layout = layout[0]
            metrics.state_packed_planes = use_packed
            if use_tiers[0]:
                starts_t, ends_t = ctx.kg_bounds()
                if tier_mgr[0] is None:
                    tier_mgr[0] = tiers_mod.TierManager(
                        ctx.max_parallelism, starts_t, ends_t,
                        tier_budget_cfg,
                        prefetch_ahead_panes=int(env.config.get(
                            _CoreOpts.STATE_TIERS_PREFETCH_AHEAD_PANES
                        )),
                        min_dwell_cycles=int(env.config.get(
                            _CoreOpts.STATE_TIERS_MIN_DWELL_CYCLES
                        )),
                        max_swaps_per_cycle=int(env.config.get(
                            _CoreOpts.STATE_TIERS_MAX_SWAPS_PER_CYCLE
                        )),
                    )
                else:
                    # elastic re-plan / restore: re-slice residency to
                    # the new shard ranges, keep the job-lifetime
                    # counters (faults/churn feed the doctor rule)
                    tier_mgr[0].rescale(starts_t, ends_t)
                tier_mask_dev[0] = jnp.asarray(tier_mgr[0].mask())
                if self._job_group is not None:
                    grp_t = self._job_group

                    def _tier_ctr(field):
                        tm = tier_mgr[0]
                        return int(getattr(tm, field)) if tm else 0

                    def _tier_res():
                        tm = tier_mgr[0]
                        return tm.resident_groups() if tm else 0

                    # idempotent like the drain gauges (register
                    # overwrites), re-run per setup for elastic re-plans
                    grp_t.gauge("tier_resident_groups", _tier_res)
                    grp_t.gauge("tier_faults",
                                partial(_tier_ctr, "tier_faults"))
                    grp_t.gauge("tier_prefetch_hits",
                                partial(_tier_ctr, "prefetch_hits"))
                    grp_t.gauge("tier_prefetch_misses",
                                partial(_tier_ctr, "prefetch_misses"))
            if graph is not None:
                # plan the downstream stages off stage 0's spec (identity
                # re-key: every stage shares the codec/layout/capacity,
                # fires stay shard-local) and reject runtime shapes the
                # chained drain cannot serve — loudly, naming the knob,
                # before any compile work
                # drain_depth sizes the downstream pane rings: the
                # chained drain advances stages 1..N-1 once per drain,
                # so they must absorb a whole ring's worth of upstream
                # fires between advances
                chain_specs[:] = graph.plan_specs(
                    spec, drain_depth=ring_depth
                )
                graph.check_runtime(
                    use_resident=use_resident,
                    overflow_lanes=ovf,
                    drain_stats=drain_stats_on,
                    reduced_fires=sink_device_reduce,
                    max_stages=env.config.get(
                        _CoreOpts.PIPELINE_STAGES_MAX_STAGES
                    ),
                )
            if not steps_by_route:
                # exchange.mode — how records reach their owning shard on
                # a multi-device mesh (the reference's keyed shuffle,
                # KeyGroupStreamPartitioner.java:53):
                #   "auto" (default): PER-BATCH adaptive. The host computes
                #     exact shard counts for each batch (cheap numpy) and
                #     dispatches the O(B/n)-per-device all_to_all step only
                #     when every shard's records provably fit its static
                #     bucket; skewed batches take the replicate-and-mask
                #     step instead. Never lossy, scalable whenever the
                #     batch actually balances.
                #   "all_to_all": always exchange; bucket overflow is
                #     counted into dropped_capacity (strict-capacity
                #     surfaces it).
                #   "mask": always replicate-and-mask (O(B) per chip).
                # The batch auto-pads up to a multiple of the shard count.
                mode = env.config.get_str("exchange.mode", "auto")
                if mode not in ("auto", "all_to_all", "mask"):
                    raise ValueError(
                        f"exchange.mode must be auto|all_to_all|mask, "
                        f"got {mode!r}"
                    )
                if graph is not None and mode == "all_to_all":
                    raise stages_mod.StageGraphError(
                        "exchange.mode=all_to_all is not supported with "
                        "chained stage graphs — the identity re-key keeps "
                        "fires shard-local, so the chained drain runs the "
                        "replicate-and-mask route; unset exchange.mode"
                    )
                want_ex = (
                    ctx.n_shards > 1 and mode in ("auto", "all_to_all")
                    and graph is None
                )
                B_step[0] = (
                    ((B + ctx.n_shards - 1) // ctx.n_shards) * ctx.n_shards
                    if want_ex else B
                )
                metrics.exchange_mode = (
                    "adaptive" if want_ex and mode == "auto"
                    else "all_to_all" if want_ex else "mask"
                )
                build_fast = spillable and win.overflow and \
                    layout[0] != "direct"
                if graph is not None:
                    # chained jobs dispatch ONLY through the chained
                    # resident drain — a plain per-batch step would
                    # advance stage 0 without feeding stage 1, so no
                    # single-step kernel exists; the placeholder keeps
                    # the route table (and the ingest plan's route
                    # tuple) shaped like the single-stage path
                    steps_by_route["mask"] = {"insert": None, "fast": None}
                elif not want_ex or mode == "auto":
                    steps_by_route["mask"] = {
                        "insert": build_window_update_step(
                            ctx, spec, kg_fill=kg_stats_on,
                            tiered=use_tiers[0],
                        ),
                        "fast": build_window_update_step(
                            ctx, spec, insert=False, kg_fill=kg_stats_on,
                            tiered=use_tiers[0],
                        ) if build_fast else None,
                    }
                if want_ex:
                    bpd = B_step[0] // ctx.n_shards
                    capf = env.config.get_float("exchange.capacity-factor",
                                                2.0)
                    ex_insert = build_window_update_step_exchange(
                        ctx, spec, bpd, capf, kg_fill=kg_stats_on,
                        tiered=use_tiers[0],
                    )
                    steps_by_route["exchange"] = {
                        "insert": ex_insert,
                        "fast": build_window_update_step_exchange(
                            ctx, spec, bpd, capf, insert=False,
                            kg_fill=kg_stats_on, tiered=use_tiers[0],
                        ) if build_fast else None,
                    }
                    exchange_cap[0] = ex_insert.bucket_cap
                if k_fuse > 1 and graph is None:
                    # K-fused megasteps mirror the [route][tier] variant
                    # table for exactly the routes built above; partial
                    # groups fall back to the single steps (bit-identical
                    # by construction). With the resident pipeline on
                    # (pipeline.fused-fire) the FIRED variants replace
                    # the plain ones outright — full groups always take
                    # the in-scan fire path, so compiling both would
                    # only double the warmup burst.
                    if use_fused_fire:
                        # device_reduce sink topologies never read fire
                        # payloads, so their fired megasteps surface
                        # ReducedFires and skip the [K, F, C] payload
                        # stacking entirely (the in-scan analog of
                        # fire_reduced_step). Only safe when the spill
                        # tier can NEVER activate (no overflow ring):
                        # spill merges need per-key payloads.
                        ff_reduced = bool(
                            sink_device_reduce and not win.overflow
                        )
                        mk_mask = partial(
                            build_window_megastep_fired,
                            reduced=ff_reduced,
                        )
                        mk_ex = partial(
                            build_window_megastep_fired_exchange,
                            reduced=ff_reduced,
                        )
                    else:
                        mk_mask = build_window_megastep
                        mk_ex = build_window_megastep_exchange
                    if "mask" in steps_by_route:
                        megasteps_by_route["mask"] = {
                            "insert": mk_mask(
                                ctx, spec, k_fuse, kg_fill=kg_stats_on,
                                tiered=use_tiers[0],
                            ),
                            "fast": mk_mask(
                                ctx, spec, k_fuse, insert=False,
                                kg_fill=kg_stats_on, tiered=use_tiers[0],
                            ) if build_fast else None,
                        }
                    if "exchange" in steps_by_route:
                        megasteps_by_route["exchange"] = {
                            "insert": mk_ex(
                                ctx, spec, bpd, k_fuse, capf,
                                kg_fill=kg_stats_on, tiered=use_tiers[0],
                            ),
                            "fast": mk_ex(
                                ctx, spec, bpd, k_fuse, capf,
                                insert=False, kg_fill=kg_stats_on,
                                tiered=use_tiers[0],
                            ) if build_fast else None,
                        }
                if use_resident and graph is not None:
                    # chained resident drain (round 16): ONE count-gated
                    # scan advances EVERY stage — stage-N fire lanes are
                    # re-keyed on device (cumsum+searchsorted+gather)
                    # and applied to stage-N+1 inside the same scan, so
                    # the whole chain costs one host dispatch per ring
                    # drain. Insert tier only: the fast tier's miss
                    # contract needs the overflow ring, which chained
                    # jobs run without (strict capacity).
                    ex_lanes = env.config.get(
                        _CoreOpts.PIPELINE_STAGES_EXCHANGE_LANES
                    )
                    all_specs = (spec,) + tuple(chain_specs)
                    residents_by_route["mask"] = {
                        "insert": build_window_chained_drain(
                            ctx, all_specs, ring_depth,
                            kg_fill=kg_stats_on,
                            exchange_lanes=ex_lanes,
                            drain_stats=drain_stats_on,
                        ),
                        "fast": None,
                    }
                    if use_dp:
                        shard_cap[0] = bucket_capacity(
                            B_step[0], ctx.n_shards, dp_capf
                        )
                        residents_by_route["sharded"] = {
                            "insert": build_window_chained_drain_sharded(
                                ctx, all_specs, ring_depth,
                                kg_fill=kg_stats_on,
                                exchange_lanes=ex_lanes,
                                drain_stats=drain_stats_on,
                            ),
                            "fast": None,
                        }
                        if self._job_group is not None:
                            # same idempotent per-shard refusal gauges
                            # as the single-stage sharded ring below
                            for _s in range(ctx.n_shards):
                                self._job_group.gauge(
                                    f"ring_publish_refusals_shard_{_s}",
                                    partial(_ring_refusals, _s),
                                )
                elif use_resident:
                    # resident ring-drain kernels (pipeline.resident-
                    # loop): ONE count-gated scan per route x tier
                    # serves EVERY fill level 1..ring_depth — the host
                    # passes the live slot count as a traced operand,
                    # so partial drains never recompile. Fired variants
                    # only: the drain is the fused-fire pipeline taken
                    # to its limit (every slot fires under its own
                    # watermark inside the scan).
                    rd_reduced = bool(
                        sink_device_reduce and not win.overflow
                    )
                    # while mode (ISSUE 20): mask + sharded routes swap
                    # the count-gated scan for the early-exit while
                    # drain sized to the while-drain BOUND; the exchange
                    # route keeps the scan kernel (the all_to_all in a
                    # data-dependent while body is not worth the
                    # collective-under-while hazard) but is sized to the
                    # same bound so while-mode drain groups fit it
                    drain_depth = wd_max_slots if use_while else ring_depth
                    if "mask" in steps_by_route:
                        if use_while:
                            residents_by_route["mask"] = {
                                "insert": build_window_while_drain(
                                    ctx, spec, wd_max_slots,
                                    kg_fill=kg_stats_on,
                                    reduced=rd_reduced,
                                    drain_stats=drain_stats_on,
                                    tiered=use_tiers[0],
                                ),
                                "fast": build_window_while_drain(
                                    ctx, spec, wd_max_slots, insert=False,
                                    kg_fill=kg_stats_on,
                                    reduced=rd_reduced,
                                    drain_stats=drain_stats_on,
                                    tiered=use_tiers[0],
                                ) if build_fast else None,
                            }
                        else:
                            residents_by_route["mask"] = {
                                "insert": build_window_resident_drain(
                                    ctx, spec, ring_depth,
                                    kg_fill=kg_stats_on,
                                    reduced=rd_reduced,
                                    drain_stats=drain_stats_on,
                                    tiered=use_tiers[0],
                                ),
                                "fast": build_window_resident_drain(
                                    ctx, spec, ring_depth, insert=False,
                                    kg_fill=kg_stats_on,
                                    reduced=rd_reduced,
                                    drain_stats=drain_stats_on,
                                    tiered=use_tiers[0],
                                ) if build_fast else None,
                            }
                    if "exchange" in steps_by_route:
                        residents_by_route["exchange"] = {
                            "insert": build_window_resident_drain_exchange(
                                ctx, spec, bpd, drain_depth, capf,
                                kg_fill=kg_stats_on, reduced=rd_reduced,
                                drain_stats=drain_stats_on,
                                tiered=use_tiers[0],
                            ),
                            "fast": build_window_resident_drain_exchange(
                                ctx, spec, bpd, drain_depth, capf,
                                insert=False, kg_fill=kg_stats_on,
                                reduced=rd_reduced,
                                drain_stats=drain_stats_on,
                                tiered=use_tiers[0],
                            ) if build_fast else None,
                        }
                    if use_dp:
                        # shard_map'd drain (pipeline.data-parallel):
                        # records arrive PRE-ROUTED to the owning
                        # shard's ring slice, so the drained body runs
                        # shard-local with ZERO collectives (the
                        # ownership mask is a safety net, not a
                        # router) and each shard gates on its OWN
                        # count — one slow shard never pads the
                        # others' drains.
                        shard_cap[0] = bucket_capacity(
                            B_step[0], ctx.n_shards, dp_capf
                        )
                        if use_while:
                            residents_by_route["sharded"] = {
                                "insert": build_window_while_drain_sharded(
                                    ctx, spec, wd_max_slots,
                                    kg_fill=kg_stats_on,
                                    reduced=rd_reduced,
                                    drain_stats=drain_stats_on,
                                    tiered=use_tiers[0],
                                ),
                                "fast": build_window_while_drain_sharded(
                                    ctx, spec, wd_max_slots, insert=False,
                                    kg_fill=kg_stats_on,
                                    reduced=rd_reduced,
                                    drain_stats=drain_stats_on,
                                    tiered=use_tiers[0],
                                ) if build_fast else None,
                            }
                        else:
                            residents_by_route["sharded"] = {
                                "insert": build_window_sharded_drain(
                                    ctx, spec, ring_depth,
                                    kg_fill=kg_stats_on,
                                    reduced=rd_reduced,
                                    drain_stats=drain_stats_on,
                                    tiered=use_tiers[0],
                                ),
                                "fast": build_window_sharded_drain(
                                    ctx, spec, ring_depth, insert=False,
                                    kg_fill=kg_stats_on,
                                    reduced=rd_reduced,
                                    drain_stats=drain_stats_on,
                                    tiered=use_tiers[0],
                                ) if build_fast else None,
                            }
                        if self._job_group is not None:
                            # per-shard refusal gauges live here (not
                            # the main gauges block) so they track the
                            # mesh size across elastic re-plans;
                            # registry.register overwrites, so the
                            # repeat registration is idempotent — and
                            # a scale-DOWN re-plan removes the series
                            # of shards that no longer exist (ISSUE 19
                            # bugfix: stale gauges reported the dead
                            # mesh forever)
                            for _s in range(ctx.n_shards):
                                self._job_group.gauge(
                                    f"ring_publish_refusals_shard_{_s}",
                                    partial(_ring_refusals, _s),
                                )
                            for _s in range(ctx.n_shards,
                                            refusal_gauge_n[0]):
                                self._job_group.remove(
                                    f"ring_publish_refusals_shard_{_s}"
                                )
                            refusal_gauge_n[0] = ctx.n_shards
                if use_resident and drain_stats_on:
                    # drain flight recorder, host half: the
                    # aggregator the lagged consume path feeds,
                    # plugged into the attribution as its resident-
                    # loop regime signal — single-stage AND chained
                    # drains (stage-aware since ISSUE 17). Rebuilt per
                    # setup() so an elastic re-plan resizes the
                    # per-shard series with the mesh. Lane count
                    # follows the RING: per-shard with the sharded
                    # ring (use_dp), one global lane otherwise
                    # (absorb_payload folds the payload's shard rows
                    # to match).
                    n_lanes = ctx.n_shards if use_dp else 1
                    n_stages_t = (
                        1 + len(chain_specs) if graph is not None else 1
                    )
                    ex_lanes_t = env.config.get(
                        _CoreOpts.PIPELINE_STAGES_EXCHANGE_LANES
                    ) if graph is not None else 0
                    drain_telem[0] = DrainTelemetry(
                        n_lanes, ring_depth, tracer=tracer,
                        n_stages=n_stages_t,
                        exchange_lanes=ex_lanes_t,
                        key_groups=maxp_kg if kg_stats_on else 0,
                        kg_alpha=env.config.get(
                            _CoreOpts.KG_HEAT_ALPHA
                        ),
                    )
                    ds_skip[0] = 0
                    if self._attribution is not None:
                        self._attribution.resident_fn = (
                            drain_telem[0].regime
                        )
                    if self._job_group is not None:
                        grp_d = self._job_group

                        def _dt_fill(s):
                            dt = drain_telem[0]
                            return dt.slot_fill(s) if dt else 0

                        def _dt_duty(s):
                            dt = drain_telem[0]
                            return (
                                round(dt.duty_cycle(s), 4)
                                if dt else 0.0
                            )

                        def _dt_lat(which, q):
                            dt = drain_telem[0]
                            if dt is None:
                                return 0.0
                            v = (
                                dt.fire_latency_ms(q)
                                if which == "fire"
                                else dt.consume_latency_ms(q)
                            )
                            return round(v, 3) if v is not None else 0.0

                        # same idempotency story as the refusal
                        # series above (registry.register overwrites;
                        # shards dropped by a re-plan unregister)
                        for _s in range(n_lanes):
                            grp_d.gauge(
                                f"drain_slot_fill_shard_{_s}",
                                partial(_dt_fill, _s),
                            )
                            grp_d.gauge(
                                f"drain_duty_cycle_shard_{_s}",
                                partial(_dt_duty, _s),
                            )
                        for _s in range(n_lanes, drain_gauge_n[0]):
                            grp_d.remove(f"drain_slot_fill_shard_{_s}")
                            grp_d.remove(f"drain_duty_cycle_shard_{_s}")
                        drain_gauge_n[0] = n_lanes
                        for _q in (50, 95, 99):
                            grp_d.gauge(
                                f"drain_fire_latency_p{_q}_ms",
                                partial(_dt_lat, "fire", float(_q)),
                            )
                            grp_d.gauge(
                                f"drain_consume_latency_p{_q}_ms",
                                partial(_dt_lat, "consume", float(_q)),
                            )

                        def _dt_stage(i, field):
                            dt = drain_telem[0]
                            return dt.stage_stat(i, field) if dt else 0

                        # per-downstream-stage gauges (chained jobs):
                        # edge pressure + coupled-watermark lag per
                        # stage, scraped like any other job gauge
                        for _i in range(1, n_stages_t):
                            for _f in ("edge_events", "fire_lanes",
                                       "dropped_capacity",
                                       "wm_lag_panes"):
                                grp_d.gauge(
                                    f"drain_stage{_i}_{_f}",
                                    partial(_dt_stage, _i, _f),
                                )
                        if kg_stats_on:
                            def _kg_heat(which):
                                dt = drain_telem[0]
                                if dt is None:
                                    return 0.0
                                v = (dt.kg_heat_max() if which == "max"
                                     else dt.kg_heat_skew())
                                return round(v, 4)

                            grp_d.gauge("kg_heat_max",
                                        partial(_kg_heat, "max"))
                            grp_d.gauge("kg_heat_skew_ratio",
                                        partial(_kg_heat, "skew"))
                if graph is not None:
                    # NO standalone fire step for chained jobs: a bare
                    # fire sweep would consume stage-0 fires without
                    # feeding them to stage 1. Every fire — steady state
                    # and end-of-stream flush — goes through the chained
                    # drain (drain_fires' chained branch dispatches
                    # empty drain rounds to sweep out residual panes).
                    fire_step = None
                else:
                    fire_step = build_window_fire_step(ctx, spec)
                if sink_device_reduce and graph is None:
                    # a second compiled fire variant with NO key/value
                    # packing; the drain picks per-iteration (the spill
                    # tier may appear mid-job, forcing the full variant)
                    fire_reduced_step = build_window_fire_reduced_step(
                        ctx, spec
                    )
            # -- ingest plan (runtime/ingest.py): publish the time domain,
            # lane geometry, exchange capacity and route shardings so the
            # prep side can route-plan and device-stage batches off the
            # step-loop thread. (Re-)installed on every setup — a restore
            # changes the time-domain origin; the producer is paused there
            # so the swap never races a batch mid-prep.
            valid_tmpl[0] = ingest_mod.make_prefix_mask_template(B_step[0])
            mask_sh, split_sh = ingest_mod.IngestPlan.shardings_for(ctx.mesh)
            ingest.set_plan(ingest_mod.IngestPlan(
                td=td, slide_ticks=int(win.slide_ticks),
                span_limit=win.ring - max(
                    2, int(win.size_ticks // win.slide_ticks) + 1
                ),
                B=B, B_step=B_step[0], n_shards=ctx.n_shards,
                max_parallelism=ctx.max_parallelism, kg_ends=_kg_ends,
                exchange_cap=exchange_cap[0],
                routes=tuple(steps_by_route) + (
                    ("sharded",) if use_dp else ()
                ),
                staging=use_staging,
                mask_sharding=mask_sh, split_sharding=split_sh,
                value_shape=(
                    () if red.kind == "sketch" else tuple(red.value_shape)
                ),
                value_dtype=(
                    np.uint32 if red.kind == "sketch" else np.float32
                ),
                ring_depth=ring_depth if use_resident else 0,
                shard_cap=shard_cap[0] if use_dp else 0,
            ))
            if use_while and ingest.device_ring is not None:
                # stand up the HBM publish cursor the while-drain's loop
                # condition re-reads: replicated scalar slot for the
                # global ring, one entry per owning chip for the sharded
                # lanes (same shardings the batch operands use)
                ingest.device_ring.enable_device_cursor(
                    split_sh if ingest.device_ring.sharded else mask_sh
                )
            if fresh_state:
                state = init_sharded_state(ctx, spec)
                if graph is not None:
                    chain_states[:] = [
                        init_sharded_state(ctx, cs) for cs in chain_specs
                    ]
                # trigger ALL compiles NOW (inside any benchmark warmup)
                # so neither the first pane-boundary fire nor the first
                # insert->fast tier switch nor the first adaptive route
                # flip is a multi-second compile stall mid-measurement;
                # firing at the MIN-sentinel watermark is a no-op on
                # fresh state
                steps0, fast0, ex0 = (metrics.steps, metrics.steps_fast,
                                      metrics.steps_exchanged)
                fused0 = metrics.fused_dispatches
                ff0 = metrics.fused_fire_dispatches
                rd0 = metrics.resident_drains
                ss0 = metrics.steps_sharded
                for route in steps_by_route:
                    for tier in ("insert", "fast"):
                        if steps_by_route[route][tier] is None:
                            continue
                        step_mode[0] = tier
                        force_route[0] = route
                        # label the compile burst so CompileEvents
                        # attributes it; anything compiling later (the
                        # "steady" bucket) is the recompile-storm alarm
                        with CompileEvents.stage(
                            f"window-update-{route}-{tier}"
                        ):
                            self._empty_step(run_update, B_step[0], red,
                                             None)
                for route in megasteps_by_route:
                    for tier in ("insert", "fast"):
                        if megasteps_by_route[route][tier] is None:
                            continue
                        step_mode[0] = tier
                        with CompileEvents.stage(
                            f"window-megastep-{route}-{tier}"
                        ):
                            run_update_fused(
                                route, [_empty_fused_item(route)
                                        for _ in range(k_fuse)]
                            )
                drain_warmup[0] = True
                try:
                    for route in residents_by_route:
                        # one compile serves every fill level (count is
                        # a traced operand); warm up at a PARTIAL fill
                        # so both cond branches execute at least once
                        # before measurement
                        for tier in ("insert", "fast"):
                            if residents_by_route[route][tier] is None:
                                continue
                            step_mode[0] = tier
                            with CompileEvents.stage(
                                f"window-drain-{route}-{tier}"
                            ):
                                run_update_resident(
                                    route, [_empty_fused_item(route)
                                            for _ in range(ring_depth - 1)]
                                )
                finally:
                    drain_warmup[0] = False
                step_mode[0] = "insert"
                force_route[0] = None
                tier_quiet[0] = 0
                mon_watch.clear()
                # warmup dispatches must not pollute the step counters the
                # operator (and the tiering test) reads
                metrics.steps, metrics.steps_fast = steps0, fast0
                metrics.steps_exchanged = ex0
                metrics.fused_dispatches = fused0
                metrics.fused_fire_dispatches = ff0
                metrics.resident_drains = rd0
                metrics.steps_sharded = ss0
                # warmup fired-megastep payloads: sentinel watermarks
                # fire nothing, and warmup must not leave handles behind
                fire_watch.clear()
                if fire_step is not None:
                    with CompileEvents.stage("window-fire"):
                        cf = run_fire(None)
                        jax.block_until_ready(cf.counts)
                        if fire_reduced_step is not None:
                            rf = run_fire(None, reduced=True)
                            jax.block_until_ready(rf.counts)
                if env.config.get_bool("observability.compile-cost",
                                       False) \
                        and self._job_group is not None \
                        and graph is None:
                    # AOT cost_analysis of the primary update step (FLOPs
                    # / bytes accessed where the backend reports them);
                    # costs a second trace+compile, hence config-gated
                    route0 = (
                        "mask" if "mask" in steps_by_route else "exchange"
                    )
                    # the exchange route's entry is a plain wrapper; its
                    # jitted inner step rides on .jit (cost_analysis
                    # needs .lower())
                    fn0 = steps_by_route[route0]["insert"]
                    fn0 = getattr(fn0, "jit", fn0)
                    Bs = B_step[0]
                    vals0 = (
                        np.zeros(Bs, np.uint32) if red.kind == "sketch"
                        else np.zeros(
                            (Bs,) + tuple(red.value_shape), np.float32
                        )
                    )
                    # labelled: this second trace+compile must not land
                    # in the "steady" recompile-storm bucket
                    with CompileEvents.stage("cost-analysis"):
                        ca = cost_analysis_of(
                            fn0, state,
                            np.zeros(Bs, np.uint32),
                            np.zeros(Bs, np.uint32),
                            np.zeros(Bs, np.int32), vals0,
                            np.zeros(Bs, bool),
                            np.zeros(ctx.n_shards, np.int32),
                        )
                    for k, v in (ca or {}).items():
                        self._job_group.settable_gauge(
                            f"xla_update_step_{k}", v
                        )
            # re-pin the doctor's recompile baseline at setup end: the
            # labelled build bursts above and the unlabelled eager
            # warm-up shapes (device_put, init zeros) that land in the
            # process-global "steady" bucket during setup are NOT this
            # job's steady-state growth — only compiles AFTER this
            # point feed the recompile-storm rule (metrics/doctor.py)
            _doctor_steady0[0] = (
                CompileEvents.report()["by_stage"].get("steady")
                or {"count": 0, "time_ms": 0.0}
            )

        # -- checkpointing (barrier = step boundary, SURVEY §3.4) ----------
        storage = None
        if env.checkpoint_dir:
            # task-local snapshot cache (checkpointing/local.py): publish
            # mirrors in, restore prefers the verified local copy
            storage = ckpt.CheckpointStorage(
                env.checkpoint_dir,
                retain=env.config.get_int("checkpoint.retain", 2),
                local=local_cache_from_config(
                    env.config, env.checkpoint_dir
                ),
            )
        # resume numbering after any checkpoints already in the directory
        next_cid = (storage.latest() or 0) + 1 if storage else 1
        steps_at_ckpt = 0
        n_keys_logged = 0

        # -- async / incremental subsystem (flink_tpu/checkpointing) -------
        # checkpoint.mode:  full        -> every checkpoint is a
        #                                  self-contained snapshot
        #                   incremental -> delta checkpoints covering only
        #                                  the dirty key groups, chained
        #                                  to a periodic full base via
        #                                  manifest.json
        # checkpoint.async: serialize + write on a background materializer
        #                   thread; the step loop blocks only for the
        #                   staging fetch (defaults on for incremental)
        ck_mode = env.config.get_str("checkpoint.mode", "full")
        ck_compact_every = max(
            1, env.config.get_int("checkpoint.compact-every", 8)
        )
        if ck_mode == "incremental" and wagg.allowed_lateness_ms:
            # dirty bits deliberately skip the global fire/purge sweeps
            # (recovery re-applies the purge cutoff), which is exact ONLY
            # without late re-fires — see checkpointing/recovery.py
            raise ValueError(
                "checkpoint.mode=incremental does not cover allowed-"
                "lateness window stages; use checkpoint.mode=full"
            )
        # the staged-delta pipeline below writes its own files, but the
        # materializer + notify/failure protocol is the SHARED one — a
        # fourth inline copy would drift from the generic paths'
        # -- failure containment (docs/fault-tolerance.md) -----------------
        # coordinator-side budget (checkpointing/policy.py, ref
        # CheckpointFailureManager): a failed or timed-out checkpoint is
        # ABORTED and counted; only exhausting checkpoint.tolerable-
        # failures escalates to the restart strategy. The policy's
        # on_completed runs at publish time — on the materializer thread
        # in async mode — so the consecutive-failure count tracks what
        # actually became durable. (The windowed path writes through its
        # own staged-delta pipeline, so ck_io carries the policy only
        # for its bounded recover/settle/close drains.)
        ck_policy = policy_from_config(env.config) if storage is not None \
            else None
        ck_io = _GenericCheckpointIO(env, storage, pipe, policy=ck_policy)
        materializer = ck_io.materializer
        metrics.failure_budget = ck_policy
        ck_declined = [False]      # one decline counted per deferred trigger
        # checkpoint.timeout bookkeeping for async in-flight cids:
        # cid -> monotonic publish deadline. An expired cid's publish is
        # CANCELLED (the materialize closure checks before writing), so a
        # wedged write can never publish a stale cut after the budget
        # already accounted for its failure.
        ck_pending = {}
        ck_cancelled = set()
        ck_lock = threading.Lock()
        # step-loop watchdog (runtime/watchdog.py): per-phase deadlines
        # that turn a hang into an attributed failure

        def _wd_trip(trip):
            metrics.watchdog_trips += 1

        wd = watchdog_from_config(env.config, on_trip=_wd_trip)
        # MTTR instrumentation (metrics/recovery.py): per-attempt
        # recovery phase spans + recovery_* gauges + /jobs/<jid>/recovery
        rec_tracker = RecoveryTracker(self._job_group, self._tracer)
        if storage is not None and storage.local is not None:
            rec_tracker.local_cache = storage.local
        env._recovery_report = rec_tracker.report
        # warm in-process restart (docs/fault-tolerance.md): transient
        # host-side failures keep the live jitted kernels and re-stage
        # only the shards whose key groups diverged from the restored cut
        from flink_tpu.core.config import CoreOptions as _CO

        warm_enabled = env.config.get(_CO.RECOVERY_WARM_RESTART)
        # incremental cuts CLEAR the device dirty bits before their write
        # is durable; if that write later aborts, the cleared bits are
        # divergence the bits no longer show. The warm splice therefore
        # unions the live bits with every cut cleared after the cid it
        # restores (pruned once a newer cut publishes).
        ck_cleared_dirty = {}
        ck_published = [0]
        # live manifest chain of the current incremental sequence (base
        # first). Starts EMPTY even when the directory holds checkpoints:
        # a delta may only chain onto a base whose state this job actually
        # carries, so the chain is adopted exclusively by
        # restore_checkpoint — a fresh job in an old directory writes a
        # new full base instead of chaining over foreign state.
        ck_chain: List[int] = []
        # observability (metrics/core.py): phase histograms + staging
        # gauges on the job's metric group, next to the cycle histograms
        ck_hists = {}
        ck_cov_gauge = None
        if self._job_group is not None and storage is not None:
            ck_hists = {
                "sync": self._job_group.histogram("checkpoint_sync_ms"),
                "async": self._job_group.histogram("checkpoint_async_ms"),
            }
            ck_cov_gauge = self._job_group.settable_gauge(
                "checkpoint_coverage_groups", 0
            )
            if materializer is not None:
                self._job_group.gauge(
                    "checkpoint_staging_occupancy", materializer.pending
                )

        def _dump_spill_stores():
            """SYNC phase: copy the host spill-tier contents out of the
            live stores (the step loop keeps draining into them once it
            resumes, so the async fold must work on frozen copies).
            Returns [(pane, keys u64, values [n, W] f32), ...]."""
            out = []
            for p, store in ovf_stores.items():
                ks, vs = store.dump()
                if len(ks):
                    out.append((int(p), np.array(ks, copy=True),
                                np.array(vs, copy=True)))
            return out

        def _fold_spill_entries(entries, dumped):
            """Spill-tier contents ride the snapshot as regular logical
            (key, pane, value) entries; duplicates with device rows are
            pre-combined because restore scatters (last write wins)."""
            if not dumped:
                return entries
            a_hi, a_lo, a_pane, a_val = [], [], [], []
            for p, ks, vs in dumped:
                a_hi.append((ks >> np.uint64(32)).astype(np.uint32))
                a_lo.append((ks & np.uint64(0xFFFFFFFF)).astype(np.uint32))
                a_pane.append(np.full(len(ks), p, np.int32))
                a_val.append(
                    vs.reshape((len(ks),) + tuple(red.value_shape))
                )
            if not a_hi:
                return entries
            khi = np.concatenate([entries["key_hi"]] + a_hi)
            klo = np.concatenate([entries["key_lo"]] + a_lo)
            pane = np.concatenate([entries["pane"]] + a_pane)
            value = np.concatenate(
                [entries["value"].astype(np.float32)] + a_val
            )
            fresh = np.concatenate([
                entries["fresh"],
                np.zeros(len(khi) - len(entries["fresh"]), bool),
            ])
            # combine duplicate (key, pane) rows (device + spill split)
            comp = (
                (khi.astype(np.uint64) << np.uint64(32)) | klo
            ).astype(np.uint64)
            uniq, inv = np.unique(
                np.stack([comp, pane.astype(np.uint64)], 1), axis=0,
                return_inverse=True,
            )
            W = max(1, int(np.prod(red.value_shape, dtype=np.int64) or 1))
            agg = np.full((len(uniq), W), ovf_neutral, np.float32)
            ufunc.at(agg, inv, value.reshape(len(value), W))
            fr = np.zeros(len(uniq), bool)
            np.logical_or.at(fr, inv, fresh)
            return {
                "key_hi": (uniq[:, 0] >> np.uint64(32)).astype(np.uint32),
                "key_lo": (uniq[:, 0] & np.uint64(0xFFFFFFFF)).astype(
                    np.uint32
                ),
                "pane": uniq[:, 1].astype(np.int32),
                "value": agg.reshape((len(uniq),) + tuple(red.value_shape)),
                "fresh": fr,
            }

        def _abort_checkpoint(cid, err, t_ck0, trigger_ms):
            """Abort-and-count one failed checkpoint attempt (the
            containment half of the failure budget). GCs the attempt's
            staging dir; in incremental mode cancels every in-flight
            publish and RESETS the manifest chain — the failed cut's
            dirty bits are already cleared, so only a fresh full base
            can cover its changes, and no future delta may chain over
            the hole. Raises (escalating to the restart strategy) only
            when the consecutive-failure budget is exhausted."""
            storage.discard_tmp(cid)
            if ck_mode == "incremental":
                with ck_lock:
                    ck_cancelled.update(ck_pending)
                    ck_pending.clear()
                ck_chain[:] = []
            metrics.checkpoints_aborted += 1
            metrics.record_checkpoint_abort(
                cid, trigger_ms, (time.perf_counter() - t_ck0) * 1e3,
                reason=f"{type(err).__name__}: {err}",
                kind="incremental" if ck_mode == "incremental" else "full",
            )
            if ck_policy.on_aborted(cid, str(err)):
                raise ck_policy.exhausted_error(cid, err) from err

        def _expire_pending():
            """checkpoint.timeout for async in-flight checkpoints: a cid
            still unpublished past its deadline is declared failed — its
            publish is cancelled and the failure counts against the
            budget — so a wedged materialization cannot silently stall
            durability forever. timeout <= 0 disables (nothing is ever
            registered as pending then)."""
            if not ck_pending:
                return
            now = time.monotonic()
            with ck_lock:
                expired = sorted(
                    c for c, dl in ck_pending.items() if now > dl
                )
                for c in expired:
                    ck_cancelled.add(c)
                    ck_pending.pop(c, None)
            for c in expired:
                _abort_checkpoint(
                    c,
                    TimeoutError(
                        f"checkpoint {c} unpublished after "
                        f"{ck_policy.timeout_s:.0f}s (checkpoint.timeout)"
                    ),
                    time.perf_counter(), time.time() * 1000,
                )

        def write_checkpoint():
            nonlocal next_cid, steps_at_ckpt, n_keys_logged, state
            flush_fused()   # snapshot cut = megastep boundary (no-op at 1)
            t_ck0 = time.perf_counter()
            trigger_ms = time.time() * 1000
            cid = next_cid
            try:
                if materializer is not None:
                    _expire_pending()
                    # surface an async write failure AT the barrier: it
                    # is a checkpoint failure — aborted and counted (the
                    # abort record carries THIS barrier's cid; the
                    # reason names the failed chk label)
                    materializer.check()
                    ck_io.drain()
            except (JobCancelledException, WatchdogError,
                    CheckpointFailureBudgetExceeded):
                raise
            except Exception as e:
                # a poisoned materializer DROPPED its queued tasks:
                # their cids will never pop themselves from pending, and
                # none of them published — stop tracking (and block any
                # straggler publish) before counting the abort
                with ck_lock:
                    ck_cancelled.update(ck_pending)
                    ck_pending.clear()
                _abort_checkpoint(cid, e, t_ck0, trigger_ms)
                next_cid += 1
                steps_at_ckpt = metrics.steps
                return
            # drain due fires so fired_through is uniform across shards
            # and the snapshot is an exact global cut (F-throttle
            # divergence). OUTSIDE the abort scope: a sink failure while
            # emitting is a job failure, not a checkpoint failure.
            drain_fires(int(wm_strategy.current()))
            wd_prev = wd.arm("checkpoint_sync") if wd is not None else None
            try:
                _write_checkpoint_cut(cid, t_ck0, trigger_ms)
            except (JobCancelledException, WatchdogError,
                    CheckpointFailureBudgetExceeded):
                raise
            except Exception as e:
                _abort_checkpoint(cid, e, t_ck0, trigger_ms)
            finally:
                if wd is not None:
                    wd.disarm(wd_prev)
            next_cid += 1
            steps_at_ckpt = metrics.steps

        def _write_checkpoint_cut(cid, t_ck0, trigger_ms):
            nonlocal n_keys_logged, state
            # ---- SYNC phase (the only step-loop stall) -----------------
            # changelog fetch: which key groups changed since the last cut
            spill_dump = _dump_spill_stores()
            kind, dirty_kgs, rows = "full", None, None
            if ck_mode == "incremental":
                dirty_kgs = cklog.dirty_key_groups(
                    np.asarray(jax.device_get(state.kg_dirty))
                )
                # spill-tier key groups are always covered: their state
                # mutates host-side (drains/prunes) without device bits
                for _p, ks, _vs in spill_dump:
                    dirty_kgs = np.union1d(dirty_kgs, cklog.entry_key_groups(
                        (ks >> np.uint64(32)).astype(np.uint32),
                        (ks & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                        ctx.max_parallelism,
                    ))
                if ck_chain and len(ck_chain) < ck_compact_every:
                    kind = "delta"
                    rows = cklog.dirty_shard_rows(
                        dirty_kgs, *ctx.kg_bounds()
                    )
                # else: first checkpoint in the directory, or compaction
                # due -> write a fresh full base
            staged = ckpt.stage_window_state(state, rows=rows, red=red)
            if ck_mode == "incremental":
                state = clear_dirty(state)
                # cleared-bits ledger for the warm splice (see above):
                # this cut's dirty set is unaccounted divergence until
                # the cut is durable
                ck_cleared_dirty[cid] = np.asarray(dirty_kgs)
                for c in [c for c in ck_cleared_dirty
                          if c <= ck_published[0]]:
                    del ck_cleared_dirty[c]
            if keep_rev:
                # atomic against the ingest thread's concurrent encodes
                # (the map may already hold keys from prefetched batches
                # past the cut — harmless supersets on restore)
                items, n_keys_logged = codec.rev_slice(n_keys_logged)
                storage.append_keymap(items)
            aux = {
                "origin_ms": td.origin_ms,
                "wm_current": wm_strategy.current(),
                "codec_rev_count": n_keys_logged if keep_rev else 0,
                "size_ms": size_ms, "slide_ms": slide_ms,
                "lateness_ms": wagg.allowed_lateness_ms,
                "state_layout": layout[0],
                "sink_states": [s.snapshot_state() for s in pipe.all_sinks],
            }
            if graph is not None:
                # downstream stage states ride the aux blob, NOT the
                # entries npz: incremental replay merges entries by
                # (key, pane) across the manifest chain, which would
                # collide rows from different stages. The chained
                # drain's watermark coupling means a drain-boundary cut
                # carries no in-flight edge payload — these full
                # per-stage snapshots alone ARE the exactly-once cut.
                aux["chain_stages"] = graph.snapshot_chain(
                    chain_states, chain_specs
                )
            # the APPLIED-offset cut (runtime/ingest.py): the prefetch
            # thread may have polled the source several batches ahead,
            # so the snapshot names the offsets of the last batch the
            # device state has absorbed — in-flight prepped batches are
            # dropped + replayed on restore, never skipped
            offsets = ingest.applied_offsets()
            # freeze offsets/sink states NOW: the step loop resumes before
            # the write lands, and live sink state must not leak into it
            aux_bytes = pickle.dumps(
                {"source_offsets": offsets, "aux": aux}
            )
            manifest = None
            if ck_mode == "incremental":
                new_chain = ck_chain + [cid] if kind == "delta" else [cid]
                manifest = ckmf.build_manifest(
                    cid, kind, new_chain,
                    "all" if kind == "full"
                    else sorted(int(g) for g in dirty_kgs),
                    ctx.max_parallelism,
                )
                ck_chain[:] = new_chain
                if ck_cov_gauge is not None:
                    cov_n = (
                        ctx.max_parallelism if kind == "full"
                        else len(dirty_kgs)
                    )
                    ck_cov_gauge.set(cov_n)
            staging_wait = 0.0
            if materializer is not None:
                # bounded: a wedged in-flight write must surface as an
                # abortable checkpoint failure, not an unbounded stall
                # (MaterializerStall -> _abort_checkpoint)
                slot_prev = (
                    wd.arm("materializer_slot") if wd is not None else None
                )
                try:
                    staging_wait = materializer.wait_for_slot(
                        timeout=(
                            ck_policy.timeout_s
                            if ck_policy.timeout_s > 0 else None
                        )
                    )
                finally:
                    if wd is not None:
                        wd.disarm(slot_prev)
            occupancy = materializer.pending() if materializer else 0
            sync_ms = (time.perf_counter() - t_ck0) * 1e3
            if ck_hists:
                ck_hists["sync"].update(sync_ms)
            # checkpoints are rare and exactly the stalls worth seeing in
            # a trace: record regardless of the cycle sampling decision
            if tracer is not None:
                tracer.rec("checkpoint_sync", t_ck0, cid=cid, kind=kind)

            # ---- ASYNC phase (materializer thread; inline when sync) ---
            def materialize():
                try:
                    with ck_lock:
                        if cid in ck_cancelled:
                            return        # timed out: abort already counted
                    t_a0 = time.perf_counter()
                    entries, scalars = ckpt.extract_entries(staged, win)
                    entries = _fold_spill_entries(entries, spill_dump)
                    if kind == "delta":
                        entries = cklog.filter_entries_to_key_groups(
                            entries, dirty_kgs, ctx.max_parallelism
                        )
                    # last cancellation point before durability: a cut
                    # declared timed-out must never publish (its failure
                    # is already in the budget and the chain was reset)
                    with ck_lock:
                        if cid in ck_cancelled:
                            return
                    path = storage.write(
                        cid, entries, scalars,
                        manifest=manifest, aux_bytes=aux_bytes,
                    )
                    ck_policy.on_completed(cid)
                    # durable: bits cleared at or before this cut are
                    # accounted for by it (int store is GIL-atomic; the
                    # ledger itself is pruned on the step-loop thread)
                    ck_published[0] = max(ck_published[0], cid)
                    # the checkpoint is durable: commit offsets externally
                    # + let sinks finalize (ref notifyCheckpointComplete
                    # fan-out). Async mode queues — the step loop delivers.
                    if materializer is not None:
                        ck_io.queue_notification(cid, offsets)
                    else:
                        with ck_io.source_lock:
                            pipe.source.notify_checkpoint_complete(
                                cid, offsets
                            )
                        for s in pipe.all_sinks:
                            s.notify_checkpoint_complete(cid)
                    nbytes = sum(
                        os.path.getsize(os.path.join(path, f))
                        for f in os.listdir(path)
                    ) if path and os.path.isdir(path) else 0
                    async_ms = (time.perf_counter() - t_a0) * 1e3
                    if ck_hists:
                        ck_hists["async"].update(async_ms)
                    metrics.record_checkpoint(
                        cid, trigger_ms,
                        (time.perf_counter() - t_ck0) * 1e3,
                        nbytes, len(entries["key_hi"]),
                        # sync mode: the WHOLE checkpoint stalls the loop
                        kind=kind,
                        sync_ms=sync_ms if materializer is not None
                        else None,
                        async_ms=async_ms if materializer is not None
                        else 0.0,
                        coverage=(
                            None if dirty_kgs is None or kind == "full"
                            else len(dirty_kgs)
                        ),
                        staging_wait_ms=staging_wait * 1e3,
                        staging_occupancy=occupancy,
                    )
                finally:
                    with ck_lock:
                        ck_pending.pop(cid, None)

            if materializer is not None:
                if ck_policy.timeout_s > 0:     # 0/negative = no timeout
                    with ck_lock:
                        ck_pending[cid] = (
                            time.monotonic() + ck_policy.timeout_s
                        )
                try:
                    materializer.submit(f"chk-{cid}", materialize)
                except BaseException:
                    with ck_lock:      # never-queued cid must not "expire"
                        ck_pending.pop(cid, None)
                    raise
            else:
                materialize()

        def _try_warm_splice(entries, scalars, restored_cid):
            """Warm dirty-only re-stage: rebuild ONLY the shards whose
            key-group range diverged since the restored cut and splice
            them into the live device state; clean shards never leave
            the device. Sound only when the cut's fire horizon still
            matches the live state — fire/purge sweeps mutate shards
            WITHOUT marking dirty bits (deliberately, see
            ops/window_kernels.py), so any fire, purge, or ring
            rotation since the cut sends the caller down the full
            re-stage path. Returns True when the splice happened. The
            spill-tier precondition is the CALLER's (the stores are
            already closed/cleared by the time this runs)."""
            nonlocal state
            live = jax.device_get({
                "fired_through": state.fired_through,
                "max_pane": state.max_pane,
                "min_pane": state.min_pane,
                "kg_dirty": state.kg_dirty,
                "ovf_n": state.ovf_n,
            })
            if (
                int(np.min(live["fired_through"]))
                != int(scalars["fired_through"])
                or int(np.max(live["max_pane"])) != int(scalars["max_pane"])
                or int(np.min(live["min_pane"])) != int(scalars["min_pane"])
                or int(np.asarray(live["ovf_n"]).sum()) != 0
            ):
                return False
            dirty = cklog.dirty_key_groups(live["kg_dirty"])
            # plus every dirty set a post-cut checkpoint cleared without
            # becoming durable (the bits no longer show that divergence)
            for c, kgs in list(ck_cleared_dirty.items()):
                if c > restored_cid:
                    dirty = np.union1d(dirty, kgs)
            rows = cklog.dirty_shard_rows(dirty, *ctx.kg_bounds())
            if len(rows) >= ctx.n_shards:
                return False     # everything diverged: splice == full
            S = ctx.n_shards
            repl = {
                # global scalars rewind to the cut (fired_through /
                # max_pane / min_pane are equal by the guard; watermark
                # and the drop counters are re-driven by replay)
                "watermark": ckpt._scal(S, scalars["watermark"], ctx),
                "dropped_late": ckpt._scal(
                    S, scalars["dropped_late"], ctx, split=True
                ),
                "dropped_capacity": ckpt._scal(
                    S, scalars["dropped_capacity"], ctx, split=True
                ),
                # the restored state IS the chain's state
                "kg_dirty": jax.device_put(
                    np.zeros((S, ctx.max_parallelism), bool),
                    ctx.state_sharding,
                ),
            }
            if rows:
                leftover = []
                built = ckpt.restore_window_rows(
                    entries, scalars, ctx, spec, rows=rows,
                    leftover=leftover,
                )
                if leftover:
                    return False     # rows need the spill tier: full path
                idx = jnp.asarray(np.asarray(rows, np.int32))

                def spl(live_arr, sub):
                    return jax.device_put(
                        live_arr.at[idx].set(jnp.asarray(sub)),
                        ctx.state_sharding,
                    )

                repl.update(
                    table=type(state.table)(
                        spl(state.table.keys, built["keys"]),
                        spec.probe_len,
                    ),
                    fresh=spl(state.fresh, built["fresh"]),
                    pane_ids=spl(state.pane_ids, built["pane_ids"]),
                    n_fresh=spl(state.n_fresh, built["n_fresh"]),
                )
                if use_packed:
                    # restore rows are logical; re-pack before splicing
                    # onto the live packed plane (touched rides inside)
                    repl.update(acc=spl(state.acc, wk.make_packed(
                        built["acc"], built["touched"], red
                    )))
                else:
                    repl.update(
                        acc=spl(state.acc, built["acc"]),
                        touched=spl(state.touched, built["touched"]),
                    )
            # rows == []: nothing diverged since the cut — the live
            # arrays ARE the checkpoint; only the scalars rewind
            state = dataclasses.replace(state, **repl)
            return True

        def _seed_spill_leftover(leftover):
            """Snapshot rows that no longer fit the device table go back
            to the host spill tier they came from (shared by the full
            restore and the live savepoint-cut rescale — a rescale to
            FEWER shards shrinks total device capacity, so rows that fit
            at N shards may spill at M)."""
            if not leftover:
                return
            from flink_tpu.native import SpillStore

            for l_hi, l_lo, l_pane, l_val in leftover:
                k64 = (
                    l_hi.astype(np.uint64) << np.uint64(32)
                ) | l_lo.astype(np.uint64)
                for p in np.unique(l_pane):
                    m = l_pane == p
                    store = ovf_stores.get(int(p))
                    if store is None:
                        store = ovf_stores[int(p)] = SpillStore(
                            width=ovf_w, initial_capacity=1024
                        )
                    store.put(
                        k64[m],
                        l_val[m].reshape(-1, ovf_w).astype(np.float32),
                    )

        def _replan_mesh(devices):
            """Re-slice + rebuild for a NEW shard count (elastic
            degrade onto survivors, or the scale-back-up): a fresh
            MeshContext over ``devices`` (key-group ranges re-slice
            through the unchanged compute_key_group_range math — keys
            never change key group), and every mesh-derived compiled/
            cached artifact is dropped so the next setup() rebuilds the
            whole jitted step family, the exchange geometry, and the
            ingest plan at the new ``n_shards``. The caller completes
            the re-plan with a restore (rescaled cut) — state is NOT
            touched here."""
            nonlocal ctx, _kg_ends, compact_step_fn
            if (kg_slices_hold[0] is not None
                    and len(kg_slices_hold[0]) != len(devices)):
                # a heat-balanced slicing is per-shard-count evidence:
                # an elastic re-plan to a DIFFERENT count falls back to
                # the uniform slices (the controller re-derives later)
                kg_slices_hold[0] = None
            ctx = MeshContext.create(
                len(devices), env.max_parallelism, devices=devices,
                kg_slices=kg_slices_hold[0],
            )
            _kg_ends = np.asarray(ctx.kg_bounds()[1])
            steps_by_route.clear()
            megasteps_by_route.clear()
            residents_by_route.clear()
            compact_step_fn = None
            kg_occ_step_fn[0] = None
            kg_occ_cache[0] = None
            exchange_cap[0] = 0
            shard_cap[0] = 0    # re-sliced by setup() at the new n_shards
            force_route[0] = None
            # in-flight monitoring handles reference the OLD mesh (a
            # dead device on real hardware): drop without blocking
            inflight.clear()

        def _rescale_live(targets, kind: str, cause: str):
            """Planned savepoint-cut rescale at a cycle boundary — the
            scale-back-up edge that bounds degraded mode (and, by
            symmetry, any operator-triggered live re-plan). Semantics
            match write_savepoint: pending fused groups dispatch, due
            windows fire BEFORE the cut, then the logical snapshot
            (device + spill tier) re-buckets onto the new mesh and the
            source rewinds to the applied-offset cut so prefetched
            batches replay — exactly-once, no restart, no durable-
            storage round trip."""
            nonlocal state, host_fired_pane, applied_max_pane
            t0 = time.perf_counter()
            n_before = ctx.n_shards
            flush_fused()
            consume_fires(force=True)
            drain_fires(int(wm_strategy.current()), time.perf_counter())
            ingest.pause()
            fused.clear()
            fire_watch.clear()
            entries, scalars = ckpt.snapshot_window_state(state, win,
                                                          red=red)
            entries = _fold_spill_entries(entries, _dump_spill_stores())
            for store in ovf_stores.values():
                store.close()
            ovf_stores.clear()
            offsets = ingest.applied_offsets()
            # downstream stage states re-bucket over the new mesh the
            # same way: logical snapshot before the re-plan, restore
            # against the re-planned chain_specs after setup()
            ch_payload = (
                graph.snapshot_chain(chain_states, chain_specs)
                if graph is not None else None
            )
            _replan_mesh(targets)
            setup(td.origin_ms, fresh_state=False)
            leftover = [] if win.overflow else None
            state = ckpt.restore_window_state(
                entries, scalars, ctx, spec, leftover=leftover
            )
            if graph is not None:
                chain_states[:] = graph.restore_chain(
                    ch_payload, ctx, chain_specs
                )
            _seed_spill_leftover(leftover)
            # live-state divergence since the last durable cut has no
            # dirty bits anymore (the re-bucketed state restores with
            # clean bits): the next incremental checkpoint must re-base
            # full instead of chaining a delta over the hole
            ck_chain[:] = []
            host_fired_pane = -(2**62)
            applied_max_pane = (
                int(entries["pane"].max()) if len(entries["pane"])
                else None
            )
            step_mode[0] = "insert"
            tier_quiet[0] = 0
            miss_tolerance[0] = 0
            bounce_miss[0] = 0
            mon_watch.clear()
            pipe.source.restore_offsets(offsets)
            ingest.resume(offsets)
            mttr_ms = (time.perf_counter() - t0) * 1e3
            elastic_ctl.record(kind, n_before, ctx.n_shards, cause=cause,
                               mttr_ms=mttr_ms)
            rec_tracker.note_rescale(
                n_before, ctx.n_shards, elastic_ctl.degraded_shards
            )

        def restore_checkpoint(path_or_storage, cid=None, warm=False):
            nonlocal state, next_cid, steps_at_ckpt, n_keys_logged
            nonlocal host_fired_pane, applied_max_pane
            t_plan0 = time.perf_counter()
            # park the prefetch producer FIRST: everything below mutates
            # state it reads (source offsets, the codec reverse map, the
            # ingest plan); resume() at the end bumps the epoch so every
            # batch prepped before this restore is discarded + replayed
            ingest.pause()
            # pending fused batches belong to the pre-restore epoch: they
            # were never applied and never marked, so dropping them here
            # simply lets the rewound source replay them
            fused.clear()
            # unread resident-pipeline fire payloads die with the failed
            # state: the restored cut re-fires them on replay (the same
            # at-least-once sink contract as fires emitted-then-replayed)
            fire_watch.clear()
            if materializer is not None:
                ck_io.recover()           # durable cuts still notify
            with ck_lock:
                # restoring IS the recovery from any in-flight attempt:
                # whatever landed during recover()'s bounded drain is a
                # valid cut; the rest stop being tracked. ck_cancelled
                # is KEPT — a cancelled cid whose wedged write outlived
                # the drain must still never publish (cids are
                # monotonic, so stale entries can never block new ones).
                ck_pending.clear()
            host_fired_pane = -(2**62)   # re-arm boundary fire detection
            applied_max_pane = None      # re-armed from the snapshot below
            # restored table contents differ from the running population:
            # re-enter insert mode until the lagged signal proves quiet
            step_mode[0] = "insert"
            tier_quiet[0] = 0
            miss_tolerance[0] = 0
            bounce_miss[0] = 0
            mon_watch.clear()
            # spill contents were folded into the snapshot's entries; the
            # restored device state supersedes the host tier. Whether the
            # tier WAS in play decides warm-splice eligibility below:
            # spill keys' cut entries live nowhere on device, so only the
            # full restore (its leftover path) can resurrect them.
            had_spill = bool(ovf_stores)
            for store in ovf_stores.values():
                store.close()
            ovf_stores.clear()
            st = _storage_for_restore_path(storage, path_or_storage)
            cid = cid if cid is not None else st.latest()
            if cid is None:
                raise FileNotFoundError(f"no checkpoint in {st.dir}")
            rec_tracker.mark_phase("restore_plan", t_plan0)
            t_fetch0 = time.perf_counter()
            entries, scalars, offsets, aux = st.read(cid)
            rec_tracker.mark_phase("fetch", t_fetch0)
            if (aux["size_ms"], aux["slide_ms"]) != (size_ms, slide_ms):
                raise ValueError("checkpoint window spec mismatch")
            # re-arm the between-polls jump guard from the snapshot: the
            # restored ring holds unfired panes up to this id, and the
            # first post-restore batch may arrive after an arbitrary
            # event-time gap (the resume-after-gap scenario is exactly a
            # restore) — with the guard disarmed it would rotate the ring
            # over them
            if len(entries["pane"]):
                applied_max_pane = int(entries["pane"].max())
            # resume in the layout the snapshot was taken with (auto only;
            # an explicit config wins): an auto-direct run restored as
            # "hash" would upsert a dense key population into a table at
            # ~100% load factor and fail. Snapshot entries are logical, so
            # restore_window_state re-buckets them into whatever layout
            # the stage runs; pre-layout checkpoints (no key) were hash.
            if layout[0] is None:
                layout[0] = (
                    aux.get("state_layout", "hash")
                    if layout_cfg == "auto" else layout_cfg
                )
            t_stage0 = time.perf_counter()
            # warm in-process restart: the transient-failure path keeps
            # the live jitted kernels and the installed ingest plan (the
            # time-domain origin is unchanged for a same-job restore)
            # and, when the cut's fire horizon still matches, re-stages
            # only the dirty shards
            mode = "full"
            if (
                warm and warm_enabled and state is not None
                and td is not None and win is not None
                and aux["origin_ms"] == td.origin_ms
                and aux.get("state_layout", layout[0]) == layout[0]
            ):
                # a live spill tier rules out the splice (its keys' cut
                # entries exist on no device shard — only the full
                # rebuild's leftover path resurrects them) but not the
                # kernel-warm full restore
                # chained jobs always take the full re-stage: the splice
                # only re-stages stage 0's dirty shards, but the cut's
                # chain_stages snapshots replace EVERY downstream state
                # wholesale — a spliced stage 0 paired with wholesale
                # downstream restores would tear the watermark coupling
                mode = (
                    "warm-splice"
                    if not had_spill and graph is None
                    and _try_warm_splice(entries, scalars, cid)
                    else "warm-full"
                )
            leftover = None
            if mode != "warm-splice":
                if mode == "full":
                    setup(aux["origin_ms"], fresh_state=False)
                leftover = [] if win.overflow else None
                state = ckpt.restore_window_state(
                    entries, scalars, ctx, spec, leftover=leftover
                )
                if graph is not None:
                    if "chain_stages" not in aux:
                        raise ValueError(
                            "checkpoint carries no chain_stages payload "
                            "but the job is a chained stage graph — "
                            "restore with the matching pipeline"
                        )
                    chain_states[:] = graph.restore_chain(
                        aux["chain_stages"], ctx, chain_specs
                    )
                elif aux.get("chain_stages"):
                    raise ValueError(
                        "checkpoint carries chained stage state but the "
                        "job is single-stage — restore with the matching "
                        "pipeline"
                    )
            rec_tracker.mark_phase("stage", t_stage0)
            rec_tracker.set_mode(mode, cid)
            _seed_spill_leftover(leftover)
            pipe.source.restore_offsets(offsets)
            sink_states = aux.get("sink_states")
            if sink_states:
                if len(sink_states) != len(pipe.all_sinks):
                    raise ValueError(
                        f"checkpoint has {len(sink_states)} sink states but "
                        f"the job topology has {len(pipe.all_sinks)} sinks — "
                        f"restore with the matching pipeline"
                    )
                for s, ss in zip(pipe.all_sinks, sink_states):
                    s.restore_state(ss)
            wm_strategy._current = aux["wm_current"]
            count = aux.get("codec_rev_count", 0)
            if count:
                codec._rev = st.read_keymap(count)
            same_dir = storage is not None and (
                os.path.abspath(st.dir) == os.path.abspath(storage.dir)
            )
            n_keys_logged = len(codec._rev) if same_dir else 0
            if ck_mode == "incremental":
                # extend the restored checkpoint's chain; a FOREIGN
                # restore (savepoint) starts a fresh chain with a full
                # base — its members don't exist in our directory
                m = st.read_manifest(cid) if same_dir else None
                ck_chain[:] = (
                    list(m["chain"]) if m is not None
                    else [cid] if same_dir else []
                )
            steps_at_ckpt = metrics.steps
            # restart production from the rewound source; the restored
            # snapshot's offsets ARE the applied cut until the first
            # post-restore batch lands
            ingest.resume(offsets)

        def write_savepoint(path: str) -> str:
            """Manually-triggered versioned snapshot into its own directory
            (ref SavepointStore + CliFrontend ACTION_SAVEPOINT). Unlike
            periodic checkpoints, the full key map is embedded so the
            savepoint directory is self-contained.

            DOCUMENTED DIVERGENCE from the reference: windows already due
            at the current watermark are fired and emitted to the sinks
            BEFORE the snapshot (the reference's savepoint barrier
            snapshots pending fires instead). This keeps the savepoint an
            exact between-steps cut — restoring never re-fires or loses a
            due window — at the cost of output timing being advanced by a
            control-plane action."""
            if td is None:
                raise RuntimeError("no state to savepoint yet")
            sp = ckpt.CheckpointStorage(path, retain=10**9)
            flush_fused()   # savepoint cut = megastep boundary
            drain_fires(int(wm_strategy.current()))
            entries, scalars = ckpt.snapshot_window_state(state, win,
                                                          red=red)
            entries = _fold_spill_entries(entries, _dump_spill_stores())
            n_rev = 0
            if keep_rev:
                # atomic snapshot vs concurrent ingest-thread encodes
                items, n_rev = codec.rev_slice(0)
                sp.append_keymap(items)
            aux = {
                "origin_ms": td.origin_ms,
                "wm_current": wm_strategy.current(),
                "codec_rev_count": n_rev,
                "size_ms": size_ms, "slide_ms": slide_ms,
                "lateness_ms": wagg.allowed_lateness_ms,
                "state_layout": layout[0],
                "sink_states": [s.snapshot_state() for s in pipe.all_sinks],
            }
            if graph is not None:
                # same aux-not-entries placement as the periodic cut
                aux["chain_stages"] = graph.snapshot_chain(
                    chain_states, chain_specs
                )
            cid = (sp.latest() or 0) + 1
            # applied-offset cut, like periodic checkpoints: prefetched-
            # ahead batches are NOT part of the savepoint and replay on
            # restore from the rewound source position
            return sp.write(cid, entries, scalars,
                            ingest.applied_offsets(), aux)

        self._savepoint_writer = write_savepoint

        def kv_read(key):
            """Live point lookup into the device window state (queryable
            state read path, SURVEY §2.2): host-side probe of the shard's
            hash table + pane ring for the key. Returns
            {"panes": {pane_id: value}, "slide_ms", "size_ms"} or None.
            MUST run on the executor thread while the job is live: the
            window step donates the state buffers, so reading them from
            another thread races XLA's in-place reuse (round-1 bug)."""
            if td is None or state is None:
                return None
            from flink_tpu.core.keygroups import assign_to_key_group
            from flink_tpu.ops.hashing import route_hash

            hi, lo = codec.encode(
                np.asarray([key]) if np.isscalar(key) or isinstance(
                    key, (int, float)
                ) else [key],
                keep_reverse=False,
            )
            kg = int(assign_to_key_group(
                route_hash(hi, lo, np), ctx.max_parallelism, np
            )[0])
            shard = int(ctx.shard_of_key_groups(np.asarray([kg]))[0])
            tkeys = np.asarray(state.table.keys[shard])
            match = np.nonzero(
                (tkeys[:, 0] == hi[0]) & (tkeys[:, 1] == lo[0])
            )[0]
            panes = {}
            if match.size:
                slot = int(match[0])
                R = win.ring
                C_cap = tkeys.shape[0]
                acc_s = np.asarray(state.acc[shard])
                if state.packed >= 0:
                    acc_s, touched_f = wk.split_packed(
                        acc_s, state.packed, red
                    )
                    touched = np.asarray(touched_f).reshape(R, C_cap)
                else:
                    touched = np.asarray(
                        state.touched[shard]
                    ).reshape(R, C_cap)
                acc2 = acc_s.reshape((R, C_cap) + acc_s.shape[1:])
                pane_ids = np.asarray(state.pane_ids[shard])
                for r in range(R):
                    if touched[r, slot] and pane_ids[r] != wk.PANE_NONE:
                        panes[int(pane_ids[r])] = np.asarray(
                            acc2[r, slot]
                        ).tolist()
            # degraded mode: contributions for this key may live in the host
            # spill tier (table filled mid-pane, or the key was evicted by
            # compaction) — combine them so queryable state matches what a
            # fire would emit (round-2 ADVICE: spill rows were omitted).
            if ovf_stores:
                k64 = np.asarray(
                    [(np.uint64(hi[0]) << np.uint64(32)) | np.uint64(lo[0])],
                    np.uint64,
                )
                for p, store in ovf_stores.items():
                    if len(store) == 0:
                        continue
                    old, found = store.get(k64)
                    if not bool(found[0]):
                        continue
                    sv = old.reshape(1, ovf_w)
                    if p in panes:
                        dev = np.asarray(panes[p], np.float32).reshape(
                            1, ovf_w
                        )
                        panes[p] = host_combine(sv, dev).reshape(
                            tuple(red.value_shape) or ()
                        ).tolist()
                    else:
                        panes[p] = sv.reshape(
                            tuple(red.value_shape) or ()
                        ).tolist()
            if not panes:
                return None
            return {
                "panes": panes,
                "slide_ms": slide_ms,
                "size_ms": size_ms,
            }

        # -- queryable-state mailbox: queries from web/HTTP threads are
        # served by the executor thread at step boundaries (between steps
        # the donated device buffers are stable). `owner` claims in `box`
        # are GIL-atomic dict setdefaults, so a request is served exactly
        # once even when the job quiesces while a waiter is queued.
        kv_mailbox = queue.SimpleQueue()
        job_live = threading.Event()

        def kv_query(key):
            if not job_live.is_set():
                return kv_read(key)     # job quiescent: direct read is safe
            box = {}
            ev = threading.Event()
            kv_mailbox.put((key, box, ev))
            while not ev.wait(0.25):
                if not job_live.is_set():
                    if box.setdefault("owner", "waiter") == "waiter":
                        return kv_read(key)
                    ev.wait(5.0)
                    break
            if "err" in box:
                raise box["err"]
            return box.get("val")

        def drain_kv_mailbox():
            while not kv_mailbox.empty():
                key, box, ev = kv_mailbox.get()
                if box.setdefault("owner", "exec") != "exec":
                    ev.set()
                    continue
                try:
                    box["val"] = kv_read(key)
                except Exception as e:   # deliver to the querying thread
                    box["err"] = e
                ev.set()

        reg = getattr(env, "_kv_registry", None)
        if reg is not None:
            reg.register(wagg.name, kv_query)

        # cycle phase accumulators (CycleAttribution) + LatencyMarker stamp
        phase_acc = {"dispatch": 0.0, "emit": 0.0}
        last_ingest_t = [None]
        # step-loop span tracer (observability.tracing); local alias so
        # the hot path pays one load + None-check when tracing is off
        tracer = self._tracer

        # -- device-resident skew telemetry (ISSUE 2 tentpole part 2) ------
        # kg_fill_total: cumulative per-key-group record counts from the
        # SAMPLED lagged monitoring fetches (the traffic view — which
        # groups are receiving records). kg_occ_cache: per-key-group live-
        # key occupancy refreshed by the device kernel at fire boundaries
        # on a wall-clock budget (the state view — which groups hold
        # keys). Both are host numpy caches so gauges and the /keygroups
        # endpoint read them from web threads without ever touching the
        # donated device buffers.
        maxp_kg = ctx.max_parallelism
        kg_fill_total = np.zeros(maxp_kg, np.int64)
        kg_fill_sampled = [0]          # batches the fill counts cover
        kg_occ_cache = [None]          # np.int64 [maxp] or None
        kg_occ_step_fn = [None]        # lazily compiled occupancy kernel
        kg_last_refresh = [0.0]
        kg_interval_s = env.config.get_float(
            "observability.kg-stats-interval-ms", 1000.0
        ) / 1e3
        # observability.kg-stats gates the parts with a cost of their
        # own: the occupancy kernel (one compile + an O(C) sweep per
        # interval) and the sampled monitoring fetch for stages that
        # never fetch otherwise (no overflow ring). Defaults to ON
        # exactly when tracing is on — the shipping default's hot path
        # is byte-identical to before, and the fill counts still ride
        # the overflow monitoring fetch that spillable stages already
        # pay for.
        kg_stats_on = env.config.get_bool(
            "observability.kg-stats", tracer is not None
        )
        # observability.drain-stats gates the drain-interior flight
        # recorder (ISSUE 14): with it on, the resident/sharded drain
        # kernels stack per-slot DRAIN_STAT_FIELDS counters the consume
        # path unpacks LAGGED; with it off (the shipping default unless
        # tracing is on) the drains compile without any telemetry work —
        # the op-budget ledger pins the OFF variants byte-identical.
        drain_stats_on = env.config.get_bool(
            "observability.drain-stats", tracer is not None
        )
        # one-element holder (not a plain local) so the runtime
        # controller's drain-stats-cadence actuator can retune the host
        # fetch cadence live (ISSUE 19) — the device computes the
        # payload every drain either way; this only paces the keeps
        drain_stats_every = [max(1, env.config.get_int(
            "observability.drain-stats-every", 8
        ))]
        drain_telem = [None]   # DrainTelemetry; built in setup() when
        ds_skip = [0]          # the resident loop is live (payload cadence)
        # per-shard gauge high-water marks: how many labelled series the
        # last setup() registered, so a scale-down re-plan can remove
        # the stale tail (setup() resolves these at call time, like
        # `ingest` below)
        refusal_gauge_n = [0]
        drain_gauge_n = [0]

        def refresh_kg_occupancy(force: bool = False):
            """Run the per-key-group occupancy kernel and cache the host
            view. Called at fire boundaries (the loop is already syncing
            for the barrier fetch there) at most once per interval."""
            if not kg_stats_on or state is None or spec is None:
                return
            now = time.monotonic()
            if not force and now - kg_last_refresh[0] < kg_interval_s:
                return
            kg_last_refresh[0] = now
            if kg_occ_step_fn[0] is None:
                kg_occ_step_fn[0] = build_kg_occupancy_step(ctx, spec)
            span = (
                tracer.span("kg_occupancy") if tracer is not None
                else contextlib.nullcontext()
            )
            with span, CompileEvents.stage("kg-occupancy"):
                occ = np.asarray(
                    jax.device_get(kg_occ_step_fn[0](state))
                ).sum(axis=0)
            kg_occ_cache[0] = occ.astype(np.int64)

        def _top_k(arr, k):
            if arr is None or not len(arr):
                return []
            k = max(1, min(int(k), len(arr)))
            idx = np.argsort(arr)[::-1][:k]
            return [
                {"group": int(g), "count": int(arr[g])}
                for g in idx if arr[g] > 0
            ]

        def kg_report(k: int = 10) -> dict:
            return {
                "key_groups": maxp_kg,
                "n_shards": ctx.n_shards,
                "occupancy_top": _top_k(kg_occ_cache[0], k),
                "fill_top": _top_k(kg_fill_total, k),
                "fill_sampled_batches": kg_fill_sampled[0],
                "occupied_groups": (
                    int((kg_occ_cache[0] > 0).sum())
                    if kg_occ_cache[0] is not None else None
                ),
            }

        env._kg_report = kg_report

        def pipeline_report() -> dict:
            """/jobs/<jid>/pipeline body: the consolidated resident-
            pipeline health view (drain telemetry + refusals + the
            attribution verdict)."""
            dt = drain_telem[0]
            if dt is None:
                rep = {
                    "available": False,
                    "reason": "observability.drain-stats off or the "
                              "resident loop is not active",
                }
                # the tiers block does not need the recorder: tiered
                # jobs stay observable with drain-stats off
                if tier_mgr[0] is not None:
                    rep["tiers"] = tier_mgr[0].report()
                return rep
            try:
                dr = ingest.device_ring
            except NameError:
                dr = None      # scraped before the pipeline is built
            rep = dt.report(
                refusals=dr.refusals() if dr is not None else None
            )
            rep["drain_stats_every"] = drain_stats_every[0]
            if tier_mgr[0] is not None:
                rep["tiers"] = tier_mgr[0].report()
            if self._attribution is not None:
                rep["classification"] = self._attribution.classify()
            return rep

        env._pipeline_report = pipeline_report

        # CompileEvents is process-global: its "steady" bucket carries
        # every unlabelled compile since process start (other jobs,
        # eager warm-up shapes). The doctor's recompile-storm rule is
        # about growth DURING THIS JOB, so pin a job-start baseline and
        # serve the delta; setup() re-pins it after its build bursts.
        _doctor_steady0[0] = (
            CompileEvents.report()["by_stage"].get("steady")
            or {"count": 0, "time_ms": 0.0}
        )

        def doctor_report() -> dict:
            """/jobs/<jid>/doctor body: joins every telemetry plane into
            one snapshot and runs the ranked-findings rule engine over it
            (metrics/doctor.py). The snapshot and thresholds are embedded
            in the payload so ``python -m flink_tpu.doctor`` can replay
            the exact diagnosis offline."""
            if not env.config.get(_CoreOpts.DOCTOR):
                return {
                    "available": False,
                    "reason": "observability.doctor off",
                }
            from flink_tpu.metrics.doctor import diagnose

            comp = CompileEvents.report()
            steady = dict(comp["by_stage"].get("steady")
                          or {"count": 0, "time_ms": 0.0})
            steady["count"] = max(
                0, steady["count"] - _doctor_steady0[0]["count"]
            )
            steady["time_ms"] = round(max(
                0.0, steady["time_ms"] - _doctor_steady0[0]["time_ms"]
            ), 2)
            comp["by_stage"] = {**comp["by_stage"], "steady": steady}
            snapshot = {
                "pipeline": pipeline_report(),
                "metrics": {
                    f: getattr(metrics, f, 0)
                    for f in JobMetrics.GAUGE_FIELDS
                },
                "checkpoints": list(metrics.checkpoint_stats or []),
                "compile": comp,
                "fire_latency_ms": {
                    "p50": metrics.fire_latency_pct(50),
                    "p99": metrics.fire_latency_pct(99),
                },
            }
            rec_rep = getattr(env, "_recovery_report", None)
            if rec_rep is not None:
                try:
                    snapshot["recovery"] = rec_rep()
                except Exception:
                    pass
            thresholds = {
                "starved": env.config.get(
                    _CoreOpts.DOCTOR_STARVED_THRESHOLD),
                "saturated": env.config.get(
                    _CoreOpts.DOCTOR_SATURATED_THRESHOLD),
                "edge_utilization": env.config.get(
                    _CoreOpts.DOCTOR_EDGE_UTILIZATION_THRESHOLD),
                "kg_skew": env.config.get(
                    _CoreOpts.DOCTOR_KG_SKEW_THRESHOLD),
                "recompile": env.config.get(
                    _CoreOpts.DOCTOR_RECOMPILE_THRESHOLD),
                "tier_churn": env.config.get(
                    _CoreOpts.DOCTOR_TIER_CHURN_THRESHOLD),
                "tier_miss": env.config.get(
                    _CoreOpts.DOCTOR_TIER_MISS_THRESHOLD),
            }
            payload = diagnose(snapshot, thresholds)
            payload["snapshot"] = snapshot
            payload["thresholds"] = thresholds
            return payload

        env._doctor_report = doctor_report
        if self._job_group is not None:
            grp = self._job_group
            # effective fused depth of the most recent dispatch (K for a
            # megastep, 1 for single-step / partial-group flushes)
            fuse_gauge[0] = grp.settable_gauge("steps_per_dispatch", 1)
            # configured HBM batch-ring depth, 0 while the resident
            # loop is off (the resident_drains counter rides
            # JobMetrics.GAUGE_FIELDS)
            grp.gauge("ring_depth",
                      lambda: ring_depth if use_resident else 0)
            # publish-refusal backpressure (round 13): total refusals
            # across shards, plus a per-shard labelled series once the
            # ring is sharded — a stalled shard shows up here instead
            # of being inferred from throughput dips. `ingest` binds
            # later in this scope; the lambda resolves at scrape time.

            def _ring_refusals(shard=None):
                try:
                    dr = ingest.device_ring
                except NameError:
                    return 0   # scraped before the pipeline is built
                if dr is None:
                    return 0
                r = dr.refusals()
                if shard is None:
                    return int(sum(r))
                return int(r[shard]) if shard < len(r) else 0

            grp.gauge("ring_publish_refusals", _ring_refusals)
            # the per-shard labelled series registers from setup():
            # use_dp is only finalized after the resident-loop config
            # resolves, well past this point in the linear body.

            def _occ_stat(fn, default=0):
                occ = kg_occ_cache[0]
                if occ is None:
                    return default
                nz = occ[occ > 0]
                return fn(nz) if len(nz) else default

            grp.gauge("kg_occupied_groups",
                      lambda: _occ_stat(len))
            grp.gauge("kg_occupancy_max",
                      lambda: _occ_stat(lambda nz: int(nz.max())))
            grp.gauge("kg_occupancy_mean",
                      lambda: _occ_stat(
                          lambda nz: round(float(nz.mean()), 2)))
            # skew = hottest group / mean over occupied groups; 1.0 is a
            # perfectly balanced population, >> 1 is the untunable-skew
            # signal (Multicore-SSP: you cannot tune what you cannot
            # attribute)
            grp.gauge("kg_skew_ratio",
                      lambda: _occ_stat(lambda nz: round(
                          float(nz.max() / nz.mean()), 3), default=1.0))
            grp.gauge("kg_fill_max",
                      lambda: int(kg_fill_total.max(initial=0)))
            grp.gauge("kg_hot_group",
                      lambda: int(kg_fill_total.argmax())
                      if kg_fill_total.any() else -1)
            # per-stage watermark + lag gauges (tentpole part 2): how far
            # the watermark trails wall clock and the data it has seen
            grp.gauge("watermark_ms", wm_strategy.current)
            grp.gauge("watermark_lag_ms",
                      lambda: wm_strategy.watermark_lag_ms(
                          int(time.time() * 1000)))
            grp.gauge("event_time_lag_ms", wm_strategy.event_time_lag_ms)

        # Bounded step pipelining: async dispatch lets the host run ahead
        # of the device, but an UNBOUNDED queue means a pane-boundary fire
        # — and therefore every fired window's latency — waits behind the
        # whole backlog (the round-3 p99 was ~3x the reference drain's for
        # exactly this reason). Keep at most `max_inflight` update steps
        # in flight by waiting on the tiny monitoring handle from
        # `max_inflight` steps back before dispatching further: the wait
        # overlaps with the queued steps, costs nothing while the device
        # keeps up, and caps the fire wait at ~max_inflight step times.
        inflight = deque()
        max_inflight = env.config.get_int("pipeline.max-inflight-steps", 4)

        def _hold_inflight(act_handle):
            """Queue a dispatched step's activity handle; past the depth
            cap, block on the oldest. Returns when the block began, or
            None when there was none."""
            inflight.append(act_handle)
            if len(inflight) <= max_inflight:
                return None
            t_w0 = time.perf_counter()
            inflight.popleft().block_until_ready()
            return t_w0

        def _rec_dispatch(name, t0, t_wait, t1, batch, **attrs):
            """A dispatch span (the host enqueue) and, where the inflight
            depth blocked, the inflight_wait span that follows it."""
            tracer.rec(name, t0, t1 if t_wait is None else t_wait,
                       batch=batch, **attrs)
            if t_wait is not None:
                tracer.rec("inflight_wait", t_wait, t1, batch=batch)

        def _batch_seqs(items):
            """The poll sequence numbers of a dispatch group's batches."""
            return [pb.seq for _, _, pb in items if pb is not None]

        # precomputed for the per-batch adaptive route choice
        _kg_ends = np.asarray(ctx.kg_bounds()[1])

        def _pick_route(hi, lo, valid):
            """Step-loop route fallback for batches the ingest side did
            not plan (warmup, catch-up slices, chunked polls). ONE
            implementation of the exchange-feasibility math exists —
            ingest.plan_route — so prep-planned and loop-routed batches
            can never disagree on bucket fit; callers pass prefix-valid
            masks, so the valid lanes are exactly the leading
            count_nonzero lanes (matching prep's unpadded view)."""
            if force_route[0] is not None:
                return force_route[0]
            n_valid = int(np.count_nonzero(valid))
            return ingest_mod.plan_route(
                ingest.plan, hi[:n_valid], lo[:n_valid]
            )

        def _tier_args():
            # trailing residency-mask operand of every tiered kernel —
            # data, not structure: a demote/promote swaps the device
            # array, never the compiled step
            return (tier_mask_dev[0],) if use_tiers[0] else ()

        def run_update(hi, lo, ticks, values, valid, wm_ms, staged=None,
                       route=None, batch=None):
            """Dispatch one update-only device step. No host sync: the
            result is not read, so transfers and compute of successive
            steps overlap (the round-1 loop blocked on every step). The
            step's tiny (ovf_n, activity) output handles are queued for
            LAGGED monitoring — inspected a few steps later when they have
            already materialized, so the pipeline never stalls. `activity`
            drives the insert<->fast step tiering (wk.update insert flag).

            `route`/`staged`: precomputed by the ingest side
            (runtime/ingest.py) — the route plan and the device-resident
            padded arrays of a prefetched batch. When the ingest plan has
            staging on, host-array calls (warmup, catch-up slices) are
            staged HERE with the same shardings, so every dispatch feeds
            the compiled step identically-committed inputs and the step
            never recompiles mid-stream."""
            nonlocal state
            wm_ticks = (
                min(int(td.to_ticks(wm_ms)), 2**31 - 4)
                if wm_ms is not None else None
            )
            # numpy, NOT jnp.full: an eager device op for this tiny vector
            # is a dispatch of its own; as a jit argument it rides the
            # step's (queued, cheap) input transfer
            # lint: allow(retrace): deliberate tiny [n_shards] per-dispatch vector — see the comment above; hoisting would share a buffer across queued async dispatches
            wmv = np.full((ctx.n_shards,), np.int32(
                wm_ticks if wm_ticks is not None else -(2**31) + 1
            ))
            t_d0 = time.perf_counter()
            if route is None:
                route = _pick_route(hi, lo, valid)
            # route span: only a sampled-traced cycle pays the extra
            # perf_counter read between routing and dispatch
            t_r1 = (
                time.perf_counter()
                if tracer is not None and tracer.active else None
            )
            tiers = steps_by_route[route]
            tier = (
                "fast"
                if step_mode[0] == "fast" and tiers["fast"] is not None
                else "insert"
            )
            active = tiers[tier]
            if active is None:
                # chained stage graphs register route placeholders only
                # (every dispatch goes through the chained resident
                # drain); reaching here means a dispatch path missed its
                # chained branch — fail loudly, never silently drop
                raise RuntimeError(
                    f"no single-step kernel for route {route!r}: chained "
                    f"stage jobs must dispatch via the resident drain"
                )
            # chaos seam: a dying chip surfaces as a runtime error out
            # of the dispatch — the device_loss fault class injects
            # exactly there (no-op module-global check in production)
            faults.inject("step.dispatch", step=metrics.steps,
                          route=route)
            if staged is None:
                s_args, did_stage = _stage_planned(
                    (hi, lo, ticks, values, valid), route
                )
                if did_stage:
                    staged = s_args
            if staged is not None:
                state, (ovf_handle, act_handle, kgf_handle) = active(
                    state, *staged, wmv, *_tier_args(),
                )
            else:
                state, (ovf_handle, act_handle, kgf_handle) = active(
                    state, jnp.asarray(hi), jnp.asarray(lo),
                    jnp.asarray(ticks), jnp.asarray(values),
                    jnp.asarray(valid), wmv, *_tier_args(),
                )
            # dispatch normally returns immediately; it BLOCKS when the
            # device pipeline is saturated -> the device-bound signal.
            # The depth-cap wait below is part of the same device-bound
            # attribution: it only takes time when the device lags.
            t_w0 = _hold_inflight(act_handle)
            t_d1 = time.perf_counter()
            phase_acc["dispatch"] += t_d1 - t_d0
            if t_r1 is not None:
                tracer.rec("route", t_d0, t_r1, route=route, batch=batch)
                _rec_dispatch("dispatch", t_r1, t_w0, t_d1, batch,
                              route=route, tier=tier, step=metrics.steps)
            metrics.steps += 1
            if tier == "fast":
                metrics.steps_fast += 1
            if route == "exchange":
                metrics.steps_exchanged += 1
            # SAMPLED lagged monitoring: every device->host fetch is a
            # blocking round trip, so only every
            # MON_EVERY-th step's handles are retained and inspected;
            # the overflow ring is auto-sized to absorb the whole
            # detection lag (see setup()). The kg_fill skew counts ride
            # the same sampled fetch for free; observability.kg-stats
            # additionally enables it for stages with no overflow ring
            # (strict capacity / direct layout), which otherwise never
            # pay a monitoring fetch at all.
            if win.overflow or kg_stats_on:
                mon_skip[0] += 1
                if mon_skip[0] >= MON_EVERY:
                    mon_skip[0] = 0
                    mon_watch.append(
                        (ovf_handle, act_handle, kgf_handle, 1)
                    )
                    check_overflow_pressure()

        def _pad_planned(pb):
            """Pad a planned batch's host arrays to step shape: the
            5-tuple (hi, lo, ticks, values, valid) every update-step
            variant takes. The ONE copy of the padding recipe."""
            Bs = B_step[0]
            return (
                _pad(pb.hi, Bs, np.uint32),
                _pad(pb.lo, Bs, np.uint32),
                _pad(pb.ticks, Bs, np.int32),
                _pad(pb.values, Bs, pb.values.dtype),
                ingest_mod.prefix_mask(valid_tmpl[0], pb.n),
            )

        def _stage_planned(args, route):
            """Stage a padded 5-tuple with the route's committed
            shardings when the ingest plan stages (enqueue-only
            device_put — the arrays are fresh per call, so there is no
            buffer-recycle hazard). Returns (args, staged_mode)."""
            plan = ingest.plan
            if plan is not None and plan.staging:
                return (
                    ingest_mod.stage_batch_arrays(plan, route, *args),
                    True,
                )
            return args, False

        def _empty_fused_item(route):
            """One zero batch in megastep-operand form (compile warmup)."""
            if route == "sharded":
                # sharded drains consume [n_shards, cap] ring slices
                # (leading axis split across the mesh)
                shape = (ctx.n_shards, shard_cap[0])
            else:
                shape = (B_step[0],)
            vals = (
                np.zeros(shape, np.uint32) if red.kind == "sketch"
                else np.zeros(shape + tuple(red.value_shape), np.float32)
            )
            args = (np.zeros(shape, np.uint32), np.zeros(shape, np.uint32),
                    np.zeros(shape, np.int32), vals, np.zeros(shape, bool))
            args, _ = _stage_planned(args, route)
            return (args, None, None)

        def run_update_fused(route, items):
            """Dispatch ONE K-fused megastep: `items` is exactly k_fuse
            (args, wm_ms, pb) tuples of the same route and staging mode
            (the fused slot's grouping contract). A single jitted
            lax.scan applies all K batches against donated state, so the
            fixed per-dispatch cost — this function, tracing, the
            dispatch round trip — is paid once for K micro-batches. The
            monitoring handles come back with single-step shapes (the
            megastep sums/finalizes over K on device), so the lagged
            monitoring consumer is shared; the skip counter advances by
            K to keep MON_EVERY's per-MICRO-BATCH sampling cadence (and
            therefore the overflow-detection lag) unchanged."""
            nonlocal state
            t_d0 = time.perf_counter()
            t_r1 = (
                time.perf_counter()
                if tracer is not None and tracer.active else None
            )
            tiers = megasteps_by_route[route]
            tier = (
                "fast"
                if step_mode[0] == "fast" and tiers["fast"] is not None
                else "insert"
            )
            active = tiers[tier]
            # chaos seam (see run_update): device loss out of a fused
            # dispatch takes the same elastic recovery branch
            faults.inject("step.dispatch", step=metrics.steps,
                          route=route, k=k_fuse)
            flat = []
            # lint: allow(retrace): tiny [n_shards, K] watermark matrix, fresh per fused dispatch for the same reason as run_update's wmv (queued async dispatches must not share the buffer)
            wmv = np.empty((ctx.n_shards, k_fuse), np.int32)
            for i, (args, wm_ms, _pb) in enumerate(items):
                flat.extend(args)
                wmv[:, i] = np.int32(
                    min(int(td.to_ticks(wm_ms)), 2**31 - 4)
                    if wm_ms is not None else -(2**31) + 1
                )
            if getattr(active, "fused_fire", False):
                # resident pipeline: the scan fired each sub-batch under
                # its own watermark; queue the payload handles for LAGGED
                # consumption (consume_fires) — no step-loop sync here.
                # The post-scan ovf_n handle rides along: emitting a
                # window whose spill contributions still sit in the
                # DEVICE ring would lose them, so the consumer drains
                # the ring first whenever that fill is nonzero (ovf_n is
                # monotone until a host drain, so the post-scan value
                # can never under-report the fill at fire time).
                state, (ovf_handle, act_handle, kgf_handle), fires = \
                    active(state, *flat, wmv, *_tier_args())
                # no drain-stats lane on megasteps (resident drains only)
                fire_watch.append(
                    (fires, ovf_handle, time.perf_counter(), None)
                )
                metrics.fused_fire_dispatches += 1
            else:
                state, (ovf_handle, act_handle, kgf_handle) = active(
                    state, *flat, wmv, *_tier_args(),
                )
            t_w0 = _hold_inflight(act_handle)
            t_d1 = time.perf_counter()
            phase_acc["dispatch"] += t_d1 - t_d0
            if t_r1 is not None:
                _rec_dispatch("dispatch", t_r1, t_w0, t_d1,
                              _batch_seqs(items), route=route, tier=tier,
                              step=metrics.steps, k=k_fuse)
            metrics.steps += k_fuse
            metrics.fused_dispatches += 1
            if tier == "fast":
                metrics.steps_fast += k_fuse
            if route == "exchange":
                metrics.steps_exchanged += k_fuse
            if fuse_gauge[0] is not None:
                fuse_gauge[0].set(k_fuse)
            if win.overflow or kg_stats_on:
                mon_skip[0] += k_fuse
                if mon_skip[0] >= MON_EVERY:
                    mon_skip[0] = 0
                    # a megastep's kg_fill handle sums K batches' counts:
                    # carry K so the sampled-batch denominator stays per
                    # micro-batch
                    mon_watch.append(
                        (ovf_handle, act_handle, kgf_handle, k_fuse)
                    )
                    check_overflow_pressure()

        def run_update_resident(route, items):
            """Dispatch ONE resident ring drain: `items` is 1..ring_depth
            (args, wm_ms, pb) tuples of the same route, all device-staged
            (the drain group's contract). A single count-gated jitted
            scan applies + fires every live slot against donated state —
            slots past the count cost only the scalar predicate — so the
            fixed per-dispatch cost is paid once per ring drain at ANY
            fill level, with no per-fill recompile. Exit policy (ring
            empty, fire high-water, monitoring cadence, checkpoint cut)
            is host-side COUNT policy: whatever bounded this group's
            accumulation decides what the device consumes; slots past a
            cut simply stay in the ring for the next drain."""
            nonlocal state
            count = len(items)
            t_d0 = time.perf_counter()
            t_r1 = (
                time.perf_counter()
                if tracer is not None and tracer.active else None
            )
            tiers = residents_by_route[route]
            tier = (
                "fast"
                if step_mode[0] == "fast" and tiers["fast"] is not None
                else "insert"
            )
            active = tiers[tier]
            # chaos seam (see run_update): device loss / crash out of a
            # drain dispatch — the mid-drain exactly-once test injects
            # exactly here. Warmup drains are exempt: they dispatch
            # synthetic empty batches (already excluded from the step
            # counters), and counting them would make a rule's
            # occurrence index depend on which kernel tiers got built
            if not drain_warmup[0]:
                faults.inject("step.drain", step=metrics.steps,
                              route=route, slots=count)
                # the drain IS the steady-state dispatch: a dying chip
                # surfaces here, so the device_loss fault class
                # (step.dispatch) must be able to target resident jobs
                faults.inject("step.dispatch", step=metrics.steps,
                              route=route, slots=count)
            is_while = getattr(active, "while_drain", False)
            # the kernel's slot depth: ring depth for the scan drains,
            # the while-drain bound for while mode (the exchange scan is
            # also built at the bound there, so groups up to the bound
            # always fit whatever kernel serves the route)
            depth_k = int(getattr(active, "ring_depth", ring_depth))
            flat = []
            # lint: allow(retrace): tiny [n_shards, D] watermark matrix, fresh per drain dispatch for the same reason as run_update's wmv (queued async dispatches must not share the buffer)
            wmv = np.empty((ctx.n_shards, depth_k), np.int32)
            for i, (args, wm_ms, _pb) in enumerate(items):
                flat.extend(args)
                wmv[:, i] = np.int32(
                    min(int(td.to_ticks(wm_ms)), 2**31 - 4)
                    if wm_ms is not None else -(2**31) + 1
                )
            # pad the operand list to the kernel depth by repeating the
            # last slot: the skip branch never applies them, and the
            # MIN-sentinel watermark fires nothing even if it did — the
            # pad exists only so the scan's stacked xs keep one static
            # shape (the while drain's staged clamp plays the same role)
            for i in range(count, depth_k):
                flat.extend(items[-1][0])
                wmv[:, i] = np.int32(-(2**31) + 1)
            wd_prev = None
            if wd is not None:
                # deadline scales with the work actually handed to the
                # device: per-slot seconds x slots consumed. A sharded
                # drain retires every shard's slots concurrently — free
                # on real chips, but on the CPU backend the virtual
                # shards contend for the same host cores, so the
                # legitimate wall time grows ~n_shards x and the arm
                # must too (a deep 8-shard drain would otherwise trip a
                # deadline tuned for one chip's slots)
                # the while drain may legitimately retire MORE slots
                # than the host packed (cursor stores landing mid-drain
                # on an aliasing runtime), so its deadline arms at the
                # per-dispatch BOUND, not the observed fill — the bound
                # is what makes "one while dispatch" a well-defined unit
                # of work for the watchdog to time
                wd_scale = depth_k if is_while else count
                if (getattr(active, "sharded_drain", False)
                        and jax.default_backend() == "cpu"):
                    wd_scale = wd_scale * ctx.n_shards
                wd_prev = wd.arm("device-drain",
                                 detail=f"slots={count}", scale=wd_scale)
            try:
                # resident drains always fire in-scan: queue the payload
                # handles for LAGGED consumption (consume_fires); the
                # post-scan ovf_n handle rides along as in
                # run_update_fused
                # sharded drain kernels gate per shard: a uniform count
                # vector here (every publish fills one slot per shard,
                # possibly with an empty valid mask), but the kernel
                # contract keeps the vector so a future skew-aware ring
                # can under-fill individual shards without recompiling
                cnt = (
                    np.full(ctx.n_shards, count, np.int32)
                    if getattr(active, "sharded_drain", False)
                    else np.int32(count)
                )
                if getattr(active, "chained_drain", False):
                    # chained drain: donated state is the TUPLE of every
                    # stage's state; fires are the FINAL stage's
                    res = active(
                        (state,) + tuple(chain_states), *flat, wmv, cnt
                    )
                    sts = res[0]
                    state = sts[0]
                    chain_states[:] = sts[1:]
                    (ovf_handle, act_handle, kgf_handle), fires = \
                        res[1], res[2]
                elif is_while:
                    # while drain: the count operand becomes (cursor,
                    # base, staged). The cursor is the ring's live HBM
                    # slot (donated — the kernel reuses its buffer for
                    # the consumed count, and on an aliasing runtime the
                    # donation is what lets a mid-drain commit store be
                    # observed); base anchors it so cursor - base equals
                    # this group's fill at dispatch; staged clamps the
                    # trip count to the payloads actually packed above
                    dr = ingest.device_ring
                    # the ring cursor only fits a kernel of the SAME
                    # layout (scalar slot vs per-shard vector) — a dp
                    # job's mask-route fallback drain synthesizes a
                    # frozen cursor instead (== scan count gating)
                    cur = (
                        dr.device_cursor()
                        if dr is not None and dr.sharded
                        == bool(getattr(active, "sharded_drain", False))
                        else None
                    )
                    if getattr(active, "sharded_drain", False):
                        staged_op = np.full(ctx.n_shards, count, np.int32)
                        if cur is None:
                            cursor_op = np.full(
                                ctx.n_shards, count, np.int32)
                            base_op = np.zeros(ctx.n_shards, np.int32)
                        else:
                            cursor_op, snap = cur
                            base_op = (
                                np.asarray(snap, np.int32)
                                - np.int32(count)
                            )
                    else:
                        staged_op = np.int32(count)
                        if cur is None:
                            cursor_op = np.full(1, count, np.int32)
                            base_op = np.int32(0)
                        else:
                            cursor_op, snap = cur
                            base_op = np.int32(snap - count)
                    res = active(state, *flat, wmv, cursor_op, base_op,
                                 staged_op, *_tier_args())
                    if dr is not None and cur is not None:
                        # the dispatch consumed (donated) the grabbed
                        # cursor array; stand up a fresh one so a quiet
                        # stream's next drain never re-passes a deleted
                        # buffer
                        dr.refresh_device_cursor()
                    # res[3] is the consumed count — the host already
                    # knows the release boundary (the packed items'
                    # ring seqs; staged clamps the kernel to exactly
                    # them), so the handle is dropped, never synced
                    state, (ovf_handle, act_handle, kgf_handle), fires = \
                        res[:3]
                else:
                    res = active(state, *flat, wmv, cnt, *_tier_args())
                    # telemetry-ON drains return a 4th element: the
                    # [n_shards, D, len(DRAIN_STAT_FIELDS)] flight-
                    # recorder payload. Its handle is kept every
                    # drain-stats-every-th drain only (the device
                    # computes it every drain; the host fetch cadence is
                    # the knob) and rides the lagged fire_watch channel
                    # — never a fresh sync
                    state, (ovf_handle, act_handle, kgf_handle), fires = \
                        res[:3]
                ds_h = None
                if drain_stats_on:
                    ds_skip[0] += 1
                    if ds_skip[0] >= drain_stats_every[0]:
                        ds_skip[0] = 0
                        # while drains slot the consumed count at res[3],
                        # so their recorder payload rides one later
                        ds_h = res[4] if is_while else res[3]
                fire_watch.append(
                    (fires, ovf_handle, time.perf_counter(), ds_h)
                )
                t_w0 = _hold_inflight(act_handle)
            finally:
                if wd is not None:
                    wd.disarm(wd_prev)
            t_d1 = time.perf_counter()
            phase_acc["dispatch"] += t_d1 - t_d0
            if t_r1 is not None:
                _rec_dispatch("drain", t_r1, t_w0, t_d1, _batch_seqs(items),
                              route=route, tier=tier, step=metrics.steps,
                              slots=count, ring_depth=ring_depth)
            metrics.steps += count
            metrics.resident_drains += 1
            metrics.fused_fire_dispatches += 1
            if tier == "fast":
                metrics.steps_fast += count
            if route == "exchange":
                metrics.steps_exchanged += count
            elif route == "sharded":
                metrics.steps_sharded += count
            if fuse_gauge[0] is not None:
                fuse_gauge[0].set(count)
            if win.overflow or kg_stats_on:
                mon_skip[0] += count
                if mon_skip[0] >= MON_EVERY:
                    mon_skip[0] = 0
                    # the drain's kg_fill handle sums `count` batches'
                    # counts — carry the batch count so the sampled
                    # denominator stays per micro-batch
                    mon_watch.append(
                        (ovf_handle, act_handle, kgf_handle, count)
                    )
                    check_overflow_pressure()

        def flush_fused():
            """Dispatch whatever the fused slot holds: a full group as
            one megastep, a partial group as sequential single steps
            (bit-identical by construction — the scan body IS the single
            step), then mark the LAST batch's offsets applied. That mark
            is the megastep-boundary checkpoint cut: a snapshot taken
            after this flush names offsets whose every prior record the
            device state has absorbed, so exactly-once is preserved with
            fusion on.

            Resident-pipeline mode (fused.hold_fires): groups are no
            longer broken at fire boundaries, so this flush also OWNS
            the crossing bookkeeping — a full group's crossings fired
            in-scan (host_fired_pane catches up here, and a modeled
            lane-backlog overrun falls back to the split drain), while a
            partial group dispatched as singles still needs the split
            drain for any crossing it carried."""
            if not len(fused):
                return
            route, staged_mode, items = fused.drain()
            # resident loop: a STAGED group of any fill 1..ring_depth is
            # one count-gated drain dispatch — partial groups no longer
            # fall back to sequential singles
            resident_ok = (
                use_resident and staged_mode
                and route in residents_by_route
            )
            full = len(items) == k_fuse
            if resident_ok:
                run_update_resident(route, items)
            elif full and route in megasteps_by_route:
                run_update_fused(route, items)
            elif staged_mode:
                for args, wm_ms, _pb in items:
                    run_update(None, None, None, None, None, wm_ms,
                               staged=args, route=route,
                               batch=None if _pb is None else _pb.seq)
                if fuse_gauge[0] is not None:
                    fuse_gauge[0].set(1)
            else:
                for args, wm_ms, _pb in items:
                    run_update(*args, wm_ms, route=route,
                               batch=None if _pb is None else _pb.seq)
                if fuse_gauge[0] is not None:
                    fuse_gauge[0].set(1)
            last_pb = items[-1][2]
            if last_pb is not None:
                ingest.mark_applied(last_pb)
            if resident_ok:
                # ring-drain exactly-once boundary: the drain has been
                # dispatched for every slot in this group, and the
                # offsets cut above names it — retire the HBM ring
                # slots so the prefetch thread can recycle them (the
                # async runtime keeps the buffers alive until the
                # queued drain has consumed them)
                dr = ingest.device_ring
                released = None
                if dr is not None and dr.sharded:
                    # per-shard applied cut: each shard retires through
                    # ITS highest released sequence (a refused lane's
                    # None simply leaves that shard's cursor alone), so
                    # one slow shard never pins the others' slots
                    nsh = len(dr.refusals())
                    cut = [None] * nsh
                    for it in items:
                        pb = it[2]
                        if pb is None or pb.ring_seqs is None:
                            continue
                        for s, sq in enumerate(pb.ring_seqs):
                            if sq is not None and (
                                cut[s] is None or sq > cut[s]
                            ):
                                cut[s] = sq
                    if any(sq is not None for sq in cut):
                        dr.release_shards(cut)
                    released = cut
                elif dr is not None:
                    seqs = [
                        it[2].ring_seq for it in items
                        if it[2] is not None and it[2].ring_seq is not None
                    ]
                    if seqs:
                        dr.release_through(max(seqs))
                    released = [max(seqs) if seqs else None]
                dt = drain_telem[0]
                if dt is not None and dr is not None:
                    # flight-recorder tick: absorb the ring's publish-
                    # time stamps (bounded deque swaps — no device
                    # traffic) and record this drain's duty-cycle /
                    # occupancy / publish-to-consume samples
                    if not dr.stats_enabled:
                        dr.stats_enabled = True
                    dt.ingest_publish(dr.publish_samples())
                    fills = dr.occupancy_shards()
                    dt.on_drain(
                        [len(items)] * len(fills), fills,
                        released if released is not None
                        else [None] * len(fills),
                    )
            if fused.hold_fires:
                fired_in_scan = resident_ok or (full and getattr(
                    megasteps_by_route.get(route, {}).get("insert"),
                    "fused_fire", False,
                ))
                _fused_fire_bookkeep(items, fired_in_scan)
                # lagged payload consumption: by now the PREVIOUS
                # group's fires have long materialized on device
                consume_fires()

        def _fused_fire_bookkeep(items, fired_in_scan):
            """Track pane crossings through a resident-pipeline flush.

            A full fired-megastep group emitted every due window IN the
            scan (up to F lanes per sub-step, leftovers rolling to the
            next sub-step); the host models that lane budget and only
            falls back to the split drain when the model says dues could
            have outrun the lanes (or the group was dispatched split —
            partial flush — with a crossing pending). Also catches
            host_fired_pane up to the group's last watermark, and drains
            eagerly with allowed lateness (re-fire backlogs are data-
            dependent, which the host cannot see)."""
            nonlocal host_fired_pane
            F_on = win.fires_per_step
            # device dues per advance are bounded by the ring span plus
            # the window's pane count (fire-lane plan), so a fresh job's
            # sentinel host_fired_pane cannot fake an unbounded backlog
            cap = win.ring + win.size_ticks // win.slide_ticks
            backlog = 0
            prev = host_fired_pane
            last_wm = None
            crossed = False
            for _args, wm_ms, _pb in items:
                if wm_ms is None:
                    continue
                last_wm = wm_ms
                wp = wm_pane_of(wm_ms)
                if wp > prev:
                    crossed = True
                    backlog += min(wp - prev, cap)
                    prev = wp
                if fired_in_scan:
                    backlog = max(0, backlog - F_on)
            if last_wm is None:
                return
            host_fired_pane = max(host_fired_pane, prev)
            need_split_drain = (
                backlog > 0
                or (not fired_in_scan and (crossed or eager_fire))
                or (eager_fire and fired_in_scan)
            )
            if need_split_drain:
                drain_fires(last_wm, time.perf_counter())

        def run_fire(wm_ms, reduced: bool = False):
            nonlocal state
            wm_ticks = (
                min(int(td.to_ticks(wm_ms)), 2**31 - 4)
                if wm_ms is not None else None
            )
            wmv = np.full((ctx.n_shards,), np.int32(   # numpy: see run_update
                wm_ticks if wm_ticks is not None else -(2**31) + 1
            ))
            active = fire_reduced_step if reduced else fire_step
            state, cf = active(state, wmv)
            return cf

        # -- spill tier: overflow-ring drain + host pane stores ------------
        # Records whose key found no table slot land in the device overflow
        # ring; at fire boundaries the host drains the ring into per-pane
        # native SpillStores (the RocksDB-analog tier, SURVEY §2.10 item 2 /
        # RocksDBKeyedStateBackend.java:82), compacts the device table to
        # free dead-key slots, and merges spill contributions into window
        # emissions. State capacity overruns therefore degrade to host
        # memory instead of failing the job.
        ovf_stores = {}          # pane -> native SpillStore
        compact_step_fn = None
        ovf_w = max(1, int(np.prod(red.value_shape, dtype=np.int64) or 1))
        # single host-side dispatch table for the builtin reduce kinds the
        # spill tier supports: (accumulating ufunc, neutral element)
        ufunc, ovf_neutral = _HOST_REDUCE.get(red.kind, (None, None))
        # lagged + sampled ring monitoring: every MON_EVERY-th step's
        # (ovf_n, activity) handles are retained; the oldest is inspected
        # once OVF_LAG newer samples exist — by then its step has long
        # finished, so the read is one settled round trip, amortized to
        # ~1/MON_EVERY of the fixed d2h latency per step
        mon_watch = deque()
        mon_skip = [0]
        MON_EVERY = 8
        OVF_LAG = 1

        def _absorb_kg(kgf_h, n_batches):
            """Fold one sampled dispatch's per-key-group record counts
            ([n_shards, maxp] — shards are disjoint, sum them;
            [n_shards, 0] when the steps were built without kg_fill)
            into the skew telemetry. n_batches = micro-batches the
            handle covers (K for a fused megastep), so fill-per-sampled-
            batch stays a per-batch rate."""
            kgf = np.asarray(kgf_h)
            if not kgf.size:
                return
            kg_sum = kgf.sum(axis=0)
            kg_fill_total[:] += kg_sum
            kg_fill_sampled[0] += n_batches
            # key-group heat (ISSUE 17): the same sampled fill
            # vector folds into the flight recorder's EWMA heat +
            # recency series — the demote/prefetch and
            # live-rebalance sensor; host numpy on the fetched
            # lagged handle, no extra sync
            dt_kg = drain_telem[0]
            if dt_kg is not None:
                dt_kg.absorb_kg_fill(kg_sum, n_batches)
            if tier_mgr[0] is not None:
                # tier fault accounting rides the SAME sampled
                # vector: traffic into a non-resident group = a
                # batch that fell down the route ladder (documented
                # sampled, like every MON_EVERY-cadence counter)
                tier_mgr[0].note_sample(kg_sum)

        def salvage_kg_watch():
            """Drain mon_watch keeping ONLY the kg_fill counts. The
            queued ring-fill handles go stale across an overflow drain
            (they reflect pre-drain occupancy), but the kg counts
            measure the sampled dispatch's record traffic — still valid.
            Dropping them too blinds the heat plane exactly while the
            pipeline sits in sustained overflow, which is when the
            skew sensor (tier placement, live rebalance) is the only
            thing that can relieve the pressure."""
            while mon_watch:
                _, _, kgf_h, n_batches = mon_watch.popleft()
                _absorb_kg(kgf_h, n_batches)

        def check_overflow_pressure():
            if len(mon_watch) <= OVF_LAG:
                return
            ovf_h, act_h, kgf_h, n_batches = mon_watch.popleft()
            fill = int(np.asarray(ovf_h).max(initial=0))
            act = int(np.asarray(act_h).sum())
            _absorb_kg(kgf_h, n_batches)
            # -- adaptive step tiering: while new keys are being PLACED,
            # run the upsert step; once placement stops
            # (TIER_QUIET_CHECKS consecutive zero-activity checks), switch
            # to the lookup-only fast step (~6x cheaper). A miss in fast
            # mode flips back: a missed key that insert CAN place recurs
            # as a miss on every subsequent batch, so leaving it on the
            # spill tier compounds into expensive ring drains — bouncing
            # to insert mode heals it permanently. A bounce that places
            # NOTHING proves the missing keys are chain-exhausted (insert
            # can never help); their miss level becomes the fast-mode
            # tolerance so an over-capacity residue settles in fast mode
            # instead of oscillating.
            has_fast = any(
                t["fast"] is not None for t in steps_by_route.values()
            )
            if has_fast:
                if step_mode[0] == "insert":
                    if act == 0:
                        tier_quiet[0] += 1
                        if tier_quiet[0] >= TIER_QUIET_CHECKS:
                            step_mode[0] = "fast"
                            if bounce_miss[0] and not bounce_placed[0]:
                                miss_tolerance[0] = max(
                                    miss_tolerance[0], bounce_miss[0]
                                )
                            bounce_miss[0] = 0
                    else:
                        tier_quiet[0] = 0
                        bounce_placed[0] = True
                elif act > miss_tolerance[0]:
                    step_mode[0] = "insert"
                    tier_quiet[0] = 0
                    bounce_miss[0] = act
                    bounce_placed[0] = False
            if fill > max(1, B // 8):
                # meaningful pressure: drain NOW rather than waiting for
                # the next pane boundary. The auto-sized ring (~6*B lanes)
                # absorbs the <= (OVF_LAG+1) steps of lag, so nothing is
                # lost; the sync + compaction is the degraded-mode price.
                drain_overflow()

        def host_combine(a, b):
            return ufunc(a, b)

        def _merge_ring_into_stores():
            """One pass: fetch + clear the device ring into pane stores.
            Returns True if anything was drained."""
            nonlocal state
            counts = np.asarray(jax.device_get(state.ovf_n))   # [S]
            if counts.max(initial=0) <= 0:
                return False
            slices = []
            for s in range(ctx.n_shards):
                n = int(counts[s])
                if n:
                    slices.append((state.ovf_hi[s, :n], state.ovf_lo[s, :n],
                                   state.ovf_pane[s, :n], state.ovf_val[s, :n]))
            fetched = jax.device_get(slices)
            hi = np.concatenate([f[0] for f in fetched])
            lo = np.concatenate([f[1] for f in fetched])
            panes = np.concatenate([f[2] for f in fetched])
            vals = np.concatenate([f[3] for f in fetched]).reshape(-1, ovf_w)
            k64 = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(
                np.uint64
            )
            from flink_tpu.native import SpillStore

            for p in np.unique(panes):
                sel = panes == p
                uk, inv = np.unique(k64[sel], return_inverse=True)
                agg = np.full((len(uk), ovf_w), ovf_neutral, np.float32)
                ufunc.at(agg, inv, vals[sel].astype(np.float32))
                store = ovf_stores.get(int(p))
                if store is None:
                    store = ovf_stores[int(p)] = SpillStore(
                        width=ovf_w, initial_capacity=1024
                    )
                old, found = store.get(uk)
                merged = np.where(found[:, None], host_combine(old, agg), agg)
                store.put(uk, merged)
            if tier_mgr[0] is not None:
                # pending-pane index for the prefetcher: every ring lane
                # that just folded cold is a (key-group, pane) the
                # watermark will eventually fire
                tier_mgr[0].note_cold(
                    tiers_mod.entries_key_groups(
                        {"key_hi": hi, "key_lo": lo}, ctx.max_parallelism
                    ),
                    panes,
                )
            state = clear_overflow(state)
            return True

        def drain_overflow():
            """Drain the device overflow ring into the host pane stores and
            compact the table to make room. Compaction can itself evict
            non-refitting keys' state INTO the just-cleared ring, so a
            second merge pass picks those up before any emission."""
            nonlocal state, compact_step_fn
            if win is None or not win.overflow or state is None:
                return
            if not _merge_ring_into_stores():
                return
            salvage_kg_watch()    # fill handles reflect pre-drain fill;
            #                       the kg traffic counts stay valid
            miss_tolerance[0] = 0  # compaction may change placeability
            if spec.layout == "direct":
                # no dead slots to free (slot == key, table immutable) —
                # and a hash rebuild would destroy the identity rows
                return
            # free dead-key slots so future records fit (RocksDB-compaction
            # analog); compiled lazily — overflow is the rare path
            if compact_step_fn is None:
                compact_step_fn = build_compact_step(ctx, spec)
            state = compact_step_fn(state)
            _merge_ring_into_stores()   # compaction evictees

        def spill_window_contrib(end_pane: int):
            """Combined spill contributions for the window ending at pane
            end_pane (composes its k panes). Returns (keys u64 SORTED
            unique, values [n, W] float32) — empty arrays when none."""
            k = win.panes_per_window
            ks_l, vs_l = [], []
            for q in range(end_pane - k + 1, end_pane + 1):
                store = ovf_stores.get(q)
                if store is None or len(store) == 0:
                    continue
                ks, vs = store.dump()
                ks_l.append(ks)
                vs_l.append(vs)
            if not ks_l:
                return (np.zeros(0, np.uint64),
                        np.zeros((0, ovf_w), np.float32))
            ks = np.concatenate(ks_l)
            vs = np.concatenate(vs_l)
            uk, inv = np.unique(ks, return_inverse=True)
            agg = np.full((len(uk), ovf_w), ovf_neutral, np.float32)
            ufunc.at(agg, inv, vs)
            return uk, agg

        def prune_stores(wm_ms):
            """Drop pane stores past the same horizon the device purges:
            every containing window fired AND the lateness horizon passed."""
            if not ovf_stores:
                return
            k = win.panes_per_window
            wm_ticks = min(int(td.to_ticks(wm_ms)), 2**31 - 4)
            base = max(
                wm_ticks - win.lateness_ticks,
                -(2**31) + 1 + win.slide_ticks,
            )
            wm_pane_l = (base + 1 - win.slide_ticks) // win.slide_ticks
            cutoff = min(host_fired_pane, wm_pane_l)
            for q in [q for q in ovf_stores if q + k - 1 <= cutoff]:
                ovf_stores.pop(q).close()
            if tier_mgr[0] is not None:
                # same horizon for the prefetcher's pending-pane index
                tier_mgr[0].prune_cold(cutoff - k + 1)

        def _apply_tier_plan(plan):
            """Demote/promote swap at the exactly-once cut: move the
            affected key-groups' logical entries between device slot
            rows and host pane stores, then re-splice each touched
            shard in place (the warm-restore splice machinery).
            Correctness is residency-INVARIANT — a (key, pane)'s
            pending state may legally split across both tiers (the
            mid-pane-fill overflow path already does) and fire/
            checkpoint/restore compose the halves — so the swap is
            purely a placement action; a crash anywhere inside it
            restores bit-exact from the last cut. The one ordering
            obligation: pending fire payloads were computed against
            the CURRENT placement, so they are consumed before any
            entry moves (a window must never merge the same entry
            from both tiers)."""
            nonlocal state
            tm = tier_mgr[0]
            by_shard = {}
            for g in plan.demote:
                by_shard.setdefault(tm.shard_of(g), ([], []))[0].append(g)
            for g in plan.promote:
                by_shard.setdefault(tm.shard_of(g), ([], []))[1].append(g)
            if by_shard:
                flush_fused()
                consume_fires(force=True)
                _merge_ring_into_stores()
                from flink_tpu.native import SpillStore

                def mk_store():
                    return SpillStore(width=ovf_w, initial_capacity=1024)

                def fold_cold(ent, fault_point):
                    tiers_mod.fold_entries(
                        ent, ovf_stores, ovf_w, ufunc, ovf_neutral,
                        mk_store, host_combine, fault_point=fault_point,
                    )
                    if len(ent["pane"]):
                        tm.note_cold(
                            tiers_mod.entries_key_groups(
                                ent, ctx.max_parallelism
                            ),
                            ent["pane"],
                        )

                def splice_shard(s_row, built):
                    nonlocal state
                    idx = jnp.asarray(np.asarray([s_row], np.int32))

                    def spl(live_arr, sub):
                        return jax.device_put(
                            live_arr.at[idx].set(jnp.asarray(sub)),
                            ctx.state_sharding,
                        )

                    repl = dict(
                        table=type(state.table)(
                            spl(state.table.keys, built["keys"]),
                            spec.probe_len,
                        ),
                        fresh=spl(state.fresh, built["fresh"]),
                        pane_ids=spl(state.pane_ids, built["pane_ids"]),
                        n_fresh=spl(state.n_fresh, built["n_fresh"]),
                    )
                    if use_packed:
                        # splice rows are logical; re-pack onto the live
                        # packed plane (touched rides inside)
                        repl["acc"] = spl(state.acc, wk.make_packed(
                            built["acc"], built["touched"], red
                        ))
                    else:
                        repl["acc"] = spl(state.acc, built["acc"])
                        repl["touched"] = spl(
                            state.touched, built["touched"]
                        )
                    state = dataclasses.replace(state, **repl)

                max_pane_h = np.asarray(jax.device_get(state.max_pane))
                kg_dirty_h = np.asarray(
                    jax.device_get(state.kg_dirty)
                ).copy()
                for s in sorted(by_shard):
                    dem, pro = by_shard[s]
                    staged = ckpt.stage_window_state(
                        state, rows=[s], red=red
                    )
                    # label ring rows from THIS shard's own pane clock:
                    # the staged scalars aggregate the GLOBAL max, which
                    # would mislabel a lagging shard's rows
                    staged["scalars"]["max_pane"] = int(max_pane_h[s])
                    entries, scalars = ckpt.extract_entries(staged, win)
                    kgs = tiers_mod.entries_key_groups(
                        entries, ctx.max_parallelism
                    )
                    dem_m = (
                        np.isin(kgs, np.asarray(dem, np.int64))
                        if dem else np.zeros(len(kgs), bool)
                    )
                    merged, demoted = tiers_mod.split_entries(
                        entries, ~dem_m
                    )
                    # unconditional: the demote seam fires once per
                    # shard swap even when no entries move, so chaos
                    # tests can land a crash on every swap
                    fold_cold(demoted, "tier.demote.write")
                    for g in pro:
                        got = tiers_mod.fetch_group_entries(
                            ovf_stores, g, ctx.max_parallelism, ovf_w,
                            staged["value_tail"], staged["value_dtype"],
                        )
                        tm.forget_cold(g)
                        on, off = tiers_mod.ring_window(
                            got, int(scalars["max_pane"]), int(win.ring)
                        )
                        # panes outside the live ring have no device row
                        # to hold them yet: straight back to the stores
                        # (combine-aware, never dropped); they merge at
                        # fire the normal spill way
                        fold_cold(off, None)
                        merged = tiers_mod.concat_entries(merged, on)
                    merged = tiers_mod.precombine_entries(
                        merged, ovf_w, ufunc, ovf_neutral
                    )
                    leftover = []
                    built = ckpt.restore_window_rows(
                        merged, scalars, ctx, spec, rows=[s],
                        leftover=leftover,
                    )
                    splice_shard(s, built)
                    for l_hi, l_lo, l_pane, l_val in leftover:
                        # promoted rows the table cannot place (chain
                        # exhaustion under the promote's extra keys) go
                        # straight back cold — fold, not put: a raw put
                        # would clobber a resident group's overflow
                        # residue sharing the (key, pane) cell
                        fold_cold({
                            "key_hi": l_hi, "key_lo": l_lo,
                            "pane": l_pane, "value": l_val,
                            "fresh": np.ones(len(l_pane), bool),
                        }, None)
                    # the swap changed these groups' rows without the
                    # kernels marking them: dirty bits keep the next
                    # incremental checkpoint honest
                    for g in dem + pro:
                        kg_dirty_h[s, g] = True
                state = dataclasses.replace(
                    state,
                    kg_dirty=jax.device_put(
                        kg_dirty_h, ctx.state_sharding
                    ),
                )
            tm.apply(plan)
            tier_mask_dev[0] = jnp.asarray(tm.mask())

        def _tier_maintenance():
            """Poll-cycle tier pass (the elastic-latch seam): rank
            groups on the flight recorder's kg-heat/recency series plus
            the watermark-derived next-fire pane, and apply any swap at
            this cycle's cut. Planning is pure host numpy; a cycle with
            an empty plan costs no device traffic at all."""
            tm = tier_mgr[0]
            if tm is None or state is None or win is None:
                return
            dt = drain_telem[0]
            maxp = ctx.max_parallelism
            heat = getattr(dt, "_kg_heat", None) if dt is not None \
                else None
            if heat is not None and len(heat) == maxp:
                heat = np.asarray(heat, np.float64)
                last = np.asarray(dt._kg_last, np.int64)
                seq = int(dt._kg_seq)
            else:
                # no recorder (drain-stats off): heat is flat and the
                # watermark prefetch signal alone drives placement
                heat = np.zeros(maxp, np.float64)
                last = np.full(maxp, -1, np.int64)
                seq = 0
            plan = tm.plan(
                heat, last, seq,
                wm_pane=(
                    host_fired_pane + 1
                    if host_fired_pane > -(2 ** 61) else None
                ),
            )
            _apply_tier_plan(plan)

        columnar_emit = (
            len(pipe.branches) == 1
            and not pipe.branches[0][0]
            and all(s.columnar for s in pipe.all_sinks)
        )
        # on-chip fire reduction (Sink.device_reduce): only aggregate
        # scalars leave the device per drain. Requires the trivially
        # columnar topology and no host-side result projection; the spill
        # tier is checked per-drain (ovf_stores may appear mid-job).
        sink_device_reduce = (
            columnar_emit
            and wagg.result_fn is None
            and all(getattr(s, "device_reduce", False)
                    for s in pipe.all_sinks)
        )

        def _merge_spill(khi, klo, end_ms, v, due_end_ticks,
                         appendable_ends=()):
            """Merge host spill-tier contributions into an emission: keys
            present in both get combined (a key's records can split across
            device and spill when the table filled mid-pane); spill-only
            keys append as new emission rows."""
            k64 = (khi.astype(np.uint64) << np.uint64(32)) | klo.astype(
                np.uint64
            )
            v2 = v.reshape(len(v), ovf_w).astype(np.float32, copy=True)
            add_hi, add_lo, add_end, add_val = [], [], [], []
            for e_ticks in due_end_ticks:
                end_pane = e_ticks // win.slide_ticks - 1
                uk, uv = spill_window_contrib(end_pane)
                if not len(uk):
                    continue
                e_ms = td.to_ms(e_ticks)
                sel = np.nonzero(end_ms == e_ms)[0]
                # batch match: emission keys of this end against the sorted
                # unique spill keys (a key appears at most once per end —
                # shards own disjoint key groups)
                pos = np.searchsorted(uk, k64[sel])
                pos_c = np.minimum(pos, len(uk) - 1)
                hit = uk[pos_c] == k64[sel]
                hit_rows = sel[hit]
                v2[hit_rows] = host_combine(v2[hit_rows], uv[pos_c[hit]])
                if e_ticks in appendable_ends:
                    # spill-only keys fire too (on-time lanes only)
                    only = np.ones(len(uk), bool)
                    only[pos_c[hit]] = False
                    if only.any():
                        ks = uk[only]
                        add_hi.append(
                            (ks >> np.uint64(32)).astype(np.uint32)
                        )
                        add_lo.append(
                            (ks & np.uint64(0xFFFFFFFF)).astype(np.uint32)
                        )
                        add_end.append(np.full(len(ks), e_ms, np.int64))
                        add_val.append(uv[only])
            if add_hi:
                khi = np.concatenate([khi] + add_hi)
                klo = np.concatenate([klo] + add_lo)
                end_ms = np.concatenate([end_ms] + add_end)
                v2 = np.concatenate([v2] + add_val)
            return khi, klo, end_ms, v2.reshape((len(v2),) + v.shape[1:])

        def emit_fires(cf, counts, lanes, ends, vsums, reduced):
            """Emit one fire result. `counts/lanes/ends/vsums` are the
            already-fetched small per-lane fields (ONE batched d2h in
            drain_fires — a cold read costs ~70ms fixed on this runtime,
            so the drain never pays it twice per iteration).

            reduced=True: cf is a wk.ReducedFires — per-lane scalars were
            reduced on-chip, the drain completes from the small fields
            alone and NOTHING O(fires) exists on device, let alone crosses
            the ~25MB/s device->host link. Otherwise cf is a CompactFires
            and only [:count] slices of the device-packed key/value
            buffers transfer. Spill-tier contributions merge in BEFORE any
            result projection."""
            if reduced or (sink_device_reduce and not ovf_stores):
                n = int((counts * lanes).sum())
                if n == 0:
                    return 0
                vs = float((vsums * lanes).sum(dtype=np.float64))
                metrics.fires += n
                metrics.records_out += n
                for s in pipe.all_sinks:
                    s.invoke_reduced(n, vs)
                return n
            traced = tracer is not None and tracer.active
            t_x0 = time.perf_counter() if traced else None
            slices, end_l = [], []
            # distinct due window ends (ticks). Spill contributions merge
            # into every fired value, but spill-ONLY keys append as new
            # rows solely for ON-TIME lanes (f < F): late lanes are
            # per-key corrections and must not re-emit unrelated keys.
            due_ends = set()
            appendable_ends = set()
            F_on = win.fires_per_step
            for sh in range(counts.shape[0]):
                for f in np.nonzero(lanes[sh])[0]:
                    due_ends.add(int(ends[sh, f]))
                    if f < F_on:
                        appendable_ends.add(int(ends[sh, f]))
                    n = int(counts[sh, f])
                    if n == 0:
                        continue
                    slices.append((cf.key_hi[sh, f, :n], cf.key_lo[sh, f, :n],
                                   cf.values[sh, f, :n]))
                    end_l.append(
                        np.full(n, td.to_ms(int(ends[sh, f])), np.int64)
                    )
            if not slices and not ovf_stores:
                return 0
            # one batched fetch: the lazy device slices transfer together
            # instead of 3 blocking round trips per (shard, lane)
            fetched = jax.device_get(slices)
            t_x1 = time.perf_counter() if traced else None
            n = _sink_fetched(cf, fetched, end_l, due_ends, appendable_ends)
            if traced:
                tracer.rec("emit_fetch", t_x0, t_x1, slices=len(slices))
                tracer.rec("emit_sink", t_x1, fired=n)
            return n

        def _sink_fetched(cf, fetched, end_l, due_ends, appendable_ends):
            """The host half of emit_fires: concatenate the fetched rows,
            merge spill contributions, project results, call the sinks."""
            khi_l = [s[0] for s in fetched]
            klo_l = [s[1] for s in fetched]
            val_l = [s[2] for s in fetched]
            if fetched:
                khi = np.concatenate(khi_l)
                klo = np.concatenate(klo_l)
                end_ms = np.concatenate(end_l)
                v = np.concatenate(val_l)
            else:
                khi = np.zeros(0, np.uint32)
                klo = np.zeros(0, np.uint32)
                end_ms = np.zeros(0, np.int64)
                v = np.zeros((0,) + tuple(np.shape(cf.values)[3:]), np.float32)
            if ovf_stores and due_ends:
                khi, klo, end_ms, v = _merge_spill(
                    khi, klo, end_ms, v, sorted(due_ends), appendable_ends
                )
            if len(v) == 0:
                return 0
            if emit_wagg.result_fn is not None:
                # chained graphs surface the FINAL stage's fires, so the
                # final stage's projection applies (emit_wagg == wagg
                # for single-stage jobs)
                v = np.asarray(emit_wagg.result_fn(v))
            metrics.fires += len(v)
            if columnar_emit:
                kid = (khi.astype(np.uint64) << np.uint64(32)) | klo.astype(
                    np.uint64
                )
                cols = {"key_id": kid, "window_end_ms": end_ms, "value": v}
                metrics.records_out += len(v)
                for s in pipe.all_sinks:
                    s.invoke_columnar(cols)
                return len(v)
            keys = codec.decode(khi, klo)
            out = [
                WindowResult(k, int(e), vv)
                for k, e, vv in zip(keys, end_ms.tolist(), v.tolist())
            ]
            return _emit_batch(pipe, out, metrics)

        class _SubstepFires:
            """Per-sub-step view of a fired megastep's stacked
            CompactFires ([n_shards, K, ...] leaves): lazy [:, k] payload
            slices that materialize only through emit_fires' [:count]
            fetches — a no-fire sub-step transfers nothing."""

            __slots__ = ("key_hi", "key_lo", "values")

            def __init__(self, cf, kk):
                self.key_hi = cf.key_hi[:, kk]
                self.key_lo = cf.key_lo[:, kk]
                self.values = cf.values[:, kk]

        def consume_fires(force: bool = False):
            """Drain lagged resident-pipeline fire payloads, oldest
            first (emission order == fire order). In steady state a
            handle sits FIRE_LAG dispatches before being read, so the
            device long since materialized it and the fetch is one
            settled round trip — the resident pipeline's analog of the
            lagged monitoring channel. ``force`` empties the queue at
            ordering boundaries: any split drain, checkpoint/savepoint
            cuts (emissions must precede the snapshot so a crash cannot
            strand a fire the restored fired_through already counts),
            idle polls and end of stream (latency guard)."""
            total = 0
            while fire_watch and (force or len(fire_watch) > FIRE_LAG):
                cf, ovf_h, t_disp, ds_h = fire_watch.popleft()
                # ReducedFires payloads (device_reduce topologies) have
                # no key planes: the small fields below ARE the drain
                reduced = not hasattr(cf, "key_hi")
                t_f0 = time.perf_counter()
                if ds_h is not None:
                    # the sampled flight-recorder payload rides the SAME
                    # batched lagged fetch — one settled round trip
                    # either way, never a fresh sync
                    counts, lanes, ends, vsums, ovf_fill, ds_np = \
                        jax.device_get(
                            (cf.counts, cf.lane_valid,
                             cf.window_end_ticks, cf.value_sums,
                             ovf_h, ds_h)
                        )
                else:
                    ds_np = None
                    counts, lanes, ends, vsums, ovf_fill = jax.device_get(
                        (cf.counts, cf.lane_valid, cf.window_end_ticks,
                         cf.value_sums, ovf_h)
                    )                          # [n_shards, K, Ft]
                if win.overflow and int(ovf_fill.max(initial=0)) > 0:
                    # spill contributions for the fired panes may still
                    # sit in the device overflow ring — move them into
                    # the host pane stores BEFORE the emission merge
                    # (the split drain orders drain_overflow the same
                    # way; entries landing after a window fired are
                    # late-dropped on device, so over-draining is safe)
                    drain_overflow()
                t_f1 = time.perf_counter()
                fires_before = metrics.fires
                n = 0
                for kk in range(counts.shape[1]):
                    if not lanes[:, kk].any():
                        continue
                    n += emit_fires(
                        None if reduced else _SubstepFires(cf, kk),
                        counts[:, kk], lanes[:, kk], ends[:, kk],
                        vsums[:, kk], reduced,
                    )
                dt = drain_telem[0]
                if dt is not None:
                    if ds_np is not None:
                        if isinstance(ds_np, tuple):
                            # chained-drain payload pair (ISSUE 17):
                            # stage-0 per-slot stack + per-stage records
                            dt.absorb_payload(ds_np[0])
                            dt.absorb_stage_payload(ds_np[1])
                        else:
                            dt.absorb_payload(ds_np)
                    live = lanes.astype(bool)
                    if live.any():
                        # event-time-to-fire: every live lane is one
                        # fired window end weighted by its key count
                        dt.note_fires(list(zip(
                            ends[live].tolist(), counts[live].tolist()
                        )))
                if tracer is not None and tracer.active:
                    tracer.rec("fire", t_f0, t_f1, fused=True)
                    tracer.rec("emit", t_f1, fired=n)
                if n:
                    metrics.record_fire_latency(
                        metrics.fires - fires_before,
                        (time.perf_counter() - t_disp) * 1e3,
                    )
                    rec_tracker.note_fire()
                    if self._latency_hist is not None and \
                            last_ingest_t[0] is not None:
                        self._latency_hist.update(
                            (time.perf_counter() - last_ingest_t[0]) * 1e3
                        )
                total += n
                phase_acc["emit"] += time.perf_counter() - t_f0
            return total

        def drain_chained(wm_ms, t_cross=None):
            """Chained-graph analog of drain_fires. There is NO
            standalone fire step for a stage chain (a bare fire sweep
            would consume stage-0 fires without feeding stage 1), so
            residual due panes are flushed by dispatching EMPTY chained
            drain rounds at the target watermark: each round fires up
            to F window ends per stage and forwards them one edge down
            inside the scan. ceil((ring + panes_per_window) / F) rounds
            per stage plus one hop per edge bound the flush; steady-
            state polls never reach the loop (in-scan fires ride the
            lagged consume path, same as the single-stage resident
            drain)."""
            t_e0 = time.perf_counter()
            # pending resident-pipeline payloads predate this flush
            total = consume_fires(force=True)
            if td is None or wm_ms is None:
                phase_acc["emit"] += time.perf_counter() - t_e0
                return total
            fires_before = metrics.fires
            route = (
                "sharded" if "sharded" in residents_by_route else "mask"
            )
            rounds = len(chain_specs) + 1
            for sp in (spec,) + tuple(chain_specs):
                w = sp.win
                rounds += -(
                    -(w.ring + w.size_ticks // w.slide_ticks)
                    // w.fires_per_step
                )
            for _ in range(rounds):
                args, _, _ = _empty_fused_item(route)
                run_update_resident(route, [(args, wm_ms, None)])
            total += consume_fires(force=True)
            if t_cross is not None:
                metrics.record_fire_latency(
                    metrics.fires - fires_before,
                    (time.perf_counter() - t_cross) * 1e3,
                )
            phase_acc["emit"] += time.perf_counter() - t_e0
            return total

        def drain_fires(wm_ms, t_cross=None):
            """Fire every due window end at watermark wm_ms. One fire step
            evaluates up to F window ends (+ up to F late re-fires); loop
            while a full lane set came back, meaning backlog may remain.

            t_cross: perf_counter() at the moment the host observed the
            watermark crossing; every window emitted by this drain records
            (now - t_cross) as its fire latency (the p99 half of the
            north-star metric; ref WindowOperator.onEventTime drain)."""
            if graph is not None:
                return drain_chained(wm_ms, t_cross)
            t_e0 = time.perf_counter()
            # pending resident-pipeline payloads predate this drain's
            # fires (and prune_stores below must not outrun them)
            consume_fires(force=True)
            drain_overflow()     # ring -> pane stores before any emission
            # skew telemetry: refresh the per-key-group occupancy view ON
            # ENTRY (interval-limited inside) — the fires below purge due
            # panes, so sampling here sees the live population the stall
            # is actually about
            refresh_kg_occupancy()
            total = 0
            F = win.fires_per_step
            # spill-tier presence is fixed for the whole drain
            # (drain_overflow above was its only producer), so the choice
            # of fire variant is loop-invariant
            use_reduced = fire_reduced_step is not None and not ovf_stores
            traced = tracer is not None and tracer.active
            while True:
                t_f0 = time.perf_counter()
                # watchdog phases: fire dispatch and the barrier fetch
                # are the step loop's device waits — a wedged ensemble
                # hangs HERE, so these arms buy the attribution
                wd_prev = wd.arm("fire") if wd is not None else None
                try:
                    cf = run_fire(wm_ms, reduced=use_reduced)
                    # fire dispatch returns immediately; the device_get
                    # below IS the step-boundary barrier — trace them
                    # separately so a stalled fetch is attributable
                    t_fd = time.perf_counter() if traced else None
                    if wd is not None:
                        wd.arm("barrier_fetch")
                    # ONE batched fetch of all small per-lane fields
                    counts, lanes, ends, vsums = jax.device_get(
                        (cf.counts, cf.lane_valid, cf.window_end_ticks,
                         cf.value_sums)
                    )
                finally:
                    if wd is not None:
                        wd.disarm(wd_prev)
                t_f1 = time.perf_counter()
                fires_before = metrics.fires
                n_emit = emit_fires(cf, counts, lanes, ends, vsums,
                                    use_reduced)
                if traced:
                    t_em = time.perf_counter()
                    tracer.rec("fire", t_f0, t_fd, reduced=use_reduced)
                    tracer.rec("barrier_fetch", t_fd, t_f1)
                    tracer.rec("emit", t_f1, t_em, fired=n_emit)
                total += n_emit
                if t_cross is not None:
                    # weight by WINDOWS fired (metrics.fires delta), not by
                    # post-chain records out — a filter/flatMap after the
                    # window must not skew the per-window percentile
                    metrics.record_fire_latency(
                        metrics.fires - fires_before,
                        (time.perf_counter() - t_cross) * 1e3,
                    )
                on_time = int(lanes[:, :F].sum(axis=1).max(initial=0))
                late = int(lanes[:, F:].sum(axis=1).max(initial=0))
                if on_time < F and late < F:
                    prune_stores(wm_ms)
                    phase_acc["emit"] += time.perf_counter() - t_e0
                    if total:
                        # the first emission after a restore stamps the
                        # detect-to-first-fire MTTR number (no-op in
                        # steady state)
                        rec_tracker.note_fire()
                    if total and self._latency_hist is not None and \
                            last_ingest_t[0] is not None:
                        # LatencyMarker analog: ingest -> sink for the
                        # youngest records feeding this emission
                        self._latency_hist.update(
                            (time.perf_counter() - last_ingest_t[0]) * 1e3
                        )
                    return total

        def batch_loop():
            end = False
            while not end:
                end = poll_cycle()

        # Host-side fire scheduling: a window only becomes due when the
        # watermark crosses a pane boundary. The host computes the
        # watermark, so between crossings it dispatches update-only steps
        # with no device readback at all. With allowedLateness > 0, late
        # records can make already-fired windows due again at ANY step, so
        # fires are drained eagerly every cycle (matching round-1 timing).
        host_fired_pane = -(2**62)
        # newest pane the ring has absorbed; guards the BETWEEN-polls time
        # jump (see the pre-fire in poll_cycle — the catch-up slicing only
        # covers a jump WITHIN one poll)
        applied_max_pane = None
        eager_fire = wagg.allowed_lateness_ms > 0

        def wm_pane_of(wm_ms) -> int:
            wm_ticks = min(int(td.to_ticks(wm_ms)), 2**31 - 4)
            b = max(wm_ticks, -(2**31) + 1 + slide_ms)
            return (b + 1 - slide_ms) // slide_ms   # floor div, as on device

        def prep_batch():
            """Front half of a cycle: source poll + host chain + key/value/
            timestamp encode. Pure host numpy with no dependence on mutable
            executor state (watermarks, time domain, device handles), so
            the prefetch thread can run it strictly ahead of the apply
            half — the encode of batch k+1 overlaps the device step of
            batch k instead of serializing with it. The post-poll offsets
            ride the batch (the epoch-tagged replay point): checkpoints
            snapshot the offsets of the last APPLIED batch, which is what
            makes running ahead compatible with exactly-once cuts."""
            polled, end, offsets = pipe.source.poll_with_offsets(B)
            t_src = time.perf_counter()
            now_ms = int(time.time() * 1000)
            hi = lo = values = None
            ts_ms = None
            n = 0
            if pipe.source.columnar and isinstance(polled, tuple):
                cols, ts_ms = polled
                if cols:
                    # columnar chain ops transform the column dict itself
                    for t in pipe.pre_chain:
                        if t.kind != "map":
                            raise NotImplementedError(
                                f"columnar sources support only 'map' "
                                f"(dict->dict) before key_by, got {t.kind!r}"
                            )
                        cols = t.fn(cols)
                    # selectors index the column dict (key_by('name') etc.)
                    keys_arr = np.asarray(pipe.key_by.key_selector(cols))
                    n = len(keys_arr)
                    hi, lo = codec.encode(keys_arr, keep_reverse=keep_rev)
                    values = wagg.extractor(cols)
                    values = (
                        wagg.value_prep(values) if wagg.value_prep is not None
                        else np.asarray(values)
                    )
                    if event_time:
                        if pipe.ts_transform is not None:
                            ts_ms = np.asarray(
                                pipe.ts_transform.timestamp_fn(cols), np.int64
                            )
                        elif ts_ms is None:
                            raise ValueError(
                                "event-time job but the columnar source "
                                "provides no timestamps and no "
                                "assign_timestamps_and_watermarks is set"
                            )
                    else:
                        ts_ms = np.full(n, now_ms, np.int64)
            else:
                elements = _apply_chain(pipe.pre_chain, self._to_elements(polled))
                n = len(elements)
                if n:
                    keys = [pipe.key_by.key_selector(e) for e in elements]
                    hi, lo = codec.encode(keys, keep_reverse=keep_rev)
                    raw = [wagg.extractor(e) for e in elements]
                    values = (
                        wagg.value_prep(raw) if wagg.value_prep is not None
                        else np.asarray(raw, np.float32)
                    )
                    if event_time and pipe.ts_transform is not None:
                        ts_ms = np.asarray(
                            [pipe.ts_transform.timestamp_fn(e) for e in elements],
                            np.int64,
                        )
                    else:
                        ts_ms = np.full(n, now_ms, np.int64)
            return ingest_mod.PreppedBatch(
                end=end, n=n, now_ms=now_ms, t_src=t_src, offsets=offsets,
                hi=hi, lo=lo, values=values, ts_ms=ts_ms,
            )

        # -- pipelined ingest (runtime/ingest.py): epoch-tagged prefetch,
        # async device staging, off-thread route planning. Checkpoint-
        # COMPATIBLE: every prepped batch carries its post-poll offsets,
        # snapshots cut at the applied offsets, and a restore's epoch
        # bump discards in-flight batches (they replay from the rewound
        # source) — so the overlap runs in the production configuration
        # too, where it used to be hard-disabled. The reference overlaps
        # the same way structurally (netty IO threads fill input buffers
        # while the task thread processes, SURVEY §2.3); one thread is
        # enough because the prep half is vectorized numpy. "off" remains
        # the fully-serial escape hatch.
        prefetch_cfg = env.config.get_str("pipeline.prefetch", "auto")
        if prefetch_cfg not in ("auto", "on", "off"):
            raise ValueError(
                f"pipeline.prefetch must be auto|on|off, got {prefetch_cfg!r}"
            )
        use_prefetch = prefetch_cfg != "off"
        # the applied-offset cut only works when restore can REWIND the
        # source to it: a non-replayable source (snapshot_offsets None —
        # sockets, transient rings) cannot replay the batches a restore's
        # epoch bump discards, so running ahead of a possible snapshot
        # (checkpointing on, or a control channel that can request a
        # savepoint) would turn at-most-once into silently-more-lost.
        # auto falls back to inline prep there; an explicit "on" is a
        # config error, not a silent downgrade.
        can_snapshot = (
            storage is not None
            or getattr(env, "_control", None) is not None
        )
        if can_snapshot and pipe.source.snapshot_offsets() is None:
            if prefetch_cfg == "on":
                raise ValueError(
                    "pipeline.prefetch=on with checkpointing/savepoints "
                    "requires a replayable source (snapshot_offsets "
                    "returning a position): this source cannot rewind to "
                    "the applied-offset cut, so batches prefetched past a "
                    "snapshot would be lost on restore"
                )
            use_prefetch = False
        staging_cfg = env.config.get_str("pipeline.device-staging", "auto")
        if staging_cfg not in ("auto", "on", "off"):
            raise ValueError(
                f"pipeline.device-staging must be auto|on|off, "
                f"got {staging_cfg!r}"
            )
        if staging_cfg == "on" and not use_prefetch:
            raise ValueError(
                "pipeline.device-staging=on requires pipeline.prefetch: "
                "the staging transfer-completion wait runs on the ingest "
                "thread and would otherwise block the step loop"
            )
        use_staging = use_prefetch and staging_cfg != "off"
        # -- finalize the resident loop (validated where res_cfg was
        # read): the drain consumes ring-published STAGED batches, so
        # "on" without the prefetch+staging substrate is a config error,
        # and "auto" lights up exactly when the fused-fire resident
        # pipeline is active with staging available
        if res_cfg in ("on", "while"):
            if not use_staging:
                raise ValueError(
                    f"pipeline.resident-loop={res_cfg} requires pipeline."
                    "prefetch + pipeline.device-staging: the drain "
                    "consumes device-staged batches published into the "
                    "HBM ring by the ingest thread"
                )
            use_resident = True
            # while-drain platform gate: CPU buffer donation does not
            # alias, so the in-kernel cursor re-read can never observe a
            # mid-drain publish there — keep the scan drain unless the
            # declared test/bench escape hatch is on (where the while
            # kernel degrades, bit-exactly, to the scan's count gating)
            use_while = res_cfg == "while" and (
                jax.default_backend() != "cpu" or wd_cpu_override
            )
        else:
            # auto is PLATFORM-gated like precombine/packed-planes: the
            # drain retires a host dispatch per megastep on
            # accelerators, but on CPU dispatch costs microseconds and
            # the extra drain-kernel compiles would be pure warmup
            # overhead
            use_resident = (
                res_cfg == "auto" and use_fused_fire and use_staging
                and jax.default_backend() != "cpu"
            )
            if graph is not None and res_cfg == "auto":
                # a chained stage graph CANNOT run outside the resident
                # drain (stage edges live inside the drain scan), so
                # auto lights it up whenever the staging substrate
                # exists — on every backend, with or without dispatch
                # fusion; setup()'s check_runtime is the loud backstop
                # when staging is off or resident-loop was forced off
                use_resident = use_staging
        if use_resident:
            # the drain group IS the ring: accumulator capacity tracks
            # ring depth, and groups always hold fires (the drain fires
            # in-scan per slot). While mode accumulates up to the
            # while-drain bound instead — batches published while the
            # previous drain was in flight join the CURRENT dispatch
            # (beyond ring depth they ride unringed fresh staging), so
            # a publish landing mid-drain never forces its own dispatch
            fused = ingest_mod.FusedBatchAccumulator(
                wd_max_slots if use_while else ring_depth,
                hold_fires=True,
            )
        # -- finalize data parallelism (validated where dp_cfg was
        # read): the sharded drain is a shard_map'd variant of the
        # resident drain, so it needs the ring substrate AND a mesh
        # with more than one shard to be worth the extra compiles
        if dp_cfg == "on":
            if not use_resident:
                raise ValueError(
                    "pipeline.data-parallel=on requires the resident "
                    "loop (pipeline.resident-loop + prefetch + device "
                    "staging): the sharded drain consumes per-shard "
                    "ring slices published by the ingest thread"
                )
            use_dp = True
        else:
            use_dp = (
                dp_cfg == "auto" and use_resident and ctx.n_shards > 1
            )
        ingest = ingest_mod.IngestPipeline(
            prep_batch, prefetch=use_prefetch,
            initial_offsets=pipe.source.snapshot_offsets(),
            depth=env.config.get_int("pipeline.prefetch-depth", 2),
            ring_depth=env.config.get_int("pipeline.staging-ring-depth", 2),
            tracer=tracer,
        )
        # checkpoint-complete offset commits may ride the poll's wire
        # connection: serialize them with the producer's polls
        ck_io.source_lock = ingest.source_lock

        # -- self-tuning runtime controller (runtime/controller.py;
        # ISSUE 19, ROADMAP item 3): the closed loop over the doctor's
        # findings + the raw regime/heat planes, serviced at the poll-
        # cycle boundary below. Constructed ONLY when controller.enabled
        # is on — the shipping default (off) builds nothing here, reads
        # no sensor, registers no gauge: the off path stays byte-neutral
        # (no new dispatches, drain kernels untouched).
        runtime_ctl = [None]

        def _controller_sensor():
            """One host dict of the planes the controller decides on —
            all already-fetched telemetry (regime/heat EWMAs maintained
            by the lagged consume path), never a fresh device sync."""
            dt = drain_telem[0]
            duty = starved = None
            heat = None
            if dt is not None:
                duty, starved = dt.regime()
                h = getattr(dt, "_kg_heat", None)
                if h is not None and len(h) == ctx.max_parallelism:
                    heat = np.array(h, np.float64)
            starts_c, ends_c = ctx.kg_bounds()
            return {
                "records": int(metrics.records_in),
                "duty": duty, "starved": starved, "heat": heat,
                "kg_starts": [int(x) for x in starts_c],
                "kg_ends": [int(x) for x in ends_c],
            }

        def _controller_rebalance(starts, ends):
            """Apply a heat-balanced re-slice LIVE through the same
            savepoint-cut machinery as the elastic scale-up — exactly-
            once preserved (tiers re-slice inside setup(), the
            incremental chain re-bases). On ANY failure the pre-
            rebalance slicing re-latches so recovery re-plans the mesh
            the job actually ran on, not the half-applied target."""
            if td is None or state is None:
                raise RuntimeError(
                    "controller rebalance before the job has state")
            # chaos seam: a crash here lands mid-rebalance, BEFORE the
            # cut — restart must recover exactly-once from the last
            # completed checkpoint (tests/test_controller.py)
            faults.inject(
                "controller.apply",
                ends=[int(e) for e in ends],
                n_shards=ctx.n_shards,
            )
            prev = kg_slices_hold[0]
            kg_slices_hold[0] = tuple(
                (int(s), int(e)) for s, e in zip(starts, ends)
            )
            try:
                _rescale_live(
                    list(np.asarray(ctx.mesh.devices).flat),
                    "rebalance", "controller heat rebalance",
                )
            except BaseException:
                kg_slices_hold[0] = prev
                raise

        if env.config.get(_CoreOpts.CONTROLLER_ENABLED):
            _acts = {}
            if use_resident:
                # effective drain fill target: the accumulator's
                # capacity is a plain attribute the count-gated drain
                # serves at ANY fill level 1..ring_depth — a live write,
                # zero recompiles. Down = drain earlier (ring-starved
                # regime), up = amortize dispatch cost (saturated).
                def _rf_set(v):
                    fused.k = int(v)

                _acts["ring-fill-target"] = controller_mod.Actuator(
                    "ring-fill-target", lambda: int(fused.k), _rf_set,
                    lo=1, hi=ring_depth,
                )
            elif k_fuse > 1:
                # without the resident ring the same attribute is the
                # megastep grouping (pipeline.steps-per-dispatch):
                # shrinking it bounds recompile exposure per dispatch
                def _dg_set(v):
                    fused.k = int(v)

                _acts["dispatch-group"] = controller_mod.Actuator(
                    "dispatch-group", lambda: int(fused.k), _dg_set,
                    lo=1, hi=k_fuse,
                )
            if drain_stats_on:
                def _ds_set(v):
                    drain_stats_every[0] = max(1, int(v))

                _acts["drain-stats-cadence"] = controller_mod.Actuator(
                    "drain-stats-cadence",
                    lambda: int(drain_stats_every[0]), _ds_set,
                    lo=1, hi=64,
                )
            if tier_budget_cfg > 0:
                def _tp_get():
                    tm = tier_mgr[0]
                    if tm is not None:
                        return int(tm.prefetch_ahead_panes)
                    return int(env.config.get(
                        _CoreOpts.STATE_TIERS_PREFETCH_AHEAD_PANES))

                def _tp_set(v):
                    tm = tier_mgr[0]
                    if tm is not None:
                        tm.prefetch_ahead_panes = max(0, int(v))

                _acts["tier-prefetch-ahead"] = controller_mod.Actuator(
                    "tier-prefetch-ahead", _tp_get, _tp_set,
                    lo=0, hi=16, step="additive",
                )

            runtime_ctl[0] = controller_mod.RuntimeController(
                _acts, _controller_sensor,
                findings_fn=lambda: (
                    (doctor_report() or {}).get("findings") or []
                ),
                rebalancer=_controller_rebalance,
                interval_cycles=int(env.config.get(
                    _CoreOpts.CONTROLLER_INTERVAL_CYCLES)),
                revert_threshold=float(env.config.get(
                    _CoreOpts.CONTROLLER_REVERT_THRESHOLD)),
                probation_cycles=int(env.config.get(
                    _CoreOpts.CONTROLLER_PROBATION_CYCLES)),
                cooldown_cycles=int(env.config.get(
                    _CoreOpts.CONTROLLER_COOLDOWN_CYCLES)),
                rebalance_threshold=float(env.config.get(
                    _CoreOpts.CONTROLLER_REBALANCE_THRESHOLD)),
                min_rebalance_interval=float(env.config.get(
                    _CoreOpts.CONTROLLER_MIN_REBALANCE_INTERVAL)),
                min_gain=float(env.config.get(
                    _CoreOpts.CONTROLLER_MIN_GAIN)),
                # durable decisions (ISSUE 20 satellite): the ledger
                # rides the checkpoint dir so a restarted job serves
                # the merged tuning history at /jobs/<jid>/controller
                persist_dir=env.checkpoint_dir or None,
            )
            if self._job_group is not None:
                grp_c = self._job_group

                def _ctl_ctr(field):
                    ctl = runtime_ctl[0]
                    return int(getattr(ctl, field)) if ctl else 0

                grp_c.gauge("controller_actions",
                            partial(_ctl_ctr, "actions"))
                grp_c.gauge("controller_reverts",
                            partial(_ctl_ctr, "reverts"))
                grp_c.gauge("controller_rebalances",
                            partial(_ctl_ctr, "rebalances"))

        def controller_report() -> dict:
            """/jobs/<jid>/controller body: the decision ledger +
            actuator/counter view (or the off stub)."""
            ctl = runtime_ctl[0]
            if ctl is None:
                return {
                    "available": False,
                    "reason": "controller.enabled off",
                }
            return ctl.report()

        env._controller_report = controller_report

        def _apply_planned(pb):
            """Apply one PLANNED single-group batch: the ingest side
            already chose the route and (with staging on) moved the
            padded arrays to the device, so this path is watermark
            arithmetic + one dispatch — no hashing, no padding, no
            per-batch allocation on the step-loop thread.

            With dispatch fusion on (pipeline.steps-per-dispatch=K > 1)
            the batch lands in the fused slot instead; the slot flushes
            as ONE megastep when full, and EARLY on a route/staging
            change or a fire boundary (fires must see every pending
            update, and a group never spans a pane crossing — fire
            timing matches the sequential path). Returns True when the
            batch is still pending in the slot: the caller must NOT mark
            its offsets applied — the flush does, at the megastep
            boundary (the exactly-once cut)."""
            nonlocal applied_max_pane, host_fired_pane
            wm_ms = (
                wm_strategy.on_batch(pb.ts_max) if event_time
                else pb.now_ms - 1
            )
            slide = int(win.slide_ticks)
            # BETWEEN-polls time jump guard (see _apply_general): the
            # planned batch is single-group by construction, but may
            # still sit past everything the ring has absorbed
            g_max_pane = pb.ticks_max // slide
            if (
                applied_max_pane is not None
                and g_max_pane - applied_max_pane >= 2
            ):
                g_min_pane = pb.ticks_min // slide
                fire_wm = min(wm_ms, int(td.to_ms(g_min_pane * slide)) - 1)
                flush_fused()   # pending updates may feed the panes fired
                drain_fires(fire_wm, time.perf_counter())
            applied_max_pane = (
                g_max_pane if applied_max_pane is None
                else max(applied_max_pane, g_max_pane)
            )
            wp = wm_pane_of(wm_ms)
            fire_now = eager_fire or wp > host_fired_pane
            deferred = False
            # resident pipeline: a crossing no longer breaks the group —
            # the fused-fire megastep fires it INSIDE the scan, and
            # flush_fused owns the crossing bookkeeping for this batch
            in_slot = (
                (k_fuse > 1 and pb.route in megasteps_by_route)
                # resident loop: the drain group accumulates regardless
                # of steps-per-dispatch — the count-gated drain
                # dispatches ANY fill level as one scan
                or (use_resident and pb.route in residents_by_route)
            )
            in_scan = fused.hold_fires and in_slot
            if in_slot:
                if pb.staged is not None:
                    args, staged_mode = pb.staged, True
                else:
                    args, staged_mode = _stage_planned(
                        _pad_planned(pb), pb.route
                    )
                if not fused.compatible(pb.route, staged_mode):
                    flush_fused()
                fused.push(args, wm_ms, pb, pb.route, staged_mode)
                if fused.full() or (fire_now and not in_scan):
                    flush_fused()
                else:
                    deferred = True
            elif pb.staged is not None:
                run_update(None, None, None, None, None, wm_ms,
                           staged=pb.staged, route=pb.route, batch=pb.seq)
            else:
                run_update(*_pad_planned(pb), wm_ms, route=pb.route,
                           batch=pb.seq)
            if fire_now and not in_scan:
                drain_fires(wm_ms, time.perf_counter())
                host_fired_pane = wp
            return deferred

        def poll_cycle():
            nonlocal td, host_fired_pane, applied_max_pane
            self._poll_control()
            # scale-back-up (runtime/elastic.py): a latched operator
            # request is serviced at the cycle boundary — a savepoint-
            # cut live rescale back to full capacity. The latch is
            # consumed only when the rescale can actually run (job has
            # state AND is degraded): a request filed early — or before
            # a loss even lands — stays pending until it applies.
            if td is not None and elastic_ctl.degraded and \
                    elastic_ctl.take_scale_up_request():
                try:
                    _rescale_live(
                        list(elastic_ctl.full_devices), "scale_up",
                        "operator scale-up request",
                    )
                except BaseException:
                    # the latch was consumed but the rescale never
                    # completed: re-latch so the request survives the
                    # recovery restart instead of being silently lost
                    # (ISSUE 19 bugfix)
                    elastic_ctl.request_scale_up()
                    raise
            # tiered state maintenance rides the same cycle-boundary
            # seam: residency swaps happen between dispatches, at a cut
            if tier_mgr[0] is not None and td is not None:
                _tier_maintenance()
            # self-tuning controller (ISSUE 19): same seam — at most one
            # knob move or rebalance per interval, between dispatches,
            # at a cut. None (the default) costs one list-index check.
            if runtime_ctl[0] is not None and td is not None \
                    and state is not None:
                runtime_ctl[0].service()
            # sampling decision for this cycle; a sampled one may anchor
            # the spans to the profiler's clock
            if tracer is not None and tracer.begin_cycle():
                tracer.clock_anchor()
            t_c0 = time.perf_counter()
            phase_acc["dispatch"] = phase_acc["emit"] = 0.0
            if pending_batch[0] is not None:
                # leftover from the resident greedy ring fill: a batch
                # the drain group could not absorb (idle, end, or
                # unplanned) — it gets this cycle's FULL handling, in
                # the order it was polled
                pb, pending_batch[0] = pending_batch[0], None
            elif wd is None:
                pb = ingest.next()
            else:
                # watchdog "source" phase (off by default): the wait for
                # the prep side — covers a dead prefetch thread or a
                # must-produce source going silent
                wd_prev = wd.arm("source")
                try:
                    pb = ingest.next()
                finally:
                    wd.disarm(wd_prev)
            # attribution: with prefetch on, "source" time is only the
            # wait for the prep thread (~0 while it keeps ahead)
            t_src = time.perf_counter()
            if tracer is not None and tracer.active:
                # source drain + host chain/encode (prefetch folds the
                # encode into the wait; both are upstream of the device)
                tracer.rec("source", t_c0, t_src, records=pb.n)
            end, n, now_ms = pb.end, pb.n, pb.now_ms

            metrics.records_in += n
            deferred = False
            if n:
                last_ingest_t[0] = pb.t_src
                if td is None:
                    # auto-layout hint: bounded non-negative int keys (the
                    # identity fits hi==0, lo < capacity on the first
                    # batch) are eligible for the direct-index backend —
                    # key == slot, no probes, no inserts. setup() combines
                    # this with spillability (out-of-bound keys must have
                    # a spill tier to degrade to, not be dropped). The
                    # first batch is always unplanned (the plan is born in
                    # setup), so its host arrays are present.
                    auto_direct_hint[0] = (
                        int(pb.hi.max(initial=0)) == 0
                        and int(pb.lo.max(initial=0))
                        < env.state_capacity_per_shard
                    )
                    setup((int(np.min(pb.ts_ms)) // size_ms) * size_ms)
                if pb.route is not None:
                    deferred = _apply_planned(pb)
                    # resident loop: greedily absorb every batch the
                    # prefetch queue ALREADY holds into the drain group,
                    # so one cycle consumes ring slots up to the write
                    # cursor instead of one batch per cycle. Each pull
                    # rides _apply_planned (time-jump guard, route
                    # compatibility, flush-on-full all apply); the loop
                    # stops at ring empty (try_next None), a flushed
                    # group (the cycle dispatched its drain), or a
                    # batch the group cannot hold (handled next cycle
                    # via pending_batch, order preserved).
                    while use_resident and deferred:
                        nxt = ingest.try_next()
                        if nxt is None:
                            break
                        if nxt.n and nxt.route is not None \
                                and not nxt.end:
                            metrics.records_in += nxt.n
                            last_ingest_t[0] = nxt.t_src
                            if not _apply_planned(nxt):
                                ingest.mark_applied(nxt)
                                break
                        else:
                            pending_batch[0] = nxt
                            break
                else:
                    _apply_general(pb)
            elif td is not None:
                # idle poll: the source went quiet — apply any pending
                # fused group now (latency guard, and this empty poll's
                # offsets sit PAST the pending batches' polls, so marking
                # them applied below is only correct once they dispatch),
                # and surface any lagged resident-pipeline fires
                flush_fused()
                consume_fires(force=True)
                # idle poll: advance processing-time watermark
                if not event_time:
                    wp = wm_pane_of(now_ms - 1)
                    if wp > host_fired_pane:
                        drain_fires(now_ms - 1, time.perf_counter())
                        host_fired_pane = wp
            if end:
                flush_fused()   # the stream is over: nothing may pend
                consume_fires(force=True)
                deferred = False
            # this batch is now part of the device state: its offsets
            # name the cut the next checkpoint/savepoint snapshots. A
            # batch deferred into the fused slot is NOT part of it yet —
            # its flush marks the cut instead (megastep boundary).
            if not deferred:
                ingest.mark_applied(pb)
            if not kv_mailbox.empty():
                drain_kv_mailbox()
            ck_io.drain()
            if (
                storage is not None
                and env.checkpoint_interval_steps > 0
                and metrics.steps - steps_at_ckpt >= env.checkpoint_interval_steps
                and td is not None
            ):
                # checkpoint.min-pause gate: a due trigger defers until
                # the pause since the last attempt elapses; ONE decline
                # is counted per deferred trigger, not per polled cycle
                if ck_policy.can_trigger():
                    ck_declined[0] = False
                    # write_checkpoint owns the megastep-boundary cut:
                    # its first act flushes any pending fused group
                    write_checkpoint()
                elif not ck_declined[0]:
                    ck_declined[0] = True
                    metrics.checkpoints_declined += 1
            if self._attribution is not None:
                t_end = time.perf_counter()
                src_s = t_src - t_c0
                disp_s = phase_acc["dispatch"]
                emit_s = phase_acc["emit"]
                host_s = max(0.0, (t_end - t_c0) - src_s - disp_s - emit_s)
                self._attribution.record(
                    idle=(n == 0), source=src_s * 1e3, host=host_s * 1e3,
                    dispatch=disp_s * 1e3, emit=emit_s * 1e3,
                )
            return end

        def _apply_general(pb):
            """The general apply path: unplanned batches (before setup, or
            re-planned after restore), catch-up replay spans that must be
            time-sliced, and host-chain polls expanded beyond B lanes."""
            nonlocal host_fired_pane, applied_max_pane
            # dispatch order must match poll order: anything the fused
            # slot still holds precedes this batch
            flush_fused()
            hi, lo, values, ts_ms = pb.hi, pb.lo, pb.values, pb.ts_ms
            n, now_ms = pb.n, pb.now_ms
            ticks = td.to_ticks(ts_ms)
            if event_time:
                wm_ms = wm_strategy.on_batch(int(np.max(ts_ms)))
            else:
                wm_ms = now_ms - 1
            values = np.asarray(values)
            # A batch spanning more panes than the ring holds (replay /
            # catch-up) must be time-sliced, or fresh panes would evict
            # unfired ones. The span bound leaves size/slide panes of
            # headroom (not just 2): every pane the rotation can evict
            # must have ALL of its windows end below the group's min
            # pane, so the safe pre-fire between groups (below) can
            # close them without touching windows the group feeds.
            panes = ticks // np.int32(win.slide_ticks)
            span_limit = win.ring - max(
                2, int(win.size_ticks // win.slide_ticks) + 1
            )
            if span_limit < 1:
                # setup() validates configured rings; this guard keeps a
                # degenerate span from ever entering the grouping loop
                # below, whose cutoff would never advance (an infinite
                # empty-group hang instead of an error)
                raise RuntimeError(
                    f"window ring {win.ring} leaves catch-up span "
                    f"{span_limit} < 1 for a "
                    f"{int(win.size_ticks // win.slide_ticks)}-pane "
                    f"window; raise window.ring-panes"
                )
            if int(panes.max()) - int(panes.min()) >= span_limit:
                order = np.argsort(panes, kind="stable")
                sorted_panes = panes[order]
                groups = []
                lo_i = 0
                while lo_i < n:
                    cutoff = sorted_panes[lo_i] + span_limit
                    hi_i = int(np.searchsorted(sorted_panes, cutoff, "left"))
                    groups.append(order[lo_i:hi_i])
                    lo_i = hi_i
            else:
                groups = None   # single group, no reindex copy
            catch_up = groups is not None
            wp = wm_pane_of(wm_ms)
            ooo_ms = wm_strategy.out_of_orderness_ms
            for sel in (groups if catch_up else (None,)):
                if sel is None:
                    g_hi, g_lo, g_ticks, g_vals, m = hi, lo, ticks, values, n
                    g_wm = wm_ms
                else:
                    g_hi, g_lo, g_ticks, g_vals, m = (
                        hi[sel], lo[sel], ticks[sel], values[sel], len(sel)
                    )
                    # group-local watermark: a replay burst's watermark
                    # trails the group being applied, or later groups'
                    # records would be late against their own poll's
                    # final watermark (the reference applies the whole
                    # burst before the periodic watermark advances)
                    g_wm = min(
                        td.to_ms(int(g_ticks.max())) - ooo_ms - 1, wm_ms
                    )
                # BETWEEN-polls time jump: if this group's panes sit
                # ahead of everything the ring has absorbed, applying
                # them could rotate the ring past still-unfired panes
                # — fire those panes' windows FIRST. (The catch-up
                # slicing above only bounds the span WITHIN one poll;
                # a quiet source resuming after an event-time gap —
                # or a processing-time job resuming after a
                # compile/GC pause — jumps between polls instead.)
                # The pre-fire watermark is capped at the group's min
                # pane boundary: a window ending there or earlier
                # receives NOTHING from this group, so firing it
                # before the update cannot split a window's records
                # across two emissions; capping at g_wm keeps the
                # watermark contract (nothing past the out-of-
                # orderness horizon closes early). Every pane the
                # rotation can evict ends all its windows below BOTH
                # caps — by the span bound above and the ring's
                # ooo-panes headroom (setup()) — so eviction only
                # ever discards already-fired state. Threshold 2:
                # steady-state polls advance at most one pane, so the
                # hot path never pays an extra drain.
                g_max_pane = int(g_ticks.max()) // int(win.slide_ticks)
                if (
                    applied_max_pane is not None
                    and g_max_pane - applied_max_pane >= 2
                ):
                    g_min_pane = (
                        int(g_ticks.min()) // int(win.slide_ticks)
                    )
                    fire_wm = min(
                        g_wm,
                        td.to_ms(g_min_pane * int(win.slide_ticks)) - 1,
                    )
                    drain_fires(fire_wm, time.perf_counter())
                applied_max_pane = (
                    g_max_pane if applied_max_pane is None
                    else max(applied_max_pane, g_max_pane)
                )
                # a host chain (flat_map) can expand one poll beyond B
                # lanes; feed the step in B-sized chunks padded to the
                # step lane count (B_step > B only when the exchange
                # splits lanes over shards). The watermark rides only
                # the LAST chunk so every record of the poll is
                # late-checked against the pre-poll watermark.
                Bs = B_step[0]
                for off in range(0, m, B):
                    hi_off = min(off + B, m)
                    chunk = (
                        _pad(g_hi[off:hi_off], Bs, np.uint32),
                        _pad(g_lo[off:hi_off], Bs, np.uint32),
                        _pad(g_ticks[off:hi_off], Bs, np.int32),
                        _pad(g_vals[off:hi_off], Bs, g_vals.dtype),
                        # reused prefix-mask template: a frozen view,
                        # not a per-chunk np.ones+pad allocation
                        ingest_mod.prefix_mask(
                            valid_tmpl[0], hi_off - off
                        ),
                    )
                    wm_chunk = g_wm if hi_off == m else None
                    if graph is not None:
                        # no single-step kernel exists for a stage
                        # chain: catch-up chunks ride the chained drain
                        # as 1-slot dispatches on the replicate-and-
                        # mask route (unrouted host arrays)
                        c_args, _ = _stage_planned(chunk, "mask")
                        run_update_resident(
                            "mask", [(c_args, wm_chunk, None)]
                        )
                    else:
                        run_update(*chunk, wm_chunk, batch=pb.seq)
                # catch-up slices must fire between groups or newer
                # panes would evict older unfired ones from the ring
                if catch_up:
                    drain_fires(g_wm, time.perf_counter())
            if eager_fire or wp > host_fired_pane:
                drain_fires(wm_ms, time.perf_counter())
                host_fired_pane = wp

        # -- run with restore + restart (ref ExecutionGraph.restart + ------
        # -- CheckpointCoordinator.restoreLatestCheckpointedState) ---------
        # go live BEFORE restore: once td/state exist, a direct kv_read off
        # the executor thread would race the first donated step
        job_live.set()
        if wd is not None:
            wd.start()

        @contextlib.contextmanager
        def _restore_guard():
            """Watchdog bracket for a whole restore: the dedicated
            ``restore`` deadline (watchdog.restore-timeout) arms and the
            steady-state phase deadlines are suspended, so a
            legitimately long cold restore cannot trip a false
            WatchdogError mid-recovery."""
            if wd is None:
                yield
                return
            prev = wd.arm("restore")
            wd.suspend()
            try:
                yield
            finally:
                wd.unsuspend()
                wd.disarm(prev)

        def _elastic_replan(loss):
            """Degraded-mode recovery for a classified device loss:
            re-slice key-group ranges over the M surviving shards,
            rebuild the mesh + compiled step family, and perform a
            RESCALED restore of the last durable cut (the logical
            snapshot format re-buckets entries by key group, so the
            restore is parallelism-agnostic by construction). A loss
            without an attributable casualty (marker-matched runtime
            error, healthy probe) falls back to a same-parallelism full
            restore; survivors below recovery.min-shards FAIL the job
            (ElasticCapacityError — retrying cannot grow the mesh)."""
            t_replan0 = time.perf_counter()
            with rec_tracker.phase("reslice"):
                cur = list(np.asarray(ctx.mesh.devices).flat)
                survivors, newly = elastic.plan_survivors(cur, loss)
                if not newly:
                    survivors = None   # unattributable: same-mesh restore
                elif len(survivors) < elastic_min_shards:
                    raise elastic.ElasticCapacityError(
                        f"device loss leaves {len(survivors)} surviving "
                        f"shard(s), below recovery.min-shards="
                        f"{elastic_min_shards}; failing the job instead "
                        f"of degrading further"
                    ) from loss
                else:
                    n_before = ctx.n_shards
                    _replan_mesh(survivors)
            if survivors is None:
                with _restore_guard():
                    restore_checkpoint(storage, warm=False)
                return
            t0 = time.perf_counter()
            try:
                with _restore_guard():
                    restore_checkpoint(storage, warm=False)
            finally:
                rec_tracker.mark_phase("rescale_restore", t0)
            # restore_checkpoint stamped mode "full"; the re-plan is the
            # headline — restate it with the shard transition. The
            # controller records first so the tracker's degraded gauge
            # derives from it (one source of truth for the count).
            rec_tracker.set_mode(
                f"rescale-{ctx.n_shards}of{elastic_ctl.full_shards}"
            )
            elastic_ctl.record(
                "degrade", n_before, ctx.n_shards,
                cause=f"{type(loss).__name__}: {loss}", lost=newly,
                mttr_ms=(time.perf_counter() - t_replan0) * 1e3,
            )
            rec_tracker.note_rescale(
                n_before, ctx.n_shards, elastic_ctl.degraded_shards
            )

        def _recover(first_exc):
            """One failure -> a restored, runnable job, or raise.
            Classifies the failure (transient host-side -> warm
            in-process restart; device loss -> elastic re-plan over the
            survivors; anything else -> full restore), and keeps a
            failure DURING restore inside the restart budget: a double
            fault consumes another should_restart() slot and retries
            with the warm path disabled (the half-restored state is no
            longer trusted), instead of escaping as an unhandled error
            or wedging the job."""
            exc = first_exc
            warm = classify_failure(first_exc) == "transient"
            while True:
                loss = (
                    elastic.as_device_loss(
                        exc, devices=list(np.asarray(ctx.mesh.devices).flat)
                    )
                    if elastic_enabled else None
                )
                rec_tracker.begin(
                    cause=f"{type(exc).__name__}: {exc}",
                    classification=(
                        "device-loss" if loss is not None
                        else "transient" if warm else "state-corrupting"
                    ),
                )
                with rec_tracker.phase("settle"):
                    if materializer is not None:
                        # let pending async cuts become durable before
                        # deciding whether a restartable checkpoint
                        # exists
                        ck_io.settle()
                can = (
                    storage is not None
                    and storage.latest() is not None
                )
                if can:
                    with rec_tracker.phase("backoff"):
                        can = restart.should_restart()
                if not can:
                    raise exc
                metrics.restarts += 1
                self._notify_restart()
                try:
                    if loss is not None:
                        _elastic_replan(loss)
                    else:
                        with _restore_guard():
                            restore_checkpoint(storage, warm=warm)
                    rec_tracker.end()
                    return
                except JobCancelledException:
                    raise
                except elastic.ElasticCapacityError:
                    # deliberately NOT retried: the surviving device
                    # set cannot grow by restoring again
                    raise
                except Exception as e2:
                    exc, warm = e2, False

        try:
            if restore_from:
                rec_tracker.begin(cause="explicit restore_from",
                                  classification="initial")
                with _restore_guard():
                    restore_checkpoint(restore_from)
                rec_tracker.end()
            restart = self._restart_strategy()
            while True:
                try:
                    batch_loop()
                    # end of stream: MAX watermark flush (ref Watermark.
                    # MAX_WATERMARK). INSIDE the restart protection: a
                    # sink failing during the final flush must recover
                    # like any mid-stream failure — restore rewinds state,
                    # source offsets, and sink state to the checkpoint
                    # cut, so the re-run re-emits without duplication.
                    if td is not None:
                        drain_fires(int(td.to_ms(2**31 - 4)),
                                    time.perf_counter())
                    if materializer is not None:
                        # an async write still failing here IS a
                        # checkpoint failure: abort-and-count like any
                        # other; only budget exhaustion raises (inside
                        # the restart protection, so recovery treats it
                        # as one) — a transient final-write failure must
                        # not fail a job whose stream already completed
                        try:
                            ck_io.flush()
                        except MaterializerError as e:
                            _abort_checkpoint(
                                next_cid, e, time.perf_counter(),
                                time.time() * 1000,
                            )
                    break
                except JobCancelledException:
                    raise
                except Exception as e:
                    _recover(e)
        finally:
            if wd is not None:
                wd.stop()
            job_live.clear()
            ingest.close()
            drain_kv_mailbox()
            ck_io.close()

        if state is not None:
            # chained jobs fold every stage's counters in: an undersized
            # inter-stage exchange (pipeline.stages.exchange-lanes)
            # lands its drops in the DOWNSTREAM stage's
            # dropped_capacity, so strict capacity surfaces it loudly
            all_states = [state] + list(chain_states)
            metrics.dropped_late = sum(
                int(np.asarray(s.dropped_late).sum()) for s in all_states
            )
            metrics.dropped_capacity = sum(
                int(np.asarray(s.dropped_capacity).sum())
                for s in all_states
            )
            if metrics.dropped_capacity and self.env.config.get_bool(
                "state.backend.strict-capacity", True
            ):
                raise RuntimeError(
                    f"state backend over capacity: {metrics.dropped_capacity} "
                    f"records lost (raise state.backend.device.slots-per-shard "
                    f"or the pane ring — for chained stage graphs also "
                    f"pipeline.stages.exchange-lanes — or set "
                    f"state.backend.strict-capacity to false to tolerate "
                    f"drops)"
                )
        return JobHandle(job_name, metrics, state=state, ctx=ctx)

    # ------------------------------------------------------------------
    def _prep_keyed_batch(self, pipe: _Pipeline, polled, extractor):
        """Shared poll -> (key_list, values) prep for keyed stages without
        event-time handling (rolling / count windows)."""
        if pipe.source.columnar and isinstance(polled, tuple):
            cols, _ts = polled
            if not cols:
                return None
            for t in pipe.pre_chain:
                if t.kind != "map":
                    raise NotImplementedError(
                        "columnar sources support only 'map' before key_by"
                    )
                cols = t.fn(cols)
            return np.asarray(pipe.key_by.key_selector(cols)), np.asarray(
                extractor(cols)
            )
        elements = _apply_chain(pipe.pre_chain, self._to_elements(polled))
        if not elements:
            return None
        key_list = [pipe.key_by.key_selector(e) for e in elements]
        values = np.asarray([extractor(e) for e in elements], np.float32)
        return key_list, values

    def _run_generic_window(self, pipe: _Pipeline, metrics: JobMetrics,
                            job_name, restore_from=None):
        """Windows with custom triggers/evictors/apply functions or
        GlobalWindows: wrap into the GenericWindowOperator (full
        WindowOperator.java semantics) and drive it as a process stage."""
        from flink_tpu.datastream.window import triggers as tg
        from flink_tpu.datastream.window.assigners import (
            CountWindowAssigner, GlobalWindows,
        )
        from flink_tpu.runtime.window_operator import GenericWindowOperator
        from flink_tpu.state.descriptors import ReducingStateDescriptor

        wagg = pipe.window_agg
        assigner, trigger = wagg.assigner, wagg.trigger
        if isinstance(assigner, CountWindowAssigner):
            # countWindow(N) IS GlobalWindows + PurgingTrigger(CountTrigger)
            # (ref KeyedStream.countWindow); the device count path handles
            # the plain case, this lowering covers custom trigger/evictor/
            # apply combinations
            if trigger is None:
                trigger = tg.PurgingTrigger(tg.CountTrigger(assigner.size_n))
            assigner = GlobalWindows.create()
        reduce_desc = None
        if wagg.reduce_spec_factory is not None:
            spec = wagg.reduce_spec_factory()
            if spec.kind == "sketch":
                # host mirror of the device sketch registers: the element
                # folds in via host_add, sessions merge via host_merge, and
                # the fire emits host_result (estimates)
                from flink_tpu.state.descriptors import (
                    AggregatingStateDescriptor,
                )
                sk_obj = spec.sketch
                reduce_desc = AggregatingStateDescriptor(
                    "window-contents",
                    add=sk_obj.host_add, merge=sk_obj.host_merge,
                    get_result=sk_obj.host_result,
                    acc_init=sk_obj.host_init,
                )
            else:
                reduce_desc = ReducingStateDescriptor(
                    "window-contents", kind=spec.kind,
                    reduce_fn=spec.combine, neutral=spec.neutral,
                )
        op = GenericWindowOperator(
            assigner=assigner,
            trigger=trigger,
            evictor=wagg.evictor,
            extractor=wagg.extractor,
            reduce_desc=reduce_desc,
            window_fn=wagg.window_fn,
            allowed_lateness_ms=wagg.allowed_lateness_ms,
            result_fn=wagg.result_fn,
        )
        proc_pipe = dataclasses.replace(
            pipe, window_agg=None,
            process=sg.ProcessTransformation("generic-window", None, fn=op),
        )
        handle = self._run_process(proc_pipe, metrics, job_name, restore_from)
        metrics.dropped_late += op.dropped_late
        metrics.fires += op.fires
        return handle

    def _cep_device_eligible(self, pipe: _Pipeline, restore_from) -> bool:
        """Route CEP.pattern() to the TPU-resident count-NFA kernel when
        the pattern fits its representation (VERDICT r2 item 3; ref
        NFA.java:132 in production position, BASELINE config #5).

        Host-NFA fallback (the generality path) only when
        cep.device.enabled=false (the explicit escape hatch, e.g. for
        millisecond-exact within() boundaries) or an event-time job has
        no timestamp assigner. within() runs on device since round 4
        (pane-bucketed partial expiry, cep/device.py; semantics equal
        the host NFA on pane-quantized timestamps); EVENT TIME runs on
        device since round 5 (a host reorder buffer releases the
        watermark-ripe prefix in timestamp order into the device NFA —
        the buffer-and-sort the reference does per key, done once
        globally); parallelism>1 shards the count-NFA state over the
        mesh by key group (DeviceCepOperator n_shards). Checkpoint/
        savepoint/restore
        and queryable state are supported on the device path (parity
        with _run_process); a checkpoint written by one path cannot be
        restored by the other (validated, clear error). The engine that
        actually ran is surfaced in JobMetrics.cep_engine and the job
        detail JSON ("cep-engine")."""
        from flink_tpu.cep.operator import CEPProcessFunction

        fn = pipe.process.fn
        ok = (
            isinstance(fn, CEPProcessFunction)
            and self.env.config.get_bool("cep.device.enabled", True)
            # event-time (round 5): supported via the host reorder buffer
            # in front of the device kernel — needs element timestamps
            and (not fn.event_time or pipe.ts_transform is not None)
        )
        if ok and restore_from:
            # route by what the checkpoint actually contains: a host-path
            # checkpoint of a (now) device-eligible job must restore on
            # the host path, not die with a payload-kind error
            try:
                st = ckpt.CheckpointStorage(restore_from)
                cid = st.latest()
                if cid is not None:
                    return bool(st.read_generic(cid).get("cep_device"))
            except (OSError, ValueError):
                pass
        return ok

    def _run_cep_device(self, pipe: _Pipeline, metrics: JobMetrics,
                        job_name, restore_from=None):
        """Device CEP: per micro-batch, vectorized stage masks + the
        segmented-matrix-scan count NFA on device decide WHICH keys
        completed matches; the host replays only those keys' compacted
        events for extraction (cep/accel.py)."""
        from flink_tpu.cep.accel import DeviceCepOperator

        env = self.env
        fn = pipe.process.fn
        metrics.cep_engine = "device"
        n_shards = max(1, min(env.parallelism, len(jax.devices())))
        op = DeviceCepOperator(
            fn.pattern,
            capacity=env.state_capacity_per_shard or (1 << 16),
            within_buckets=env.config.get_int(
                "cep.device.within-buckets", 8
            ),
            # parallelism > 1: key-group shards over the mesh
            # (replicate-and-mask; VERDICT r3 item 6 multi-shard)
            n_shards=n_shards,
            max_parallelism=env.max_parallelism,
        )
        key_selector = pipe.key_by.key_selector
        select_fn = fn.select_fn
        flat = fn.flat

        # -- event-time mode (round 5): the reference buffers per key and
        # drains in timestamp order at watermark advance
        # (AbstractKeyedCEPPatternOperator's PriorityQueue). Here ONE
        # host-side reorder buffer fronts the device kernel: arrivals
        # heap-push as (ts, seq); each watermark advance releases the
        # ripe prefix GLOBALLY sorted (which preserves every key's
        # timestamp order) and feeds it to the device NFA in pane-sized
        # groups, so within() pane bucketing sees event time. Detection
        # stays on device; the host only sorts.
        import heapq

        event_time = fn.event_time
        ts_fn = (pipe.ts_transform.timestamp_fn
                 if pipe.ts_transform is not None else None)
        wm_strategy = (
            pipe.ts_transform.strategy if pipe.ts_transform is not None
            else WatermarkStrategy.for_monotonous_timestamps()
        )
        et_heap: list = []     # (ts, seq, key, element)
        et_seq = 0
        pane_ms = getattr(op.spec, "pane_ms", 0) or 0

        def _release(bound):
            out = []
            while et_heap and et_heap[0][0] <= bound:
                out.append(heapq.heappop(et_heap))
            return out

        def _feed_released(rel):
            """Feed timestamp-ordered released events to the device op,
            grouped by within() pane (without within, one group), in
            FIXED batch_size-padded chunks. A variable pad
            (ceil(n/bs)*bs) would give every release size its own XLA
            shape — profiled at 13 distinct compiles eating 75% of the
            event-time CEP run; one fixed shape compiles once."""
            matches = []
            bs = max(1, env.batch_size)
            i = 0
            while i < len(rel):
                if pane_ms:
                    p0 = rel[i][0] // pane_ms
                    j = i + 1
                    while j < len(rel) and rel[j][0] // pane_ms == p0:
                        j += 1
                else:
                    j = len(rel)
                for off in range(i, j, bs):
                    hi_off = min(off + bs, j)
                    els = [r[3] for r in rel[off:hi_off]]
                    ks = [r[2] for r in rel[off:hi_off]]
                    matches += op.process_batch(
                        els, ks, int(rel[off][0]), pad_to=bs,
                    )
                    metrics.steps += 1
                i = j
            return matches

        reg = getattr(env, "_kv_registry", None)
        if reg is not None:
            # host-path parity: the per-key live partial matches are
            # queryable under the same name _run_process registers
            reg.register_resolver(
                lambda: ["cep-nfa-state"],
                lambda name, key: op.peek_state(key),
            )

        storage = None
        if env.checkpoint_dir:
            # task-local snapshot cache (checkpointing/local.py): publish
            # mirrors in, restore prefers the verified local copy
            storage = ckpt.CheckpointStorage(
                env.checkpoint_dir,
                retain=env.config.get_int("checkpoint.retain", 2),
                local=local_cache_from_config(
                    env.config, env.checkpoint_dir
                ),
            )
        next_cid = (storage.latest() or 0) + 1 if storage else 1
        steps_at_ckpt = 0
        ck_policy = policy_from_config(env.config) if storage is not None \
            else None
        metrics.failure_budget = ck_policy
        ck_io = _GenericCheckpointIO(env, storage, pipe, policy=ck_policy)

        def _payload():
            return {
                "cep_device": True,
                "event_time": event_time,
                "op": op.snapshot(),
                "offsets": pipe.source.snapshot_offsets(),
                "sink_states": [s.snapshot_state() for s in pipe.all_sinks],
                # event-time reorder buffer: ripe-but-unreleased events
                # are part of the cut (the host path snapshots its
                # per-key PriorityQueue the same way)
                "et_heap": list(et_heap),
                "et_seq": et_seq,
                "wm_current": wm_strategy.current(),
            }

        def write_checkpoint():
            nonlocal next_cid, steps_at_ckpt
            _guarded_generic_write(
                ck_io, ck_policy, storage, metrics, next_cid, _payload
            )
            next_cid += 1
            steps_at_ckpt = metrics.steps

        def restore_checkpoint(path_or_storage, cid=None):
            nonlocal steps_at_ckpt, et_heap, et_seq
            ck_io.recover()           # durable cuts still notify
            st = (
                ckpt.CheckpointStorage(path_or_storage)
                if isinstance(path_or_storage, str) else path_or_storage
            )
            cid = cid if cid is not None else st.latest()
            if cid is None:
                raise FileNotFoundError(f"no checkpoint in {st.dir}")
            payload = st.read_generic(cid)
            if not payload.get("cep_device"):
                raise ValueError(
                    "checkpoint was written by the host CEP path; restore "
                    "it with the same configuration (event-time/within/"
                    "parallelism) it was created under"
                )
            if bool(payload.get("event_time")) != event_time:
                raise ValueError(
                    "checkpoint time mode (event-time vs processing-"
                    "time) does not match the job configuration"
                )
            op.restore(payload["op"])
            pipe.source.restore_offsets(payload["offsets"])
            sink_states = payload.get("sink_states")
            if sink_states:
                for s, ss in zip(pipe.all_sinks, sink_states):
                    s.restore_state(ss)
            et_heap = [tuple(x) for x in payload.get("et_heap", [])]
            heapq.heapify(et_heap)
            et_seq = int(payload.get("et_seq", 0))
            wm_strategy._current = payload.get(
                "wm_current", wm_strategy.current()
            )
            steps_at_ckpt = metrics.steps

        def write_savepoint(path: str) -> str:
            sp = ckpt.CheckpointStorage(path, retain=10**9)
            cid = (sp.latest() or 0) + 1
            return sp.write_generic(cid, _payload())

        self._savepoint_writer = write_savepoint

        def batch_loop():
            nonlocal et_seq
            end = False
            n_batches = 0
            while not end:
                self._poll_control()
                n_batches += 1
                polled, end = pipe.source.poll(env.batch_size)
                elements = _apply_chain(pipe.pre_chain,
                                        self._to_elements(polled))
                if not elements:
                    if end and event_time and et_heap:
                        # end of stream: everything still buffered is
                        # ripe (the MAX-watermark drain)
                        matches = _feed_released(_release(2**62))
                        if matches:
                            out = (
                                [r for m in matches for r in
                                 select_fn(m)] if flat
                                else [select_fn(m) for m in matches]
                            )
                            _emit_batch(pipe, out, metrics)
                    continue
                metrics.records_in += len(elements)
                keys = [key_selector(e) for e in elements]
                if event_time:
                    ts_list = [int(ts_fn(e)) for e in elements]
                    for e, k, t in zip(elements, keys, ts_list):
                        heapq.heappush(et_heap, (t, et_seq, k, e))
                        et_seq += 1
                    wm = wm_strategy.on_batch(max(ts_list))
                    matches = _feed_released(
                        _release(2**62 if end else wm)
                    )
                else:
                    now_ms = int(time.time() * 1000)
                    # pre-chain ops (flat_map) can expand past
                    # batch_size: pad to the next batch_size multiple
                    # (small jit cache)
                    bs = max(1, env.batch_size)
                    pad = ((len(elements) + bs - 1) // bs) * bs
                    matches = op.process_batch(elements, keys, now_ms,
                                               pad_to=pad)
                    metrics.steps += 1
                if n_batches % 64 == 0:
                    # bound host buffers to live-partial size (a BATCH
                    # counter: event-time releases can take several device
                    # steps per batch, so metrics.steps may stride over
                    # any fixed modulus); any matches surfacing here
                    # indicate a count/extraction skew — emit rather than
                    # swallow (but never clobber the batch's own matches,
                    # still pending below)
                    pruned = op.prune_dead_keys()
                    if pruned:
                        out = ([r for m in pruned for r in select_fn(m)]
                               if flat else [select_fn(m) for m in pruned])
                        _emit_batch(pipe, out, metrics)
                if matches:
                    if flat:
                        out = [r for m in matches for r in select_fn(m)]
                    else:
                        out = [select_fn(m) for m in matches]
                    _emit_batch(pipe, out, metrics)
                ck_io.drain()
                if (
                    storage is not None
                    and env.checkpoint_interval_steps > 0
                    and metrics.steps - steps_at_ckpt
                    >= env.checkpoint_interval_steps
                ):
                    write_checkpoint()

        if restore_from:
            restore_checkpoint(restore_from)
        restart = self._restart_strategy()
        try:
            while True:
                try:
                    batch_loop()
                    ck_io.flush()
                    break
                except JobCancelledException:
                    raise
                except Exception:
                    ck_io.settle()
                    can = (
                        storage is not None
                        and storage.latest() is not None
                        and restart.should_restart()
                    )
                    if not can:
                        raise
                    metrics.restarts += 1
                    self._notify_restart()
                    restore_checkpoint(storage)
        finally:
            ck_io.close()

        # end of stream: live partials simply die (a CEP match emits the
        # moment it completes; there is no pending-fire flush)
        metrics.cep_device_steps = op.steps
        metrics.cep_matches_detected = op.matches_detected
        metrics.cep_matches_extracted = op.matches_extracted
        metrics.dropped_capacity += op.dropped_capacity
        return JobHandle(job_name, metrics)

    def _run_process(self, pipe: _Pipeline, metrics: JobMetrics, job_name,
                     restore_from=None):
        """Keyed ProcessFunction stage: host generality path over the heap
        keyed backend + internal timer service (ref StreamTimelyFlatMap /
        KeyedProcessOperator). Hot aggregations belong on the device stages;
        this path exists for arbitrary user logic and semantics parity."""
        from flink_tpu.core.time import TimeCharacteristic
        from flink_tpu.cep.operator import CEPProcessFunction
        from flink_tpu.datastream.functions import (
            Collector, OnTimerContext, ProcessContext, RichFunction,
            RuntimeContext, TimerService,
        )
        from flink_tpu.runtime.timers import InternalTimerService
        from flink_tpu.state.backend import HeapKeyedStateBackend

        if isinstance(pipe.process.fn, CEPProcessFunction):
            metrics.cep_engine = "host"

        env = self.env
        fn = pipe.process.fn
        event_time = env.time_characteristic == TimeCharacteristic.EventTime
        backend = HeapKeyedStateBackend(max_parallelism=env.max_parallelism)
        backend.serializer_registry = env.serializer_registry
        timers = InternalTimerService(env.max_parallelism)
        collector = Collector()
        timer_svc = TimerService(timers, lambda: backend.current_key)
        ctx = ProcessContext(timer_svc)
        timer_ctx = OnTimerContext(timer_svc)

        class _Triggerable:
            def _fire(self, timer, domain):
                backend.set_current_key(timer.key)
                timer_ctx.key = timer.key
                timer_ctx.namespace = timer.namespace
                timer_ctx.time_domain = domain
                timer_ctx.element_timestamp = timer.timestamp
                fn.on_timer(timer.timestamp, timer_ctx, collector)

            def on_event_time(self, timer):
                self._fire(timer, "event")

            def on_processing_time(self, timer):
                self._fire(timer, "processing")

        timers.triggerable = _Triggerable()
        if hasattr(fn, "bind_internals"):
            # operators needing namespaced timers/state (GenericWindowOperator)
            fn.bind_internals(backend, timers)
        reg = getattr(env, "_kv_registry", None)
        from flink_tpu.core.accumulators import AccumulatorRegistry
        from flink_tpu.state.operator_state import OperatorStateStore

        accumulators = AccumulatorRegistry()
        operator_state = OperatorStateStore()
        if isinstance(fn, RichFunction):
            fn.open(RuntimeContext(
                backend,
                metrics_group=(
                    self._job_group.add_group("user")
                    if self._job_group is not None else None
                ),
                accumulators=accumulators,
                operator_state=operator_state,
            ))
        if reg is not None:
            # resolve against the backend's live table set at query time so
            # states created lazily on the first record are queryable too,
            # not only those created in open() (ref KvStateRegistry)
            reg.register_resolver(
                lambda: list(backend._tables),
                lambda n, key: backend.lookup(n, key),
            )

        wm_strategy = (
            pipe.ts_transform.strategy if pipe.ts_transform is not None
            else WatermarkStrategy.for_monotonous_timestamps()
        )

        storage = None
        if env.checkpoint_dir:
            # task-local snapshot cache (checkpointing/local.py): publish
            # mirrors in, restore prefers the verified local copy
            storage = ckpt.CheckpointStorage(
                env.checkpoint_dir,
                retain=env.config.get_int("checkpoint.retain", 2),
                local=local_cache_from_config(
                    env.config, env.checkpoint_dir
                ),
            )
        next_cid = (storage.latest() or 0) + 1 if storage else 1
        steps_at_ckpt = 0
        ck_policy = policy_from_config(env.config) if storage is not None \
            else None
        metrics.failure_budget = ck_policy
        ck_io = _GenericCheckpointIO(env, storage, pipe, policy=ck_policy)

        def write_checkpoint():
            nonlocal next_cid, steps_at_ckpt
            _guarded_generic_write(
                ck_io, ck_policy, storage, metrics, next_cid,
                lambda: {
                    "backend": backend.snapshot(),
                    "timers": timers.snapshot(),
                    "offsets": pipe.source.snapshot_offsets(),
                    "wm_current": wm_strategy.current(),
                    "proc_time": timers.current_processing_time,
                    "max_parallelism": env.max_parallelism,
                    "sink_states": [
                        s.snapshot_state() for s in pipe.all_sinks
                    ],
                    "accumulators": accumulators.snapshot(),
                    "operator_state": operator_state.snapshot(),
                },
            )
            next_cid += 1
            steps_at_ckpt = metrics.steps

        def restore_checkpoint(path_or_storage, cid=None):
            nonlocal steps_at_ckpt
            ck_io.recover()           # durable cuts still notify
            st = (
                ckpt.CheckpointStorage(path_or_storage)
                if isinstance(path_or_storage, str) else path_or_storage
            )
            cid = cid if cid is not None else st.latest()
            if cid is None:
                raise FileNotFoundError(f"no checkpoint in {st.dir}")
            payload = st.read_generic(cid)
            if payload.get("cep_device"):
                raise ValueError(
                    "checkpoint was written by the device CEP path; "
                    "restoring it requires a device-eligible configuration "
                    "(no within(), processing time, parallelism 1)"
                )
            if payload["max_parallelism"] != env.max_parallelism:
                raise ValueError("checkpoint max-parallelism mismatch")
            backend.restore(payload["backend"])
            # restore throws away pending queues; re-register from snapshot
            timers._event_q.clear(); timers._proc_q.clear()
            timers._event_set.clear(); timers._proc_set.clear()
            timers.restore(payload["timers"])
            pipe.source.restore_offsets(payload["offsets"])
            sink_states = payload.get("sink_states")
            if sink_states:
                if len(sink_states) != len(pipe.all_sinks):
                    raise ValueError(
                        f"checkpoint has {len(sink_states)} sink states but "
                        f"the job topology has {len(pipe.all_sinks)} sinks — "
                        f"restore with the matching pipeline"
                    )
                for s, ss in zip(pipe.all_sinks, sink_states):
                    s.restore_state(ss)
            wm_strategy._current = payload["wm_current"]
            timers.current_watermark = payload["wm_current"]
            timers.current_processing_time = payload.get(
                "proc_time", timers.current_processing_time
            )
            # roll accumulators + operator state back to the cut: the
            # replayed records re-apply their contributions exactly once
            accumulators.restore(payload.get("accumulators", {}))
            operator_state.restore(payload.get("operator_state", {}))
            steps_at_ckpt = metrics.steps

        def write_savepoint(path: str) -> str:
            sp = ckpt.CheckpointStorage(path, retain=10**9)
            cid = (sp.latest() or 0) + 1
            return sp.write_generic(cid, {
                "backend": backend.snapshot(),
                "timers": timers.snapshot(),
                "offsets": pipe.source.snapshot_offsets(),
                "wm_current": wm_strategy.current(),
                "proc_time": timers.current_processing_time,
                "max_parallelism": env.max_parallelism,
                "sink_states": [s.snapshot_state() for s in pipe.all_sinks],
                "accumulators": accumulators.snapshot(),
                "operator_state": operator_state.snapshot(),
            })

        self._savepoint_writer = write_savepoint

        def emit():
            out = collector.drain()
            if not out:
                return
            _emit_batch(pipe, out, metrics)

        def batch_loop():
            end = False
            while not end:
                self._poll_control()
                polled, end = pipe.source.poll(env.batch_size)
                now_ms = int(time.time() * 1000)
                # sync the clock BEFORE elements see it: triggers compute
                # interval timers from current_processing_time, and the
                # -2^62 sentinel would put those timers ~2^62 in the past
                # (a ~1e15-iteration advance cascade)
                if timers.current_processing_time < now_ms:
                    timers.current_processing_time = now_ms
                elements = _apply_chain(
                    pipe.pre_chain, self._to_elements(polled)
                )
                metrics.records_in += len(elements)
                for e in elements:
                    key = pipe.key_by.key_selector(e)
                    backend.set_current_key(key)
                    if event_time and pipe.ts_transform is not None:
                        ctx.element_timestamp = int(
                            pipe.ts_transform.timestamp_fn(e)
                        )
                    else:
                        ctx.element_timestamp = now_ms
                    fn.process_element(e, ctx, collector)
                metrics.steps += 1
                if event_time:
                    ts_list = None
                    if elements and pipe.ts_transform is not None:
                        ts_list = max(
                            int(pipe.ts_transform.timestamp_fn(e))
                            for e in elements
                        )
                    wm = wm_strategy.on_batch(ts_list)
                    timers.advance_watermark(wm)
                else:
                    timers.advance_processing_time(now_ms)
                emit()
                ck_io.drain()
                if (
                    storage is not None
                    and env.checkpoint_interval_steps > 0
                    and metrics.steps - steps_at_ckpt
                    >= env.checkpoint_interval_steps
                ):
                    write_checkpoint()

        if restore_from:
            restore_checkpoint(restore_from)
        restart = self._restart_strategy()
        try:
            while True:
                try:
                    batch_loop()
                    ck_io.flush()
                    break
                except JobCancelledException:
                    raise
                except Exception:
                    ck_io.settle()
                    can = (
                        storage is not None
                        and storage.latest() is not None
                        and restart.should_restart()
                    )
                    if not can:
                        raise
                    metrics.restarts += 1
                    self._notify_restart()
                    collector.drain()  # discard partial output of failed run
                    restore_checkpoint(storage)
        finally:
            ck_io.close()

        # end of stream: flush everything pending (the device stages'
        # MAX-watermark flush analog; finite sources always drain). Single
        # pass: re-registered timers don't cascade.
        timers.drain(2**62)
        emit()
        if isinstance(fn, RichFunction):
            fn.close()
        return JobHandle(job_name, metrics, state=backend,
                         accumulator_results=accumulators.results())

    # ------------------------------------------------------------------
    def _run_rolling(self, pipe: _Pipeline, metrics: JobMetrics, job_name,
                     restore_from=None):
        """Rolling keyed reduce: emits the updated accumulator per record
        (ref StreamGroupedReduce)."""
        from flink_tpu.runtime.step import (
            RollingStageSpec, build_rolling_step, init_rolling_state,
        )

        env = self.env
        roll = pipe.rolling
        red = roll.reduce_spec_factory()
        n_dev = len(jax.devices())
        n_shards = max(1, min(env.parallelism, n_dev))
        ctx = MeshContext.create(n_shards, env.max_parallelism)
        spec = RollingStageSpec(
            red=red, capacity_per_shard=env.state_capacity_per_shard
        )
        step = build_rolling_step(ctx, spec)
        state = init_rolling_state(ctx, spec)
        B = env.batch_size
        # reused prefix-mask template (one allocation per stage; the
        # valid mask of each batch is a frozen view slice)
        valid_tmpl = ingest_mod.make_prefix_mask_template(B)
        keep_rev = env.config.get_bool("keys.reverse-map", True)
        codec = KeyCodec()

        def kv_query(key):
            """Queryable rolling accumulator (ref asQueryableState). The
            rolling step does NOT donate, so a single snapshot of the state
            reference yields a consistent pytree even while the job runs
            (reading `state` repeatedly could tear across a rebind)."""
            from flink_tpu.core.keygroups import assign_to_key_group
            from flink_tpu.ops.hashing import route_hash

            st = state
            hi, lo = codec.encode(
                np.asarray([key]) if np.isscalar(key) or isinstance(
                    key, (int, float)
                ) else [key],
                keep_reverse=False,
            )
            kg = int(assign_to_key_group(
                route_hash(hi, lo, np), ctx.max_parallelism, np
            )[0])
            shard = int(ctx.shard_of_key_groups(np.asarray([kg]))[0])
            tkeys = np.asarray(st.table.keys[shard])
            match = np.nonzero(
                (tkeys[:, 0] == hi[0]) & (tkeys[:, 1] == lo[0])
            )[0]
            if match.size == 0:
                return None
            slot = int(match[0])
            if not bool(np.asarray(st.touched[shard])[slot]):
                return None
            v = np.asarray(st.acc[shard])[slot]
            if roll.result_fn is not None:
                v = np.asarray(roll.result_fn(v))
            return v.tolist()

        reg = getattr(env, "_kv_registry", None)
        if reg is not None:
            reg.register(roll.name, kv_query)

        def emit_one(item):
            outputs, out_valid, klist, n = item
            out_np = np.asarray(outputs)[:n]
            ok_np = np.asarray(out_valid)[:n]
            if roll.result_fn is not None:
                out_np = np.asarray(roll.result_fn(out_np))
            out = [
                (k, v) for k, v, okv in zip(klist, out_np.tolist(), ok_np)
                if okv
            ]
            _emit_batch(pipe, out, metrics)

        emitter = _LaggedEmitter(env, emit_one)

        def _set_state(s):
            nonlocal state
            state = s

        ckptr = _FlatStageCheckpointer(
            self, pipe, ctx, codec, keep_rev, emitter, metrics,
            get_state=lambda: state, set_state=_set_state,
            stage_kind="rolling-reduce",
            meta={
                "capacity_per_shard": env.state_capacity_per_shard,
                "red_kind": red.kind,
            },
        )

        def batch_loop():
            nonlocal state
            end = False
            while not end:
                self._poll_control()
                polled, end = pipe.source.poll(B)
                prepped = self._prep_keyed_batch(pipe, polled,
                                                 roll.extractor)
                if prepped is None:
                    emitter.idle()  # idle source must not withhold results
                    continue
                key_list, values = prepped
                hi, lo = codec.encode(key_list, keep_reverse=keep_rev)
                n = len(hi)
                metrics.records_in += n
                state, outputs, out_valid = step(
                    state,
                    jnp.asarray(_pad(hi, B, np.uint32)),
                    jnp.asarray(_pad(lo, B, np.uint32)),
                    jnp.asarray(_pad(values, B, values.dtype)),
                    jnp.asarray(ingest_mod.prefix_mask(valid_tmpl, n)),
                )
                metrics.steps += 1
                klist = (
                    key_list.tolist() if isinstance(key_list, np.ndarray)
                    else key_list
                )
                emitter.push((outputs, out_valid, klist, n))
                ckptr.maybe_checkpoint()
            # end of stream INSIDE restart protection: a sink failing
            # during the final drain recovers like any mid-stream failure
            emitter.drain()

        ckptr.run_with_restarts(batch_loop, restore_from)

        dropped = int(np.asarray(state.dropped_capacity).sum())
        metrics.dropped_capacity = dropped
        if dropped and env.config.get_bool("state.backend.strict-capacity", True):
            raise RuntimeError(
                f"state backend over capacity: {dropped} records lost"
            )
        return JobHandle(job_name, metrics, state=state, ctx=ctx)

    # ------------------------------------------------------------------
    def _run_session(self, pipe: _Pipeline, metrics: JobMetrics, job_name,
                     restore_from=None):
        """Session windows with gap-based merging (see ops/session_windows)."""
        from flink_tpu.core.time import TimeCharacteristic
        from flink_tpu.runtime.step import (
            SessionStageSpec, build_session_step, init_session_state,
        )

        env = self.env
        wagg = pipe.window_agg
        assigner = wagg.assigner
        event_time = assigner.is_event_time and (
            env.time_characteristic == TimeCharacteristic.EventTime
        )
        red = wagg.reduce_spec_factory()
        n_dev = len(jax.devices())
        n_shards = max(1, min(env.parallelism, n_dev))
        ctx = MeshContext.create(n_shards, env.max_parallelism)
        spec = SessionStageSpec(
            red=red, gap_ticks=assigner.gap_ms,
            capacity_per_shard=env.state_capacity_per_shard,
        )
        step = build_session_step(ctx, spec)
        state = init_session_state(ctx, spec)
        B = env.batch_size
        # reused prefix-mask template (one allocation per stage; the
        # valid mask of each batch is a frozen view slice)
        valid_tmpl = ingest_mod.make_prefix_mask_template(B)
        keep_rev = env.config.get_bool("keys.reverse-map", True)
        codec = KeyCodec()
        td: Optional[TimeDomain] = None
        wm_strategy = (
            pipe.ts_transform.strategy if pipe.ts_transform is not None
            else WatermarkStrategy.for_monotonous_timestamps()
        )

        # lagged emission (_LaggedEmitter): fires + the step's table-key
        # handle are retained and read `lag` steps later, so the d2h read
        # overlaps subsequent dispatches. The session step does NOT donate
        # state, so the captured keys handle is an immutable snapshot.
        def emit(item):
            old_f, mid_f, wm_f, tkeys_handle = item
            out = []
            tkeys = np.asarray(tkeys_handle)
            for fire in (old_f, mid_f):
                khi, klo, f_start, f_end, f_vals, f_mask = map(np.asarray, fire)
                for sh in range(khi.shape[0]):
                    sel = np.nonzero(f_mask[sh])[0]
                    if not sel.size:
                        continue
                    keys = codec.decode(khi[sh, sel], klo[sh, sel])
                    for k, st_, en_, v in zip(
                        keys, f_start[sh, sel].tolist(),
                        f_end[sh, sel].tolist(), f_vals[sh, sel].tolist(),
                    ):
                        out.append(SessionResult(
                            k, int(td.to_ms(st_)), int(td.to_ms(en_)), v
                        ))
            w_start, w_end, w_vals, w_mask = map(np.asarray, wm_f)
            for sh in range(w_mask.shape[0]):
                sel = np.nonzero(w_mask[sh])[0]
                if not sel.size:
                    continue
                keys = codec.decode(tkeys[sh, sel, 0], tkeys[sh, sel, 1])
                for k, st_, en_, v in zip(
                    keys, w_start[sh, sel].tolist(),
                    w_end[sh, sel].tolist(), w_vals[sh, sel].tolist(),
                ):
                    out.append(SessionResult(
                        k, int(td.to_ms(st_)), int(td.to_ms(en_)), v
                    ))
            if not out:
                return
            if wagg.result_fn is not None:
                out = [r._replace(value=float(np.asarray(
                    wagg.result_fn(np.asarray(r.value))))) for r in out]
            metrics.fires += len(out)
            _emit_batch(pipe, out, metrics)

        emitter = _LaggedEmitter(env, emit)

        # -- checkpoint/restore: the shared flat-pytree machinery
        # (_FlatStageCheckpointer — round 4 introduced the session
        # support inline, round 5 unified it with rolling/count). The
        # session-specific non-array state (watermark + time-domain
        # origin) rides the payload's stage_extra hooks.
        def _set_state(s):
            nonlocal state
            state = s

        def _extra():
            return {
                "wm_current": wm_strategy.current(),
                "origin_ms": td.origin_ms if td is not None else None,
            }

        def _apply_extra(extra):
            nonlocal td
            wm_strategy._current = extra["wm_current"]
            if extra["origin_ms"] is not None:
                td = TimeDomain(origin_ms=extra["origin_ms"],
                                ms_per_tick=1)

        ckptr = _FlatStageCheckpointer(
            self, pipe, ctx, codec, keep_rev, emitter, metrics,
            get_state=lambda: state, set_state=_set_state,
            stage_kind="session-window",
            meta={
                "gap_ms": assigner.gap_ms,
                "capacity_per_shard": env.state_capacity_per_shard,
            },
            extra_payload=_extra, apply_extra=_apply_extra,
        )

        def run_once(hi, lo, ticks, values, valid, wm_ms):
            nonlocal state
            wmv = np.full((ctx.n_shards,), np.int32(   # numpy: an eager
                min(int(td.to_ticks(wm_ms)), 2**31 - 4)  # tiny op is a
                if wm_ms is not None else -(2**31) + 1    # dispatch of its own
            ))
            state, old_f, mid_f, wm_f = step(
                state, jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(ticks),
                jnp.asarray(values), jnp.asarray(valid), wmv,
            )
            metrics.steps += 1
            emitter.push((old_f, mid_f, wm_f, state.table.keys))

        def batch_loop():
            nonlocal td
            end = False
            while not end:
                self._poll_control()
                polled, end = pipe.source.poll(B)
                now_ms = int(time.time() * 1000)
                if pipe.source.columnar and isinstance(polled, tuple):
                    cols, ts_ms = polled
                    if not cols:
                        emitter.idle()
                        continue
                    for t in pipe.pre_chain:
                        if t.kind != "map":
                            raise NotImplementedError(
                                "columnar sources support only 'map' "
                                "before key_by"
                            )
                        cols = t.fn(cols)
                    key_list = np.asarray(pipe.key_by.key_selector(cols))
                    values = np.asarray(wagg.extractor(cols))
                    if event_time and pipe.ts_transform is not None:
                        ts_ms = np.asarray(
                            pipe.ts_transform.timestamp_fn(cols), np.int64)
                    elif not event_time or ts_ms is None:
                        ts_ms = np.full(len(key_list), now_ms, np.int64)
                else:
                    elements = _apply_chain(pipe.pre_chain,
                                            self._to_elements(polled))
                    if not elements:
                        emitter.idle()
                        continue
                    key_list = [pipe.key_by.key_selector(e)
                                for e in elements]
                    values = np.asarray(
                        [wagg.extractor(e) for e in elements], np.float32
                    )
                    if event_time and pipe.ts_transform is not None:
                        ts_ms = np.asarray(
                            [pipe.ts_transform.timestamp_fn(e)
                             for e in elements],
                            np.int64,
                        )
                    else:
                        ts_ms = np.full(len(key_list), now_ms, np.int64)
                hi, lo = codec.encode(key_list, keep_reverse=keep_rev)
                n = len(hi)
                metrics.records_in += n
                if td is None:
                    td = TimeDomain(origin_ms=int(np.min(ts_ms)),
                                    ms_per_tick=1)
                ticks = td.to_ticks(ts_ms)
                wm_ms = (
                    wm_strategy.on_batch(int(np.max(ts_ms))) if event_time
                    else now_ms - 1
                )
                run_once(
                    _pad(hi, B, np.uint32), _pad(lo, B, np.uint32),
                    _pad(ticks, B, np.int32), _pad(values, B, np.float32),
                    ingest_mod.prefix_mask(valid_tmpl, n), wm_ms,
                )
                if td is not None:
                    ckptr.maybe_checkpoint()
            if td is not None:
                # end of stream: close all open sessions. INSIDE the
                # restart protection — a sink failing during the final
                # flush recovers like any mid-stream failure.
                final_wm = int(td.to_ms(2**31 - 4))
                run_once(
                    np.zeros(B, np.uint32), np.zeros(B, np.uint32),
                    np.zeros(B, np.int32),
                    np.zeros((B,) + tuple(red.value_shape), np.float32),
                    np.zeros(B, bool), final_wm,
                )
            emitter.drain()

        ckptr.run_with_restarts(batch_loop, restore_from)

        metrics.dropped_late = int(np.asarray(state.dropped_late).sum())
        dropped = int(np.asarray(state.dropped_capacity).sum())
        metrics.dropped_capacity = dropped
        if dropped and env.config.get_bool("state.backend.strict-capacity", True):
            raise RuntimeError(
                f"state backend over capacity: {dropped} records lost"
            )
        return JobHandle(job_name, metrics, state=state, ctx=ctx)

    # ------------------------------------------------------------------
    def _run_count(self, pipe: _Pipeline, metrics: JobMetrics, job_name,
                   restore_from=None):
        """countWindow(N): per-key tumbling windows of N elements."""
        from flink_tpu.runtime.step import (
            CountStageSpec, build_count_step, init_count_state,
        )

        env = self.env
        wagg = pipe.window_agg
        red = wagg.reduce_spec_factory()
        n_dev = len(jax.devices())
        n_shards = max(1, min(env.parallelism, n_dev))
        ctx = MeshContext.create(n_shards, env.max_parallelism)
        spec = CountStageSpec(
            red=red, n_per_window=wagg.assigner.size_n,
            capacity_per_shard=env.state_capacity_per_shard,
        )
        step = build_count_step(ctx, spec)
        state = init_count_state(ctx, spec)
        B = env.batch_size
        # reused prefix-mask template (one allocation per stage; the
        # valid mask of each batch is a frozen view slice)
        valid_tmpl = ingest_mod.make_prefix_mask_template(B)
        keep_rev = env.config.get_bool("keys.reverse-map", True)
        codec = KeyCodec()

        def emit_one(item):
            khi, klo, w, vals, mask = item
            mask_np = np.asarray(mask)
            if not mask_np.any():
                return
            khi_np = np.asarray(khi)[mask_np]
            klo_np = np.asarray(klo)[mask_np]
            w_np = np.asarray(w)[mask_np]
            v_np = np.asarray(vals)[mask_np]
            if wagg.result_fn is not None:
                v_np = np.asarray(wagg.result_fn(v_np))
            keys = codec.decode(khi_np, klo_np)
            out = [
                WindowResult(k, int(wi), vv)
                for k, wi, vv in zip(keys, w_np.tolist(), v_np.tolist())
            ]
            metrics.fires += len(out)
            _emit_batch(pipe, out, metrics)

        emitter = _LaggedEmitter(env, emit_one)

        def _set_state(s):
            nonlocal state
            state = s

        ckptr = _FlatStageCheckpointer(
            self, pipe, ctx, codec, keep_rev, emitter, metrics,
            get_state=lambda: state, set_state=_set_state,
            stage_kind="count-window",
            meta={
                "capacity_per_shard": env.state_capacity_per_shard,
                "red_kind": red.kind,
                "n_per_window": wagg.assigner.size_n,
            },
        )

        def batch_loop():
            nonlocal state
            end = False
            while not end:
                self._poll_control()
                polled, end = pipe.source.poll(B)
                prepped = self._prep_keyed_batch(pipe, polled,
                                                 wagg.extractor)
                if prepped is None:
                    emitter.idle()
                    continue
                key_list, values = prepped
                hi, lo = codec.encode(key_list, keep_reverse=keep_rev)
                n = len(hi)
                metrics.records_in += n
                state, khi, klo, w, vals, mask = step(
                    state,
                    jnp.asarray(_pad(hi, B, np.uint32)),
                    jnp.asarray(_pad(lo, B, np.uint32)),
                    jnp.asarray(_pad(values, B, values.dtype)),
                    jnp.asarray(ingest_mod.prefix_mask(valid_tmpl, n)),
                )
                metrics.steps += 1
                emitter.push((khi, klo, w, vals, mask))
                ckptr.maybe_checkpoint()
            emitter.drain()

        ckptr.run_with_restarts(batch_loop, restore_from)

        dropped = int(np.asarray(state.dropped_capacity).sum())
        metrics.dropped_capacity = dropped
        if dropped and env.config.get_bool("state.backend.strict-capacity", True):
            raise RuntimeError(
                f"state backend over capacity: {dropped} records lost"
            )
        return JobHandle(job_name, metrics, state=state, ctx=ctx)

    @staticmethod
    def _empty_step(run_step, B, red, wm_ms):
        hi = np.zeros(B, np.uint32)
        lo = np.zeros(B, np.uint32)
        ticks = np.zeros(B, np.int32)
        if red.kind == "sketch":
            values = np.zeros(B, np.uint32)  # per-record item hashes
        else:
            values = np.zeros((B,) + tuple(red.value_shape), np.float32)
        valid = np.zeros(B, bool)
        return run_step(hi, lo, ticks, values, valid, wm_ms)
