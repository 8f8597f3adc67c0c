"""Cross-host data plane: multi-host keyed windows over ONE global mesh.

The reference's data fabric is every-TaskManager-shuffles-to-every-
TaskManager over TCP (RecordWriter.java:82 feeding Netty subpartitions,
TaskManager.scala:296 registration). The TPU-native redesign
(docs/DCN_INGESTION.md) inverts it:

  * each HOST ingests whatever its source partitions contain (any keys)
    and feeds only its LOCAL devices — records cross the slow network
    once, as ingestion bytes;
  * ONE collective over the global mesh routes every record to the
    device owning its key group (``all_to_all`` for the pane-ring time
    windows, ``all_gather`` + mask for the replicate-and-mask session
    kernel) — the keyed shuffle rides the accelerator fabric (ICI on a
    pod; the cross-process collective transport stands in for it here);
  * control decisions ride the SAME collectives: the global watermark is
    an on-device ``pmin`` of per-host watermarks, and loop termination is
    an on-device conjunction of per-host "source exhausted" flags — so
    every process executes an identical lockstep sequence of compiled
    steps (the SPMD invariant), with no out-of-band consensus protocol.

Round 5 generalizes the plane beyond the original tumbling-sum runner:
sliding windows (any size/slide via the pane ring), session windows
(gap-merged, ``DCNSessionRunner``), any built-in reduce kind, and the
standard ``StreamExecutionEnvironment.execute()`` path selects it when
``dcn.coordinator`` is configured (runtime/executor.py _run_dcn) — the
reference's "same program on every TaskManager" deployment story.

Worker processes join the mesh with ``jax.distributed.initialize``
(the ``--coordinator`` seam the design doc specified); on CPU test
meshes the collectives run over Gloo/TCP, which is exactly the DCN hop
being modeled. Checkpoints are per-process shard snapshots written at a
deterministic lockstep cadence, so a killed ensemble restarts from the
latest cut that EVERY process completed (the reference's
full-job-restart-from-checkpoint failure model, ExecutionGraph restart +
CheckpointCoordinator.restoreLatestCheckpointedState).

Run one worker:
  python -m flink_tpu.runtime.dcn --coordinator H:P --num-processes N
      --process-id K --builder pkg.mod:fn --out result.npz
      [--checkpoint-dir D --ckpt-every C --restore]

``builder()`` returns a DCNJobSpec.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from flink_tpu.runtime import elastic
from flink_tpu.testing import faults

MAX_TICKS = 2**31 - 4


class DCNPeerError(RuntimeError):
    """Attributed data-plane peer failure: the message names WHICH peer
    and WHAT it was doing, so one sick process surfaces as a clean job
    failure instead of an anonymous ensemble hang (the failure-
    containment contract, docs/fault-tolerance.md)."""


class DCNPeerStalledError(DCNPeerError):
    """A live peer stopped sending: the bounded recv deadline expired
    mid-frame. The connect path always had a deadline; this closes the
    steady-state hole where one stalled host wedged every reader."""


class DCNPeerLostError(DCNPeerError, elastic.DeviceLostError):
    """A peer connection reset and bounded reconnect-with-backoff could
    not re-establish the ring — the peer is declared dead.

    Also a :class:`~flink_tpu.runtime.elastic.DeviceLostError`: the
    dead peer's mesh segment (its device) is gone with it, so the
    failure classifies as DEVICE LOSS at the restart boundary. The DCN
    lockstep plane itself cannot re-plan in place (every process bakes
    the global mesh into its collectives), so recovery there is the
    ordinary job-level restart at full parallelism — but the
    classification, metrics, and any supervising controller see the
    loss for what it is."""

    def __init__(self, message: str, lost_shards=(), lost_devices=()):
        elastic.DeviceLostError.__init__(
            self, message, lost_shards=lost_shards,
            lost_devices=lost_devices,
        )


@dataclass
class DCNJobSpec:
    """One keyed windowed aggregation fed from per-host partitions.

    source_factory(process_id, num_processes) -> object with
        poll(max_records) -> (keys int64[n], ts_ms int64[n],
                              values float32[n], exhausted bool)
        snapshot() -> json-able offset state
        restore(state)
    (the per-host slice of the partitioned-consumer contract,
    connectors/partitioned.py / FlinkKafkaConsumerBase.java:65).

    window_kind "time" covers tumbling (slide_ms None/== size_ms) and
    sliding windows; "session" uses gap_ms-merged session windows.
    """

    source_factory: Callable
    size_ms: int = 0
    capacity_per_shard: int = 1 << 16
    max_parallelism: int = 128
    batch_per_host: int = 4096
    fires_per_step: int = 4
    out_of_orderness_ms: int = 0
    reduce_kind: str = "sum"
    slide_ms: Optional[int] = None
    window_kind: str = "time"      # "time" | "session" | "rolling" | "cep"
    # window_kind "cep": a zero-arg factory returning the cep Pattern
    # (factory, not instance: every lockstep process builds its own).
    # The source's VALUE lane carries the per-event stage-match bits
    # packed as a float32 integer (bit s = stage s's predicate; exact
    # for <= 24 stages) — predicates evaluate at the ingesting host, the
    # NFA advances on device, and the base ingest loop stays untouched.
    cep_pattern_factory: Optional[Callable[[], object]] = None
    gap_ms: int = 0                # session gap
    # epoch-ms timestamps exceed int32 ticks: the runner rebases every
    # ts to this origin. A SPEC field (not derived from data) so all
    # lockstep processes agree without coordination; set it to e.g. the
    # job's start-of-day epoch ms for wall-clock sources.
    origin_ms: int = 0
    # physical rebalance (ref RebalancePartitioner.java:30): underfull
    # hosts borrow ingest lanes from their ring neighbor's backlog over a
    # host-to-host TCP side channel, so a skewed partition assignment
    # keeps every host's lane budget busy (see _RebalanceRing). Device-
    # side lane spreading cannot do this — per-host lane counts are fixed
    # by the sharding, so extra ingest capacity must arrive as records
    # over the network, exactly like the reference's rebalance edge.
    rebalance: bool = False
    rebalance_addrs: Optional[list] = None   # "host:port" per process-id
    # host-level ingest partitioner (ref StreamPartitioner catalog,
    # SURVEY §2.11): "forward" (records process on the host whose
    # partition holds them), "rebalance" (deficit-driven neighbor
    # borrowing, equivalent to rebalance=True), "shuffle" (every record
    # routed to a uniformly random host via the targeted ring — the
    # ShufflePartitioner, with per-cycle balanced assignment so no
    # host's lane budget overflows), "global" (every record routed to
    # host 0 — the GlobalPartitioner, whose single-subtask bottleneck
    # cost becomes visible as host-0-bound cycle counts). "rescale" is
    # accepted as an alias of "forward": the reference's rescale keeps
    # records within the local TaskManager group, which is exactly what
    # forward ingestion does here. shuffle/global use the same
    # rebalance_addrs side channel.
    ingest_partitioner: str = "forward"
    # failure containment (docs/fault-tolerance.md): a ring peer that
    # stops sending mid-frame fails ATTRIBUTED after this deadline
    # (DCNPeerStalledError names the peer) instead of wedging the
    # ensemble; a transient peer reset gets this many reconnect
    # attempts (exponential backoff) before DCNPeerLostError.
    peer_recv_timeout_s: float = 120.0
    peer_reconnect_attempts: int = 3
    peer_reconnect_backoff_s: float = 0.25
    # pipeline.steps-per-dispatch plumb-through: the lockstep DCN plane
    # runs ONE poll → route → exchange → update round per collective
    # cycle (every host must dispatch the same step in the same round,
    # and the rebalance/shuffle side channels synchronize per cycle), so
    # K-fused dispatch does not compose with it. Values > 1 take the
    # EXPLICIT single-step fallback: noted loudly at startup, never
    # silently absorbed.
    steps_per_dispatch: int = 1
    # per-host resident mode (pipeline.resident-loop on/while under a
    # dcn.coordinator, ISSUE 20b): between DCN boundaries each host
    # polls up to resident_ring_depth local chunks and retires them in
    # ONE multi-slot drain dispatch (runtime/step.py
    # build_window_dcn_resident_drain — the trip count is pmax-agreed on
    # device, so no host-side count exchange). The rebalance/shuffle/
    # global side channels run ONCE per drain cycle, at the boundary,
    # with their frame deadlines scaled by the slots the previous drain
    # retired (deadline_scale — DCNPeerStalledError attribution keeps
    # its base semantics at scale 1). Time-window jobs only.
    resident: bool = False
    resident_ring_depth: int = 4


class GeneratorPartitionSource:
    """fn(offset, n) -> (keys, ts_ms, values) up to ``total`` records —
    the replayable test/bench partition (deterministic fetch, so offset
    restore gives exactly-once replay)."""

    def __init__(self, fn, total: int):
        self.fn = fn
        self.total = total
        self.offset = 0

    def poll(self, max_records):
        n = min(max_records, self.total - self.offset)
        if n <= 0:
            e = np.zeros(0, np.int64)
            return e, e, np.zeros(0, np.float32), True
        keys, ts, vals = self.fn(self.offset, n)
        self.offset += n
        return (np.asarray(keys, np.int64), np.asarray(ts, np.int64),
                np.asarray(vals, np.float32), self.offset >= self.total)

    def snapshot(self):
        return {"offset": self.offset}

    def restore(self, state):
        self.offset = int(state["offset"])


class _RebalanceRing:
    """Host-level physical rebalance (ref RebalancePartitioner.java:30,
    RecordWriter round-robin edges): each cycle, process p asks its ring
    neighbor (p+1) % nproc to fill p's spare ingest lanes from the
    neighbor's source backlog; records cross hosts as length-prefixed
    numpy frames over TCP — the reference's records-over-the-network
    rebalance, applied at the ingestion edge where this architecture's
    skew cost actually lives (a skewed host needs proportionally more
    lockstep cycles; the keyed all_to_all already balances compute).

    Protocol per cycle:
      1. send REQUEST(my spare lanes) on the next-link,
      2. serve the prev-link: read its spare, poll up to that many extra
         records from MY source, send them (+ my exhausted flag),
      3. read the donation from the next-link into my spare lanes.
    Lockstep safety: every process runs all three phases every cycle.
    Deadlock safety: phase-2 sends happen before anyone's phase-3 read,
    so a donation frame must never need the peer to drain it — frames
    are capped at DONATE_CAP records (≤64 KiB) and both socket buffers
    are raised to hold a full frame, so sendall always completes into
    kernel buffers even when every ring link donates at once (sources
    that trickle below max_records can leave every host with both spare
    lanes AND backlog).

    Failure containment (docs/fault-tolerance.md): steady-state reads
    run in short socket-timeout slices under a ``recv_timeout_s``
    deadline, so a stalled peer raises an attributed
    :class:`DCNPeerStalledError` instead of wedging the reader forever.
    A transient peer RESET triggers a bounded reconnect: both links are
    closed and re-established (the same deterministic dial-next /
    accept-prev dance as startup — a neighbor losing one link resyncs
    its own links too, so the repair cascades around the ring) and the
    ROUND retries from the top. Retry is lossless even when the abort
    is ASYMMETRIC (the donor's round completed while the recipient's
    recv failed): every request frame carries the requester's round
    counter, and the serve side caches its last (round, donation) — a
    re-request for an already-served round re-donates the cached
    records instead of re-polling, so an aborted round's poll is never
    lost and never double-consumed, and nothing is applied to device
    state until the round returns. Reconnect exhaustion raises
    :class:`DCNPeerLostError` naming the peer.
    """

    _REQ = "<IQ"     # spare lane count, requester round counter
    _HDR = "<IB"     # donated record count, donor-exhausted flag
    DONATE_CAP = 3200             # 3200 * 20 B = 62.5 KiB per frame
    _SOCKBUF = 1 << 18            # 256 KiB send/recv buffers
    _SLICE_S = 2.0                # per-I/O socket-timeout slice

    def __init__(self, pid: int, nproc: int, addrs,
                 recv_timeout_s: float = 120.0,
                 reconnect_attempts: int = 3,
                 reconnect_backoff_s: float = 0.25,
                 resync_window_s: float = 30.0):
        import socket
        import struct

        self.struct = struct
        self.socket = socket
        self.pid = pid
        self.nproc = nproc
        self.recv_timeout_s = float(recv_timeout_s)
        # drain-boundary deadline scaling (per-host resident mode, ISSUE
        # 20b): the runner sets this to the slot count the PREVIOUS
        # drain retired, so a peer legitimately busy draining a deep
        # ring gets proportionally more frame time before
        # DCNPeerStalledError attributes it — same contract as
        # Watchdog.arm(scale=), never below the configured base deadline
        self.deadline_scale = 1.0
        self.reconnect_attempts = max(0, int(reconnect_attempts))
        self.reconnect_backoff_s = float(reconnect_backoff_s)
        # how long a resync waits for the lost peer to come back up
        # (redial + re-accept window); bounded so a peer that is gone
        # for good attributes instead of redialing forever
        self.resync_window_s = float(resync_window_s)
        if not addrs or len(addrs) != nproc:
            raise ValueError(
                "rebalance requires rebalance_addrs with one host:port "
                "per process"
            )
        self.addrs = list(addrs)
        # asymmetric-retry protection (see class docstring): my round
        # counter stamps every request; the serve side remembers the
        # last round it donated for so a RE-request re-donates
        self._round = 0
        self._served_round = None
        self._served_cache = None
        host, port = addrs[pid].rsplit(":", 1)
        # the listen socket stays open for the ring's lifetime: a reset
        # link re-ACCEPTS through it (reconnect support), exactly like
        # the initial handshake
        self._srv = socket.create_server((host, int(port)))
        self.next_sock = None
        self.prev_sock = None
        self._dial_next(120.0)
        self._accept_prev(120.0)

    # -- link plumbing --------------------------------------------------
    def _peer(self, which: str) -> int:
        return (self.pid + (1 if which == "next" else -1)) % self.nproc

    def _sock_opts(self, s):
        # short slices so the recv loop can enforce the overall deadline
        # (and deliver async cancellation) without OS-level blocking
        s.settimeout(min(self._SLICE_S, max(0.05, self.recv_timeout_s)))
        s.setsockopt(self.socket.SOL_SOCKET, self.socket.SO_SNDBUF,
                     self._SOCKBUF)
        s.setsockopt(self.socket.SOL_SOCKET, self.socket.SO_RCVBUF,
                     self._SOCKBUF)

    def _dial_next(self, window_s: float):
        nhost, nport = self.addrs[self._peer("next")].rsplit(":", 1)
        deadline = time.monotonic() + window_s
        self.next_sock = None
        while self.next_sock is None:
            try:
                self.next_sock = self.socket.create_connection(
                    (nhost, int(nport)), timeout=5
                )
            except OSError:
                if time.monotonic() > deadline:
                    raise DCNPeerLostError(
                        f"process {self.pid}: peer {self._peer('next')} "
                        f"({nhost}:{nport}) unreachable for "
                        f"{window_s:.0f}s"
                    )
                time.sleep(0.1)
        self._sock_opts(self.next_sock)

    def _accept_prev(self, window_s: float):
        self._srv.settimeout(window_s)
        try:
            self.prev_sock, _ = self._srv.accept()
        except self.socket.timeout:
            raise DCNPeerLostError(
                f"process {self.pid}: peer {self._peer('prev')} did not "
                f"redial within {window_s:.0f}s"
            ) from None
        self._sock_opts(self.prev_sock)

    def _resync(self):
        """Close and re-establish BOTH links. A neighbor that lost only
        one link observes OUR close on the other and resyncs too, so the
        repair cascades around the ring; fresh sockets also discard any
        half-frame bytes of the aborted round."""
        for s in (self.next_sock, self.prev_sock):
            try:
                s.close()
            except OSError:
                pass
        self._dial_next(self.resync_window_s)
        self._accept_prev(self.resync_window_s)

    def _run_round(self, fn, attempts: Optional[int] = None):
        """Run one ring round; on a transient connection failure, resync
        links (bounded, backed off) and retry the whole round. Lossless
        by construction: the serve side re-donates its cached records on
        a round re-request (see exchange) and callers apply nothing
        until the round returns. Stall deadlines do NOT retry — a
        stalled-but-connected peer is attributed, not waited out
        twice."""
        attempts = self.reconnect_attempts if attempts is None else attempts
        for attempt in range(attempts + 1):
            try:
                return fn()
            except DCNPeerError:
                raise
            except (ConnectionError, OSError) as e:
                if isinstance(e, self.socket.timeout):
                    raise      # sends/recvs convert slices to deadlines
                if attempt >= attempts:
                    raise DCNPeerLostError(
                        f"process {self.pid}: ring peer lost and "
                        f"{attempts} reconnect attempt(s) failed: {e}"
                    ) from e
                time.sleep(self.reconnect_backoff_s * (2 ** attempt))
                self._resync()
        raise AssertionError("unreachable")

    def _frame_deadline_s(self) -> float:
        """The live frame deadline: base recv timeout scaled by the
        slot count the previous resident drain retired (1.0 in lockstep
        single-step mode, so behavior there is byte-identical)."""
        return self.recv_timeout_s * max(1.0, float(self.deadline_scale))

    def _send_all(self, sock, data: bytes, peer: str = "peer") -> None:
        """sendall in socket-timeout slices under the SAME deadline the
        reads get: a peer that merely pauses (checkpoint sync, GC) while
        our frame overruns the kernel buffers is waited out up to
        ``recv_timeout_s`` (drain-scaled), then attributed — never
        killed on one 2-second slice."""
        frame_s = self._frame_deadline_s()
        deadline = time.monotonic() + frame_s
        view = memoryview(data)
        sent = 0
        while sent < len(view):
            try:
                sent += sock.send(view[sent:])
            except self.socket.timeout:
                if time.monotonic() >= deadline:
                    raise DCNPeerStalledError(
                        f"process {self.pid}: peer {peer} stalled — "
                        f"send stuck at {sent}/{len(view)} frame bytes "
                        f"after {frame_s:.1f}s"
                    ) from None
                continue

    def _recv_exact(self, sock, n: int, peer: str = "peer") -> bytes:
        # ONE injection hit per FRAME read (outside the slice loop):
        # occurrence-indexed rules stay deterministic regardless of how
        # many empty timeout slices the scheduler happens to produce
        faults.inject("dcn.recv", pid=self.pid, peer=peer, sock=sock)
        buf = b""
        frame_s = self._frame_deadline_s()
        deadline = time.monotonic() + frame_s
        while len(buf) < n:
            try:
                chunk = sock.recv(n - len(buf))
            except self.socket.timeout:
                if time.monotonic() >= deadline:
                    raise DCNPeerStalledError(
                        f"process {self.pid}: peer {peer} stalled — "
                        f"{len(buf)}/{n} frame bytes after "
                        f"{frame_s:.1f}s"
                    ) from None
                continue
            if not chunk:
                raise ConnectionResetError(
                    f"rebalance peer {peer} closed the link"
                )
            buf += chunk
        return buf

    def _serve_donation(self, want: int, req_round: int, poll_extra):
        """Serve one request, re-donating from the cache when the peer
        RE-requests a round we already served (its recv of our donation
        failed): the polled records went into a dead socket, not into
        the peer — re-donating them is what makes asymmetric-abort
        retry lossless; a NEW round always polls fresh."""
        if req_round == self._served_round and self._served_cache is not None:
            return self._served_cache
        donation = poll_extra(want) if want else (
            np.zeros(0, np.int64), np.zeros(0, np.int64),
            np.zeros(0, np.float32), False,
        )
        self._served_round = req_round
        self._served_cache = donation
        return donation

    def exchange(self, spare: int, poll_extra):
        """One rebalance round. ``poll_extra(n)`` polls up to n records
        from this host's source, returning (keys, ts_ms, vals,
        exhausted). Returns (keys, ts_ms, vals, donor_done) received into
        this host's spare lanes."""
        st = self.struct

        def round_once():
            faults.inject("dcn.send", pid=self.pid, link="next",
                          sock=self.next_sock)
            self._send_all(
                self.next_sock, st.pack(self._REQ, int(spare), self._round),
                peer=f"next/{self._peer('next')}",
            )
            # serve the prev neighbor
            want, req_round = st.unpack(
                self._REQ,
                self._recv_exact(self.prev_sock, st.calcsize(self._REQ),
                                 peer=f"prev/{self._peer('prev')}"),
            )
            want = min(int(want), self.DONATE_CAP)
            keys, ts, vals, done = self._serve_donation(
                want, req_round, poll_extra
            )
            n = len(keys)
            self._send_all(
                self.prev_sock,
                st.pack(self._HDR, n, 1 if done else 0)
                + np.asarray(keys, np.int64).tobytes()
                + np.asarray(ts, np.int64).tobytes()
                + np.asarray(vals, np.float32).tobytes(),
                peer=f"prev/{self._peer('prev')}",
            )
            # collect my donation
            hdr = self._recv_exact(
                self.next_sock, st.calcsize(self._HDR),
                peer=f"next/{self._peer('next')}",
            )
            m, ddone = st.unpack(self._HDR, hdr)
            payload = self._recv_exact(
                self.next_sock, m * (8 + 8 + 4),
                peer=f"next/{self._peer('next')}",
            )
            rk = np.frombuffer(payload[: 8 * m], np.int64)
            rt = np.frombuffer(payload[8 * m: 16 * m], np.int64)
            rv = np.frombuffer(payload[16 * m:], np.float32)
            return rk, rt, rv, bool(ddone)

        out = self._run_round(round_once)
        self._round += 1
        return out

    def close(self):
        for s in (self.next_sock, self.prev_sock, self._srv):
            try:
                if s is not None:
                    s.close()
            except OSError:
                pass


class _TargetRing(_RebalanceRing):
    """Targeted ring router for the shuffle/global ingest partitioners
    (ref ShufflePartitioner.java / GlobalPartitioner.java): each cycle,
    every host stamps its polled records with a destination host and the
    ring relays frames ``nproc - 1`` hops (records flow p+1 -> p, the
    donation direction the sockets already run), so every record sits at
    its destination before the cycle's device step. Routing completes
    WITHIN the cycle — no cross-cycle in-flight records — so the
    cycle-boundary checkpoint cut stays a consistent exactly-once
    barrier without any new snapshot state.

    Termination: every frame carries the sender's accumulated
    all-sources-exhausted flag; after ``nproc - 1`` hops the AND covers
    the whole ring, and a host is done once that holds and it ingested
    nothing this cycle (the device-side stop conjunction still gates the
    ensemble, as for forward ingestion).

    Frames are (count, done, targets u8[n], keys i64[n], ts i64[n],
    vals f32[n]); the caller bounds per-cycle polls so the merged inflow
    never exceeds the lane budget (see _DCNRunnerBase._poll_budget).
    """

    def route(self, keys, ts_ms, vals, targets, exhausted: bool):
        """Returns (keys, ts_ms, vals, all_done) of the records whose
        destination is this host. The multi-hop relay is NOT retried on
        a reset: unlike the pairwise exchange (whose re-donation cache
        makes retry lossless), a host whose relay round COMPLETED while
        a neighbor's failed would see the neighbor's re-relayed records
        as next-round traffic and deliver duplicates — so a targeted-
        ring reset fails attributed (DCNPeerLostError) and recovery is
        the job-level restart-from-checkpoint path. Reads and sends
        still run under the stall deadline."""
        st = self.struct

        def round_once():
            mine_k, mine_t, mine_v = [], [], []

            def split(k, t, v, tgt):
                here = tgt == self.pid
                if here.any():
                    mine_k.append(k[here])
                    mine_t.append(t[here])
                    mine_v.append(v[here])
                away = ~here
                return k[away], t[away], v[away], tgt[away]

            pk, pt, pv, ptgt = split(
                np.asarray(keys, np.int64), np.asarray(ts_ms, np.int64),
                np.asarray(vals, np.float32), np.asarray(targets, np.uint8),
            )
            all_done = bool(exhausted)
            for _hop in range(self.nproc - 1):
                n = len(pk)
                faults.inject("dcn.send", pid=self.pid, link="prev",
                              sock=self.prev_sock)
                self._send_all(
                    self.prev_sock,
                    st.pack(self._HDR, n, 1 if all_done else 0)
                    + ptgt.tobytes() + pk.tobytes() + pt.tobytes()
                    + pv.tobytes(),
                    peer=f"prev/{self._peer('prev')}",
                )
                hdr = self._recv_exact(
                    self.next_sock, st.calcsize(self._HDR),
                    peer=f"next/{self._peer('next')}",
                )
                m, done_flag = st.unpack(self._HDR, hdr)
                payload = self._recv_exact(
                    self.next_sock, m * (1 + 8 + 8 + 4),
                    peer=f"next/{self._peer('next')}",
                )
                rtgt = np.frombuffer(payload[:m], np.uint8)
                rk = np.frombuffer(payload[m: m + 8 * m], np.int64)
                rt = np.frombuffer(payload[m + 8 * m: m + 16 * m],
                                   np.int64)
                rv = np.frombuffer(payload[m + 16 * m:], np.float32)
                all_done = all_done and bool(done_flag)
                pk, pt, pv, ptgt = split(rk, rt, rv, rtgt)
            if len(pk):
                raise RuntimeError(
                    f"{len(pk)} record(s) undeliverable after "
                    f"{self.nproc - 1} ring hops (bad target?)"
                )
            if mine_k:
                return (np.concatenate(mine_k), np.concatenate(mine_t),
                        np.concatenate(mine_v), all_done)
            return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                    np.zeros(0, np.float32), all_done)

        return self._run_round(round_once, attempts=0)


class _DCNRunnerBase:
    """One process's half of a lockstep multi-host keyed job: global-mesh
    setup, the ingest/step/emit loop, and checkpoint/restore. Subclasses
    compile the stage step (``_build_step`` setting ``self._step``) and
    decode its per-shard fire outputs (``_emit_local``). The step
    contract: step(state, hi, lo, ts, values, valid, wm, done) ->
    (state, aux, stop) with stop an all-shards-identical int32."""

    def __init__(self, spec: DCNJobSpec, process_id: int,
                 num_processes: int,
                 checkpoint_dir: Optional[str] = None,
                 ckpt_every: int = 0, restore: bool = False):
        import jax

        self.spec = spec
        self.pid = process_id
        self.nproc = num_processes
        self.ckpt_dir = checkpoint_dir
        self.ckpt_every = ckpt_every
        self.want_restore = restore
        self.source = spec.source_factory(process_id, num_processes)
        # emitted (key_id, window_start_ms, window_end_ms, value)
        self.rows_key = []
        self.rows_start = []
        self.rows_end = []
        self.rows_val = []
        self._persisted_chunks = 0   # rows chunks already in a checkpoint
        self.cycle = 0
        self._next_cid = 1

        from flink_tpu.parallel.mesh import MeshContext

        self.n = len(jax.devices())
        self.L = len(jax.local_devices())
        if self.n != self.L * num_processes:
            raise RuntimeError(
                f"expected {self.L}x{num_processes} global devices, "
                f"got {self.n}"
            )
        self.ctx = MeshContext.create(self.n, spec.max_parallelism)
        # per-host lane budget, one equal slice per local device
        self.B_local = max(self.L, (spec.batch_per_host // self.L) * self.L)
        mode = spec.ingest_partitioner
        if spec.rebalance:
            mode = "rebalance"
        ring_kw = dict(
            recv_timeout_s=spec.peer_recv_timeout_s,
            reconnect_attempts=spec.peer_reconnect_attempts,
            reconnect_backoff_s=spec.peer_reconnect_backoff_s,
        )
        if mode in ("forward", "rescale") or num_processes == 1:
            self._ring, self._router = None, None
        elif mode == "rebalance":
            self._ring = _RebalanceRing(process_id, num_processes,
                                        spec.rebalance_addrs, **ring_kw)
            self._router = None
        elif mode in ("shuffle", "global"):
            self._ring = None
            self._router = _TargetRing(process_id, num_processes,
                                       spec.rebalance_addrs, **ring_kw)
        else:
            raise ValueError(
                f"unknown ingest_partitioner {mode!r} (forward | rescale "
                f"| rebalance | shuffle | global)")
        self._mode = mode
        if spec.steps_per_dispatch > 1:
            # explicit single-step fallback (never silent): fused
            # dispatch would hold batches across collective rounds, but
            # every host must enter the same all_to_all in the same
            # round — a host with a full slot and a host with a partial
            # one would deadlock the lockstep
            print(
                f"[dcn] pipeline.steps-per-dispatch="
                f"{spec.steps_per_dispatch} does not apply to the "
                f"lockstep DCN plane; running single-step dispatch",
                file=sys.stderr,
            )
        self.ingested_local = 0   # records this host's lanes carried
        # per-host resident mode (ISSUE 20b): subclasses that support it
        # set self._drain + self._resident_depth in _build_step
        self._drain = None
        self._resident_depth = 0
        self._build_step()
        if getattr(spec, "resident", False) and self._drain is None:
            raise ValueError(
                "DCNJobSpec.resident requires a time-window job "
                "(window_kind='time'); session/rolling/cep runners keep "
                "single-step lockstep dispatch"
            )
        self._init_state()

    # -- mesh plumbing ----------------------------------------------------
    def _mk_lane_sharding(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from flink_tpu.parallel.mesh import SHARD_AXIS

        self._lane_sharding = NamedSharding(mesh, P(SHARD_AXIS))
        # slot-major stacks for the resident drain: [depth, B] with the
        # slot axis replicated and the lane axis process-sharded
        self._slot_sharding = NamedSharding(mesh, P(None, SHARD_AXIS))

    def _init_state(self):
        self.state = self._init_fn()
        self.local_wm_ticks = -(2**31) + 1
        if self.want_restore and self.ckpt_dir:
            self._restore_latest()

    def _global(self, local: np.ndarray):
        """Assemble a global [nproc*B_local] mesh-sharded array from this
        process's local lanes (jax.make_array_from_process_local_data:
        the host→local-device feed of the ingestion design)."""
        import jax

        return jax.make_array_from_process_local_data(
            self._lane_sharding, local
        )

    # -- ingest partitioning ----------------------------------------------
    def _poll_budget(self) -> int:
        """Per-cycle source poll bound. Routed modes bound the MERGED
        inflow by the lane budget: global concentrates every host's poll
        on host 0 (sum of polls <= B), shuffle's balanced per-donor split
        hands each receiver at most ceil(poll/nproc) per donor (sum <= B
        after the nproc safety margin). A frame must also fit the ring
        sockets' buffers so sendall can't deadlock the lockstep."""
        B = self.B_local
        if self._router is None:
            return B
        frame_cap = _RebalanceRing._SOCKBUF // 32   # ~21 B/record + slack
        if self._mode == "global":
            return max(1, min(B // self.nproc, frame_cap))
        return max(1, min(B - self.nproc, frame_cap))

    def _targets(self, n: int) -> np.ndarray:
        """Destination host per polled record. shuffle: a balanced random
        assignment — each record's destination is uniform, each cycle's
        per-donor counts are equal to within one, so lane budgets hold
        (the reference's ShufflePartitioner draws per record and relies
        on elastic buffers; fixed lane budgets need the balance).
        global: everything to host 0 (GlobalPartitioner.java)."""
        if self._mode == "global":
            return np.zeros(n, np.uint8)
        # modulo in int64: uint8 arange wraps at 256, which would skew
        # the per-target counts past the lane-budget margin for any
        # nproc that doesn't divide 256
        base = (np.arange(n, dtype=np.int64) % self.nproc).astype(np.uint8)
        rng = np.random.default_rng((self.pid, self.cycle))
        return rng.permutation(base)

    # -- host loop ---------------------------------------------------------
    def _poll_chunk(self, exhausted: bool, exchange: bool = True):
        """One padded ingest chunk: poll the source, run the ring /
        router side channels when ``exchange`` (the DCN boundary —
        resident mode's follow-up chunks stay host-local), pad to the
        lane budget and advance the local watermark. Returns ``(hi, lo,
        ts, values, valid, m, done_now, exhausted)``."""
        from flink_tpu.ops.hashing import key_identity64

        spec = self.spec
        B = self.B_local
        poll_budget = self._poll_budget()
        if not exhausted:
            keys, ts_ms, vals, exhausted = self.source.poll(poll_budget)
        else:
            keys = np.zeros(0, np.int64)
            ts_ms = np.zeros(0, np.int64)
            vals = np.zeros(0, np.float32)
        done_now = exhausted
        if self._router is not None:
            # targeted routing (shuffle/global): stamp destinations,
            # relay around the ring, ingest what lands here. The
            # per-host watermark advances from the SOURCE's (pre-
            # route) timestamps: the routed mix contains other
            # hosts' later timestamps, and a watermark read off the
            # merged batch would push the global pmin past records a
            # slower source hasn't polled yet (late-dropping them).
            # Source-side watermarks keep pmin = the true low mark.
            if len(ts_ms):
                rel_max = int(np.asarray(  # host-sync-ok: source-poll numpy, no device array
                    ts_ms, np.int64).max()) \
                    - spec.origin_ms
                self.local_wm_ticks = min(max(
                    self.local_wm_ticks,
                    rel_max - spec.out_of_orderness_ms - 1,
                ), MAX_TICKS)
            if exchange:
                keys, ts_ms, vals, all_done = self._router.route(
                    keys, ts_ms, vals,
                    self._targets(len(keys)), exhausted,
                )
                done_now = all_done and len(keys) == 0
            else:
                # resident follow-up chunk: the records stay on the
                # polling host's lanes (the device all_to_all still
                # delivers each to the owning shard, so results are
                # unchanged — host-level placement waits for the next
                # boundary), and peer done flags are only learned at
                # boundaries
                done_now = False
        if self._ring is not None:
            if exchange:
                # physical rebalance: offer spare lanes to the ring
                # neighbor's backlog, serve the other neighbor's
                # request from MY backlog (every process, every
                # boundary — lockstep)
                rk, rt, rv, donor_done = self._ring.exchange(
                    B - len(keys), self.source.poll
                )
                if len(rk):
                    keys = np.concatenate([keys, rk])
                    ts_ms = np.concatenate([ts_ms, rt])
                    vals = np.concatenate([vals, rv])
                # keep cycling while the donor neighbor has records
                done_now = exhausted and donor_done and not len(rk)
            else:
                done_now = False
        m = len(keys)
        self.ingested_local += m
        h = key_identity64(keys) if m else np.zeros(0, np.uint64)
        hi = np.zeros(B, np.uint32)
        lo = np.zeros(B, np.uint32)
        hi[:m] = (h >> np.uint64(32)).astype(np.uint32)
        lo[:m] = (h & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        ts = np.zeros(B, np.int32)
        if m:
            rts = np.asarray(  # host-sync-ok: source-poll numpy, no device array
                ts_ms, np.int64) - spec.origin_ms
            if int(rts.max()) > MAX_TICKS or int(rts.min()) < 0:
                # refuse rather than silently clamp (clamped records
                # would all collapse into the MAX_TICKS window)
                bad = (int(rts.min()) if int(rts.min()) < 0
                       else int(rts.max()))
                raise ValueError(
                    f"timestamp {bad + spec.origin_ms} out of int32 "
                    f"tick range relative to origin_ms="
                    f"{spec.origin_ms}; set DCNJobSpec.origin_ms to "
                    f"(at most) the stream's first timestamp"
                )
            ts[:m] = rts.astype(np.int32)
        values = np.zeros(B, np.float32)
        values[:m] = vals
        valid = np.zeros(B, bool)
        valid[:m] = True
        if m and self._router is None:
            # routed modes advanced the watermark pre-route (above)
            self.local_wm_ticks = min(max(
                self.local_wm_ticks,
                int(rts.max()) - spec.out_of_orderness_ms - 1,
            ), MAX_TICKS)
        return hi, lo, ts, values, valid, m, done_now, exhausted

    def run(self) -> dict:
        if getattr(self.spec, "resident", False):
            return self._run_resident()
        exhausted = False
        while True:
            (hi, lo, ts, values, valid, _m, done_now,
             exhausted) = self._poll_chunk(exhausted)
            wm_now = MAX_TICKS if done_now else self.local_wm_ticks
            wm = np.full(self.L, np.int32(wm_now))
            done = np.full(self.L, np.int32(1 if done_now else 0))

            self.state, aux, stop = self._step(
                self.state, self._global(hi), self._global(lo),
                self._global(ts), self._global(values), self._global(valid),
                self._global(wm), self._global(done),
            )
            self._emit_local(aux)
            self.cycle += 1
            # NO exhausted gate: with unequal partitions one host drains
            # early, and gating on the local flag would leave the ensemble
            # unable to ever complete another checkpoint (a drained
            # source's offset snapshot is simply its final offset)
            if self.ckpt_dir and self.ckpt_every and \
                    self.cycle % self.ckpt_every == 0:
                self._write_checkpoint()
            if int(np.asarray(stop)) == 1:  # host-sync-ok: lockstep stop decision, one fetch per dispatch
                break
        return self._finish()

    def _run_resident(self) -> dict:
        """Per-host resident mode (ISSUE 20b): each cycle polls up to
        ``resident_ring_depth`` chunks — the FIRST runs the DCN
        side-channel exchange (the drain boundary); follow-ups stay
        host-local — and retires them all in ONE drain dispatch.
        Stop / watermark / fill agreement ride the drain kernel's
        collectives, and the side channels' frame deadlines scale with
        the slots the previous drain retired (a host deep in a long
        drain is making progress, not stalled)."""
        drain = self._drain   # __init__ guarantees this for resident specs
        B = self.B_local
        D = self._resident_depth
        exhausted = False
        drained_prev = 1
        while True:
            for ch in (self._ring, self._router):
                if ch is not None:
                    ch.deadline_scale = max(1.0, float(drained_prev))
            hi_s = np.zeros((D, B), np.uint32)
            lo_s = np.zeros((D, B), np.uint32)
            ts_s = np.zeros((D, B), np.int32)
            val_s = np.zeros((D, B), np.float32)
            ok_s = np.zeros((D, B), bool)
            wm_s = np.empty((D, self.L), np.int32)
            fill = 0
            done_now = False
            for ci in range(D):
                (hi, lo, ts, values, valid, m, done_now,
                 exhausted) = self._poll_chunk(exhausted, exchange=ci == 0)
                hi_s[fill], lo_s[fill], ts_s[fill] = hi, lo, ts
                val_s[fill], ok_s[fill] = values, valid
                wm_s[fill] = np.int32(
                    MAX_TICKS if done_now else self.local_wm_ticks)
                fill += 1
                if done_now or m == 0:
                    # a dry local poll ends the cycle early: padding the
                    # drain with empty slots buys nothing, and the next
                    # boundary may land records from peers
                    break
            wm_s[fill:] = wm_s[fill - 1]  # pad slots hold the frontier
            done = np.full(self.L, np.int32(1 if done_now else 0))
            fills = np.full(self.L, np.int32(fill))
            self.state, cfs, stop, drained = drain(
                self.state,
                self._gslots(hi_s), self._gslots(lo_s),
                self._gslots(ts_s), self._gslots(val_s),
                self._gslots(ok_s), self._gslots(wm_s),
                self._global(done), self._global(fills),
            )
            drained_prev = int(np.asarray(drained))  # host-sync-ok: drain boundary — the agreed count scales the next frame deadline
            self._emit_local_slots(cfs, drained_prev)
            self.cycle += 1
            if self.ckpt_dir and self.ckpt_every and \
                    self.cycle % self.ckpt_every == 0:
                self._write_checkpoint()
            if int(np.asarray(stop)) == 1:  # host-sync-ok: lockstep stop decision, one fetch per dispatch
                break
        return self._finish()

    def _gslots(self, local: np.ndarray):
        """Assemble a [depth, B_local] host stack into the global
        [depth, B] slot-major array (slot axis replicated, lane axis
        sharded across processes)."""
        import jax

        return jax.make_array_from_process_local_data(
            self._slot_sharding, local
        )

    def _finish(self) -> dict:
        if self._ring is not None:
            self._ring.close()
        if self._router is not None:
            self._router.close()
        return {
            "key_id": (np.concatenate(self.rows_key)
                       if self.rows_key else np.zeros(0, np.uint64)),
            "window_start_ms": (np.concatenate(self.rows_start)
                                if self.rows_start
                                else np.zeros(0, np.int64)),
            "window_end_ms": (np.concatenate(self.rows_end)
                              if self.rows_end else np.zeros(0, np.int64)),
            "value": (np.concatenate(self.rows_val)
                      if self.rows_val else np.zeros(0, np.float32)),
            "cycles": self.cycle,
            "ingested_local": self.ingested_local,
            "dropped_capacity": self._state_dropped(),
        }

    def _state_dropped(self) -> int:
        """Sum the device state's drop counter over THIS process's
        shards. The counter lives in the checkpointed state (exchange
        overflow + table-full drops fold into it inside the step), so
        it survives kill-recover — a run that lost records can never
        report an affirmative zero."""
        # no silent-zero guard: a runner state without the counter is a
        # bug, and reporting an affirmative 0 for it would be exactly the
        # false assurance this accessor exists to prevent
        dc = self.state.dropped_capacity
        return int(sum(
            np.asarray(s.data).sum() for s in dc.addressable_shards
        ))

    # -- checkpoint / restore ---------------------------------------------
    # Deterministic lockstep cadence: every process reaches cycle k
    # together, so "all P proc files for cid exist" is a consistent global
    # cut (the step boundary IS the barrier, SURVEY §3.4).
    def _write_checkpoint(self):
        import jax

        cid = self._next_cid
        # fault seam: a raising rule models a process crashing mid-cut;
        # the lockstep cadence means the ensemble's OTHER procs publish
        # their halves and restore skips the globally-incomplete cid
        faults.inject("dcn.ckpt.write", pid=self.pid, cid=cid)
        d = os.path.join(self.ckpt_dir, f"chk-{cid:06d}")
        os.makedirs(d, exist_ok=True)
        leaves, _ = jax.tree_util.tree_flatten(self.state)
        arrs = {}
        for i, leaf in enumerate(leaves):
            shards = sorted(leaf.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            arrs[f"leaf{i}"] = np.concatenate(
                [np.asarray(s.data) for s in shards], axis=0
            )
        # emission DELTA since the previous checkpoint: each checkpoint is
        # O(new rows), and restore replays the deltas in cid order (the
        # per-checkpoint sink-offset pattern of runtime/checkpoint.py)
        dk = self.rows_key[self._persisted_chunks:]
        ds = self.rows_start[self._persisted_chunks:]
        de = self.rows_end[self._persisted_chunks:]
        dv = self.rows_val[self._persisted_chunks:]
        arrs["rows_key"] = (np.concatenate(dk) if dk
                            else np.zeros(0, np.uint64))
        arrs["rows_start"] = (np.concatenate(ds) if ds
                              else np.zeros(0, np.int64))
        arrs["rows_end"] = (np.concatenate(de) if de
                            else np.zeros(0, np.int64))
        arrs["rows_val"] = (np.concatenate(dv) if dv
                            else np.zeros(0, np.float32))
        tmpf = tempfile.NamedTemporaryFile(
            dir=d, prefix=f"proc-{self.pid}.", suffix=".tmp", delete=False
        )
        np.savez(tmpf, **arrs)
        tmpf.close()
        os.replace(tmpf.name, os.path.join(d, f"proc-{self.pid}.npz"))
        meta = {
            "cycle": self.cycle,
            "wm_ticks": self.local_wm_ticks,
            "source": self.source.snapshot(),
            "next_cid": cid + 1,
        }
        tmp = os.path.join(d, f"proc-{self.pid}.meta.tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, os.path.join(d, f"proc-{self.pid}.meta.json"))
        self._next_cid = cid + 1
        self._persisted_chunks = len(self.rows_key)

    def _latest_complete(self) -> Optional[str]:
        if not os.path.isdir(self.ckpt_dir):
            return None
        best = None
        for name in sorted(os.listdir(self.ckpt_dir)):
            if not name.startswith("chk-"):
                continue
            d = os.path.join(self.ckpt_dir, name)
            if all(
                os.path.exists(os.path.join(d, f"proc-{p}.meta.json"))
                for p in range(self.nproc)
            ):
                best = d
        return best

    def _restore_latest(self):
        import jax

        d = self._latest_complete()
        if d is None:
            return
        # fault seam: restore-time read of this process's half of the cut
        faults.inject("dcn.ckpt.read", pid=self.pid)
        with open(os.path.join(d, f"proc-{self.pid}.meta.json")) as f:
            meta = json.load(f)
        data = np.load(os.path.join(d, f"proc-{self.pid}.npz"))
        leaves, treedef = jax.tree_util.tree_flatten(self.state)
        new_leaves = []
        for i, leaf in enumerate(leaves):
            new_leaves.append(jax.make_array_from_process_local_data(
                leaf.sharding, data[f"leaf{i}"]
            ))
        self.state = jax.tree_util.tree_unflatten(treedef, new_leaves)
        # emissions = concatenation of every delta up to (and including)
        # the restored cut; deltas past it belong to a globally
        # incomplete checkpoint and will be re-emitted by replay
        self.rows_key, self.rows_start = [], []
        self.rows_end, self.rows_val = [], []
        chosen = os.path.basename(d)
        for name in sorted(os.listdir(self.ckpt_dir)):
            if not name.startswith("chk-") or name > chosen:
                continue
            delta = np.load(os.path.join(
                self.ckpt_dir, name, f"proc-{self.pid}.npz"
            ))
            if len(delta["rows_key"]):
                self.rows_key.append(delta["rows_key"])
                self.rows_start.append(
                    delta["rows_start"] if "rows_start" in delta
                    else np.zeros(len(delta["rows_key"]), np.int64)
                )
                self.rows_end.append(delta["rows_end"])
                self.rows_val.append(delta["rows_val"])
        self._persisted_chunks = len(self.rows_key)
        self.cycle = int(meta["cycle"])
        self._next_cid = int(meta["next_cid"])
        self.local_wm_ticks = int(meta["wm_ticks"])
        self.source.restore(meta["source"])


class DCNWindowRunner(_DCNRunnerBase):
    """Aligned time windows (tumbling AND sliding via the pane ring) over
    the global mesh; the keyed shuffle is ONE all_to_all
    (RecordWriter.java:82 redesigned)."""

    def _build_step(self):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from flink_tpu.ops import window_kernels as wk
        from flink_tpu.parallel.exchange import bucket_capacity
        from flink_tpu.parallel.mesh import SHARD_AXIS
        from flink_tpu.runtime.step import (
            WindowStageSpec,
            exchange_update_shard,
        )

        spec = self.spec
        n = self.n
        maxp = spec.max_parallelism
        if spec.size_ms <= 0:
            raise ValueError(
                "time-window DCN job requires size_ms > 0 "
                "(set DCNJobSpec.size_ms)"
            )
        slide = spec.slide_ms or spec.size_ms
        if spec.size_ms % slide:
            raise ValueError(
                f"size_ms {spec.size_ms} must be a multiple of slide_ms "
                f"{slide}"
            )
        ppw = spec.size_ms // slide
        # ring covers in-flight windows + out-of-orderness backlog (the
        # executor's sizing, executor.py setup())
        ring = max(8, 2 * ppw + spec.out_of_orderness_ms // slide + 4)
        self.win = wk.WindowSpec(
            size_ticks=spec.size_ms, slide_ticks=slide,
            ring=ring, fires_per_step=spec.fires_per_step,
        )
        self.red = wk.ReduceSpec(kind=spec.reduce_kind)
        win, red = self.win, self.red
        bpd = self.B_local // self.L    # lanes per device
        cap = bucket_capacity(bpd, n, 2.0)
        self.bucket_cap = cap
        starts, ends = self.ctx.kg_bounds()
        starts_j = jnp.asarray(starts)
        ends_j = jnp.asarray(ends)
        F = spec.fires_per_step
        C = spec.capacity_per_shard
        probe_len = 16
        mesh = self.ctx.mesh

        stage = WindowStageSpec(win=win, red=red, capacity_per_shard=C,
                                probe_len=probe_len)

        def shard_body(state, kg_start, kg_end, hi, lo, ts, values, valid,
                       wm, done):
            state = jax.tree_util.tree_map(lambda x: x[0], state)
            kg_start, kg_end = kg_start[0], kg_end[0]
            # global control values: decisions ride the same fabric as
            # records, so every process sees identical results and the
            # lockstep invariant holds by construction
            gwm = jax.lax.pmin(wm[0], SHARD_AXIS)
            gdone = jax.lax.pmin(done[0], SHARD_AXIS)
            # the cross-host keyed shuffle: ONE all_to_all over the
            # global mesh (RecordWriter.java:82 redesigned) — shared body
            # with the single-host exchange step (runtime/step.py)
            state, _ = exchange_update_shard(
                state, stage, kg_start, kg_end, hi, lo, ts, values, valid,
                n, maxp, cap,
            )
            state, fr = wk.advance_and_fire(state, win, red, gwm)
            cf = wk.compact_fires(state.table, fr)
            # fire backlog: a full on-time lane set means more window ends
            # may be due — every process must keep stepping
            pending = (jnp.sum(fr.lane_valid[:F], dtype=jnp.int32)
                       >= jnp.int32(F)).astype(jnp.int32)
            gpending = jax.lax.pmax(pending, SHARD_AXIS)
            stop = gdone * (1 - gpending)
            pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
            return pack(state), pack(cf), stop

        sharded = shard_map(
            shard_body, mesh=mesh,
            in_specs=(
                P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                # batch lanes are SPLIT over the global mesh: each host's
                # records sit on its local devices only
                P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS),
                P(SHARD_AXIS), P(SHARD_AXIS),
            ),
            out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
            check_vma=False,
        )

        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, hi, lo, ts, values, valid, wm, done):
            return sharded(state, starts_j, ends_j, hi, lo, ts, values,
                           valid, wm, done)

        self._step = step

        def sharded_init():
            st = wk.init_state(C, probe_len, win, red)
            return jax.tree_util.tree_map(lambda x: x[None], st)

        self._init_fn = jax.jit(shard_map(
            sharded_init, mesh=mesh, in_specs=(),
            out_specs=P(SHARD_AXIS), check_vma=False,
        ))
        self._mk_lane_sharding(mesh)

        if getattr(spec, "resident", False):
            # per-host resident mode (ISSUE 20b): same stage spec and
            # bucket capacity as the lockstep step — the drain IS the
            # lockstep body run up to resident_ring_depth times per
            # dispatch, with control collectives at the boundary
            from flink_tpu.runtime.step import (
                build_window_dcn_resident_drain,
            )

            self._resident_depth = max(1, int(spec.resident_ring_depth))
            self._drain = build_window_dcn_resident_drain(
                self.ctx, stage, bpd, self._resident_depth,
                capacity_factor=2.0,
            )

    def _emit_local_slots(self, cfs, drained: int):
        """Resident-drain fires: [n_shards, depth, ...] stacks — emit
        the first ``drained`` slots of each addressable shard in slot
        order (pad slots past the agreed count never fired)."""
        for (counts_sh, lanes_sh, ends_sh, khi_sh, klo_sh,
             vals_sh) in zip(
                cfs.counts.addressable_shards,
                cfs.lane_valid.addressable_shards,
                cfs.window_end_ticks.addressable_shards,
                cfs.key_hi.addressable_shards,
                cfs.key_lo.addressable_shards,
                cfs.values.addressable_shards):
            counts = np.asarray(counts_sh.data)[0]  # host-sync-ok: fire-payload fetch AFTER the drain retired
            lanes = np.asarray(lanes_sh.data)[0]  # host-sync-ok: fire-payload fetch
            ends = np.asarray(ends_sh.data)[0]  # host-sync-ok: fire-payload fetch
            khi = None
            for i in range(min(int(drained), counts.shape[0])):
                for f in np.nonzero(lanes[i])[0]:
                    c = int(counts[i, f])
                    if c == 0:
                        continue
                    if khi is None:
                        khi = np.asarray(khi_sh.data)[0]  # host-sync-ok: lazy key fetch, only when a slot fired
                        klo = np.asarray(klo_sh.data)[0]  # host-sync-ok: lazy key fetch
                        vv = np.asarray(vals_sh.data)[0]  # host-sync-ok: lazy value fetch
                    k64 = (khi[i, f, :c].astype(np.uint64)
                           << np.uint64(32)) \
                        | klo[i, f, :c].astype(np.uint64)
                    end_ms = int(ends[i, f]) + self.spec.origin_ms
                    self.rows_key.append(k64)
                    self.rows_start.append(
                        np.full(c, end_ms - self.spec.size_ms, np.int64))
                    self.rows_end.append(np.full(c, end_ms, np.int64))
                    self.rows_val.append(vv[i, f, :c].astype(np.float32))

    def _emit_local(self, cf):
        """Each process emits fires from ITS addressable shards only —
        "records enter on host A, fire from host B" is literal: the keys
        in these rows arrived via the all_to_all from whichever host
        ingested them."""
        size = self.spec.size_ms
        for (counts_sh, lanes_sh, ends_sh, khi_sh, klo_sh,
             vals_sh) in zip(
                cf.counts.addressable_shards,
                cf.lane_valid.addressable_shards,
                cf.window_end_ticks.addressable_shards,
                cf.key_hi.addressable_shards, cf.key_lo.addressable_shards,
                cf.values.addressable_shards):
            counts = np.asarray(counts_sh.data)[0]
            lanes = np.asarray(lanes_sh.data)[0]
            ends = np.asarray(ends_sh.data)[0]
            khi = None
            for f in np.nonzero(lanes)[0]:
                c = int(counts[f])
                if c == 0:
                    continue
                if khi is None:
                    khi = np.asarray(khi_sh.data)[0]
                    klo = np.asarray(klo_sh.data)[0]
                    vv = np.asarray(vals_sh.data)[0]
                k64 = (khi[f, :c].astype(np.uint64) << np.uint64(32)) \
                    | klo[f, :c].astype(np.uint64)
                end_ms = int(ends[f]) + self.spec.origin_ms
                self.rows_key.append(k64)
                self.rows_start.append(np.full(c, end_ms - size, np.int64))
                self.rows_end.append(np.full(c, end_ms, np.int64))
                self.rows_val.append(vv[f, :c].astype(np.float32))


class DCNSessionRunner(_DCNRunnerBase):
    """Gap-merged session windows over the global mesh. The session
    kernel is replicate-and-mask (ops/session_windows — every shard scans
    the batch and keeps its key groups), so the DCN hop is ONE
    ``all_gather`` of each host's lanes onto every shard; watermark and
    termination ride pmin exactly like the time-window runner. Sessions
    merging records from DIFFERENT hosts is the point: the gap merge
    happens in the owning shard's device state wherever the records
    entered."""

    def _build_step(self):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from flink_tpu.ops import session_windows as sw
        from flink_tpu.ops import window_kernels as wk
        from flink_tpu.ops.hashing import route_hash
        from flink_tpu.core.keygroups import assign_to_key_group
        from flink_tpu.parallel.mesh import SHARD_AXIS

        spec = self.spec
        if spec.gap_ms <= 0:
            raise ValueError("session DCN job requires gap_ms > 0")
        maxp = spec.max_parallelism
        self.red = wk.ReduceSpec(kind=spec.reduce_kind)
        red = self.red
        gap = spec.gap_ms
        starts, ends = self.ctx.kg_bounds()
        starts_j = jnp.asarray(starts)
        ends_j = jnp.asarray(ends)
        C = spec.capacity_per_shard
        probe_len = 16
        mesh = self.ctx.mesh

        def shard_body(state, kg_start, kg_end, hi, lo, ts, values, valid,
                       wm, done):
            state = jax.tree_util.tree_map(lambda x: x[0], state)
            kg_start, kg_end = kg_start[0], kg_end[0]
            gwm = jax.lax.pmin(wm[0], SHARD_AXIS)
            gdone = jax.lax.pmin(done[0], SHARD_AXIS)
            # the DCN hop: every shard sees every host's lanes (the
            # replicate side of replicate-and-mask; traffic-equivalent to
            # the single-host step's replicated batch feed)
            hi_g = jax.lax.all_gather(hi, SHARD_AXIS, tiled=True)
            lo_g = jax.lax.all_gather(lo, SHARD_AXIS, tiled=True)
            ts_g = jax.lax.all_gather(ts, SHARD_AXIS, tiled=True)
            va_g = jax.lax.all_gather(values, SHARD_AXIS, tiled=True)
            ok_g = jax.lax.all_gather(valid, SHARD_AXIS, tiled=True)
            kg = assign_to_key_group(route_hash(hi_g, lo_g, jnp), maxp,
                                     jnp)
            mine = ok_g & (kg >= kg_start.astype(jnp.uint32)) & (
                kg <= kg_end.astype(jnp.uint32)
            )
            state, old_f, mid_f, wm_f = sw.update_and_fire(
                state, red, gap, hi_g, lo_g, ts_g, va_g, mine, gwm
            )
            # slot-space wm fires carry no keys — attach them here so the
            # host never needs the (donated) state
            wkeys = state.table.keys
            wm_out = (wkeys[:, 0], wkeys[:, 1]) + tuple(wm_f)
            # any records this step? sessions opened by the final batch
            # need ONE empty follow-up step at wm=MAX to flush, so stop
            # only on a globally record-free exhausted step
            has_rec = jnp.any(ok_g).astype(jnp.int32)
            stop = gdone * (1 - jax.lax.pmax(has_rec, SHARD_AXIS))
            pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
            return (pack(state), (pack(old_f), pack(mid_f), pack(wm_out)),
                    stop)

        sharded = shard_map(
            shard_body, mesh=mesh,
            in_specs=(
                P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS), P(SHARD_AXIS),
            ),
            out_specs=(
                P(SHARD_AXIS),
                (P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
                P(),
            ),
            check_vma=False,
        )

        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, hi, lo, ts, values, valid, wm, done):
            return sharded(state, starts_j, ends_j, hi, lo, ts, values,
                           valid, wm, done)

        self._step = step

        def sharded_init():
            st = sw.init_state(C, probe_len, red)
            return jax.tree_util.tree_map(lambda x: x[None], st)

        self._init_fn = jax.jit(shard_map(
            sharded_init, mesh=mesh, in_specs=(),
            out_specs=P(SHARD_AXIS), check_vma=False,
        ))
        self._mk_lane_sharding(mesh)

    def _emit_local(self, aux):
        """Session fires from this process's addressable shards: two
        lane-space sets (superseded/merged) carrying their own keys, plus
        the slot-space watermark-close set keyed by the table rows."""
        old_f, mid_f, wm_out = aux
        origin = self.spec.origin_ms
        for fire in (old_f, mid_f):
            khi_l, klo_l, st_l, en_l, va_l, mk_l = (
                a.addressable_shards for a in fire
            )
            for khi_s, klo_s, st_s, en_s, va_s, mk_s in zip(
                    khi_l, klo_l, st_l, en_l, va_l, mk_l):
                mask = np.asarray(mk_s.data)[0]
                sel = np.nonzero(mask)[0]
                if not sel.size:
                    continue
                khi = np.asarray(khi_s.data)[0][sel]
                klo = np.asarray(klo_s.data)[0][sel]
                self._push_rows(
                    khi, klo,
                    np.asarray(st_s.data)[0][sel],
                    np.asarray(en_s.data)[0][sel],
                    np.asarray(va_s.data)[0][sel], origin,
                )
        wkhi_l, wklo_l, st_l, en_l, va_l, mk_l = (
            a.addressable_shards for a in wm_out
        )
        for khi_s, klo_s, st_s, en_s, va_s, mk_s in zip(
                wkhi_l, wklo_l, st_l, en_l, va_l, mk_l):
            mask = np.asarray(mk_s.data)[0]
            sel = np.nonzero(mask)[0]
            if not sel.size:
                continue
            self._push_rows(
                np.asarray(khi_s.data)[0][sel],
                np.asarray(klo_s.data)[0][sel],
                np.asarray(st_s.data)[0][sel],
                np.asarray(en_s.data)[0][sel],
                np.asarray(va_s.data)[0][sel], origin,
            )

    def _push_rows(self, khi, klo, starts, ends, vals, origin):
        k64 = (khi.astype(np.uint64) << np.uint64(32)) | klo.astype(
            np.uint64)
        self.rows_key.append(k64)
        self.rows_start.append(starts.astype(np.int64) + origin)
        # kernel fire `end` is already last + gap (session TimeWindow
        # semantics, ops/session_windows.update_and_fire docstring)
        self.rows_end.append(ends.astype(np.int64) + origin)
        self.rows_val.append(vals.astype(np.float32))


class DCNRollingRunner(_DCNRunnerBase):
    """Rolling keyed reduce (the reference's StreamGroupedReduce on
    ValueState) over the global mesh: records route to their owner shard
    through the SAME one-collective keyed shuffle as the window runners
    (exchange_records), the owner applies the running reduce, and the
    per-record UPDATED aggregate emits from the owner shard. Per-key
    emission order equals per-key arrival order on the owning channel —
    the reference's partition-order guarantee; there is no cross-key
    global order, exactly as in the reference. Closes the "rolling
    cannot run multi-host" gap (VERDICT r4 missing #4 tail)."""

    def _build_step(self):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from flink_tpu.ops import rolling
        from flink_tpu.ops import window_kernels as wk
        from flink_tpu.parallel.exchange import (
            bucket_capacity,
            exchange_owned,
        )
        from flink_tpu.parallel.mesh import SHARD_AXIS

        spec = self.spec
        n = self.n
        maxp = spec.max_parallelism
        red = wk.ReduceSpec(kind=spec.reduce_kind)
        C = spec.capacity_per_shard
        probe_len = 16
        bpd = self.B_local // self.L
        cap = bucket_capacity(bpd, n, 2.0)
        self.bucket_cap = cap
        starts, ends = self.ctx.kg_bounds()
        starts_j = jnp.asarray(starts)
        ends_j = jnp.asarray(ends)
        mesh = self.ctx.mesh

        def shard_body(state, kg_start, kg_end, hi, lo, ts, values,
                       valid, wm, done):
            import dataclasses as _dc

            state = jax.tree_util.tree_map(lambda x: x[0], state)
            kg_start, kg_end = kg_start[0], kg_end[0]
            gdone = jax.lax.pmin(done[0], SHARD_AXIS)
            cols, r_hi, r_lo, mine, n_over = exchange_owned(
                {"values": values}, hi, lo, valid, n, maxp, cap,
                kg_start, kg_end,
            )
            state, outputs, out_valid = rolling.update(
                state, red, r_hi, r_lo, cols["values"], mine
            )
            # exchange-bucket overflow folds into the CHECKPOINTED state
            # counter alongside rolling.update's own table-full drops
            # (runtime/step.py:exchange_update_shard does the same) —
            # surfaced at run end via _state_dropped, surviving restore
            state = _dc.replace(
                state,
                dropped_capacity=state.dropped_capacity + n_over,
            )
            pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
            aux = (r_hi, r_lo, outputs, out_valid)
            # rolling has no fire backlog: the ensemble stops when every
            # source is drained
            return pack(state), pack(aux), gdone

        sharded = shard_map(
            shard_body, mesh=mesh,
            in_specs=(
                P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS), P(SHARD_AXIS),
            ),
            out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
            check_vma=False,
        )

        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, hi, lo, ts, values, valid, wm, done):
            return sharded(state, starts_j, ends_j, hi, lo, ts, values,
                           valid, wm, done)

        self._step = step

        def sharded_init():
            st = rolling.init_state(C, probe_len, red)
            return jax.tree_util.tree_map(lambda x: x[None], st)

        self._init_fn = jax.jit(shard_map(
            sharded_init, mesh=mesh, in_specs=(),
            out_specs=P(SHARD_AXIS), check_vma=False,
        ))
        self._mk_lane_sharding(mesh)

    def _emit_local(self, aux):
        """Emit (key, updated aggregate) per exchanged record from THIS
        process's shards. window_start/end are 0: rolling emissions are
        continuous per-record updates, not window results."""
        r_hi, r_lo, outputs, out_valid = aux
        for hi_sh, lo_sh, out_sh, val_sh in zip(
                r_hi.addressable_shards, r_lo.addressable_shards,
                outputs.addressable_shards, out_valid.addressable_shards):
            mask = np.asarray(val_sh.data)[0]
            idx = np.nonzero(mask)[0]
            if not len(idx):
                continue
            khi = np.asarray(hi_sh.data)[0][idx]
            klo = np.asarray(lo_sh.data)[0][idx]
            vals = np.asarray(out_sh.data)[0][idx]
            k64 = (khi.astype(np.uint64) << np.uint64(32)) \
                | klo.astype(np.uint64)
            self.rows_key.append(k64)
            self.rows_start.append(np.zeros(len(idx), np.int64))
            self.rows_end.append(np.zeros(len(idx), np.int64))
            self.rows_val.append(vals.astype(np.float32))


class DCNCEPRunner(_DCNRunnerBase):
    """Device count-NFA pattern matching over the global mesh — CEP
    multi-host, the last stage kind on VERDICT r4's cannot-run-multi-
    host list. Replicate-and-mask like the session runner: ONE
    all_gather puts every host's lanes on every shard, each shard
    advances the NFA for its own key groups (cep/device.py's segmented
    matrix scan), and match completions emit from the OWNER shard.
    Cross-host event order is the deterministic lockstep lane order
    (cycle-major, host-major) — the processing-time arrival-order
    semantics of the reference's operator. Stage predicates evaluate at
    the INGESTING host (bits packed in the value lane, see DCNJobSpec);
    the device carries only the bit masks, so arbitrary Python
    conditions cost nothing on the accelerator. within() is not carried
    here yet: its pane ring needs pane-quantized batches (cep/accel.py's
    host slicing), which the lockstep loop does not do — a
    pattern.within_ms raises rather than silently ignoring the bound.
    Match EXTRACTION stays host-side per the single-host engine's lazy
    replay; rows here are (key, completion ts, completions-at-event) —
    the match-count stream the CEP bench measures."""

    def _build_step(self):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from flink_tpu.cep import device as cdev
        from flink_tpu.core.keygroups import assign_to_key_group
        from flink_tpu.ops.hashing import route_hash
        from flink_tpu.parallel.mesh import SHARD_AXIS

        spec = self.spec
        if spec.cep_pattern_factory is None:
            raise ValueError(
                "cep DCN job requires DCNJobSpec.cep_pattern_factory"
            )
        pattern = spec.cep_pattern_factory()
        if getattr(pattern, "within_ms", None):
            raise ValueError(
                "within() is not supported on the DCN CEP runner yet "
                "(needs pane-quantized batches); run single-host via "
                "cep/accel.py or drop the within bound"
            )
        dspec = cdev.DevicePatternSpec.from_pattern(pattern)
        S = dspec.n_stages
        if S > 24:
            raise ValueError(
                f"{S} stages exceed the 24 mask bits a float32 value "
                f"lane carries exactly"
            )
        maxp = spec.max_parallelism
        C = spec.capacity_per_shard
        probe_len = 16
        starts, ends = self.ctx.kg_bounds()
        starts_j = jnp.asarray(starts)
        ends_j = jnp.asarray(ends)
        mesh = self.ctx.mesh

        def shard_body(state, kg_start, kg_end, hi, lo, ts, values,
                       valid, wm, done):
            state = jax.tree_util.tree_map(lambda x: x[0], state)
            kg_start, kg_end = kg_start[0], kg_end[0]
            gdone = jax.lax.pmin(done[0], SHARD_AXIS)
            # the DCN hop: every shard sees every host's lanes
            hi_g = jax.lax.all_gather(hi, SHARD_AXIS, tiled=True)
            lo_g = jax.lax.all_gather(lo, SHARD_AXIS, tiled=True)
            ts_g = jax.lax.all_gather(ts, SHARD_AXIS, tiled=True)
            va_g = jax.lax.all_gather(values, SHARD_AXIS, tiled=True)
            ok_g = jax.lax.all_gather(valid, SHARD_AXIS, tiled=True)
            bits = va_g.astype(jnp.int32)
            masks = ((bits[:, None] >> jnp.arange(S, dtype=jnp.int32))
                     & 1).astype(bool)
            kg = assign_to_key_group(route_hash(hi_g, lo_g, jnp), maxp,
                                     jnp)
            mine = ok_g & (kg >= kg_start.astype(jnp.uint32)) & (
                kg <= kg_end.astype(jnp.uint32)
            )
            state, delta, _total = cdev.advance(
                state, dspec, hi_g, lo_g, masks, mine
            )
            pack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
            aux = (hi_g, lo_g, ts_g, delta)
            # count-NFA matches complete on arrival: nothing flushes at
            # end of stream, so stop when every source is drained
            return pack(state), pack(aux), gdone

        sharded = shard_map(
            shard_body, mesh=mesh,
            in_specs=(
                P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS), P(SHARD_AXIS),
            ),
            out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
            check_vma=False,
        )

        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, hi, lo, ts, values, valid, wm, done):
            return sharded(state, starts_j, ends_j, hi, lo, ts, values,
                           valid, wm, done)

        self._step = step

        def sharded_init():
            st = cdev.init_state(C, probe_len, dspec)
            return jax.tree_util.tree_map(lambda x: x[None], st)

        self._init_fn = jax.jit(shard_map(
            sharded_init, mesh=mesh, in_specs=(),
            out_specs=P(SHARD_AXIS), check_vma=False,
        ))
        self._mk_lane_sharding(mesh)

    def _emit_local(self, aux):
        """Emit (key, completion ts, matches-completed-at-event) from
        THIS process's shards — deltas are nonzero only on lanes whose
        key the shard owns."""
        hi_g, lo_g, ts_g, delta = aux
        origin = self.spec.origin_ms
        for hi_sh, lo_sh, ts_sh, d_sh in zip(
                hi_g.addressable_shards, lo_g.addressable_shards,
                ts_g.addressable_shards, delta.addressable_shards):
            d = np.asarray(d_sh.data)[0]
            idx = np.nonzero(d)[0]
            if not len(idx):
                continue
            khi = np.asarray(hi_sh.data)[0][idx]
            klo = np.asarray(lo_sh.data)[0][idx]
            ts = np.asarray(ts_sh.data)[0][idx]
            k64 = (khi.astype(np.uint64) << np.uint64(32)) \
                | klo.astype(np.uint64)
            self.rows_key.append(k64)
            self.rows_start.append(np.zeros(len(idx), np.int64))
            self.rows_end.append(ts.astype(np.int64) + origin)
            self.rows_val.append(d[idx].astype(np.float32))


def runner_for_spec(spec: DCNJobSpec, process_id: int, num_processes: int,
                    **kw) -> _DCNRunnerBase:
    if spec.window_kind == "session":
        return DCNSessionRunner(spec, process_id, num_processes, **kw)
    if spec.window_kind == "time":
        return DCNWindowRunner(spec, process_id, num_processes, **kw)
    if spec.window_kind == "rolling":
        return DCNRollingRunner(spec, process_id, num_processes, **kw)
    if spec.window_kind == "cep":
        return DCNCEPRunner(spec, process_id, num_processes, **kw)
    raise ValueError(f"unknown window_kind {spec.window_kind!r}")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", required=True, help="HOST:PORT")
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--builder", required=True,
                    help="module:function returning a DCNJobSpec")
    ap.add_argument("--out", required=True, help="result .npz path")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--restore", action="store_true")
    a = ap.parse_args(argv)

    import jax

    from flink_tpu.core.jaxenv import refuse_cpu_fallback

    refuse_cpu_fallback()
    jax.distributed.initialize(
        coordinator_address=a.coordinator,
        num_processes=a.num_processes, process_id=a.process_id,
    )
    from flink_tpu.runtime.worker import load_builder

    spec = load_builder(a.builder)()
    runner = runner_for_spec(
        spec, a.process_id, a.num_processes,
        checkpoint_dir=a.checkpoint_dir or None,
        ckpt_every=a.ckpt_every, restore=a.restore,
    )
    out = runner.run()
    tmp = a.out + ".tmp"
    # lint: allow(fault-seam): one-shot CLI result dump after the job ended — not a recovery seam; a failure here is an ordinary process error
    with open(tmp, "wb") as f:    # file object: savez appends no suffix
        np.savez(f, key_id=out["key_id"],
                 window_start_ms=out["window_start_ms"],
                 window_end_ms=out["window_end_ms"], value=out["value"],
                 dropped_capacity=out["dropped_capacity"])
    # lint: allow(fault-seam): same one-shot result publish as the open above
    os.replace(tmp, a.out)
    print(json.dumps({"rows": int(len(out["key_id"])),
                      "cycles": out["cycles"], "pid": a.process_id,
                      "dropped_capacity": out["dropped_capacity"],
                      "ingested_local": int(out["ingested_local"])}),
          flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
