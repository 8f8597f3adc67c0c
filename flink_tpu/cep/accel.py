"""Production bridge putting the device CEP kernel behind CEP.pattern().

Division of labor (ref flink-cep NFA.java:132 / SharedBuffer — redesigned
for the batch/SPMD execution model instead of per-event JVM calls):

  * DEVICE (cep/device.py): per micro-batch, the segmented associative
    matrix scan advances EVERY key's match-count NFA and reports, per
    lane, how many matches COMPLETED there (`delta`). This is the
    detection engine — exact counts, no per-key host work.
  * HOST (this module): keeps, per key, only the COMPACTED stream of
    stage-matching events (the SharedBuffer analog — non-matching events
    are never stored) plus a one-bit gap marker per stored event ("were
    there intervening non-matching events of this key?"), which is all a
    linear NFA needs: a gap kills partials waiting on a STRICT stage and
    is invisible to RELAXED stages. Only when the device reports a
    completion for a key does the host replay that key's pending
    compacted events through the exact host NFA (cep/nfa.py) to build the
    {stage: event} match dicts.

Result: per-event Python work is O(predicate hits), NFA branching work is
O(events of completing keys), and the device scan decides both. For
detection workloads (rare matches over dense streams) this removes the
per-record NFA from the hot path entirely, the same way the window
kernels removed HeapReducingState.add.

Eligibility (executor falls back to the host operator otherwise):
processing-time mode (arrival order; the event-time buffer-and-sort
drain stays host-side). within() IS supported (round 4): partial counts
are bucketed by start-time pane on device (cep/device.py ring rotation
= expiry), semantics equal to the host NFA on pane-quantized timestamps
(cep.device.within-buckets config, default 8 buckets per within
horizon). parallelism>1 shards the count-NFA over the mesh by key group
(round 4, n_shards; replicate-and-mask + one psum). Checkpoint/
savepoint/restore are fully supported (snapshot()/restore() below; the
barrier is the step boundary).

Memory note: a key's compacted events stay buffered while it has live
partials that could still complete (exactly the events the reference's
SharedBuffer would be holding); keys whose device count-state is all
zero hold no buffer entries after their next replay.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from flink_tpu.cep.device import (
    CepShardState, DevicePatternSpec, advance, init_state,
)
from flink_tpu.cep.nfa import NFA
from flink_tpu.cep.pattern import Pattern, RELAXED
from flink_tpu.core.types import KeyCodec


def batch_gaps(inv: np.ndarray, hit: np.ndarray,
               trailing_in: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-hit-lane gap bits for one micro-batch, vectorized.

    inv[B]        factorized key id per lane (0..G-1)
    hit[B]        lane matched >=1 stage predicate
    trailing_in[G] per key-group: non-matching events of this key were
                  seen after its last stored event (carried across batches)

    Returns (gap[B] — True at hit lanes whose key saw >=1 non-hit event
    since its previous hit event; False elsewhere — and trailing_out[G]).
    """
    B = len(inv)
    if B == 0:
        return np.zeros(0, bool), trailing_in.copy()
    perm = np.argsort(inv, kind="stable")     # group by key, arrival order
    inv_s = inv[perm]
    hit_s = hit[perm]
    idx = np.arange(B)

    is_new = np.r_[True, inv_s[1:] != inv_s[:-1]]
    grp_id = np.cumsum(is_new) - 1            # dense group ids, sorted order
    grp_start = np.nonzero(is_new)[0]
    grp_key = inv_s[grp_start]                # group -> key factor id

    nh_before = np.cumsum(~hit_s) - (~hit_s)  # non-hits strictly before lane
    nhw = nh_before - nh_before[grp_start][grp_id]   # ...within the group

    ph = np.maximum.accumulate(np.where(hit_s, idx, -1))
    prev_hit = np.r_[-1, ph[:-1]]             # last hit at or before lane-1
    has_prev = prev_hit >= grp_start[grp_id]  # ...within the same group
    prev_nhw = np.where(has_prev, nhw[np.clip(prev_hit, 0, B - 1)], 0)

    tin_s = trailing_in[grp_key][grp_id]      # per-lane carried trailing bit
    gap_s = np.where(
        has_prev, (nhw - prev_nhw) > 0, (nhw > 0) | tin_s
    ) & hit_s

    # carry-out per key: non-hits after the key's last hit in this batch
    # (whole batch counts if the key had no hit — OR with the carried bit)
    grp_end = np.r_[grp_start[1:], B] - 1
    nh_total = nhw[grp_end] + (~hit_s[grp_end])
    last_hit = ph[grp_end]
    had_hit = last_hit >= grp_start
    nh_after = np.where(
        had_hit,
        nh_total - (nhw[np.clip(last_hit, 0, B - 1)]
                    + 0),                     # last_hit lane is a hit
        nh_total,
    )
    trailing_out = trailing_in.copy()
    trailing_out[grp_key] = np.where(
        had_hit, nh_after > 0, trailing_in[grp_key] | (nh_total > 0)
    )

    gap = np.zeros(B, bool)
    gap[perm] = gap_s
    return gap, trailing_out


class DeviceCepOperator:
    """Keyed CEP over micro-batches: device count-NFA detection + lazy
    host replay extraction. One instance per job.

    n_shards > 1 (round 4): the count-NFA state shards over the device
    mesh by key group — each shard holds its keys' tables and carry
    vectors and masks the batch to the key groups it owns
    (replicate-and-mask, the same exchange the window kernels default
    from at small batch); per-lane completion deltas are disjoint across
    shards, so one psum over the mesh axis reassembles the global [B]
    delta. The host side (compacted buffers, replay extraction) stays a
    single process, exactly like the executor's windowed path."""

    def __init__(self, pattern: Pattern, capacity: int = 1 << 16,
                 probe_len: int = 16, within_buckets: int = 8,
                 n_shards: int = 1, max_parallelism: int = 128):
        self.pattern = pattern
        self.spec = DevicePatternSpec.from_pattern(
            pattern, within_buckets=within_buckets
        )
        self.nfa = NFA(pattern)
        self.stages = pattern.stages
        self.codec = KeyCodec()
        self.capacity = 1 << max(1, int(capacity) - 1).bit_length()
        self.n_shards = n_shards
        self.max_parallelism = max_parallelism
        if n_shards > 1:
            self._init_sharded(probe_len)
        else:
            self.state: CepShardState = init_state(
                self.capacity, probe_len, self.spec
            )
            self._advance = jax.jit(
                advance, static_argnums=1, donate_argnums=0
            )
        # per-key host side (keyed by the 64-bit codec hash; original key
        # objects ride inside the buffered events for match extraction)
        self.buffers: Dict[int, List[Tuple[Any, bool, int]]] = {}
        self.partials: Dict[int, list] = {}
        self.trailing: Dict[int, bool] = {}
        # honesty metrics: the device count and host extraction must agree
        self.matches_detected = 0      # device-side completions
        self.matches_extracted = 0     # host-replay match dicts
        self.steps = 0
        # within(): panes rebase to the first batch's pane so epoch-ms
        # timestamps fit the device's int32 pane arithmetic
        self._pane_origin: Optional[int] = None

    def _init_sharded(self, probe_len: int):
        """Build the SPMD advance step: state sharded [S, ...] over the
        mesh, batch replicated, key-group masking per shard, deltas
        reassembled with one psum."""
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from flink_tpu.core.keygroups import assign_to_key_group
        from flink_tpu.ops.hashing import route_hash
        from flink_tpu.parallel.mesh import SHARD_AXIS, MeshContext

        ctx = MeshContext.create(self.n_shards, self.max_parallelism)
        self._ctx = ctx
        starts, ends = ctx.kg_bounds()
        starts_j = jnp.asarray(starts)
        ends_j = jnp.asarray(ends)
        spec = self.spec
        maxp = self.max_parallelism
        # `capacity` is PER SHARD (matching env.state_capacity_per_shard
        # and the single-shard path)
        cap_per_shard = self.capacity

        def shard_body(state, kg_start, kg_end, hi, lo, masks, valid,
                       pane):
            state = jax.tree_util.tree_map(lambda x: x[0], state)
            kg_start, kg_end = kg_start[0], kg_end[0]
            kg = assign_to_key_group(route_hash(hi, lo, jnp), maxp, jnp)
            mine = valid & (kg >= kg_start.astype(jnp.uint32)) & (
                kg <= kg_end.astype(jnp.uint32)
            )
            state, delta, _tot = advance(state, spec, hi, lo, masks,
                                         mine, pane)
            # owned lanes are disjoint across shards: psum reassembles
            total_delta = jax.lax.psum(delta, SHARD_AXIS)
            return (
                jax.tree_util.tree_map(lambda x: x[None], state),
                total_delta,
            )

        sharded = shard_map(
            shard_body, mesh=ctx.mesh,
            in_specs=(
                P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                P(), P(), P(), P(), P(),
            ),
            out_specs=(P(SHARD_AXIS), P()),
            check_vma=False,
        )

        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, hi, lo, masks, valid, pane):
            return sharded(state, starts_j, ends_j, hi, lo, masks, valid,
                           pane)

        def sharded_init():
            st = init_state(cap_per_shard, probe_len, spec)
            return jax.tree_util.tree_map(lambda x: x[None], st)

        init_fn = jax.jit(shard_map(
            sharded_init, mesh=ctx.mesh, in_specs=(),
            out_specs=P(SHARD_AXIS), check_vma=False,
        ))
        self.state = init_fn()
        self._sharded_step = step

        def adv(state, _spec, hi, lo, masks, valid, pane):
            st, delta = step(state, hi, lo, masks, valid, pane)
            # the caller discards the per-batch total; never pay an
            # extra eager device op for it on the hot path (per-op
            # dispatch latency is the cost model on this runtime)
            return st, delta, None

        self._advance = adv

    @property
    def dropped_capacity(self) -> int:
        return int(np.asarray(self.state.dropped_capacity).sum())

    def _masks(self, elements: Sequence) -> np.ndarray:
        S = len(self.stages)
        m = np.zeros((len(elements), S), bool)
        for j, st in enumerate(self.stages):
            # matches_batch evaluates vectorized where_batch predicates
            # once per micro-batch (and is exactly per-event equivalent
            # for scalar predicates)
            m[:, j] = st.matches_batch(elements)
        return m

    def process_batch(self, elements: Sequence, keys: Sequence,
                      ts: int, pad_to: Optional[int] = None) -> List[dict]:
        """Advance by one micro-batch (arrival order); returns the list of
        completed match dicts {stage_name: event}."""
        B = len(elements)
        if B == 0:
            return []
        # within(): device pruning is pane-bucketed (device.py), so the
        # host replay must see the SAME quantized timestamps or its exact
        # within check could disagree with the device's count decisions
        pane = 0
        if self.spec.pane_ms:
            pane = int(ts) // self.spec.pane_ms
            ts = pane * self.spec.pane_ms
            if self._pane_origin is None:
                self._pane_origin = pane
            pane -= self._pane_origin
        masks = self._masks(elements)
        hi, lo = self.codec.encode(list(keys), keep_reverse=False)
        hi = np.asarray(hi, np.uint32)
        lo = np.asarray(lo, np.uint32)
        k64 = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)

        n = pad_to or B
        valid = np.zeros(n, bool)
        valid[:B] = True
        if n != B:
            hi = np.pad(hi, (0, n - B))
            lo = np.pad(lo, (0, n - B))
            masks = np.pad(masks, ((0, n - B), (0, 0)))

        self.state, delta, _total = self._advance(
            self.state, self.spec, hi, lo, masks, valid, np.int32(pane)
        )
        delta = np.asarray(delta)[:B]
        masks = masks[:B]
        self.steps += 1

        # ---- host compaction: store hit events (+ gap bits) per key ----
        hit = masks.any(axis=1)
        uniq, inv = np.unique(k64, return_inverse=True)
        tin = np.fromiter(
            (self.trailing.get(int(u), False) for u in uniq),
            bool, count=len(uniq),
        )
        gap, tout = batch_gaps(inv, hit, tin)
        for g, u in zip(tout, uniq):
            self.trailing[int(u)] = bool(g)
        for i in np.nonzero(hit)[0]:
            self.buffers.setdefault(int(k64[i]), []).append(
                (elements[i], bool(gap[i]), ts)
            )

        # ---- lazy extraction: replay only keys the device flags --------
        out: List[dict] = []
        done = np.nonzero(delta > 0)[0]
        if len(done):
            self.matches_detected += int(round(float(delta[done].sum())))
            for u in np.unique(k64[done]):
                out.extend(self._replay(int(u)))
        self.matches_extracted += len(out)
        return out

    # -- checkpoint / savepoint / queryable seams -----------------------
    def snapshot(self) -> dict:
        """Full operator state as host objects (device arrays fetched),
        ready for CheckpointStorage.write_generic. The barrier is the
        step boundary, as everywhere in this framework (SURVEY §3.4)."""
        return {
            "device": jax.tree_util.tree_map(
                lambda x: np.asarray(x), jax.device_get(self.state)
            ),
            "buffers": dict(self.buffers),
            "partials": dict(self.partials),
            "trailing": dict(self.trailing),
            "matches_detected": self.matches_detected,
            "matches_extracted": self.matches_extracted,
            "steps": self.steps,
            "capacity": self.capacity,
            "pane_origin": self._pane_origin,
            # within() bucketing params: a restore under a different
            # cep.device.within-buckets would reinterpret the ring
            "pane_ms": self.spec.pane_ms,
            "within_panes": self.spec.within_panes,
            "n_shards": self.n_shards,
            "max_parallelism": self.max_parallelism,
        }

    def restore(self, snap: dict):
        import jax.numpy as jnp

        if snap["capacity"] != self.capacity:
            raise ValueError(
                f"device CEP capacity mismatch: snapshot {snap['capacity']} "
                f"vs configured {self.capacity}"
            )
        if snap.get("n_shards", 1) != self.n_shards:
            raise ValueError(
                f"device CEP shard-count mismatch: snapshot has "
                f"{snap.get('n_shards', 1)} shard(s), job configured for "
                f"{self.n_shards} — restore with the same parallelism"
            )
        snap_maxp = snap.get("max_parallelism", self.max_parallelism)
        if snap_maxp != self.max_parallelism:
            # the key-group routing baked into shard tables would silently
            # misroute keys (same contract as the executor's keyed paths)
            raise ValueError(
                f"device CEP max-parallelism mismatch: snapshot "
                f"{snap_maxp} vs configured {self.max_parallelism}"
            )
        snap_pane = (snap.get("pane_ms", self.spec.pane_ms),
                     snap.get("within_panes", self.spec.within_panes))
        if snap_pane != (self.spec.pane_ms, self.spec.within_panes):
            raise ValueError(
                f"device CEP within() bucketing mismatch: snapshot used "
                f"pane_ms={snap_pane[0]}, ring={snap_pane[1]} but the job "
                f"is configured for pane_ms={self.spec.pane_ms}, ring="
                f"{self.spec.within_panes} — restore with the same "
                f"cep.device.within-buckets setting"
            )
        self.state = jax.tree_util.tree_map(jnp.asarray, snap["device"])
        self.buffers = dict(snap["buffers"])
        self.partials = dict(snap["partials"])
        self.trailing = dict(snap["trailing"])
        self.matches_detected = snap["matches_detected"]
        self.matches_extracted = snap["matches_extracted"]
        self.steps = snap["steps"]
        self._pane_origin = snap.get("pane_origin")

    def peek_state(self, key):
        """Queryable-state read: this key's live partial matches, with
        pending (unreplayed) compacted events applied NON-destructively —
        pending events never contain a completion (the device would have
        flagged it), so no match is swallowed. Returns None when the key
        has no live partials (host-path 'cep-nfa-state' parity)."""
        hi, lo = self.codec.encode([key], keep_reverse=False)
        k = int((np.uint64(hi[0]) << np.uint64(32)) | np.uint64(lo[0]))
        partials, _ms = self._advance_partials(
            list(self.partials.get(k, [])), list(self.buffers.get(k, []))
        )
        # the carried trailing bit (non-matching events after the last
        # stored event) is normally folded into the NEXT hit's gap bit;
        # the host path kills strict-waiting partials the moment the
        # non-match arrives, so a parity read must apply it eagerly
        if partials and self.trailing.get(k, False):
            partials = [
                p for p in partials
                if self.stages[p.stage_idx + 1].contiguity == RELAXED
            ]
        return partials or None

    def _advance_partials(self, partials: list,
                          buf: Sequence) -> Tuple[list, List[dict]]:
        """The single replay loop shared by extraction and queryable
        reads: gap bits kill partials waiting on a STRICT stage, then the
        exact host NFA advances."""
        matches: List[dict] = []
        for ev, gap_before, ts in buf:
            if gap_before and partials:
                partials = [
                    p for p in partials
                    if self.stages[p.stage_idx + 1].contiguity == RELAXED
                ]
            partials, ms = self.nfa.process(partials, ev, ts)
            matches.extend(ms)
        return partials, matches

    def _replay(self, k: int) -> List[dict]:
        partials, matches = self._advance_partials(
            self.partials.get(k, []), self.buffers.pop(k, [])
        )
        self.partials[k] = partials
        return matches

    def prune_dead_keys(self) -> List[dict]:
        """Bound host memory to true NFA-partials size (the SharedBuffer
        pruning analog). Pending buffers of unflagged keys contain NO
        completions (the device would have flagged them), so they can be
        drained destructively into each key's partials; dead 'a x a x'
        histories then collapse to the <=1 live partial the host NFA
        would hold. Keys that never won a table slot (capacity overflow,
        counted in dropped_capacity) can never be flagged for replay —
        their state is freed outright. Returns any matches found during
        the drain (expected empty; emitted defensively by the runner
        rather than swallowed). One device fetch per call."""
        if not (self.buffers or self.partials or self.trailing):
            return []
        from flink_tpu.ops.hashtable import EMPTY

        # flattens both layouts: single-shard [C, 2] and sharded [S, C, 2]
        tk = np.asarray(jax.device_get(self.state.table.keys)).reshape(-1, 2)
        occ = ~np.all(tk == EMPTY, axis=1)
        k64 = (tk[:, 0].astype(np.uint64) << np.uint64(32)) | \
            tk[:, 1].astype(np.uint64)
        in_table = set(int(v) for v in k64[occ])

        unexpected: List[dict] = []
        for k in list(self.buffers):
            if k not in in_table:
                del self.buffers[k]          # capacity-dropped key
                continue
            partials, ms = self._advance_partials(
                self.partials.get(k, []), self.buffers.pop(k)
            )
            unexpected.extend(ms)
            if partials:
                self.partials[k] = partials
            else:
                self.partials.pop(k, None)
        for k in [k for k in self.partials
                  if not self.partials[k] or k not in in_table]:
            del self.partials[k]
        # trailing bits only matter for keys with live strict-waiting
        # partials; everything else regrows from scratch
        for k in [k for k in self.trailing if k not in self.partials]:
            del self.trailing[k]
        self.matches_extracted += len(unexpected)
        return unexpected
