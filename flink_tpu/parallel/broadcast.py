"""Physical broadcast on the device mesh (ref BroadcastPartitioner.java:30).

The reference physically copies every record to every downstream subtask
over Netty — N network sends per record. On a device mesh, broadcast is
a SHARDING declaration: an operand with in_spec P() is materialized once
in EVERY shard's address space (XLA lowers the replication to one host
transfer plus an on-fabric broadcast), so "send to all" costs one
collective instead of N point-to-point copies.

`build_broadcast_join_step` is the canonical consumer: a small build
side (dimension/rules table) replicated to all shards, probed by each
shard's O(B/n) slice of the record stream — the broadcast hash join of
the reference's BROADCAST_HASH_FIRST/SECOND hints
(flink-runtime/.../operators/hash/MutableHashTable.java build side)
executed as one SPMD step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from flink_tpu.parallel.mesh import SHARD_AXIS, MeshContext


_STEP_CACHE: dict = {}


def build_broadcast_join_step(ctx: MeshContext):
    """Compile a broadcast-join step over the mesh (memoized per mesh:
    jax.jit caches by function identity, so rebuilding the shard_map
    closure per call would recompile the kernel on every join).

    step(keys, valid, tkeys, tvals) with
      keys/valid: [B] record lanes, SPLIT over shards (each device
        probes only its B/n slice — work scales with chips),
      tkeys: [K] SORTED unique build-side keys, REPLICATED to every shard,
      tvals: [K] build-side payload, replicated.
    Returns (joined [B], matched bool [B]) in lane order: joined[i] =
    tvals[searchsorted(tkeys, keys[i])] where keys match; 0 otherwise.
    """
    mesh = ctx.mesh
    cached = _STEP_CACHE.get(id(mesh))
    if cached is not None:
        return cached

    def shard_body(keys, valid, tkeys, tvals):
        pos = jnp.searchsorted(tkeys, keys)
        pos_c = jnp.minimum(pos, tkeys.shape[0] - 1)
        hit = valid & (tkeys[pos_c] == keys)
        joined = jnp.where(hit, tvals[pos_c], 0).astype(tvals.dtype)
        return joined, hit

    sharded = shard_map(
        shard_body, mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS),
            P(), P(),     # build side REPLICATED: the physical broadcast
        ),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,
    )

    @jax.jit
    def step(keys, valid, tkeys, tvals):
        return sharded(keys, valid, tkeys, tvals)

    _STEP_CACHE[id(mesh)] = step
    return step


def broadcast_join(keys, tkeys, tvals, ctx: MeshContext = None):
    """One-shot broadcast join of host arrays over all devices.

    keys: record stream keys ([B] int); tkeys/tvals: build side
    (unsorted ok, [K]). Returns (joined [B] float, matched [B] bool).
    B is padded up to a shard multiple internally."""
    ctx = ctx or MeshContext.create()
    n = ctx.n_shards
    keys = np.asarray(keys)
    order = np.argsort(tkeys, kind="stable")
    tkeys_s = np.asarray(tkeys)[order]
    tvals_s = np.asarray(tvals, np.float32)[order]
    B = len(keys)
    Bp = ((B + n - 1) // n) * n
    pad = Bp - B
    kp = np.concatenate([keys, np.zeros(pad, keys.dtype)])
    valid = np.concatenate([np.ones(B, bool), np.zeros(pad, bool)])
    step = build_broadcast_join_step(ctx)
    joined, hit = step(kp, valid, tkeys_s, tvals_s)
    return np.asarray(joined)[:B], np.asarray(hit)[:B]
