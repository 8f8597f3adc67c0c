"""Device-resident open-addressing key index — the heart of keyed state.

The reference's keyed backends resolve ``(key)`` -> state via JVM HashMap
probes per record (HeapKeyedStateBackend/StateTable, SURVEY §2.4) or RocksDB
point lookups. TPU-native replacement: each key-group shard owns a fixed-
capacity open-addressing table held in HBM:

    keys: uint32[C, 2]   -- (hi, lo) 64-bit key identity per slot; the
                            all-ones row is the EMPTY sentinel.

State values live in separate [C, ...] arrays indexed by slot (managed by the
state backend), so one table serves every state descriptor of an operator.

All operations are batched and jit-compatible:

  * ``lookup``  — for B records, gather a P-long linear probe chain
    ([B, P] gathers) and pick the matching or first-empty slot. No scalar
    loops; one XLA gather + reductions.
  * ``upsert``  — insert unseen keys via *iterative scatter-claim*: every
    missing lane scatters its key row into its first empty slot (single
    [2]-wide scatter => row-atomic; duplicate claims -> exactly one winner),
    then re-looks-up. Lanes that lost a claim race retry against the updated
    table. Rounds are STATICALLY UNROLLED (no device control flow: an
    extra probe gather is cheaper than a data-dependent branch in the
    step). Duplicate keys within a batch need no dedup: they follow
    identical probe chains and claim identical slots with identical rows.

Failure is explicit: a lane whose probe chain has neither its key nor an
empty slot reports ok=False (table over capacity) and the runtime surfaces a
state-backend-full error, like RocksDB surfacing disk-full.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.ops.hashing import probe_hash

EMPTY = np.uint32(0xFFFFFFFF)


@jax.tree_util.register_pytree_node_class
@dataclass
class SlotTable:
    keys: jax.Array  # uint32[C, 2]
    probe_len: int = 16

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    def used_mask(self) -> jax.Array:
        return ~jnp.all(self.keys == EMPTY, axis=1)

    def tree_flatten(self):
        return (self.keys,), (self.probe_len,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0])


def create(capacity: int, probe_len: int = 16) -> SlotTable:
    if capacity & (capacity - 1):
        raise ValueError(f"capacity must be a power of two, got {capacity}")
    keys = jnp.full((capacity, 2), EMPTY, dtype=jnp.uint32)
    return SlotTable(keys, probe_len)


def _chain(hi, lo, capacity: int, probe_len: int):
    """[B, P] candidate slot indices along each record's probe chain."""
    base = probe_hash(hi, lo, jnp) & jnp.uint32(capacity - 1)
    offs = jnp.arange(probe_len, dtype=jnp.uint32)
    return ((base[:, None] + offs[None, :]) & jnp.uint32(capacity - 1)).astype(
        jnp.int32
    )


def _probe(table_keys, cand, hi, lo):
    """Gather the chain and classify each candidate slot."""
    rows = table_keys[cand]  # [B, P, 2]
    t_hi, t_lo = rows[..., 0], rows[..., 1]
    empty = (t_hi == EMPTY) & (t_lo == EMPTY)
    match = (~empty) & (t_hi == hi[:, None]) & (t_lo == lo[:, None])
    return match, empty


def lookup(
    table: SlotTable, hi: jax.Array, lo: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Find slots for a batch of keys.

    Returns (slot int32[B], found bool[B]). Unfound lanes get slot=capacity
    (out-of-range => safe to use with mode='drop' scatters / clipped gathers).
    """
    cand = _chain(hi, lo, table.capacity, table.probe_len)
    match, _ = _probe(table.keys, cand, hi, lo)
    found = match.any(axis=1)
    slot = jnp.take_along_axis(
        cand, jnp.argmax(match, axis=1)[:, None], axis=1
    )[:, 0]
    return jnp.where(found, slot, table.capacity), found


def _lookup_or_empty(table_keys, capacity, probe_len, hi, lo):
    cand = _chain(hi, lo, capacity, probe_len)
    match, empty = _probe(table_keys, cand, hi, lo)
    found = match.any(axis=1)
    has_empty = empty.any(axis=1)
    match_slot = jnp.take_along_axis(cand, jnp.argmax(match, 1)[:, None], 1)[:, 0]
    empty_slot = jnp.take_along_axis(cand, jnp.argmax(empty, 1)[:, None], 1)[:, 0]
    return found, match_slot, has_empty, empty_slot


@partial(jax.jit, static_argnums=(3,))
def _upsert_impl(table_keys, hi, lo, static, valid):
    capacity, probe_len, max_rounds = static

    # STATICALLY UNROLLED claim rounds — deliberately no lax.cond /
    # lax.while_loop: data-dependent control flow in the step costs more
    # than an extra [B, P] probe gather (not yet measured on the chip;
    # ROADMAP S4). So every step unconditionally runs
    # `max_rounds` claim+relookup rounds; with no missing keys the claim
    # scatters write nothing (all indices out of range, mode='drop') and
    # the relookups are pure gathers. A lane whose claim loses the
    # slot race to a different key retries against the updated table next
    # round; conflicts decay geometrically, and max_rounds=4 settles even
    # cold-start insert storms at the load factors we run (<=0.5).
    rows = jnp.stack([hi, lo], axis=1)
    found, slot, has_empty, empty_slot = _lookup_or_empty(
        table_keys, capacity, probe_len, hi, lo
    )
    found0 = found
    for _ in range(max_rounds):
        claim = valid & ~found & has_empty
        idx = jnp.where(claim, empty_slot, capacity)
        table_keys = table_keys.at[idx].set(rows, mode="drop")
        found, slot, has_empty, empty_slot = _lookup_or_empty(
            table_keys, capacity, probe_len, hi, lo
        )
    ok = valid & found
    # n_new counts lanes whose key was PLACED this call (absent before,
    # resident after). Lanes that fail to place (chain exhausted) are
    # deliberately excluded: they can never be placed by re-running the
    # insert step either — they belong to the overflow/spill tier, and
    # counting them would permanently pin the executor's step tiering in
    # insert mode for a key population that partially overflows.
    n_new = jnp.sum(valid & ~found0 & found, dtype=jnp.int32)
    slot = jnp.where(ok, slot, capacity)
    return table_keys, slot, ok, n_new


def upsert(
    table: SlotTable, hi: jax.Array, lo: jax.Array, valid: jax.Array,
    max_rounds: int = 4,
) -> Tuple[SlotTable, jax.Array, jax.Array]:
    """Insert-or-find a batch of keys.

    Returns (new_table, slot int32[B], ok bool[B]). ok=False lanes were valid
    records whose key could not be placed (chain exhausted — table too full).
    """
    new_keys, slot, ok, _ = _upsert_impl(
        table.keys, hi, lo, (table.capacity, table.probe_len, max_rounds), valid
    )
    return SlotTable(new_keys, table.probe_len), slot, ok


def upsert_counted(
    table: SlotTable, hi: jax.Array, lo: jax.Array, valid: jax.Array,
    max_rounds: int = 4,
) -> Tuple[SlotTable, jax.Array, jax.Array, jax.Array]:
    """upsert() that also reports n_new: how many valid lanes' keys were
    PLACED by this call (absent before, resident after). Lanes that fail
    to place (probe chain exhausted) are excluded — re-running insert can
    never place them, so they must not hold the executor's adaptive step
    tiering in insert mode. n_new == 0 certifies the batch changed no
    table row (see runtime/step.py / executor tiering)."""
    new_keys, slot, ok, n_new = _upsert_impl(
        table.keys, hi, lo, (table.capacity, table.probe_len, max_rounds), valid
    )
    return SlotTable(new_keys, table.probe_len), slot, ok, n_new


def remove_slots(table: SlotTable, slots: jax.Array, mask: jax.Array) -> SlotTable:
    """Mark slots empty (used by state clear / TTL eviction).

    NOTE: with linear probing, removal must not break other keys' chains.
    We therefore only use this during full-shard compaction (rebuild), not
    point deletes; point "clear" of state zeroes the value arrays instead.
    """
    idx = jnp.where(mask, slots, table.capacity)
    rows = jnp.full((slots.shape[0], 2), EMPTY, dtype=jnp.uint32)
    return SlotTable(table.keys.at[idx].set(rows, mode="drop"), table.probe_len)
