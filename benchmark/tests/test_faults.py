"""The timed path broken underneath, one fault at a time: a run of the
harness (its look for a chip skipped) has to come out not correct."""

import jax
import jax.numpy as jnp
import pytest


def state_unchanged(monkeypatch):
    from flink_tpu.ops import window_kernels as wk

    orig = wk.update

    def update(state, *a, **k):
        _, activity, kgf = orig(state, *a, **k)
        return state, activity, kgf

    monkeypatch.setattr(wk, "update", update)


def half_batch_left_out(monkeypatch):
    from flink_tpu.runtime import ingest

    orig = ingest.IngestPipeline._finish

    def finish(self, pb):
        if pb.n > 1:
            h = pb.n // 2
            pb.hi, pb.lo = pb.hi[:h], pb.lo[:h]
            pb.values, pb.ts_ms, pb.n = pb.values[:h], pb.ts_ms[:h], h
        return orig(self, pb)

    monkeypatch.setattr(ingest.IngestPipeline, "_finish", finish)


def exchange_left_out(monkeypatch):
    """Each shard keeps the local lanes it happens to own; nothing moves
    between chips."""
    from flink_tpu.core.keygroups import assign_to_key_group
    from flink_tpu.ops.hashing import route_hash
    from flink_tpu.parallel import exchange

    def owned(cols, hi, lo, valid, n_shards, maxp, cap, kg_start, kg_end):
        pad = n_shards * cap - hi.shape[0]
        grow = lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        hi, lo, valid = grow(hi), grow(lo), grow(valid)
        kg = assign_to_key_group(route_hash(hi, lo, jnp), maxp, jnp)
        mine = valid & (kg >= kg_start.astype(jnp.uint32)) & (
            kg <= kg_end.astype(jnp.uint32))
        return ({k: grow(v) for k, v in cols.items()}, hi, lo, mine,
                jnp.int32(0))

    monkeypatch.setattr(exchange, "exchange_owned", owned)


def answer_altered(monkeypatch):
    from flink_tpu.ops import window_kernels as wk

    orig = wk.advance_and_fire

    def fire(*a, **k):
        state, fr = orig(*a, **k)
        fr.values = fr.values + 1
        return state, fr

    monkeypatch.setattr(wk, "advance_and_fire", fire)


@pytest.mark.parametrize("cell,fault,reading", [
    ("tiny.sat", state_unchanged, "pairs_never_fired"),
    ("tiny.sat", half_batch_left_out, "sums_differing"),
    ("tiny.sat", answer_altered, "sums_differing"),
    ("tiny.rate", answer_altered, "sums_differing"),
    ("tiny4.sat", exchange_left_out, "pairs_never_fired"),
])
def test_fault_makes_the_run_not_correct(tiny_root, on_cpu, monkeypatch,
                                         cell, fault, reading):
    from benchmark import job

    # a job that never fires opens its window after this wait
    monkeypatch.setattr(job, "FIRE_WAIT_S", 3.0)
    jax.clear_caches()
    fault(monkeypatch)
    out = on_cpu(cell, 5, 1.0, False, root=str(tiny_root))
    jax.clear_caches()
    assert out["correct"] is False
    assert out["checks"][reading]["value"] > 0
    assert out["failed"] > 0
