"""Ahead-of-time compiles of the main path's kernels for a described TPU
v5e, at the widths ``chip_smoke.py`` runs: 1,048,576 keys in the direct
layout, batches of 262,144, a 5 s tumbling window, with pre-combine and
packed planes on as ``auto`` picks them on the TPU. Nothing runs: these
catch what the chip's compiler refuses (tiling, memory, partitioning)
without chip time. The topology is described inside a fixture, so only
the worker given this file loads the TPU compiler (see the
``on-chip-measurement`` guide, section 2)."""

import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from flink_tpu.ops import window_kernels as wk
from flink_tpu.parallel.exchange import bucket_capacity
from flink_tpu.parallel.mesh import SHARD_AXIS, MeshContext
from flink_tpu.runtime.step import (
    WindowStageSpec,
    build_window_resident_drain,
    build_window_sharded_drain,
    build_window_update_step,
    build_window_while_drain,
    init_sharded_state,
)

N_KEYS = 1 << 20
BATCH = 262_144
RING_DEPTH = 16            # pipeline.ring-depth default: the drain depth
MAX_SLOTS = 2 * RING_DEPTH  # pipeline.while-drain.max-slots auto
HBM_BYTES = 16 * 2**30      # one v5e chip
# the executor's auto overflow ring for this job with the resident loop:
# (stride 16 * (lag 1 + 1) + 4 + ring 16) batches + 8192 lanes
OVERFLOW = 52 * BATCH + 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _spec():
    return WindowStageSpec(
        win=wk.WindowSpec(size_ticks=5_000, slide_ticks=5_000, ring=8,
                          fires_per_step=2, overflow=OVERFLOW),
        red=wk.ReduceSpec("sum", jnp.float32),
        capacity_per_shard=N_KEYS, layout="direct", precombine=True,
        packed=True,
    )


def _abstract(ctx, tree, spec_of):
    """Shapes of ``tree`` placed on the described mesh: each leaf gets the
    PartitionSpec ``spec_of(leaf)``."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(ctx.mesh, spec_of(x))),
        tree)


def _state(ctx, spec):
    shapes = jax.eval_shape(lambda: init_sharded_state(ctx, spec))
    return _abstract(ctx, shapes, lambda _: P(SHARD_AXIS))


def _batch(ctx, lanes, shard_leading=False):
    """One staged batch 5-tuple (hi, lo, ticks, values, valid); with
    ``shard_leading`` each is [n_shards, lanes] split over the mesh."""
    lead = (ctx.n_shards,) if shard_leading else ()
    leaves = tuple(jax.ShapeDtypeStruct(lead + (lanes,), dt) for dt in
                   (jnp.uint32, jnp.uint32, jnp.int32, jnp.float32, bool))
    return _abstract(ctx, leaves,
                     lambda _: P(SHARD_AXIS) if shard_leading else P())


def _scalars(ctx, *shapes):
    return _abstract(
        ctx, tuple(jax.ShapeDtypeStruct(s, jnp.int32) for s in shapes),
        lambda _: P())


def _lowered(topo):
    """name -> lowered program, for every kernel this file compiles."""
    one = MeshContext.create(1, 128, devices=topo.devices[:1])
    four = MeshContext.create(4, 128, devices=topo.devices[:4])
    spec = _spec()
    state1 = _state(one, spec)
    lanes = bucket_capacity(BATCH, four.n_shards, 2.0)
    return {
        "update": build_window_update_step(one, spec).lower(
            state1, *_batch(one, BATCH), *_scalars(one, (1,))),
        "resident": build_window_resident_drain(one, spec, RING_DEPTH).lower(
            state1, *_batch(one, BATCH) * RING_DEPTH,
            *_scalars(one, (1, RING_DEPTH), ())),              # wmv, count
        "while": build_window_while_drain(one, spec, MAX_SLOTS).lower(
            state1, *_batch(one, BATCH) * MAX_SLOTS,
            # wmv, cursor, base, staged
            *_scalars(one, (1, MAX_SLOTS), (1,), (), ())),
        "sharded": build_window_sharded_drain(four, spec, RING_DEPTH).lower(
            _state(four, spec),
            *_batch(four, lanes, shard_leading=True) * RING_DEPTH,
            *_scalars(four, (4, RING_DEPTH), (4,))),           # wmv, counts
    }


@pytest.fixture(scope="module")
def compiled(topo):
    """name -> future of the compiled program. The compiles run at once
    on threads (the compiler releases the GIL), which keeps this file's
    wall time near its longest compile instead of the sum."""
    lowered = _lowered(topo)
    with ThreadPoolExecutor(len(lowered)) as pool:
        yield {name: pool.submit(low.compile)
               for name, low in lowered.items()}


@pytest.mark.parametrize("name", ["update", "resident", "while", "sharded"])
def test_main_path_kernel_compiles_for_v5e(compiled, name):
    program = compiled[name].result()
    mem = program.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < need < HBM_BYTES, need
    if name == "sharded":
        assert len(program.output_shardings[0].acc.device_set) == 4
