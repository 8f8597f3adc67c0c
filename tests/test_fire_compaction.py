"""Fire compaction parity: the device pack of fired windows
(``window_kernels._pack_fire_lanes`` / ``compact_fires``, built on
``segment.stable_partition``) against an independent numpy reference
built on ``np.flatnonzero``.

Every field is compared bit for bit: the packed key columns, the packed
values (including -0.0), the counts, the value sums, and the zeroed tail
past each lane's count.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.ops import window_kernels as wk
from flink_tpu.ops.hashing import hash64_host
from flink_tpu.ops.segment import stable_partition

C = 256
F = 4
B = 256


def _split_keys(keys):
    h = hash64_host(np.asarray(keys, dtype=np.int64))
    return ((h >> np.uint64(32)).astype(np.uint32),
            (h & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _reference(keys, mask, values):
    """Per lane: the emitting slots' (key_hi, key_lo, value) in slot
    order, zeros past the count, plus the count and the value sum."""
    keys = np.asarray(keys)
    mask = np.asarray(mask)
    values = np.asarray(values)
    n_lanes, cap = mask.shape
    khi = np.zeros((n_lanes, cap), np.uint32)
    klo = np.zeros((n_lanes, cap), np.uint32)
    vals = np.zeros_like(values)
    counts = np.zeros(n_lanes, np.int32)
    vsums = np.zeros(n_lanes, np.float32)
    for f in range(n_lanes):
        idx = np.flatnonzero(mask[f])
        n = idx.size
        khi[f, :n] = keys[idx, 0]
        klo[f, :n] = keys[idx, 1]
        vals[f, :n] = values[f, idx]
        counts[f] = n
        # integer-valued float32 addends: the sum is exact in any order
        vsums[f] = values[f, idx].astype(np.float64).sum()
    return khi, klo, vals, counts, vsums


def _bits(a):
    a = np.ascontiguousarray(np.asarray(a))
    if a.dtype == np.float32:
        return a.view(np.uint32)
    return a


def _assert_pack_equal(got, ref):
    names = ("key_hi", "key_lo", "values", "counts", "value_sums")
    for name, g, r in zip(names, got, ref):
        g = np.asarray(g)
        assert g.shape == r.shape and g.dtype == r.dtype, name
        np.testing.assert_array_equal(_bits(g), _bits(r), err_msg=name)


def _hash_table(rng):
    """A hash SlotTable holding real keys (and EMPTY rows)."""
    win = wk.WindowSpec(10, 10, ring=4, fires_per_step=F)
    red = wk.ReduceSpec("sum", jnp.float32)
    st = wk.init_state(C, 8, win, red, n_key_groups=64)
    hi, lo = _split_keys(rng.integers(0, 10**9, 160).astype(np.int64))
    st, _a, _k = wk.update(
        st, win, red, jnp.asarray(hi), jnp.asarray(lo),
        jnp.zeros(160, jnp.int32), jnp.ones(160, jnp.float32),
        jnp.ones(160, bool), kg_fill=64,
    )
    keys = np.asarray(st.table.keys)
    assert 0 < np.sum(~np.all(keys == 0xFFFFFFFF, axis=1)) <= 160
    return st.table


def _direct_table():
    win = wk.WindowSpec(10, 10, ring=4, fires_per_step=F)
    red = wk.ReduceSpec("sum", jnp.float32)
    return wk.init_state(C, 8, win, red, layout="direct").table


def _mask(rng, kind):
    if kind == "empty":
        return np.zeros((F, C), bool)
    if kind == "full":
        return np.ones((F, C), bool)
    density = {"p01": 0.01, "p50": 0.5, "p99": 0.99}[kind]
    m = rng.random((F, C)) < density
    m[0, rng.integers(0, C)] = True     # p01 keeps one emitting lane
    m[1] = False                        # a dead lane among live ones
    m[3] = False
    return m


def _values(rng, kind):
    shape = (F, C) if kind == "scalar" else (F, C, 3)
    v = rng.integers(-50, 50, shape).astype(np.float32)
    v[v == 0] = -0.0                    # sign bits must move intact
    return v


@pytest.mark.parametrize("values_kind", ["scalar", "vector3"])
@pytest.mark.parametrize("mask_kind", ["empty", "full", "p01", "p50", "p99"])
@pytest.mark.parametrize("layout", ["hash", "direct"])
def test_pack_fire_lanes_matches_flatnonzero(rng, layout, mask_kind,
                                             values_kind):
    table = _hash_table(rng) if layout == "hash" else _direct_table()
    mask = _mask(rng, mask_kind)
    values = _values(rng, values_kind)
    got = wk._pack_fire_lanes(table, jnp.asarray(mask), jnp.asarray(values))
    _assert_pack_equal(got, _reference(table.keys, mask, values))

    fr = wk.FireResult(
        jnp.asarray(mask), jnp.asarray(values),
        jnp.arange(F, dtype=jnp.int32), jnp.asarray(F, jnp.int32),
        jnp.ones(F, bool),
    )
    cf = wk.compact_fires(table, fr)
    _assert_pack_equal(
        (cf.key_hi, cf.key_lo, cf.values, cf.counts, cf.value_sums),
        _reference(table.keys, mask, values),
    )


@pytest.mark.parametrize("layout", ["hash", "direct"])
def test_compact_fires_packed_planes_matches_flatnonzero(rng, layout):
    """Real fires off packed state planes: the split drain's
    compact_fires and the resident in-scan pack both equal the numpy
    reference on every advance."""
    win = wk.WindowSpec(10, 10, ring=8, fires_per_step=F)
    red = wk.ReduceSpec("sum", jnp.float32)
    direct = layout == "direct"
    st = wk.init_state(C, 8, win, red, layout=layout, n_key_groups=64,
                       packed=True)
    fired = 0
    for i in range(6):
        keys = rng.integers(0, 200, B).astype(np.int64)
        if direct:
            hi, lo = np.zeros(B, np.uint32), keys.astype(np.uint32)
        else:
            hi, lo = _split_keys(keys)
        ts = rng.integers(i * 10, i * 10 + 25, B).astype(np.int32)
        vals = rng.integers(-4, 6, B).astype(np.float32)
        st, _a, _k = wk.update(
            st, win, red, jnp.asarray(hi), jnp.asarray(lo),
            jnp.asarray(ts), jnp.asarray(vals), jnp.ones(B, bool),
            direct=direct, kg_fill=64,
        )
        wm = jnp.int32(i * 10 + 9)
        _s, _purge, res = wk.advance_and_fire_resident(st, win, red, wm)
        st, fr = wk.advance_and_fire(st, win, red, wm)
        ref = _reference(st.table.keys, fr.mask, fr.values)
        cf = wk.compact_fires(st.table, fr)
        for packed in (cf, res):
            _assert_pack_equal(
                (packed.key_hi, packed.key_lo, packed.values,
                 packed.counts, packed.value_sums), ref,
            )
        fired += int(np.sum(ref[3]))
    assert fired > 0


@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
def test_stable_partition_moves_every_column(rng, density):
    """Columns of mixed dtypes and ranks come out in input order of the
    kept rows, zero past the count."""
    n = 97
    mask = rng.random(n) < density
    cols = (
        rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32),
        rng.integers(-9, 9, (n, 2, 2)).astype(np.int32),
        rng.random(n).astype(np.float32),
    )
    count, *packed = stable_partition(jnp.asarray(mask),
                                      *map(jnp.asarray, cols))
    idx = np.flatnonzero(mask)
    assert int(count) == idx.size
    for got, col in zip(packed, cols):
        want = np.zeros_like(col)
        want[:idx.size] = col[idx]
        np.testing.assert_array_equal(_bits(got), _bits(want))
