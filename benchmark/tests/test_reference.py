import numpy as np

from benchmark import registry
from benchmark.reference import gen_values
from benchmark.traffic import Traffic

from .conftest import TINY_JOB, TINY_TRAFFIC

REF = registry.load("references", "tumbling_sum")
J = TINY_JOB


def compare(rows, ref, K, W):
    return REF.compare(rows, ref, dict(J, keys=K, window={"kind": "tumbling",
                                                         "size_ms": W}))


def rows_of(ref, K, W):
    flat = np.nonzero(ref)[0]
    return {"key_id": (flat % K).astype(np.uint64),
            "window_end_ms": (flat // K + 1) * W,
            "value": ref[flat].astype(np.float32)}


def setup(seed=12345678901, n=200_000):
    traffic = Traffic(TINY_TRAFFIC["tiny_sat"], TINY_JOB)
    ref = REF.reference(seed, TINY_JOB, n, traffic, chunk=1 << 16)
    return traffic, ref


def test_reference_matches_a_plain_loop():
    seed, n = 7, 30_000
    traffic, ref = setup(seed, n)
    K = TINY_JOB["keys"]
    idx = np.arange(n)
    keys = traffic.keys(idx, seed)
    vals = gen_values(idx, seed, 1, 1000)
    want = {}
    for i in range(n):
        k = (int(traffic.sched.event_ms(i)) // 5000, int(keys[i]))
        want[k] = want.get(k, 0) + int(vals[i])
    got = {(int(f) // K, int(f) % K): int(ref[f]) for f in np.nonzero(ref)[0]}
    assert got == want


def test_values_are_integers_in_range_and_seeded():
    v = gen_values(np.arange(100_000), 2**31 + 5, 1, 1000)
    assert v.dtype == np.float32 and v.min() == 1 and v.max() == 1000
    assert np.all(v == np.round(v))
    assert not np.array_equal(v, gen_values(np.arange(100_000), 6, 1, 1000))


def test_keys_are_uniform_seeded_and_repeat_within_a_batch():
    traffic = Traffic(TINY_TRAFFIC["tiny_sat"], TINY_JOB)
    K = TINY_JOB["keys"]
    k = traffic.keys(np.arange(64 * K), 2**33 + 3)
    counts = np.bincount(k, minlength=K)
    assert k.min() >= 0 and k.max() < K
    # 64 draws a key on average: every key is there, none far off
    assert counts.min() > 20 and counts.max() < 120
    assert not np.array_equal(k[:2048], traffic.keys(np.arange(2048), 4))
    assert len(np.unique(k[:2048])) < 2048


def test_exact_rows_pass():
    _, ref = setup()
    out = compare(rows_of(ref, TINY_JOB["keys"], 5000), ref,
                  TINY_JOB["keys"], 5000)
    assert out == {k: 0 for k in out}


def test_each_fault_in_the_rows_is_counted():
    K = TINY_JOB["keys"]
    _, ref = setup()
    rows = rows_of(ref, K, 5000)
    bad = {k: v.copy() for k, v in rows.items()}
    bad["value"][3] += 1
    assert compare(bad, ref, K, 5000)["sums_differing"] == 1
    dup = {k: np.concatenate([v, v[:5]]) for k, v in rows.items()}
    assert compare(dup, ref, K, 5000)["pairs_fired_twice"] == 5
    short = {k: v[10:] for k, v in rows.items()}
    assert compare(short, ref, K, 5000)["pairs_never_fired"] == 10
    out = {k: v.copy() for k, v in rows.items()}
    out["window_end_ms"][0] = 10**9
    assert compare(out, ref, K, 5000)["rows_outside_input"] == 1


def test_a_bfloat16_accumulated_window_is_rejected():
    _, ref = setup()
    K = TINY_JOB["keys"]
    rows = rows_of(ref, K, 5000)
    w0 = rows["window_end_ms"] == 5000
    # one window's sums as bfloat16 accumulation rounds them
    import ml_dtypes

    bf = rows["value"].copy()
    bf[w0] = bf[w0].astype(ml_dtypes.bfloat16).astype(np.float32)
    rows["value"] = bf
    assert compare(rows, ref, K, 5000)["sums_differing"] > 0


def test_control_fails_the_comparison():
    traffic = Traffic(TINY_TRAFFIC["tiny_sat"], TINY_JOB)
    ref = REF.reference(3, TINY_JOB, 200_000, traffic)
    rows = REF.control_rows(3, TINY_JOB, 200_000, traffic, chunk=1 << 16)
    out = REF.compare(rows, ref, TINY_JOB)
    assert out["sums_differing"] > 1000
