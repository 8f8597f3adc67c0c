"""Native layer: C++ ring buffer, record codec, spill store (SURVEY §2.10
equivalents of the reference's Unsafe/Netty/RocksDB surfaces)."""

import os
import threading

import numpy as np
import pytest

from flink_tpu.native import RECORD_BYTES, RingBuffer, SpillStore


def test_ring_roundtrip_columnar():
    rb = RingBuffer(1 << 16)
    keys = np.arange(100, dtype=np.uint64)
    ts = (np.arange(100) * 10).astype(np.int64)
    vals = np.linspace(0, 1, 100).astype(np.float32)
    assert rb.write_records(keys, ts, vals)
    out = rb.read_batch()
    assert out is not None
    k, t, v = out
    np.testing.assert_array_equal(k, keys)
    np.testing.assert_array_equal(t, ts)
    np.testing.assert_allclose(v, vals)
    assert rb.read_batch() is None
    rb.close()


def test_ring_backpressure_and_wraparound():
    rb = RingBuffer(4096)
    batch = (
        np.arange(100, dtype=np.uint64),
        np.zeros(100, np.int64),
        np.ones(100, np.float32),
    )
    writes = 0
    while rb.write_records(*batch):   # fill until backpressure
        writes += 1
    assert writes == 4096 // (100 * RECORD_BYTES + 4)
    # drain one, write one: wraparound path
    for _ in range(50):
        assert rb.read_batch() is not None
        rb.write_records(*batch)
    # drain everything
    drained = 0
    while rb.read_batch() is not None:
        drained += 1
    assert drained > 0
    rb.close()


def test_ring_threaded_producer_consumer():
    rb = RingBuffer(1 << 20)
    N, B = 200, 256
    total = np.zeros(1)

    def produce():
        for i in range(N):
            keys = np.full(B, i, np.uint64)
            ts = np.zeros(B, np.int64)
            vals = np.ones(B, np.float32)
            while not rb.write_records(keys, ts, vals):
                pass

    t = threading.Thread(target=produce)
    t.start()
    got = 0
    while got < N * B:
        out = rb.read_batch()
        if out is None:
            continue
        got += len(out[0])
        total[0] += float(out[2].sum())
    t.join()
    assert got == N * B
    assert total[0] == N * B
    rb.close()


def test_shared_memory_ring_cross_handle():
    name = f"/flink-tpu-test-{os.getpid()}"
    producer = RingBuffer(1 << 14, name=name, create=True)
    consumer = RingBuffer(1 << 14, name=name, create=False)
    keys = np.array([7, 8], np.uint64)
    producer.write_records(keys, np.zeros(2, np.int64),
                           np.ones(2, np.float32))
    out = consumer.read_batch()
    np.testing.assert_array_equal(out[0], keys)
    consumer.close()
    producer.close()


def test_spill_store_put_get_delete():
    s = SpillStore(width=2, initial_capacity=16)
    keys = np.arange(1, 1001, dtype=np.uint64)
    vals = np.stack([keys.astype(np.float32), keys.astype(np.float32) * 2],
                    axis=1)
    s.put(keys, vals)
    assert len(s) == 1000
    got, found = s.get(np.array([1, 500, 9999], np.uint64))
    assert found.tolist() == [True, True, False]
    assert got[1].tolist() == [500.0, 1000.0]
    assert s.delete(np.array([500, 500, 777], np.uint64)) == 2
    _, found = s.get(np.array([500, 777, 1], np.uint64))
    assert found.tolist() == [False, False, True]
    assert len(s) == 998
    s.close()


def test_spill_store_grow_preserves_entries():
    s = SpillStore(width=1, initial_capacity=16)
    for chunk in range(10):
        keys = np.arange(chunk * 100, chunk * 100 + 100, dtype=np.uint64) + 1
        s.put(keys, keys.astype(np.float32))
    got, found = s.get(np.arange(1, 1001, dtype=np.uint64))
    assert found.all()
    np.testing.assert_allclose(got[:, 0], np.arange(1, 1001))
    s.close()


def test_spill_store_save_load(tmp_path):
    s = SpillStore(width=3, initial_capacity=16)
    keys = np.array([10, 20, 30], np.uint64)
    vals = np.arange(9, dtype=np.float32).reshape(3, 3)
    s.put(keys, vals)
    path = str(tmp_path / "spill.bin")
    s.save(path)
    s.close()
    s2 = SpillStore.load(path)
    assert s2.width == 3
    assert len(s2) == 3
    got, found = s2.get(np.array([20], np.uint64))
    assert found[0]
    assert got[0].tolist() == [3.0, 4.0, 5.0]
    dk, dv = s2.dump()
    assert sorted(dk.tolist()) == [10, 20, 30]
    s2.close()


def test_ring_source_end_to_end_window_job():
    """Producer thread -> native ring -> columnar window sum on device."""
    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CollectSink
    from flink_tpu.runtime.sources import RingBufferSource

    src = RingBufferSource(capacity=1 << 20)
    n_batches, B = 20, 512

    def produce():
        for i in range(n_batches):
            idx = np.arange(i * B, (i + 1) * B)
            src.ring.write_records(
                (idx % 100).astype(np.uint64),
                (idx * 2).astype(np.int64),
                np.ones(B, np.float32),
            )
        src.end_of_stream()

    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    env.batch_size = 1024
    env.set_state_capacity(2048)
    sink = CollectSink()
    (
        env.add_source(src)
        .key_by(lambda c: c["key_id"])
        .time_window(5000)
        .sum(lambda c: c["value"])
        .add_sink(sink)
    )
    t = threading.Thread(target=produce)
    t.start()
    env.execute("ring-ingest")
    t.join()
    assert sum(r.value for r in sink.results) == n_batches * B


def test_library_is_named_by_a_hash_of_its_sources(tmp_path, monkeypatch):
    """A binary that was not built from the committed sources is never
    loaded: the name the loader builds and loads carries a hash of the
    sources, so it moves with every source byte."""
    import flink_tpu.native as native

    loaded = native._so_path()
    assert os.path.basename(loaded).startswith("_flink_tpu_native.")
    assert native._build() == loaded
    srcs = []
    for s in native._SRCS:
        dst = tmp_path / os.path.basename(s)
        dst.write_bytes(open(s, "rb").read())
        srcs.append(str(dst))
    monkeypatch.setattr(native, "_SRCS", tuple(srcs))
    assert native._so_path() == loaded
    with open(srcs[0], "ab") as f:
        f.write(b"\n// edited\n")
    assert native._so_path() != loaded
