"""C++ native runtime tests (native/src/*.cc via ctypes bindings).

Mirrors the reference's C++ gtest coverage for these components
(reference: paddle/fluid/memory/allocation/*_test.cc,
framework/data_feed_test.cc, operators/reader/ queue tests) — run from
python against the C ABI."""
import json
import os
import threading
import time

import numpy as np
import pytest

from paddle_tpu import native


pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


def test_version():
    assert "paddle_tpu_native" in native.version()


def test_failed_build_raises_instead_of_python_fallback(monkeypatch,
                                                        tmp_path):
    """No .build_failed marker, no quiet queue.Queue: a checkout whose
    native sources do not build says so at the first use."""
    import subprocess
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_LIB_PATH", str(tmp_path / "missing.so"))

    def failing_make(cmd, **kw):
        raise subprocess.CalledProcessError(2, cmd, stderr=b"g++: boom")
    monkeypatch.setattr(native.subprocess, "run", failing_make)
    for _ in range(2):                      # not latched off after one try
        with pytest.raises(RuntimeError, match="g\\+\\+: boom"):
            native.available()


def test_arena_alloc_free_stats():
    a = native.HostArena(chunk_bytes=1 << 20)
    ptrs = [a.alloc(1000) for _ in range(100)]
    st = a.stats()
    assert st["allocs"] == 100 and st["in_use"] >= 100 * 1000
    assert st["chunks"] == 1                      # all carved from one chunk
    for p in ptrs:
        a.free(p)
    st = a.stats()
    assert st["frees"] == 100 and st["in_use"] == 0
    # coalescing: after freeing everything a full-chunk alloc must succeed
    # without growing a new chunk
    big = a.alloc((1 << 20) - 64)
    assert a.stats()["chunks"] == 1
    a.free(big)


def test_arena_grows_for_large_request():
    a = native.HostArena(chunk_bytes=1 << 16)
    p = a.alloc(1 << 20)                          # bigger than chunk
    assert p and a.stats()["reserved"] >= 1 << 20
    a.free(p)


def test_queue_fifo_and_timeout():
    q = native.NativeQueue(capacity=2)
    assert q.push({"x": 1}) and q.push((2, 3))
    assert not q.push("overflow", timeout_ms=50)  # full → timeout
    assert q.pop() == {"x": 1}
    assert q.pop() == (2, 3)
    assert q.pop(timeout_ms=50) is None           # empty → timeout


def test_queue_cross_thread_and_close():
    q = native.NativeQueue(capacity=4)
    got = []

    def consumer():
        while True:
            item = q.pop()
            if item is None:
                return
            got.append(item)

    t = threading.Thread(target=consumer)
    t.start()
    for i in range(20):
        q.push(i)
    time.sleep(0.1)
    q.close()
    t.join(timeout=5)
    assert got == list(range(20))


def test_profiler_spans_chrome_trace():
    rec = native.TraceRecorder()
    rec.clear()
    rec.enable(True)
    h = rec.begin("matmul", "op")
    time.sleep(0.002)
    rec.end(h)
    rec.instant("step_begin")
    rec.enable(False)
    assert rec.num_events() == 2
    trace = json.loads(rec.dump_json())
    evs = trace["traceEvents"]
    names = {e["name"] for e in evs}
    assert names == {"matmul", "step_begin"}
    span = next(e for e in evs if e["name"] == "matmul")
    assert span["ph"] == "X" and span["dur"] >= 1000  # >= 1ms in us
    rec.clear()


def test_profiler_python_api(tmp_path):
    from paddle_tpu.utils import profiler as prof
    prof.reset_profiler()
    prof.start_profiler()
    with prof.RecordEvent("forward"):
        time.sleep(0.001)
    path = str(tmp_path / "trace.json")
    data = prof.stop_profiler(profile_path=path)
    evs = json.loads(data)["traceEvents"]
    assert any(e["name"] == "forward" for e in evs)
    assert os.path.exists(path)


def _write_slot_file(path, rows, seed):
    """2 slots: ragged int64 ids + 3 floats (MultiSlot text format)."""
    rs = np.random.RandomState(seed)
    lines = []
    expect = []
    for _ in range(rows):
        n = rs.randint(1, 5)
        ids = rs.randint(0, 1000, n)
        fs = rs.randn(3).astype(np.float32)
        lines.append(f"{n} " + " ".join(map(str, ids)) + " 3 " +
                     " ".join(f"{v:.6f}" for v in fs))
        expect.append((ids.astype(np.int64), np.asarray(
            [float(f"{v:.6f}") for v in fs], np.float32)))
    path.write_text("\n".join(lines) + "\n")
    return expect


def test_multislot_feed_parses_batches(tmp_path):
    expect = _write_slot_file(tmp_path / "part-0", 10, seed=1)
    feed = native.MultiSlotFeed(["int64", "float32"], batch_size=4)
    feed.add_file(str(tmp_path / "part-0"))
    feed.start(num_threads=1)                     # 1 thread = stable order
    rows_seen = 0
    while True:
        batch = feed.next_batch()
        if batch is None:
            break
        (offs_i, ids), (offs_f, fs) = batch
        rows = len(offs_i) - 1
        for r in range(rows):
            exp_ids, exp_fs = expect[rows_seen + r]
            np.testing.assert_array_equal(ids[offs_i[r]:offs_i[r + 1]],
                                          exp_ids)
            np.testing.assert_allclose(fs[offs_f[r]:offs_f[r + 1]], exp_fs,
                                       rtol=1e-6)
        rows_seen += rows
    assert rows_seen == 10


def test_multislot_feed_multifile_threads(tmp_path):
    total = 0
    for i in range(4):
        _write_slot_file(tmp_path / f"part-{i}", 25, seed=i)
        total += 25
    feed = native.MultiSlotFeed(["int64", "float32"], batch_size=8)
    for i in range(4):
        feed.add_file(str(tmp_path / f"part-{i}"))
    feed.start(num_threads=4)
    rows = 0
    while True:
        b = feed.next_batch()
        if b is None:
            break
        rows += len(b[0][0]) - 1
    assert rows == total


def test_inmemory_dataset_record_shuffle(tmp_path):
    from paddle_tpu.distributed.fleet import InMemoryDataset
    _write_slot_file(tmp_path / "d0", 20, seed=9)
    ds = InMemoryDataset()
    ds.init(batch_size=8, thread_num=1)
    ds.set_use_var([("ids", "int64"), ("feat", "float32")])
    ds.set_filelist([str(tmp_path / "d0")])
    ds.load_into_memory()

    def rows(d):
        out = []
        for b in d:
            offs, vals = b[0]
            for r in range(len(offs) - 1):
                out.append(tuple(vals[offs[r]:offs[r + 1]].tolist()))
        return out

    before = rows(ds)
    ds.local_shuffle(seed=1)
    after = rows(ds)
    assert sorted(before) == sorted(after)     # same records...
    assert before != after                     # ...new order
    # batch composition changed, not just batch order (record granularity)
    assert set(before[:8]) != set(after[:8])


def test_queue_dataset_matches_python_fallback(tmp_path):
    from paddle_tpu.distributed.fleet import QueueDataset
    _write_slot_file(tmp_path / "d0", 12, seed=7)

    def run(force_py):
        ds = QueueDataset()
        ds.init(batch_size=5, thread_num=1)
        ds.set_use_var([("ids", "int64"), ("feat", "float32")])
        ds.set_filelist([str(tmp_path / "d0")])
        it = ds._py_iter() if force_py else iter(ds)
        return [([o.tolist(), v.tolist()]) for b in it for o, v in b]

    np.testing.assert_equal(run(True), run(False))


class TestHostAllocatorFacade:
    """Strategy facade + retry tier (r4; reference:
    allocator_facade.h:41, retry_allocator.cc)."""

    def _need(self):
        from paddle_tpu import native
        if not native.available():
            pytest.skip("native toolchain unavailable")
        return native

    def test_auto_growth_with_limit(self):
        native = self._need()
        a = native.HostAllocator("auto_growth", chunk_bytes=1 << 16,
                                 limit_bytes=1 << 20)
        p1 = a.alloc(512 << 10)
        with pytest.raises(MemoryError):
            a.alloc(600 << 10)          # would exceed the 1 MB limit
        a.free(p1)
        p2 = a.alloc(600 << 10)         # fits again after the free
        a.free(p2)
        s = a.stats()
        assert s["allocs"] >= 2 and s["in_use"] == 0

    def test_naive_pool_never_grows(self):
        native = self._need()
        a = native.HostAllocator("naive_best_fit", limit_bytes=256 << 10)
        assert a.stats()["chunks"] == 1    # pool carved up-front
        p = a.alloc(200 << 10)
        with pytest.raises(MemoryError):
            a.alloc(200 << 10)             # pool exhausted, no growth
        a.free(p)
        assert a.stats()["chunks"] == 1

    def test_naive_pool_without_limit_is_still_fixed(self):
        """naive_best_fit with no limit must carve ONE chunk_bytes pool
        and freeze growth — not silently degrade to a growing arena (r4
        advisor finding)."""
        native = self._need()
        a = native.HostAllocator("naive_best_fit", chunk_bytes=256 << 10)
        assert a.stats()["chunks"] == 1     # pool carved up-front
        p = a.alloc(200 << 10)
        with pytest.raises(MemoryError):
            a.alloc(200 << 10)              # pool exhausted, no growth
        a.free(p)
        assert a.stats()["chunks"] == 1

    def test_limit_accounts_aligned_sizes(self):
        """The limit gate tracks ALIGNED sizes: many odd-sized blocks must
        not let real arena usage exceed limit_bytes by alignment slack (r4
        advisor finding)."""
        native = self._need()
        limit = 64 << 10
        a = native.HostAllocator("auto_growth", chunk_bytes=1 << 16,
                                 alignment=256, limit_bytes=limit)
        ptrs = []
        try:
            while True:
                ptrs.append(a.alloc(1))     # 1 byte requested, 256 used
        except MemoryError:
            pass
        assert len(ptrs) <= limit // 256    # raw-byte accounting -> 64k
        assert a.stats()["in_use"] <= limit
        for p in ptrs:
            a.free(p)

    def test_retry_tier_waits_for_concurrent_free(self):
        import threading
        import time
        native = self._need()
        a = native.HostAllocator("auto_growth", limit_bytes=1 << 20,
                                 retry_ms=2000)
        p = a.alloc(900 << 10)

        def free_later():
            time.sleep(0.3)
            a.free(p)

        t = threading.Thread(target=free_later)
        t.start()
        t0 = time.time()
        p2 = a.alloc(900 << 10)   # blocks until the free, then succeeds
        waited = time.time() - t0
        t.join()
        a.free(p2)
        assert 0.2 < waited < 2.0

    def test_retry_tier_gives_up_after_deadline(self):
        import time
        native = self._need()
        a = native.HostAllocator("auto_growth", limit_bytes=64 << 10,
                                 retry_ms=300)
        p = a.alloc(60 << 10)
        t0 = time.time()
        with pytest.raises(MemoryError):
            a.alloc(60 << 10)
        assert time.time() - t0 >= 0.25
        a.free(p)

    def test_bad_strategy_rejected(self):
        native = self._need()
        with pytest.raises(ValueError, match="strategy"):
            native.HostAllocator("buddy")
