"""Async device feed (io/prefetch.py DevicePrefetcher): ordering parity
with the source, StopIteration/exception contracts, clean shutdown through
the multiprocess dead-worker machinery, placement routing, the
pt_feed_stall_ms accounting, and the <=5%-overhead contract when the
consumer (not the feed) is the bottleneck."""
import multiprocessing
import os
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.io import (DataLoader, DataLoaderWorkerError, Dataset,
                           DevicePrefetcher, prefetch_to_device)
from paddle_tpu.observability import tracing


def _tensor_batches(n, shape=(4, 3)):
    for i in range(n):
        yield (Tensor(np.full(shape, float(i), np.float32)),
               Tensor(np.int64(i)))


class ArrDataset(Dataset):
    def __init__(self, n=32):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rs = np.random.RandomState(i)
        return rs.randn(8, 8).astype(np.float32), np.int64(i)


# ------------------------------------------------------------- iteration
class TestIteration:
    def test_order_and_values_preserved(self):
        feed = prefetch_to_device(_tensor_batches(10))
        try:
            out = list(feed)
        finally:
            feed.close()
        assert len(out) == 10
        for i, (x, y) in enumerate(out):
            np.testing.assert_array_equal(np.asarray(x._data), float(i))
            assert int(y._data) == i

    def test_leaves_are_committed_device_arrays(self):
        feed = prefetch_to_device(_tensor_batches(2))
        try:
            x, _ = next(feed)
        finally:
            feed.close()
        assert isinstance(x._data, jax.Array)
        # device_put commits the array to a concrete device
        assert x._data.committed

    def test_non_tensor_leaves_pass_through(self):
        """Raw-numpy feeds keep exact downstream semantics: only Tensor
        leaves are converted, containers keep their types."""
        arr = np.arange(6, dtype=np.float32).reshape(2, 3)
        src = iter([{"x": arr, "n": 7, "t": Tensor(arr)}])
        with prefetch_to_device(src) as feed:
            out = next(feed)
        assert out["x"] is arr          # untouched, not copied
        assert out["n"] == 7
        assert isinstance(out["t"], Tensor)
        assert isinstance(out["t"]._data, jax.Array)

    def test_stop_gradient_preserved(self):
        t = Tensor(np.ones((2,), np.float32))
        t.stop_gradient = False
        with prefetch_to_device(iter([t])) as feed:
            out = next(feed)
        assert out.stop_gradient is False

    def test_exhaustion_raises_stopiteration_repeatedly(self):
        feed = prefetch_to_device(_tensor_batches(3))
        try:
            assert len(list(feed)) == 3
            with pytest.raises(StopIteration):
                next(feed)
            with pytest.raises(StopIteration):
                next(feed)
        finally:
            feed.close()

    def test_placement_callable_routes_to_device(self):
        dev = jax.devices("cpu")[1]     # conftest pins 8 virtual devices
        with prefetch_to_device(_tensor_batches(2),
                                placement=lambda arr: dev) as feed:
            x, y = next(feed)
        assert x._data.devices() == {dev}
        assert y._data.devices() == {dev}


# ----------------------------------------------------------- error paths
class TestErrors:
    def test_source_exception_propagates_after_good_items(self):
        def src():
            yield Tensor(np.zeros((2,), np.float32))
            yield Tensor(np.ones((2,), np.float32))
            raise ValueError("decode exploded")

        feed = prefetch_to_device(src())
        try:
            next(feed)
            next(feed)
            with pytest.raises(ValueError, match="decode exploded"):
                next(feed)
            # after the error the feed is terminal, not wedged
            with pytest.raises(StopIteration):
                next(feed)
        finally:
            feed.close()

    def test_dead_mp_worker_error_reaches_consumer(self):
        """PR 4 contract one level up: a worker that dies under the
        multiprocess loader must surface through the device feed as the
        same DataLoaderWorkerError, not a hang or a swallowed end."""
        class Dying(Dataset):
            def __len__(self):
                return 16

            def __getitem__(self, i):
                from paddle_tpu.io import get_worker_info
                if i == 9 and get_worker_info() is not None:
                    os._exit(13)
                return np.full((8, 8), float(i), np.float32)

        loader = DataLoader(Dying(), batch_size=4, num_workers=2,
                            shuffle=False, prefetch_to_device=2)
        with pytest.raises(DataLoaderWorkerError, match=r"pid \d+"):
            list(loader)


# -------------------------------------------------------------- shutdown
class TestShutdown:
    def test_close_joins_feeder_and_closes_source(self):
        closed = []

        def src():
            try:
                for i in range(1000):
                    yield Tensor(np.full((4,), float(i), np.float32))
            finally:
                closed.append(True)

        feed = DevicePrefetcher(src(), size=2)
        next(feed)
        feed.close()                    # mid-stream: feeder blocked in put
        assert not feed._thread.is_alive()
        assert closed == [True]         # generator finally ran

    def test_close_is_idempotent(self):
        feed = DevicePrefetcher(_tensor_batches(4))
        feed.close()
        feed.close()
        assert not feed._thread.is_alive()

    def test_context_manager_closes(self):
        with DevicePrefetcher(_tensor_batches(100)) as feed:
            next(feed)
        assert not feed._thread.is_alive()

    def test_early_close_tears_down_mp_workers(self):
        """Abandoning iteration mid-epoch must run the generator source's
        finally, which tears down MultiprocessIter's pool — no orphaned
        worker processes."""
        loader = DataLoader(ArrDataset(64), batch_size=4, num_workers=2,
                            shuffle=False, prefetch_to_device=2)
        it = iter(loader)
        next(it)
        it.close()                      # generator close -> feed.close()
        deadline = time.time() + 10
        while multiprocessing.active_children() and time.time() < deadline:
            time.sleep(0.05)
        assert not multiprocessing.active_children()


# ----------------------------------------------- DataLoader / fit wiring
    @pytest.mark.parametrize("device_feed", [0, 2])
    def test_abandoned_loader_iterator_stops_its_producer(self, device_feed):
        """An iterator dropped mid-epoch (a train loop that breaks, a
        smoke that takes six batches of a million) closes the host
        prefetch queue: the producer thread exits instead of staying
        blocked on a full queue until the interpreter shuts down."""
        import threading
        before = set(threading.enumerate())
        loader = DataLoader(ArrDataset(256), batch_size=2, shuffle=False,
                            prefetch_to_device=device_feed)
        it = iter(loader)
        next(it), next(it)
        started = set(threading.enumerate()) - before
        assert started                       # the producer (and feeder)
        it.close()
        deadline = time.time() + 10
        while any(t.is_alive() for t in started) and time.time() < deadline:
            time.sleep(0.05)
        assert not [t.name for t in started if t.is_alive()]


class TestDataLoaderIntegration:
    def test_parity_with_and_without_device_feed(self):
        ds = ArrDataset(16)
        ref = [(x.numpy().copy(), y.numpy().copy()) for x, y in
               DataLoader(ds, batch_size=4, shuffle=False)]
        got = [(x.numpy().copy(), y.numpy().copy()) for x, y in
               DataLoader(ds, batch_size=4, shuffle=False,
                          prefetch_to_device=2)]
        assert len(ref) == len(got) == 4
        for (rx, ry), (gx, gy) in zip(ref, got):
            np.testing.assert_array_equal(rx, gx)
            np.testing.assert_array_equal(ry, gy)

    def test_reader_decorator(self):
        from paddle_tpu import reader as rd

        def source():
            for i in range(5):
                yield Tensor(np.full((2,), float(i), np.float32))

        out = list(rd.prefetch_to_device(source, size=2)())
        assert [float(t._data[0]) for t in out] == [0.0, 1.0, 2.0, 3.0, 4.0]

    def test_fit_device_prefetch_records_feed_stall(self):
        import paddle_tpu.nn as nn

        paddle.seed(0)
        net = nn.Linear(8, 4)
        m = paddle.Model(net)
        m.prepare(paddle.optimizer.SGD(learning_rate=0.01,
                                       parameters=net.parameters()),
                  nn.CrossEntropyLoss())
        X = np.random.RandomState(0).rand(16, 8).astype("float32")
        Y = np.zeros((16, 1), np.int64)
        ds = [(X[i], Y[i]) for i in range(16)]
        c0 = tracing.FEED_STALL.count
        m.fit(ds, batch_size=8, epochs=1, verbose=0, device_prefetch=2)
        assert tracing.FEED_STALL.count - c0 >= 2   # one per batch


# ------------------------------------------------------ overhead contract
class TestOverhead:
    def test_stall_under_5pct_when_consumer_bound(self):
        """When the consumer is the bottleneck (feed always ready), the
        per-batch feed stall must stay under 5% of the compute window —
        the same contract bench.py's feed_stall_ms column is judged by."""
        compute_s = 0.010
        steps = 30
        feed = prefetch_to_device(_tensor_batches(steps + 2, shape=(4,)))
        try:
            next(feed)                  # warmup: feeder spin-up excluded
            s0, c0 = tracing.FEED_STALL.sum, tracing.FEED_STALL.count
            for _ in range(steps):
                next(feed)
                time.sleep(compute_s)
            dc = tracing.FEED_STALL.count - c0
            stall_ms = (tracing.FEED_STALL.sum - s0) / dc
        finally:
            feed.close()
        assert dc == steps
        assert stall_ms <= compute_s * 1e3 * 0.05, stall_ms
