"""Async device feed: background host thread + double-buffered device_put.

The DataLoader's thread path overlaps *decode* with compute, but the
host→device transfer itself still happens synchronously inside the train
step's dispatch — on TPU that serializes PCIe/ICI copy time into every
step. `prefetch_to_device` closes the gap (the python analogue of the
reference's operators/reader/buffered_reader.cc double buffering):

  * a feeder thread pulls batches from the source iterator and issues
    `jax.device_put` immediately — the copy is async, so by the time the
    consumer asks for batch N+1 its arrays are already on (or in flight
    to) the device while step N computes;
  * a bounded queue (default size=2: classic double buffering) applies
    backpressure so at most `size` batches of HBM are pinned;
  * sharding-aware: pass `placement` (a jax Sharding, or a callable
    `arr -> sharding/device`) so world>1 feeds land pre-sharded across
    the dp/sharding mesh axes instead of replicated-then-resharded.

Every `next()` observes the milliseconds the consumer waited into
`pt_feed_stall_ms` (0 included — the histogram mean IS per-batch stall),
so feed starvation is attributable in `ptdoctor summary` and bench JSON.

Error contract (mirrors the PR 4 dead-worker machinery one level up):
feeder exceptions — including a `DataLoaderWorkerError` from a dead
multiprocess worker — are re-raised in the consumer, never swallowed;
`close()` joins the feeder and then closes the source (a generator
source's `finally` runs, which is what tears down MultiprocessIter's
worker pool).

Only Tensor leaves are converted (their `_data` becomes a device-placed
jax array via `Tensor(..., _internal=True)`); numpy/scalar leaves pass
through untouched so raw-numpy feeds keep their exact downstream
semantics.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterator, Optional, Union

from ..framework.tensor import Tensor
from ..observability import spans, tracing

__all__ = ["prefetch_to_device", "DevicePrefetcher"]

_STOP_POLL_S = 0.05


class DevicePrefetcher:
    """Iterator wrapper; see module docstring. Iterate it like the source;
    call `close()` (or exhaust it) to reclaim the feeder thread."""

    def __init__(self, iterator: Iterator, size: int = 2,
                 placement: Optional[Union[Any, Callable]] = None):
        self._src = iter(iterator)
        self._placement = placement
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(size)))
        self._stop = threading.Event()
        self._done = False
        self._thread = threading.Thread(
            target=self._feed, name="pt-device-feed", daemon=True)
        self._thread.start()

    # -- feeder side ---------------------------------------------------
    def _feed(self):
        try:
            for item in self._src:
                item = self._to_device(item)
                if not self._put(("item", item)):
                    return  # closed: skip the sentinel, consumer is gone
        except BaseException as exc:  # noqa: BLE001 - re-raised in consumer
            self._put(("exc", exc))
            return
        self._put(("end", None))

    def _put(self, msg) -> bool:
        """Bounded-queue put that gives up when close() was requested, so
        the feeder can never deadlock against a departed consumer."""
        while not self._stop.is_set():
            try:
                self._q.put(msg, timeout=_STOP_POLL_S)
                return True
            except queue.Full:
                continue
        return False

    def _to_device(self, obj):
        if isinstance(obj, (list, tuple)):
            return type(obj)(self._to_device(o) for o in obj)
        if isinstance(obj, dict):
            return {k: self._to_device(v) for k, v in obj.items()}
        if isinstance(obj, Tensor):
            import jax
            place = self._placement
            if callable(place):
                place = place(obj._data)
            if place is None:
                arr = jax.device_put(obj._data)
            else:
                arr = jax.device_put(obj._data, place)
            return Tensor(arr, stop_gradient=obj.stop_gradient,
                          _internal=True)
        return obj

    # -- consumer side -------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        t0 = time.perf_counter()
        msg = self._q.get()
        kind, payload = msg
        if kind == "item":
            # only waits that produced a batch: the terminal sentinel wait
            # is end-of-data, not feed starvation
            wait_ms = (time.perf_counter() - t0) * 1000.0
            tracing.record_feed_stall(wait_ms)
            # the queue wait alone, as a child of the caller's "feed"
            # span: separates feed starvation from batch unpack cost
            spans.record("feed_wait", wait_ms, parent=spans.current(), t0=t0)
            return payload
        self._done = True
        if kind == "exc":
            raise payload
        raise StopIteration

    def close(self):
        """Stop the feeder, join it, then close the source iterator (runs
        a generator source's `finally`, e.g. MultiprocessIter teardown)."""
        self._done = True
        self._stop.set()
        # drain so a feeder blocked in put() can see the stop flag
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        if not self._thread.is_alive():
            close = getattr(self._src, "close", None)
            if callable(close):
                try:
                    close()
                except Exception:
                    pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False


def prefetch_to_device(iterator: Iterator, size: int = 2,
                       placement=None) -> DevicePrefetcher:
    """Wrap `iterator` in an async device feed (see DevicePrefetcher)."""
    return DevicePrefetcher(iterator, size=size, placement=placement)
