"""Data pipeline: Dataset / Sampler / BatchSampler / DataLoader.

TPU-native equivalent of the reference's python DataLoader stack
(/root/reference/python/paddle/fluid/reader.py:146 and fluid/dataloader/):
dataset protocols, samplers, collation, worker prefetch. v1 runs in-process
with a background prefetch thread double-buffering batches to device (the
analogue of the reference's buffered_reader.cc double buffering); the C++
shared-memory worker pool is a later phase."""
from __future__ import annotations

import itertools
import queue
import threading
from typing import Iterable, List, Optional

import numpy as np

from ..framework.tensor import Tensor

__all__ = ["Dataset", "IterableDataset", "TensorDataset", "ComposeDataset",
           "ChainDataset", "Subset", "random_split", "Sampler",
           "SequenceSampler", "RandomSampler", "WeightedRandomSampler",
           "BatchSampler", "DistributedBatchSampler", "DataLoader",
           "DataLoaderWorkerError", "get_worker_info",
           "prefetch_to_device", "DevicePrefetcher"]

from .multiprocess import DataLoaderWorkerError  # noqa: E402,F401
from .prefetch import DevicePrefetcher, prefetch_to_device  # noqa: E402,F401


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset has no __getitem__")

    def __len__(self):
        raise RuntimeError("IterableDataset has no __len__")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        assert all(t.shape[0] == tensors[0].shape[0] for t in tensors)
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            sample = d[idx]
            if isinstance(sample, (list, tuple)):
                out.extend(sample)
            else:
                out.append(sample)
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        for d in self.datasets:
            yield from d


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    if sum(lengths) != len(dataset):
        raise ValueError("sum of lengths != dataset size")
    perm = np.random.permutation(len(dataset))
    out, offset = [], 0
    for ln in lengths:
        out.append(Subset(dataset, perm[offset:offset + ln].tolist()))
        offset += ln
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(self.weights), self.num_samples,
                               replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Rank-sharded batch sampler (reference:
    fluid/dataloader/batch_sampler.py DistributedBatchSampler)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        from ..distributed import get_world_size, get_rank
        self.nranks = num_replicas if num_replicas is not None else get_world_size()
        self.local_rank = rank if rank is not None else get_rank()
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
            self.epoch += 1
        else:
            indices = list(range(n))
        indices += indices[: (self.total_size - len(indices))]
        indices = indices[self.local_rank::self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


def _collate(batch, wrap):
    """Shared stacking recursion; `wrap` converts the stacked numpy leaf
    (Tensor for the in-process path, identity for multiprocess workers —
    one recursion so the two paths' leaf handling cannot diverge)."""
    sample = batch[0]
    if isinstance(sample, (list, tuple)):
        return tuple(_collate([b[i] for b in batch], wrap)
                     for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: _collate([b[k] for b in batch], wrap) for k in sample}
    if isinstance(sample, Tensor):
        return wrap(np.stack([s.numpy() for s in batch]))
    if isinstance(sample, np.ndarray):
        return wrap(np.stack(batch))
    if isinstance(sample, (int, np.integer)):
        return wrap(np.asarray(batch, np.int64))
    if isinstance(sample, (float, np.floating)):
        return wrap(np.asarray(batch, np.float32))
    return batch


def default_collate_fn(batch):
    return _collate(batch, Tensor)


def _np_collate(batch):
    """Worker-side collate for the multiprocess path: numpy leaves —
    forked workers must never touch the jax backend; the consumer wraps."""
    return _collate(batch, lambda a: a)


def _np_tree_to_tensor(obj):
    if isinstance(obj, (list, tuple)):
        return type(obj)(_np_tree_to_tensor(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _np_tree_to_tensor(v) for k, v in obj.items()}
    if isinstance(obj, np.ndarray):
        return Tensor(obj)
    return obj


_worker_info = None


class WorkerInfo:
    def __init__(self, wid, num_workers, dataset, seed):
        self.id = wid
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed


def _set_worker_info(wid, num_workers, dataset, seed):
    """Called inside multiprocess workers (io/multiprocess.py)."""
    global _worker_info
    _worker_info = WorkerInfo(wid, num_workers, dataset, seed)


def get_worker_info():
    return _worker_info


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, prefetch_to_device=0,
                 device_placement=None):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.prefetch = use_buffer_reader
        self.prefetch_factor = max(2, prefetch_factor)
        # >0: wrap iteration in io.prefetch.DevicePrefetcher with that
        # queue depth (async device_put feed); device_placement is its
        # sharding (Sharding or arr->sharding callable) for world>1
        self.prefetch_to_device = max(0, int(prefetch_to_device or 0))
        self.device_placement = device_placement
        self.num_workers = max(0, int(num_workers))
        self.use_shared_memory = use_shared_memory
        self.worker_init_fn = worker_init_fn
        self.timeout = timeout
        if persistent_workers:
            import warnings
            warnings.warn(
                "persistent_workers=True is accepted for API parity but "
                "not implemented: the worker pool is re-created per epoch",
                RuntimeWarning)
        self._iterable_ds = isinstance(dataset, IterableDataset)
        if self._iterable_ds:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        elif batch_size is None:
            self.batch_sampler = None
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)

    def __len__(self):
        if self._iterable_ds:
            raise TypeError("IterableDataset has no fixed length")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    def _mp_dataset_ok(self):
        """Probe one sample in the PARENT: datasets whose __getitem__
        produces (or computes with) framework Tensors would run jax ops
        inside the forked child — observed to deadlock (inherited backend
        locks). Such datasets fall back to the thread path with a
        warning."""
        def has_tensor(obj):
            if isinstance(obj, Tensor):
                return True
            if isinstance(obj, (list, tuple)):
                return any(has_tensor(o) for o in obj)
            if isinstance(obj, dict):
                return any(has_tensor(v) for v in obj.values())
            return False

        try:
            probe = self.dataset[0]
        except Exception:
            return True  # let the worker surface the real error
        if has_tensor(probe):
            import warnings
            warnings.warn(
                "DataLoader(num_workers>0): dataset __getitem__ returns "
                "framework Tensors; jax must not run inside forked "
                "workers — falling back to the thread prefetch path. "
                "Return numpy arrays from the dataset for multiprocess "
                "loading.", RuntimeWarning)
            return False
        return True

    def _raw_iter(self):
        if self._iterable_ds:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(batch)
        elif self.batch_sampler is None:
            for i in range(len(self.dataset)):
                yield self.collate_fn([self.dataset[i]])
        else:
            for indices in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in indices])

    def __iter__(self):
        if self.prefetch_to_device > 0:
            feed = DevicePrefetcher(self._host_iter(),
                                    size=self.prefetch_to_device,
                                    placement=self.device_placement)
            try:
                yield from feed
            finally:
                feed.close()
        else:
            yield from self._host_iter()

    def _host_iter(self):
        # process workers + shared-memory transport (reference:
        # fluid/dataloader/dataloader_iter.py:320 multiprocess path +
        # memory/allocation/mmap_allocator.cc). GIL-free decode; iterable
        # datasets keep the thread path.
        if (self.num_workers > 0 and not self._iterable_ds
                and self.batch_sampler is not None
                and self._mp_dataset_ok()):
            from .multiprocess import MultiprocessIter
            user_collate = self.collate_fn is not default_collate_fn
            worker_collate = self.collate_fn if user_collate else _np_collate
            it = MultiprocessIter(
                self.dataset, worker_collate, iter(self.batch_sampler),
                num_workers=self.num_workers,
                prefetch_factor=self.prefetch_factor,
                worker_init_fn=self.worker_init_fn,
                timeout=self.timeout,
                seed=int(np.random.randint(0, 2 ** 31)),
                use_shared_memory=self.use_shared_memory)
            try:
                for batch in it:
                    yield batch if user_collate else _np_tree_to_tensor(batch)
            finally:
                it.close()
            return
        if not self.prefetch:
            yield from self._raw_iter()
            return
        # background prefetch thread (double buffering; the host→device copy
        # overlaps with compute because jax device_put is async). Uses the
        # C++ blocking queue (native/src/queue.cc — the reference's
        # operators/reader/blocking_queue.h) when built, else queue.Queue.
        from .. import native as _native
        if _native.available():
            q = _native.NativeQueue(capacity=self.prefetch_factor)
            put, get, close = q.push, q.pop, q.close   # push: False once closed
        else:
            pyq: "queue.Queue" = queue.Queue(maxsize=self.prefetch_factor)
            gone = threading.Event()

            def put(item):
                while not gone.is_set():
                    try:
                        pyq.put(item, timeout=0.1)
                        return True
                    except queue.Full:
                        pass
                return False

            get, close = pyq.get, gone.set
        sentinel = object()
        err = []

        def producer():
            try:
                for item in self._raw_iter():
                    if not put(item):
                        return      # the consumer closed the queue
            except BaseException as e:  # noqa: BLE001
                err.append(e)
            finally:
                put(sentinel)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = get()
                if item is sentinel:
                    if err:
                        raise err[0]
                    return
                yield item
        finally:
            # an iterator abandoned mid-epoch must not strand its producer
            # blocked on a full queue for the life of the process (and
            # inside native code when the interpreter shuts down)
            close()
            t.join(timeout=5.0)
