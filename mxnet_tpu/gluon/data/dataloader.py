"""DataLoader (ref: python/mxnet/gluon/data/dataloader.py).

The reference forks worker processes that return CPUShared-storage
NDArrays. TPU-native redesign: workers are *threads* by default —
batchification is numpy (releases the GIL in C loops) and the expensive
device transfer happens once on the main thread via a single device_put,
overlapping with compute thanks to XLA async dispatch.

``thread_pool=False`` (with ``num_workers>0``) restores the reference's
process-worker escape hatch for GIL-heavy pure-Python transform chains
(ref: dataloader.py — _MultiWorkerIter + worker_loop): forked workers run
``dataset[i]`` + a numpy-only batchify and ship pickled numpy back; the
parent does the single device_put. Worker code must stay numpy/PIL —
JAX is fork-unsafe once its backend is initialized, so the child path
never touches jax (the reference had the same split: cheap CPUShared
numpy in workers, device copy in the consumer).
"""
from __future__ import annotations

import collections
import concurrent.futures
import multiprocessing

import numpy as np

from ...base import MXNetError
from ...ndarray.ndarray import NDArray
from ...ndarray import ndarray as _nd
from .sampler import SequentialSampler, RandomSampler, BatchSampler

__all__ = ["DataLoader", "default_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (ref: dataloader.py — default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        # ONE stacked device op instead of an asnumpy() host sync per
        # sample per batch (each sync is a full dispatch round-trip)
        import jax.numpy as jnp

        return NDArray(jnp.stack([d.data for d in data]))
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    out = np.asarray(data)
    return _nd.array(out, dtype=out.dtype)


def _issue_device_put(batch):
    """Issue (async) device placement for every array in a batch. XLA
    dispatch returns immediately, so by the time the consumer's train step
    touches the batch the H2D transfer has been overlapping compute."""
    import jax

    if isinstance(batch, list):
        return [_issue_device_put(b) for b in batch]
    if isinstance(batch, tuple):
        return tuple(_issue_device_put(b) for b in batch)
    if isinstance(batch, dict):
        return {k: _issue_device_put(v) for k, v in batch.items()}
    if isinstance(batch, NDArray):
        batch._set_data(jax.device_put(batch.data))
    return batch


class _DevicePrefetcher:
    """Double-buffer: keep ``depth`` batches materialized ahead of the
    consumer, issuing each one's ``device_put`` as soon as it is pulled —
    so batch N+1's host→device transfer overlaps the step running on
    batch N. Order-preserving; purely a scheduling wrapper. The buffered
    batches' bytes register in the diagnostics HBM ledger ('prefetch'
    pool — shape metadata, never a device read)."""

    def __init__(self, it, depth=2, to_device=True):
        self._it = iter(it)
        self._depth = max(1, depth)
        self._to_device = to_device
        self._buf = collections.deque()
        self._key = "prefetcher-%x" % id(self)

    @staticmethod
    def _batch_nbytes(batch):
        if isinstance(batch, (list, tuple)):
            return sum(_DevicePrefetcher._batch_nbytes(b) for b in batch)
        if isinstance(batch, dict):
            return sum(_DevicePrefetcher._batch_nbytes(b)
                       for b in batch.values())
        return int(getattr(getattr(batch, "data", batch), "nbytes", 0)
                   or 0)

    def _publish(self):
        from ... import diagnostics

        diagnostics.hbm_set(
            "prefetch", self._key,
            sum(self._batch_nbytes(b) for b in self._buf))

    def _pull(self):
        if self._it is None:
            return
        try:
            batch = next(self._it)
        except StopIteration:
            self._it = None
            return
        if self._to_device:
            batch = _issue_device_put(batch)
        self._buf.append(batch)

    def __iter__(self):
        from ... import diagnostics

        try:
            while len(self._buf) < self._depth and self._it is not None:
                self._pull()
            self._publish()
            while self._buf:
                batch = self._buf.popleft()
                self._pull()  # refill BEFORE yielding: next H2D in flight
                self._publish()
                yield batch
        finally:
            diagnostics.hbm_release("prefetch", self._key)


def _np_batchify(data):
    """Numpy-only batchify for process workers (no jax in a forked
    child). Mirrors default_batchify_fn's structure handling."""
    if isinstance(data[0], tuple):
        return tuple(_np_batchify(i) for i in zip(*data))
    if isinstance(data[0], NDArray):
        # reading a device array would re-enter JAX inside a fork()ed
        # child — likely deadlock. Fail loudly with the fix.
        raise TypeError(
            "dataset returned NDArray samples under thread_pool=False; "
            "process workers must stay numpy/PIL (JAX is fork-unsafe). "
            "Return numpy from __getitem__, or use thread workers.")
    return np.asarray(data)


def _np_to_nd(batch):
    if isinstance(batch, tuple):
        return [_np_to_nd(b) for b in batch]
    return _nd.array(batch, dtype=batch.dtype)


# fork-inherited dataset handle (one per worker process)
_worker_dataset = None


def _worker_init(dataset):
    global _worker_dataset
    _worker_dataset = dataset


def _worker_load(indices):
    samples = [_worker_dataset[i] for i in indices]
    return _np_batchify(samples)


def _worker_samples(indices):
    samples = [_worker_dataset[i] for i in indices]
    for s in samples:
        items = s if isinstance(s, tuple) else (s,)
        if any(isinstance(i, NDArray) for i in items):
            # same fork-safety guard as _np_batchify: pickling a device
            # array re-enters JAX inside the forked child
            raise TypeError(
                "dataset returned NDArray samples under "
                "thread_pool=False; process workers must stay numpy/PIL "
                "(JAX is fork-unsafe). Return numpy from __getitem__, or "
                "use thread workers.")
    return samples


class DataLoader:
    """Load a Dataset in mini-batches (ref: dataloader.py — DataLoader)."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, prefetch=None,
                 thread_pool=True, prefetch_to_device=False):
        """prefetch: how many batches to keep in flight ahead of the
        consumer (default 2*num_workers). Honored on the num_workers=0
        path too — the serial loader then pulls ``prefetch`` batches
        ahead through the device prefetcher instead of silently ignoring
        the argument.

        prefetch_to_device: double-buffer device placement — issue the
        next batch's ``device_put`` while the current step runs, so H2D
        transfer overlaps compute (the tf.data prefetch_to_device
        analog)."""
        self._dataset = dataset
        del pin_memory  # device placement is one device_put on TPU
        self._prefetch_to_device = prefetch_to_device

        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size must be specified unless batch_sampler is "
                    "specified")
            if sampler is None:
                if shuffle:
                    sampler = RandomSampler(len(dataset))
                else:
                    sampler = SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(
                sampler, batch_size, last_batch if last_batch else "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._custom_batchify = batchify_fn is not None
        self._thread_pool = thread_pool
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)

    def __len__(self):
        return len(self._batch_sampler)

    def _load_batch(self, indices):
        return self._batchify_fn([self._dataset[i] for i in indices])

    def _iter_serial(self):
        for indices in self._batch_sampler:
            yield self._load_batch(indices)

    def __iter__(self):
        if self._num_workers == 0:
            base = self._iter_serial()
            if self._prefetch > 0 or self._prefetch_to_device:
                # honor prefetch without workers: pull ahead on the
                # consumer thread so the next batch's transfers are
                # already dispatched when the current step runs
                base = _DevicePrefetcher(base, self._prefetch or 2,
                                         self._prefetch_to_device)
        else:
            base = self._iter_threads() if self._thread_pool \
                else self._iter_processes()
            if self._prefetch_to_device:
                base = _DevicePrefetcher(base, 2, True)
        return self._instrumented(base)

    @staticmethod
    def _instrumented(base):
        """Clock how long the CONSUMER waits for each batch — the
        'data_wait' phase of the step timeline (telemetry.py). With
        healthy prefetch this is ~0; a feed-bound run shows it eating
        the step budget. Host wall-clock only, no device reads."""
        import time

        from jax.profiler import TraceAnnotation

        from ... import telemetry

        it = iter(base)
        done = object()
        n = 0
        while True:
            # the same interval as the streaming loader's span of this
            # name, so either loader names a device gap in the trace
            with TraceAnnotation("mxt.data.wait", n=n + 1):
                t0 = time.perf_counter()
                batch = next(it, done)
                dt = time.perf_counter() - t0
            if batch is done:
                return
            n += 1
            telemetry.record_phase("data_wait", dt, stream="dataloader",
                                   step=n)
            yield batch

    def _iter_threads(self):
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=self._num_workers) as pool:
            pending = collections.deque()
            it = iter(self._batch_sampler)
            try:
                for _ in range(max(1, self._prefetch)):
                    pending.append(pool.submit(self._load_batch, next(it)))
            except StopIteration:
                it = None
            while pending:
                batch = pending.popleft().result()
                if it is not None:
                    try:
                        pending.append(pool.submit(self._load_batch,
                                                   next(it)))
                    except StopIteration:
                        it = None
                yield batch

    def _iter_processes(self):
        """Reference-style fork workers. dataset[i] + numpy batchify run
        in the child; device placement (and any custom batchify_fn, which
        may build NDArrays) runs in the parent. Child exceptions re-raise
        at .result(); an abruptly dead worker (OOM-kill, SIGKILL) is
        detected by the executor and surfaced as a descriptive
        MXNetError rather than hanging the consumer (which a plain
        multiprocessing.Pool would do: its result queue just never
        delivers)."""
        from concurrent.futures.process import BrokenProcessPool

        ctx = multiprocessing.get_context("fork")
        job = _worker_samples if self._custom_batchify else _worker_load
        with concurrent.futures.ProcessPoolExecutor(
                max_workers=self._num_workers, mp_context=ctx,
                initializer=_worker_init,
                initargs=(self._dataset,)) as pool:
            pending = collections.deque()
            it = iter(self._batch_sampler)
            try:
                for _ in range(max(1, self._prefetch)):
                    pending.append(pool.submit(job, next(it)))
            except StopIteration:
                it = None
            while pending:
                try:
                    raw = pending.popleft().result()
                except BrokenProcessPool as e:
                    raise MXNetError(
                        "DataLoader worker process died unexpectedly "
                        "(killed by the OS — OOM? — or crashed hard). "
                        "Reduce worker memory use or num_workers, or "
                        "switch to thread workers (thread_pool=True)."
                    ) from e
                if it is not None:
                    try:
                        pending.append(pool.submit(job, next(it)))
                    except StopIteration:
                        it = None
                if self._custom_batchify:
                    yield self._batchify_fn(raw)
                else:
                    yield _np_to_nd(raw)
