"""Data iterators.

TPU-native analogue of python/mxnet/io.py + the C++ iterator pipeline
(src/io/, SURVEY §2.1 #27). This module provides the Python-visible layer:
DataDesc/DataBatch/DataIter contracts, NDArrayIter, ResizeIter, and
PrefetchingIter (background-thread double buffering ≡ the reference's
PrefetcherIter, iter_prefetcher.h). File-format iterators (MNISTIter,
CSVIter, ImageRecordIter) live in iterators.py / image.py and register here.
"""
from __future__ import annotations

import threading
from collections import namedtuple
from typing import Any, Dict, List, Optional

import numpy as np

from . import ndarray as nd
from .base import MXNetError
from .ndarray import NDArray


class DataDesc(namedtuple("DataDesc", ["name", "shape"])):
    """Name+shape(+dtype+layout) of one input (reference io.py DataDesc)."""

    def __new__(cls, name, shape, dtype=np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")


class DataBatch:
    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter:
    """Iterator contract (reference io.py DataIter / IIterator<DataBatch>)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(
                data=self.getdata(), label=self.getlabel(),
                pad=self.getpad(), index=self.getindex(),
            )
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError()

    def getdata(self):
        raise NotImplementedError()

    def getlabel(self):
        raise NotImplementedError()

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError()


def _init_data(data, allow_empty, default_name):
    """Normalize input data into list of (name, numpy) (reference io.py)."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {"_%d_%s" % (i, default_name): d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, list or dict")
    return [
        (k, v.asnumpy() if isinstance(v, NDArray) else np.asarray(v))
        for k, v in data.items()
    ]


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays with pad/discard/roll_over last-batch
    handling (reference io.py:470)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data", label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True, default_name=label_name)
        self.num_data = self.data[0][1].shape[0]

        if shuffle:
            idx = np.arange(self.num_data)
            np.random.shuffle(idx)
            self.data = [(k, v[idx]) for k, v in self.data]
            self.label = [(k, v[idx]) for k, v in self.label]

        if last_batch_handle == "discard":
            new_n = self.num_data - self.num_data % batch_size
            self.data = [(k, v[:new_n]) for k, v in self.data]
            self.label = [(k, v[:new_n]) for k, v in self.label]
            self.num_data = new_n

        self.data_list = [v for _, v in self.data] + [v for _, v in self.label]
        self.num_source = len(self.data_list)
        assert self.num_data >= batch_size, "batch_size needs to be smaller than data size."
        self.cursor = -batch_size
        self.last_batch_handle = last_batch_handle

    @property
    def provide_data(self):
        return [
            DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
            for k, v in self.data
        ]

    @property
    def provide_label(self):
        return [
            DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
            for k, v in self.label
        ]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self.last_batch_handle == "roll_over" and self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) % self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        if self.iter_next():
            return DataBatch(
                data=self.getdata(), label=self.getlabel(),
                pad=self.getpad(), index=None,
            )
        raise StopIteration

    def _getdata(self, data_source):
        assert self.cursor < self.num_data, "DataIter needs reset."
        if self.cursor + self.batch_size <= self.num_data:
            return [nd.array(v[self.cursor : self.cursor + self.batch_size]) for _, v in data_source]
        pad = self.batch_size - self.num_data + self.cursor
        return [
            nd.array(np.concatenate([v[self.cursor :], v[:pad]], axis=0))
            for _, v in data_source
        ]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class ResizeIter(DataIter):
    """Resize an iterator to `size` batches per epoch (reference io.py ResizeIter)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Background-thread prefetch over one or more iterators — the Python
    face of the reference's PrefetcherIter double buffering
    (iter_prefetcher.h:28,129 / io.py PrefetchingIter)."""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0][1][0]
        self.data_ready = [threading.Event() for _ in range(self.n_iter)]
        self.data_taken = [threading.Event() for _ in range(self.n_iter)]
        for e in self.data_taken:
            e.set()
        self.started = True
        self.current_batch = [None for _ in range(self.n_iter)]
        self.next_batch = [None for _ in range(self.n_iter)]

        def prefetch_func(self, i):
            while True:
                self.data_taken[i].wait()
                if not self.started:
                    break
                try:
                    self.next_batch[i] = self.iters[i].next()
                except StopIteration:
                    self.next_batch[i] = None
                self.data_taken[i].clear()
                self.data_ready[i].set()

        self.prefetch_threads = [
            threading.Thread(target=prefetch_func, args=[self, i], daemon=True)
            for i in range(self.n_iter)
        ]
        for thread in self.prefetch_threads:
            thread.start()

    def __del__(self):
        self.started = False
        for e in self.data_taken:
            e.set()

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum(
            [
                [DataDesc(r[x.name], x.shape, x.dtype) if isinstance(x, DataDesc)
                 else DataDesc(*x) for x in i.provide_data]
                for r, i in zip(self.rename_data, self.iters)
            ],
            [],
        )

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum(
            [
                [DataDesc(r[x.name], x.shape, x.dtype) if isinstance(x, DataDesc)
                 else DataDesc(*x) for x in i.provide_label]
                for r, i in zip(self.rename_label, self.iters)
            ],
            [],
        )

    def reset(self):
        for e in self.data_ready:
            e.wait()
        for i in self.iters:
            i.reset()
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()

    def iter_next(self):
        for e in self.data_ready:
            e.wait()
        if self.next_batch[0] is None:
            for i in self.next_batch:
                assert i is None, "Number of entry mismatches between iterators"
            return False
        for batch in self.next_batch:
            assert batch.pad == self.next_batch[0].pad, \
                "Number of entry mismatches between iterators"
        self.current_batch = DataBatch(
            sum([batch.data for batch in self.next_batch], []),
            sum([batch.label for batch in self.next_batch], []),
            self.next_batch[0].pad,
            self.next_batch[0].index,
        )
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class DevicePrefetchIter(DataIter):
    """Host→device prefetch: engine ops pull batches from the wrapped
    iterator and *place them on device* ahead of consumption, so host
    decode AND the H2D transfer overlap the device step — the TPU-native
    recreation of the reference's pinned-buffer + copy-stream pipelining
    (PrefetcherIter feeding kCopyToGPU engine ops, SURVEY §3.1, and the
    infeed double-buffering called out in §7's risk register).

    Each prefetch stage is an engine op holding the iterator's write-var
    (exactly the reference: iter_prefetcher.h:28 pushes the copy as an
    engine op on the output's var), so base-iterator access serializes in
    push order while independent host work (checkpoint writes, PS RPCs)
    runs concurrently on the same worker pool.

    depth = number of device-resident batches kept in flight (2 =
    classic double buffering)."""

    def __init__(self, base, ctx=None, depth=2, cast_dtype=None):
        import queue as _queue

        from . import engine

        super().__init__(getattr(base, "batch_size", 0))
        self._base = base
        self._ctx = ctx
        self._cast = cast_dtype  # cast data ON DEVICE after the transfer
        #   (uint8 wire format + device-side cast: 4x less H2D traffic)
        self._depth = max(1, int(depth))
        self._q = _queue.Queue()
        self._gen = 0
        self._lock = threading.Lock()
        self._engine = engine
        self._iter_var = engine.get().new_variable()
        self._closed = False
        self._done = False
        self._wedged = False  # a prefetch op failed to finish in time
        self._waiter = None   # reusable bounded-wait thread
        self._waiter_covers = 0  # ops_pushed snapshot when waiter started
        self._ops_pushed = 0
        self._start()

    def _device(self):
        import jax

        if self._ctx is not None:
            return self._ctx.jax_device()
        return jax.devices()[0]

    def _place(self, batch):
        import jax
        from . import ndarray as _ndmod

        dev = self._device()

        def put(arr, cast=None):
            data = arr._data if isinstance(arr, _ndmod.NDArray) else arr
            out = jax.device_put(data, dev)
            if cast is not None and str(out.dtype) != str(cast):
                out = out.astype(cast)  # on-device cast, off the wire
            # NO per-batch block_until_ready: transfers pipeline
            # asynchronously; the queue depth bounds batches in flight.
            return _ndmod.NDArray(out)

        return DataBatch([put(d, self._cast) for d in batch.data],
                         [put(l) for l in batch.label] if batch.label else [],
                         pad=batch.pad, index=batch.index)

    def _start(self):
        with self._lock:
            self._gen += 1
        self._q = type(self._q)()
        self._done = False
        # prime the pipeline: `depth` prefetch ops in flight; next() pushes
        # one replacement op per consumed batch
        for _ in range(self._depth):
            self._push_fetch()

    def _push_fetch(self):
        with self._lock:
            gen = self._gen
        q = self._q

        def fetch(gen=gen, q=q):
            with self._lock:
                if gen != self._gen:  # retired generation: no-op
                    return
            try:
                batch = self._base.next()
            except StopIteration:
                q.put(None)
                return
            except BaseException as e:  # surface in the consumer —
                q.put(e)                # a silent death would hang next()
                return
            try:
                q.put(self._place(batch))
            except BaseException as e:
                q.put(e)

        self._ops_pushed += 1
        self._engine.get().push(fetch, mutable_vars=[self._iter_var],
                                name="prefetch_batch")

    @property
    def provide_data(self):
        return self._base.provide_data

    @property
    def provide_label(self):
        return self._base.provide_label

    def _retire_worker(self):
        """Invalidate queued prefetch ops and WAIT on the iterator var so
        nothing touches the (non-thread-safe) base iterator afterwards."""
        with self._lock:
            self._gen += 1  # in-queue ops become no-ops
        # Bounded wait: a fetch wedged in a device transfer must not hang
        # reset()/close() (and interpreter shutdown) forever. A waiter
        # thread only proves quiescence for ops pushed BEFORE it started
        # (the native WaitForVar read op is enqueued at call time), so it
        # is reusable only while no new fetch has been pushed since; then
        # a wedged retry can re-check briefly instead of a full 60s.
        waiter = self._waiter
        reusable = (waiter is not None and waiter.is_alive()
                    and self._waiter_covers == self._ops_pushed)
        if not reusable:
            waiter = threading.Thread(
                target=self._engine.get().wait_for_var,
                args=(self._iter_var,), daemon=True)
            self._waiter_covers = self._ops_pushed
            waiter.start()
            self._waiter = waiter
        timeout = 5 if (self._wedged and reusable) else 60
        waiter.join(timeout=timeout)
        if waiter.is_alive():
            self._wedged = True
            raise RuntimeError(
                "DevicePrefetchIter: in-flight prefetch op did not finish "
                "within %ds; refusing to reuse the base iterator while it "
                "may still be reading it" % timeout)
        self._wedged = False
        self._waiter = None
        # drop already-produced batches of the retired generation
        try:
            while True:
                self._q.get_nowait()
        except Exception:
            pass

    def reset(self):
        if self._closed:
            raise RuntimeError("DevicePrefetchIter is closed (its engine "
                               "variable was retired); construct a new one")
        self._retire_worker()
        self._base.reset()
        self._start()

    def next(self):
        if self._closed:
            raise RuntimeError("DevicePrefetchIter is closed (its engine "
                               "variable was retired); construct a new one")
        if self._done:
            raise StopIteration  # exhausted: the None sentinel is one-shot
        batch = self._q.get()
        if batch is None:
            self._done = True
            raise StopIteration
        if isinstance(batch, BaseException):
            self._done = True
            raise batch
        # keep `depth` fetches in flight
        self._push_fetch()
        return batch

    def close(self):
        """Retire in-flight prefetch ops — call before interpreter
        shutdown: an engine op killed mid-device-transfer aborts the
        process on some PJRT plugins. Also retires the engine variable:
        long-running jobs construct many iterators, and an undeleted var
        per instance grows the engine's var table without bound."""
        if getattr(self, "_closed", False):
            return
        self._retire_worker()
        self._engine.get().delete_variable(self._iter_var)
        self._closed = True

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# Registered iterators (reference MXNET_REGISTER_IO_ITER classes) live in
# io_iters.py; re-exported here so callers use mx.io.ImageRecordIter etc.
from .io_iters import (ImageRecordIter, ImageRecordUInt8Iter,  # noqa: E402,F401
                       ImageDetRecordIter, CSVIter, MNISTIter)
