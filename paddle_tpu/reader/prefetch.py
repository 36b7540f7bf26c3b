"""Host→device double-buffer prefetch.

Reference: /root/reference/paddle/fluid/operators/reader/
create_double_buffer_reader_op.cc:25-68 — a background thread pulls batches
from the decorated reader and stages them into a small pool of device-side
buffers ahead of the consumer.

TPU-native form: a ``DeviceFeedIterator`` wraps a batched feed-dict reader;
a daemon thread converts each batch with the DataFeeder (or a user convert
fn), ``jax.device_put``s it (optionally pre-cast, e.g. images to bf16 for
AMP), and parks it in a bounded queue. The training loop's ``next()`` then
hands back an already-device-resident feed, so the host transfer overlaps
device compute — the same pipelining the reference gets from its
double-buffer thread.
"""

from __future__ import annotations

import queue as _queue
import threading

import jax

from ..core.profiler import record_event
from ..obs.metrics import REGISTRY as _METRICS

_M_BATCHES = _METRICS.counter(
    "paddle_tpu_reader_batches",
    "batches through reader.prefetch.background_buffer: staged = pulled "
    "and staged by the feeder thread; starved = handed to a consumer that "
    "found the queue empty at least once (the step waited for the reader)",
    labels=("event",))
_M_STAGED = _M_BATCHES.labels(event="staged")
_M_STARVED = _M_BATCHES.labels(event="starved")


def background_buffer(reader, capacity=2, stage=None, register=None):
    """Record-agnostic bounded background prefetch: returns a creator whose
    iterator is fed by a daemon thread (``stage`` runs per item IN the
    feeder, e.g. jax.device_put). BaseException-safe: the end sentinel is
    enqueued in a finally so the consumer can never hang, feeder errors
    re-raise consumer-side, and abandoning the iterator mid-pass releases
    the feeder (stop flag polled on every bounded put). ``register`` is
    called with ``(thread, stop_event)`` before each feeder starts
    (WorkerPool.background uses it to bookkeep stagers and cancel/join
    them at shutdown). One implementation for the feed-dict
    (DeviceFeedIterator), slot-tuple (reader-graph op), and pool-staging
    flavors.

    Spans (``core.profiler.record_event``; in a ``jax.profiler`` trace while
    one is taken): on the feeder thread ``reader.pull`` (``next()`` on the
    decorated reader) and ``reader.stage``, both with the batch's ordinal as
    ``batch=n``, and ``reader.put_wait`` while the full queue blocks it (the
    reader is ahead); on the consumer ``reader.get_wait`` while the empty
    queue blocks it (the step is starved)."""

    def make():
        q = _queue.Queue(maxsize=max(1, int(capacity)))
        end, err = object(), []
        stop = threading.Event()

        def put(item):
            # bounded put that notices an abandoned consumer: without the
            # stop check a `break` out of the consuming loop would leave the
            # feeder blocked forever on the full queue, pinning its staged
            # (device-resident) batches and the open readers
            try:
                q.put_nowait(item)
                return True
            except _queue.Full:
                pass
            with record_event("reader.put_wait", kind="reader"):
                while True:
                    try:
                        q.put(item, timeout=0.05)
                        return True
                    except _queue.Full:
                        if stop.is_set():
                            return False

        def get():
            try:
                return q.get_nowait()
            except _queue.Empty:
                pass
            _M_STARVED.inc()
            with record_event("reader.get_wait", kind="reader"):
                while True:
                    try:
                        return q.get(timeout=0.05)
                    except _queue.Empty:
                        if stop.is_set():
                            # cancelled externally (pool shutdown): the
                            # feeder is gone and may not have managed to
                            # enqueue the end sentinel — fail loudly
                            # instead of hanging
                            raise RuntimeError(
                                "background reader cancelled mid-stream")

        def feed():
            try:
                it, n = iter(reader()), 0
                while True:
                    with record_event("reader.pull", kind="reader", batch=n):
                        item = next(it, end)
                    if item is end:
                        return
                    if stage is not None:
                        with record_event("reader.stage", kind="reader",
                                          batch=n):
                            item = stage(item)
                    _M_STAGED.inc()
                    if not put(item) or stop.is_set():
                        return
                    n += 1
            except BaseException as e:   # surface in consumer
                err.append(e)
            finally:
                put(end)

        t = threading.Thread(target=feed, daemon=True)
        if register is not None:
            register(t, stop)
        t.start()
        try:
            while True:
                item = get()
                if item is end:
                    if err:
                        raise err[0]
                    return
                yield item
        finally:
            stop.set()

    return make


def double_buffer(reader, place=None, capacity=2, convert=None):
    """Decorate a feed-dict reader so its batches arrive device-resident.
    Returns a reader (zero-arg callable) like every other decorator."""

    def data_reader():
        return iter(DeviceFeedIterator(reader, place=place,
                                       capacity=capacity, convert=convert))

    return data_reader


class DeviceFeedIterator:
    """Iterates device-staged feed dicts produced by a background thread."""

    class _End:
        pass

    def __init__(self, reader, place=None, capacity=2, convert=None,
                 cast=None):
        self._reader = reader
        self._capacity = max(1, int(capacity))
        self._convert = convert
        self._cast = dict(cast or {})
        if place is None:
            self._device = jax.devices()[0]
        else:
            from ..core.executor import _resolve_device
            self._device = _resolve_device(place)

    def _stage(self, batch):
        if self._convert is not None:
            batch = self._convert(batch)
        # ONE device_put per batch: the feed dict transfers as a single
        # pytree submission instead of a host->device round trip per key
        staged = dict(jax.device_put(dict(batch), self._device))
        for k, dt in self._cast.items():
            if k in staged:
                staged[k] = staged[k].astype(dt)
        return staged

    def __iter__(self):
        return background_buffer(self._reader, self._capacity,
                                 self._stage)()
