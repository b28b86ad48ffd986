"""AsyncDataSetIterator — background-thread prefetch over any iterator.

Reference parity: ``org.deeplearning4j.datasets.iterator.AsyncDataSetIterator``
(worker thread + bounded queue so host ETL overlaps device compute).
Backing store is the native SPSC ring (`native/dl4j_tpu_native.cpp`) when the
lib is available — a batch that fits a slot is serialized (npz) into it, and
the copies into and out of the slot run with the GIL released — with a
pure-Python queue fallback that hands the batch over by reference. A batch
whose arrays alone exceed a ring slot (ImageNet b128 f32 is 77 MB) is not
packed at all: it rides the queue as it is, a marker holding its place in
the ring. The size is looked at BEFORE packing; only a batch whose arrays
fit and whose npz (a few hundred bytes more) does not is packed and then
sent unpacked. Either way the consumer API is a normal DataSetIterator, and
a look at an empty ring costs one native call and no allocation.

What each side does is recorded where it happens (``obs.span``, the
process's registry). The producer thread writes one ``data.produce`` span
a batch (attrs ``batch``: the k-th batch this wrapper hands over, the
consumer's count) with the children ``data.source_next`` (the inner
iterator's ``next``), ``data.pack`` (``np.savez`` + ``getvalue``; attrs
``bytes``, ``oversize``; written ONLY where a batch is packed, so neither
on the queue fallback nor for a batch sent unpacked by its size) and
``data.put`` (blocked until a slot is free);
the pass that finds the source exhausted carries ``end`` and no ``batch``.
Its parent is the span that was current when the generation started (a
thread inherits no ``contextvars``, so it is handed over). The consumer
writes ``data.unpack`` round ``_unpack``; how long it waited is the
caller's to time (``fit.next``). Counters: ``dl4j_data_batches_total``,
``dl4j_data_oversize_batches_total`` (sent through the queue unpacked,
packed first or not), ``dl4j_data_packed_bytes_total``,
``dl4j_data_pack_discarded_bytes_total`` (packed, then sent through the
queue unpacked) and ``dl4j_data_consumer_waits_total`` (batches that
``__next__`` did not find ready at its first look: the loop is
producer-bound where this keeps pace with the batches).

reset() swaps in a FRESH ring/queue generation before restarting the
producer: an old producer blocked on a full buffer keeps writing (and
sentinel-ing) only its own abandoned generation, so a stale sentinel can
never truncate the next epoch.
"""

from __future__ import annotations

import io
import queue
import threading
from typing import Optional

import numpy as np

from ..obs import get_registry, get_tracer
from .dataset import DataSet, MultiDataSet

_SENTINEL = b"__END__"
_OVERSIZE = b"__VIA_QUEUE__"   # ring marker: the batch itself is in the queue


def _arrays(ds) -> dict:
    """The arrays a batch holds, under the names they have in the npz."""
    if isinstance(ds, MultiDataSet):
        parts = {}
        for i, f in enumerate(ds.features):
            parts[f"mf{i}"] = f
        for i, l in enumerate(ds.labels):
            parts[f"ml{i}"] = l
        for i, m in enumerate(ds.features_masks or []):
            if m is not None:
                parts[f"mfm{i}"] = m
        for i, m in enumerate(ds.labels_masks or []):
            if m is not None:
                parts[f"mlm{i}"] = m
    else:
        parts = {"features": ds.features, "labels": ds.labels}
        if ds.features_mask is not None:
            parts["features_mask"] = ds.features_mask
        if ds.labels_mask is not None:
            parts["labels_mask"] = ds.labels_mask
    return parts


def _pack(ds) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **_arrays(ds))
    return buf.getvalue()


def _unpack(raw: bytes):
    with np.load(io.BytesIO(raw)) as z:
        if "features" in z:
            return DataSet(z["features"], z["labels"],
                           z["features_mask"] if "features_mask" in z else None,
                           z["labels_mask"] if "labels_mask" in z else None)
        def series(prefix):
            out = []
            for i in range(len(z.files)):
                if f"{prefix}{i}" not in z:
                    break
                out.append(z[f"{prefix}{i}"])
            return out
        feats, labs = series("mf"), series("ml")
        fmasks = [z[f"mfm{i}"] if f"mfm{i}" in z else None
                  for i in range(len(feats))]
        lmasks = [z[f"mlm{i}"] if f"mlm{i}" in z else None
                  for i in range(len(labs))]
        return MultiDataSet(
            feats, labs,
            fmasks if any(m is not None for m in fmasks) else None,
            lmasks if any(m is not None for m in lmasks) else None)


def _put(ring, q, stop, item):
    """Blocking hand-over to the consumer — into the ring when there is
    one, else the queue — that gives up once ``stop`` is set (reset/close
    abandon a producer blocked on a full buffer)."""
    while not stop.is_set():
        if ring is not None:
            if ring.push(item):
                return
            stop.wait(0.001)
        else:
            try:
                q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue


def maybe_wrap_async(iterator, queue_size: int = 2):
    """(possibly-wrapped iterator, wrapper-or-None): wrap when the source
    opts in via async_supported() and isn't already async — the shared
    policy for MultiLayerNetwork.fit and ComputationGraph.fit."""
    if getattr(iterator, "async_supported", lambda: False)() \
            and not isinstance(iterator, AsyncDataSetIterator):
        wrapped = AsyncDataSetIterator(iterator, queue_size=queue_size)
        return wrapped, wrapped
    return iterator, None


class AsyncDataSetIterator:
    def __init__(self, inner, queue_size: int = 4, use_native: bool = True,
                 slot_size: int = 64 << 20):
        self.inner = inner
        self.queue_size = queue_size
        self.use_native = use_native
        self.slot_size = slot_size
        self.batch_size = getattr(inner, "batch_size", None)
        self._ring = None
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop: Optional[threading.Event] = None
        self._handed = 0     # batches handed to the consumer so far
        reg = get_registry()
        self._n_batches = reg.counter(
            "dl4j_data_batches_total",
            "batches the async prefetch producer handed over")
        self._n_oversize = reg.counter(
            "dl4j_data_oversize_batches_total",
            "batches larger than a ring slot, sent through the queue")
        self._n_packed_bytes = reg.counter(
            "dl4j_data_packed_bytes_total",
            "bytes the async prefetch producer packed for the ring")
        self._n_discarded_bytes = reg.counter(
            "dl4j_data_pack_discarded_bytes_total",
            "bytes packed and thrown away: the batch exceeded a ring slot")
        self._n_waits = reg.counter(
            "dl4j_data_consumer_waits_total",
            "batches the consumer did not find ready at its first look")
        self._start()

    def _make_buffers(self):
        self._ring = None
        if self.use_native:
            try:
                from ..utils.native import NativeRing
                self._ring = NativeRing(self.slot_size, self.queue_size)
            except Exception:  # noqa: BLE001 — fall back to queue
                self._ring = None
        self._q = queue.Queue(maxsize=self.queue_size)

    # ------------------------------------------------------------- producer
    def _start(self):
        self._make_buffers()
        self._stop = threading.Event()
        self._error = []   # generation-local; producer appends, consumer raises
        self._thread = threading.Thread(
            target=self._produce,
            args=(self._ring, self._q, self._stop, self._error,
                  get_tracer().current_context(), self._handed),
            daemon=True)
        self._thread.start()

    def _produce(self, ring, q, stop, error, parent, k):
        """Writes ONLY to the generation's own (ring, q, stop, error) — after
        reset() these are abandoned objects and nothing here touches the
        live ones. A source exception is captured into `error` and re-raised
        on the CONSUMER side at the sentinel — silently truncating an epoch
        because the data pipeline died would be a training-integrity bug.
        ``parent`` is the span this generation's spans hang under and ``k``
        the number of its first batch."""
        span = get_tracer().span
        try:
            source = iter(self.inner)
            while not stop.is_set():
                with span("data.produce", parent=parent) as produce:
                    with span("data.source_next") as source_next:
                        ds = next(source, _SENTINEL)
                        if ds is _SENTINEL:
                            produce.set_attr("end", True)
                            return
                        produce.set_attr("batch", k)
                        source_next.set_attr("batch", k)
                    self._hand_over(ring, q, stop, ds, k)
                k += 1
        except BaseException as e:  # noqa: BLE001 — handed to the consumer
            error.append(e)
        finally:
            _put(ring, q, stop, _SENTINEL)

    def _hand_over(self, ring, q, stop, ds, k):
        span = get_tracer().span
        payload, oversize = ds, False
        if ring is not None:
            # the npz only adds to its arrays (it does not compress): a
            # batch whose arrays alone exceed a slot goes unpacked
            oversize = sum(a.nbytes for a in _arrays(ds).values()) \
                > ring.slot_size
            if not oversize:
                with span("data.pack", attrs={"batch": k}) as pack:
                    payload = _pack(ds)
                    oversize = len(payload) > ring.slot_size
                    pack.set_attr("bytes", len(payload))
                    pack.set_attr("oversize", oversize)
                self._n_packed_bytes.inc(len(payload))
                if oversize:    # the arrays fit, their npz did not
                    self._n_discarded_bytes.inc(len(payload))
            if oversize:
                self._n_oversize.inc()
        with span("data.put", attrs={"batch": k}):
            if oversize:
                # queue first, marker second: a consumer that pops
                # the marker always finds the batch waiting
                _put(None, q, stop, ds)
                payload = _OVERSIZE
            _put(ring, q, stop, payload)
        self._n_batches.inc()

    # ------------------------------------------------------------- consumer
    def __iter__(self):
        return self

    def __next__(self) -> DataSet:
        ring, q = self._ring, self._q
        looks = 0
        while True:
            looks += 1
            if ring is not None:
                raw = ring.pop()
                if raw is None:
                    self._stop.wait(0.001)
                    continue
                if raw == _SENTINEL:
                    self._raise_producer_error()
                    raise StopIteration
                k = self._took_batch(looks)
                if raw == _OVERSIZE:
                    return q.get()
                with get_tracer().span("data.unpack", attrs={"batch": k}):
                    return _unpack(raw)
            try:
                item = q.get(block=looks > 1)
            except queue.Empty:
                continue
            if isinstance(item, bytes) and item == _SENTINEL:
                self._raise_producer_error()
                raise StopIteration
            self._took_batch(looks)
            return item

    def _took_batch(self, looks: int) -> int:
        """The number of the batch just taken, found at the ``looks``-th
        look."""
        if looks > 1:
            self._n_waits.inc()
        self._handed += 1
        return self._handed - 1

    def _raise_producer_error(self):
        if self._error:
            raise RuntimeError(
                "async data producer failed mid-epoch (source iterator "
                "raised) — training would silently truncate"
            ) from self._error[0]

    def __len__(self):
        return len(self.inner)

    def reset(self):
        self._stop.set()
        old_thread, old_ring = self._thread, self._ring
        if old_thread is not None:
            old_thread.join(timeout=5)
        if hasattr(self.inner, "reset"):
            self.inner.reset()
        self._start()  # fresh generation: new ring/queue/stop event
        # free the old ring ONLY if its producer actually exited (a live
        # producer pushing into freed memory would be use-after-free)
        if old_ring is not None and (old_thread is None or not old_thread.is_alive()):
            old_ring.close()

    def total_outcomes(self):
        return getattr(self.inner, "total_outcomes", lambda: -1)()

    def close(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._ring is not None:
            self._ring.close()
            self._ring = None
