"""AsyncDataSetIterator — background-thread prefetch over any iterator.

Reference parity: ``org.deeplearning4j.datasets.iterator.AsyncDataSetIterator``
(worker thread + bounded queue so host ETL overlaps device compute).
Backing store is the native SPSC ring (`native/dl4j_tpu_native.cpp`) when the
lib is available — batches are serialized into fixed byte slots, so the
producer thread never holds the GIL during the copy — with a pure-Python
queue fallback. A batch larger than a ring slot (ImageNet b128 f32 is
77 MB) rides the queue as it is, a marker holding its place in the ring.
Either way the consumer API is a normal DataSetIterator.

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

from .dataset import DataSet, MultiDataSet

_SENTINEL = b"__END__"
_OVERSIZE = b"__VIA_QUEUE__"   # ring marker: the batch itself is in the queue


def _pack(ds) -> bytes:
    buf = io.BytesIO()
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
    np.savez(buf, **parts)
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
            args=(self._ring, self._q, self._stop, self._error),
            daemon=True)
        self._thread.start()

    def _produce(self, ring, q, stop, error):
        """Writes ONLY to the generation's own (ring, q, stop, error) — after
        reset() these are abandoned objects and nothing here touches the
        live ones. A source exception is captured into `error` and re-raised
        on the CONSUMER side at the sentinel — silently truncating an epoch
        because the data pipeline died would be a training-integrity bug."""
        try:
            for ds in self.inner:
                payload = _pack(ds) if ring is not None else ds
                if ring is not None and len(payload) > ring.slot_size:
                    # queue first, marker second: a consumer that pops
                    # the marker always finds the batch waiting
                    _put(None, q, stop, ds)
                    payload = _OVERSIZE
                _put(ring, q, stop, payload)
                if stop.is_set():
                    return
        except BaseException as e:  # noqa: BLE001 — handed to the consumer
            error.append(e)
        finally:
            _put(ring, q, stop, _SENTINEL)

    # ------------------------------------------------------------- consumer
    def __iter__(self):
        return self

    def __next__(self) -> DataSet:
        ring, q = self._ring, self._q
        while True:
            if ring is not None:
                raw = ring.pop()
                if raw is None:
                    self._stop.wait(0.001)
                    continue
                if raw == _SENTINEL:
                    self._raise_producer_error()
                    raise StopIteration
                if raw == _OVERSIZE:
                    return q.get()
                return _unpack(raw)
            item = q.get()
            if isinstance(item, bytes) and item == _SENTINEL:
                self._raise_producer_error()
                raise StopIteration
            return item

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
