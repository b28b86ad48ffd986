"""Span tracer — nested wall-clock (optionally device-synced) timing
regions that stitch across process/thread boundaries.

A span is ONE record on ONE clock: name, trace/span/parent ids, the name
of the thread that ran it, start and end from ``time.perf_counter_ns()``
(``t0_ns`` / ``t1_ns``; spans of two threads of one process order against
each other), and free-form attrs. ``start_ts`` (epoch seconds) and
``time_s`` (duration) are derived from those two through one process-wide
offset taken at import. ``Tracer.span`` also holds a
``jax.profiler.TraceAnnotation`` of the same name open, so under a
profiler session the span lands in the ``.xplane.pb`` on the thread that
ran it, on the device lines' clock; with no session that is one check.
The current span rides a ``contextvars.ContextVar``
so nesting is automatic within a thread; across threads, processes, or
sockets the parent travels as a serialized ``SpanContext`` header
(``to_header`` / ``from_header`` — ``parallel/transport.py`` packs it
into wire frames, ``parallel/scaleout.py`` hands it to every worker so a
master round and its worker fits land in ONE trace tree), or is handed
to the thread as ``parent=`` (a ``threading.Thread`` inherits no
``contextvars``: ``data/async_iter.py``).

Timing levels mirror ``utils/tracing.py``'s discipline: the default is
host wall-clock; pass/set a ``sync`` value (any jax pytree) and the span
calls ``jax.block_until_ready`` on it before taking the end timestamp,
so the span covers device work too. Export is JSONL, one record per span, carrying the same
``time_s`` key as tracing.py's profile records so existing tooling can
aggregate either stream:

    {"kind": "span", "name": ..., "trace_id": ..., "span_id": ...,
     "parent_id": ..., "start_ts": <epoch s>, "time_s": <duration s>,
     "synced": bool, "attrs": {...}, "t0_ns": ..., "t1_ns": ...,
     "thread": ...}
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import itertools
import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

#: epoch ns minus perf_counter ns, taken once: every span's ``start_ts``
#: is its ``t0_ns`` plus this, so one process's spans share one clock.
#: A wall clock stepped later is not followed: ``start_ts`` of a long-lived
#: process then differs from ``time.time()`` by the step, and spans of two
#: processes merged by ``start_ts`` are ordered only as well as that
EPOCH_OFFSET_NS = time.time_ns() - time.perf_counter_ns()

_ID_PREFIX = os.urandom(4).hex()        # per process
_id_counter = itertools.count(1)        # next() is atomic under the GIL


def _new_id() -> str:
    """16 hex characters: the process's random prefix and a counter."""
    return "%s%08x" % (_ID_PREFIX, next(_id_counter) & 0xFFFFFFFF)


def _redraw_prefix():
    global _ID_PREFIX
    _ID_PREFIX = os.urandom(4).hex()


if hasattr(os, "register_at_fork"):     # a forked child is a new process
    os.register_at_fork(after_in_child=_redraw_prefix)


def derived_span_id(trace_id: str, *parts: Any) -> str:
    """Deterministic span id from (trace, parts) — lets two sides of a
    wire agree on a span's identity WITHOUT a round-trip (scaleout
    workers parent their fit spans to round k's id before the master has
    finished round k)."""
    h = hashlib.md5(":".join([trace_id, *map(str, parts)]).encode())
    return h.hexdigest()[:16]


@dataclass(frozen=True)
class SpanContext:
    trace_id: str
    span_id: str

    def to_header(self) -> str:
        return json.dumps({"trace_id": self.trace_id,
                           "span_id": self.span_id})

    @staticmethod
    def from_header(header: Optional[str]) -> Optional["SpanContext"]:
        if not header:
            return None
        try:
            d = json.loads(header)
            return SpanContext(str(d["trace_id"]), str(d["span_id"]))
        except (ValueError, KeyError, TypeError):
            return None


class Span:
    """One finished or open span. ``t0_ns`` / ``t1_ns`` are the record;
    ``start_ts`` and ``time_s`` read them. A span assembled by hand
    (``Tracer.add_span``: the scaleout hub, ``obs.reqtrace``,
    ``obs.compiles``) gives ``start_ts`` and ``time_s`` instead and is
    placed on the same clock through the process's offset; its ``thread``
    is None unless the assembler names one, since no one thread ran it."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "t0_ns",
                 "t1_ns", "thread", "synced", "attrs", "_sync")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str] = None, start_ts: float = 0.0,
                 time_s: float = 0.0, synced: bool = False,
                 attrs: Optional[Dict[str, Any]] = None, _sync: Any = None,
                 t0_ns: Optional[int] = None, t1_ns: Optional[int] = None,
                 thread: Optional[str] = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        if t0_ns is None:
            t0_ns = int(start_ts * 1e9) - EPOCH_OFFSET_NS
        self.t0_ns = t0_ns
        self.t1_ns = t0_ns + int(time_s * 1e9) if t1_ns is None else t1_ns
        self.thread = thread
        self.synced = synced
        self.attrs = {} if attrs is None else attrs
        self._sync = _sync

    @property
    def start_ts(self) -> float:
        """Start in epoch seconds."""
        return (self.t0_ns + EPOCH_OFFSET_NS) / 1e9

    @property
    def time_s(self) -> float:
        """Duration in seconds."""
        return (self.t1_ns - self.t0_ns) / 1e9

    def set_attr(self, key: str, value: Any) -> "Span":
        self.attrs[key] = value
        return self

    def set_sync(self, value: Any) -> "Span":
        """Register a jax value to block on before the end timestamp."""
        self._sync = value
        return self

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def record(self) -> dict:
        return {"kind": "span", "name": self.name,
                "trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "start_ts": self.start_ts,
                "time_s": self.time_s, "synced": self.synced,
                "attrs": self.attrs, "t0_ns": self.t0_ns,
                "t1_ns": self.t1_ns, "thread": self.thread}

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.time_s * 1e3:.3f} ms, "
                f"thread={self.thread!r}, attrs={self.attrs!r})")


class _OpenSpan:
    """The ``with`` block of ``Tracer.span``: makes the span current, holds
    a profiler annotation of the same name open (under a profiler session
    the span is in the trace too, on the device's clock; with none that
    is one atomic check) and deposits the span when the block ends."""

    __slots__ = ("_tracer", "_span", "_token", "_annotation")

    def __init__(self, tracer: "Tracer", sp: Span):
        self._tracer, self._span = tracer, sp

    def __enter__(self) -> Span:
        sp = self._span
        self._token = self._tracer._current.set(sp.context)
        self._annotation = TraceAnnotation(sp.name)
        self._annotation.__enter__()
        sp.t0_ns = time.perf_counter_ns()
        return sp

    def __exit__(self, *exc):
        sp, tracer = self._span, self._tracer
        tracer._current.reset(self._token)
        if sp._sync is not None:
            try:
                import jax
                jax.block_until_ready(sp._sync)
                sp.synced = True
            except Exception:  # noqa: BLE001 — sync is best-effort
                pass
        sp.t1_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        tracer.add_span(sp)
        return False


class Tracer:
    """Collects finished spans (bounded ring — never OOMs a long run;
    drops are counted, not silent) and owns the current-span context.
    The ring evicts the OLDEST spans: late spans are the enclosing ones
    (a job root closes last), and an exported tree must keep its root
    for the orphan-free stitching walk the tests perform."""

    def __init__(self, max_spans: int = 20000):
        self.max_spans = max_spans
        self.dropped = 0
        self._finished: "deque[Span]" = deque(maxlen=max_spans)
        self._lock = threading.Lock()
        self._current: "contextvars.ContextVar[Optional[SpanContext]]" = \
            contextvars.ContextVar("dl4j_current_span", default=None)

    # ------------------------------------------------------ context
    def current_context(self) -> Optional[SpanContext]:
        return self._current.get()

    @contextlib.contextmanager
    def use_context(self, ctx: Optional[SpanContext]):
        """Adopt a remote parent (deserialized from a wire header) for
        the duration of the block — the receiving half of cross-
        transport propagation."""
        token = self._current.set(ctx)
        try:
            yield ctx
        finally:
            self._current.reset(token)

    # ------------------------------------------------------ spans
    def span(self, name: str, attrs: Optional[Dict[str, Any]] = None,
             sync: Any = None, parent: Optional[SpanContext] = None,
             span_id: Optional[str] = None) -> "_OpenSpan":
        """``with tracer.span("name") as sp:`` — a child of ``parent``, or
        of the span current in this context, or a new trace's root."""
        parent_ctx = parent if parent is not None else self._current.get()
        trace_id = parent_ctx.trace_id if parent_ctx else _new_id()
        return _OpenSpan(self, Span(
            name, trace_id, span_id or _new_id(),
            parent_ctx.span_id if parent_ctx else None,
            attrs=dict(attrs) if attrs else {}, _sync=sync, t0_ns=0,
            thread=threading.current_thread().name))

    def add_span(self, sp: Span):
        """Record an externally-assembled span. The scaleout hub times a
        round across several handler threads (first frame -> close), so
        no single thread can hold the ``span()`` context manager open —
        it builds the Span by hand and deposits it here."""
        with self._lock:
            if len(self._finished) == self.max_spans:
                self.dropped += 1
            self._finished.append(sp)

    def add_spans(self, spans):
        """Deposit a batch of externally-assembled spans under ONE lock
        acquisition — what a request-trace assembly (root + prefills +
        per-token events, ``obs.reqtrace``) uses so a long generation's
        close-out doesn't pay the lock per token."""
        with self._lock:
            for sp in spans:
                if len(self._finished) == self.max_spans:
                    self.dropped += 1
                self._finished.append(sp)

    # ------------------------------------------------------ export
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._finished)

    def clear(self):
        with self._lock:
            self._finished.clear()
            self.dropped = 0

    def export_jsonl(self, path, clear: bool = False) -> int:
        """Append every finished span to ``path`` as JSONL; returns the
        number written. Ordered by completion time (children before
        parents, as in any post-order trace dump)."""
        with self._lock:
            spans = list(self._finished)
            if clear:
                self._finished.clear()
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        with open(p, "a") as f:
            for sp in spans:
                f.write(json.dumps(sp.record()) + "\n")
        return len(spans)


def load_spans(path) -> List[dict]:
    """Read a span JSONL file back (torn trailing line skipped, like
    ui.load_stats)."""
    out = []
    try:
        text = Path(path).read_text()
    except OSError:
        return out
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and rec.get("kind") == "span":
            out.append(rec)
    return out


_tracer = Tracer()


def get_tracer() -> Tracer:
    return _tracer


def span(name: str, **kw):
    """Module-level shorthand: ``with obs.span("round"): ...``"""
    return _tracer.span(name, **kw)
