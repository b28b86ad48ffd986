"""Span tracer — nested wall-clock (optionally device-synced) timing
regions that stitch across process/thread boundaries.

A span records name, trace/span/parent ids, start timestamp, duration,
and free-form attrs. The current span rides a ``contextvars.ContextVar``
so nesting is automatic within a thread; across threads, processes, or
sockets the parent travels as a serialized ``SpanContext`` header
(``to_header`` / ``from_header`` — ``parallel/transport.py`` packs it
into wire frames, ``parallel/scaleout.py`` hands it to every worker so a
master round and its worker fits land in ONE trace tree).

Timing levels mirror ``utils/tracing.py``'s discipline: the default is
host wall-clock; pass/set a ``sync`` value (any jax pytree) and the span
calls ``jax.block_until_ready`` on it before taking the end timestamp,
so the span covers device work too. Export is JSONL, one record per span, carrying the same
``time_s`` key as tracing.py's profile records so existing tooling can
aggregate either stream:

    {"kind": "span", "name": ..., "trace_id": ..., "span_id": ...,
     "parent_id": ..., "start_ts": <epoch s>, "time_s": <duration s>,
     "synced": bool, "attrs": {...}}
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import json
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


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


@dataclass
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: Optional[str] = None
    start_ts: float = 0.0
    time_s: float = 0.0
    synced: bool = False
    attrs: Dict[str, Any] = field(default_factory=dict)
    _sync: Any = None

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
                "attrs": self.attrs}


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
    @contextlib.contextmanager
    def span(self, name: str, attrs: Optional[Dict[str, Any]] = None,
             sync: Any = None, parent: Optional[SpanContext] = None,
             span_id: Optional[str] = None):
        parent_ctx = parent if parent is not None else self._current.get()
        trace_id = parent_ctx.trace_id if parent_ctx else _new_id()
        sp = Span(name=name, trace_id=trace_id,
                  span_id=span_id or _new_id(),
                  parent_id=parent_ctx.span_id if parent_ctx else None,
                  attrs=dict(attrs or {}), _sync=sync)
        token = self._current.set(sp.context)
        sp.start_ts = time.time()
        t0 = time.perf_counter()
        try:
            yield sp
        finally:
            self._current.reset(token)
            if sp._sync is not None:
                try:
                    import jax
                    jax.block_until_ready(sp._sync)
                    sp.synced = True
                except Exception:  # noqa: BLE001 — sync is best-effort
                    pass
            sp.time_s = time.perf_counter() - t0
            with self._lock:
                if len(self._finished) == self.max_spans:
                    self.dropped += 1   # deque(maxlen) evicts the oldest
                self._finished.append(sp)

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
