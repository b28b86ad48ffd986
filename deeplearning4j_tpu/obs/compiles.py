"""Compile sentinel — retrace detection on the jitted entry points
(ISSUE 12).

A silent retrace storm erases any serving or memory win: one stray
weak-typed scalar or shape drift turns the "compiled once, reused
forever" contract into a per-call compile, and nothing in the metrics
plane would say so — throughput just craters. The sentinel makes
compilation a first-class observable:

- every jitted entry point that matters (MLN/CG train step, the
  engine's ``prefill`` / ``prefill_slot`` / ``decode_step`` /
  ``sample_tokens``, the ParallelWrapper step) is wrapped in a
  :class:`CompileSentinel`;
- each compile is counted per (fn, abstract signature)
  (``dl4j_compile_total{component=}``), timed
  (``dl4j_compile_seconds{component=}``) and deposited as a
  ``compile.<name>`` span on the process tracer;
- after ``mark_warm()`` any further compile is a RETRACE: it increments
  ``dl4j_compile_retraces_total{component=}`` and raises a
  ``RuntimeWarning`` — the regression tests assert the donated train
  step and the decode sweep are zero-recompile after warmup, and
  bucket-padded prefill compiles at most once per bucket.

Detection is the jit cache itself where available
(``fn._cache_size()`` growing across a call — exact, and O(1) on the
hot path), falling back to new-abstract-signature detection on
callables that don't expose a cache. The wrapper is transparent:
``lower``, ``__wrapped__`` and everything else delegate to the wrapped
function, so floor probes (``.lower()``) and ``fit_scanned``
(``step_fn.__wrapped__``) see the jit object they always saw.

Timing caveat, documented rather than hidden: a sentinel's "compile"
observation spans the whole first call at that signature — trace + compile
+ first execution. For the retrace-storm failure mode that is the right
number (it is the latency the caller actually lost). The parts are counted
apart, for the whole process and whether or not a sentinel wraps the
function, by :func:`listen_to_compile_phases`:
``dl4j_compile_phase_seconds_total{phase="trace"|"lower"|"backend"}`` from
JAX's own duration events, ``dl4j_compile_cache_misses_total``, and beside
them the kernel races' ``dl4j_autotune_race_seconds_total``.

Hot-path budget: a non-compiling call costs one ``_cache_size()`` read
and two clock reads; the sentinel self-times into
``overhead_seconds`` and the plane-wide <2% budget test covers it.
"""

from __future__ import annotations

import threading
import time
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

#: JAX's duration events -> the ``phase`` they are counted under: tracing a
#: function to a jaxpr, lowering the jaxpr to a module, and the backend's
#: compile OR the persistent cache's answer in its place
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_listening = threading.Lock()
_listens = False


def phase_counters():
    """``(phase seconds, cache misses, race seconds)``: the three counters a
    set-up is split by. Fetched per event, so that a registry reset (tests)
    cannot orphan them; the third is ``kernels/autotune.py``'s to add to and
    is registered here as well, so that a process that never races reads 0
    and not nothing."""
    from . import get_registry
    reg = get_registry()
    return (reg.counter(
                "dl4j_compile_phase_seconds_total",
                "Seconds this process spent tracing, lowering and compiling "
                "(or reading the persistent cache), by JAX's own events",
                labelnames=("phase",)),
            reg.counter(
                "dl4j_compile_cache_misses_total",
                "Programs compiled and written to the persistent cache: its "
                "misses as JAX counts them (a compile under the cache's "
                "least time is neither a hit nor a miss)"),
            reg.counter(
                "dl4j_autotune_race_seconds_total",
                "Wall seconds spent racing kernel candidates, their "
                "compiles included"))


def _on_duration(event, secs, **_):
    phase = COMPILE_PHASES.get(event)
    if phase is not None:
        phase_counters()[0].inc(max(secs, 0.0), phase=phase)


def _on_event(event, **_):
    if event == CACHE_MISS_EVENT:
        phase_counters()[1].inc()


def listen_to_compile_phases() -> None:
    """Count every compile of this process by phase, from here on:
    ``dl4j_compile_phase_seconds_total{phase=}`` and
    ``dl4j_compile_cache_misses_total`` in ``obs.get_registry()``. Registered
    with ``jax.monitoring`` once a process, however often it is called
    (``utils.compile_cache.enable_compile_cache`` calls it); the listeners
    run on compile events only, a dictionary lookup and an add each, and
    nothing on a step's path."""
    global _listens
    with _listening:
        if _listens:
            return
        import jax
        phase_counters()    # registered from the start, at 0
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listens = True


def abstract_signature(args: tuple, kwargs: dict) -> Tuple:
    """Hashable (treedef, per-leaf shape/dtype) key — two calls with the
    same signature trace to the same jaxpr. Non-array leaves key by
    ``repr`` (the static-argument behaviour of jit itself)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))

    def one(x):
        shape = getattr(x, "shape", None)
        dt = getattr(x, "dtype", None)
        if shape is not None and dt is not None:
            return (tuple(shape), str(dt),
                    bool(getattr(x, "weak_type", False)))
        return ("static", repr(x))

    return (treedef, tuple(one(x) for x in leaves))


class CompileSentinel:
    """Transparent wrapper around one jitted callable that observes its
    compiles. Construct once next to the ``jax.jit`` call; invoke like
    the function it wraps."""

    def __init__(self, name: str, fn: Callable, *, registry=None):
        self.name = str(name)
        self._fn = fn
        self._registry = registry
        self.compiles = 0
        self.retraces_after_warm = 0
        self.warm = False
        self.signatures: Dict[Tuple, int] = {}
        self._overhead = 0.0
        self._last_size = self._cache_size()

    # ------------------------------------------------------- plumbing
    def __getattr__(self, item):
        # transparency: .lower (floor probes), .__wrapped__
        # (fit_scanned's scan body), ._cache_size, anything else
        if item == "_fn":        # guard: nothing may recurse before
            raise AttributeError(item)   # __init__ binds the target
        return getattr(self._fn, item)

    def _cache_size(self) -> Optional[int]:
        try:
            return int(self._fn._cache_size())
        except Exception:  # noqa: BLE001 — not a jit wrapper; fall back
            return None

    def _m(self):
        reg = self._registry
        if reg is None:
            from . import get_registry
            reg = get_registry()
        return (
            reg.counter(
                "dl4j_compile_total",
                "Compilations observed per jitted entry point",
                labelnames=("component",)),
            reg.histogram(
                "dl4j_compile_seconds",
                "Wall time of the call that compiled (trace + compile + "
                "first execution at that signature)",
                labelnames=("component",)),
            reg.counter(
                "dl4j_compile_retraces_total",
                "Compilations AFTER mark_warm() — each one is a retrace "
                "storm warning",
                labelnames=("component",)),
        )

    # ------------------------------------------------------ lifecycle
    def mark_warm(self) -> "CompileSentinel":
        """Declare warmup over: every compile from here on is a retrace
        (warned + counted). Arming is EXPLICIT — the caller decides
        when the working set of shapes is complete, because only the
        caller knows it (auto-arming after one cycle would false-alarm
        on the first prompt to hit a new, legitimate prefill bucket).
        ``engine.mark_warm()`` arms all four serving entry points at
        once; benches arm after their warm-up request, operators after
        their traffic's bucket sweep."""
        self.warm = True
        return self

    @property
    def overhead_seconds(self) -> float:
        """Cumulative sentinel bookkeeping cost, wrapped-call excluded
        (the MetricsListener self-timing discipline)."""
        return self._overhead

    def report(self) -> Dict[str, Any]:
        return {"name": self.name, "compiles": self.compiles,
                "signatures": len(self.signatures), "warm": self.warm,
                "retraces_after_warm": self.retraces_after_warm}

    # ----------------------------------------------------------- call
    def __call__(self, *args, **kwargs):
        t0 = time.perf_counter()
        before = self._last_size
        t_call = time.perf_counter()
        out = self._fn(*args, **kwargs)
        t_done = time.perf_counter()
        after = self._cache_size()
        self._last_size = after
        if before is not None and after is not None:
            compiled = after > before
            sig = abstract_signature(args, kwargs) if compiled else None
        else:
            # no cache introspection on this callable: a new abstract
            # signature is the best available compile signal (misses a
            # same-signature retrace; the jit-backed path catches those)
            sig = abstract_signature(args, kwargs)
            compiled = sig not in self.signatures
        if compiled:
            self._record_compile(sig, t_done - t_call)
        self._overhead += (t_call - t0) + (time.perf_counter() - t_done)
        return out

    def _record_compile(self, sig, dt: float):
        self.compiles += 1
        self.signatures[sig] = self.signatures.get(sig, 0) + 1
        c_total, c_secs, c_retr = self._m()
        c_total.inc(component=self.name)
        c_secs.observe(dt, component=self.name)
        try:
            from .spans import Span, derived_span_id, get_tracer
            tracer = get_tracer()
            trace_id = derived_span_id("dl4j_compile", self.name)
            tracer.add_span(Span(
                name=f"compile.{self.name}", trace_id=trace_id,
                span_id=derived_span_id(trace_id, self.compiles),
                start_ts=time.time() - dt, time_s=dt,
                attrs={"component": self.name,
                       "compile_index": self.compiles,
                       "retrace": self.warm}))
        except Exception:  # noqa: BLE001 — span export is decoration
            pass
        if self.warm:
            self.retraces_after_warm += 1
            c_retr.inc(component=self.name)
            warnings.warn(
                f"post-warmup retrace #{self.retraces_after_warm} of "
                f"{self.name!r} (compile {self.compiles}, "
                f"{dt * 1e3:.1f} ms): a shape/dtype/static-arg drifted — "
                "a retrace storm erases the compiled-once contract",
                RuntimeWarning, stacklevel=3)


def wrap_jit(name: str, fn: Callable, *, registry=None) -> CompileSentinel:
    """Construction shorthand: ``wrap_jit("decode_step", jax.jit(f))``."""
    return CompileSentinel(name, fn, registry=registry)
