"""Unified autotune harness — persistent cost records for every tuned
surface (ISSUE 17 tentpole, second half; SURVEY §7 R2 item).

The reference leans on cuDNN's internal autotuner
(cudnnFindConvolutionAlgorithmEx et al., arXiv 1410.0759); XLA has no
equivalent for hand-written pallas kernels, so this is ours — the TVM
cost-record pattern (arXiv 1802.04799), minus the learned model: time
each candidate on the REAL device with the same marginal-chained-steps
discipline bench.py uses, pick the fastest, and persist the verdict as
a cost record in ``~/.deeplearning4j_tpu/autotune.json`` so one
process's sweep pays for every later run on the same chip generation.

One store, one key grammar, three tuned surfaces today:

- ``flash5:...`` — flash-attention (block_q, block_k) per shape
  (``flash_attention._tuned_blocks``);
- ``serving_page_len: / serving_prefill_chunk: / serving_decode_slots:``
  — the serving knobs (``serving/tune.py``);
- ``paged_decode:...`` — the pallas paged-attention decode kernel's
  fidelity-gated kernel-vs-XLA promotion verdicts
  (``kernels/paged_attention.py``).

A key's KIND is everything before the first ``:`` — the public
:func:`records` filter. Every record is::

    {"choice": [...],                 # the winning candidate
     "meta":   {"measured_at": ..., "best_s": ...,
                "measurements": [[cand, seconds|null|"error text"], ...],
                ...},
     "sha":    "..." | absent}        # source fingerprint, see below

**Sha auto-invalidation**: a record written with ``sha=`` (the digest
of the kernel source that was measured — :func:`source_sha`) is only
served while the caller presents the SAME sha. A lookup with a
different sha deletes the record, bumps
``dl4j_autotune_invalidations_total`` and falls through to the
re-measure path — editing a kernel can never be served a stale verdict
measured against the old code. Records without a sha (flash blocks,
serving knobs: the measured code is the caller itself) never
invalidate this way.

Public API (ISSUE 17 satellite — ``serving/tune.py`` and every new
consumer go through these, not the private store internals):

- :func:`autotune` — race candidates, cache the winner (sha-aware);
- :func:`records` / :func:`lookup` / :func:`choice` — read records
  back (``kind=`` filters by key kind-prefix);
- :func:`put` / :func:`invalidate` — write/drop one record;
- :func:`source_sha` — fingerprint a kernel's source for ``sha=``;
- :func:`measurement_meta` / :func:`clear_cache` — as before.

``_disk_cache`` / ``_entry_choice`` remain as deprecated shims for the
PR 14 private imports; new code uses :func:`records` / :func:`choice`.
"""

from __future__ import annotations

import hashlib
import inspect
import json
import os
import time
import warnings
from pathlib import Path
from typing import Callable, Dict, Iterable, Optional, Tuple

_memory_cache: Dict[str, Tuple] = {}
_CACHE_PATH = Path(os.environ.get(
    "DL4J_TPU_DATA", Path.home() / ".deeplearning4j_tpu")) / "autotune.json"


# ------------------------------------------------------------- store --

def _load_store() -> dict:
    try:
        return json.loads(_CACHE_PATH.read_text())
    except Exception:  # noqa: BLE001 — absent/corrupt cache = empty
        return {}


def _save_store(store: dict):
    try:
        _CACHE_PATH.parent.mkdir(parents=True, exist_ok=True)
        _CACHE_PATH.write_text(json.dumps(store, indent=1))
    except OSError:
        pass  # read-only home: in-process cache still works


def _normalize(entry) -> dict:
    """Disk entries are either the bare choice list (legacy) or a
    ``{"choice": [...], "meta": {...}, "sha": ...}`` record with
    measurement provenance (TVM cost-record discipline: every cached
    verdict says when and from what measurements it was reached)."""
    if isinstance(entry, dict):
        return {"choice": list(entry.get("choice", [])),
                "meta": entry.get("meta"),
                "sha": entry.get("sha")}
    return {"choice": list(entry), "meta": None, "sha": None}


def _kind(key: str) -> str:
    return key.split(":", 1)[0]


# ------------------------------------------------------ public reads --

def records(kind: Optional[str] = None) -> Dict[str, dict]:
    """Every persisted cost record, normalized to
    ``{key: {choice, meta, sha}}``. ``kind=`` filters by the key's
    kind segment (everything before the first ``:``) — prefix-matched,
    so ``kind="serving"`` returns all three ``serving_*`` knob
    families and ``kind="serving_page_len"`` exactly one."""
    out = {}
    for key, entry in _load_store().items():
        if kind is not None and not _kind(key).startswith(kind):
            continue
        out[key] = _normalize(entry)
    return out


def lookup(key: str, sha: Optional[str] = None) -> Optional[dict]:
    """The record for ``key`` — ``{choice, meta, sha}`` — or None.
    When the caller presents a ``sha`` and the record carries a
    DIFFERENT one, the record is stale against the current kernel
    source: it is deleted (memory + disk), the invalidation counter
    bumps, and None returns — the caller re-measures."""
    store = _load_store()
    if key not in store:
        return None
    rec = _normalize(store[key])
    if sha is not None and rec["sha"] is not None and rec["sha"] != sha:
        invalidate(key, reason="sha")
        return None
    return rec


def choice(key: str, sha: Optional[str] = None) -> Optional[Tuple]:
    """The cached winning candidate for ``key`` as a tuple, or None
    (miss, or sha-invalidated — see :func:`lookup`)."""
    rec = lookup(key, sha=sha)
    return None if rec is None else tuple(rec["choice"])


def measurement_meta(key: str) -> Optional[dict]:
    """The measurement provenance recorded for `key`, or None (cache
    miss / legacy entry)."""
    rec = lookup(key)
    return None if rec is None else rec["meta"]


# ----------------------------------------------------- public writes --

def put(key: str, chosen, meta: Optional[dict] = None,
        sha: Optional[str] = None):
    """Persist one cost record (memory + disk). ``chosen`` is the
    winning candidate (any sequence); ``meta`` the measurement
    provenance; ``sha`` the source fingerprint that gates staleness."""
    store = _load_store()
    entry = {"choice": list(chosen)}
    if meta is not None:
        entry["meta"] = meta
    if sha is not None:
        entry["sha"] = sha
    store[key] = entry
    _memory_cache[key] = tuple(chosen)
    _save_store(store)


def invalidate(key: str, reason: str = "explicit") -> bool:
    """Drop one record from memory and disk; counts into
    ``dl4j_autotune_invalidations_total{kernel,reason}``. Returns True
    if a disk record existed."""
    _memory_cache.pop(key, None)
    store = _load_store()
    existed = store.pop(key, None) is not None
    if existed:
        _save_store(store)
        try:
            from ..obs import get_registry
            get_registry().counter(
                "dl4j_autotune_invalidations_total",
                "Cost records dropped (sha change, explicit reset)",
                labelnames=("kernel", "reason")).inc(
                    kernel=_kind(key), reason=reason)
        except Exception:  # noqa: BLE001 — telemetry is decoration
            pass
    return existed


def clear_cache():
    _memory_cache.clear()
    try:
        _CACHE_PATH.unlink()
    except OSError:
        pass


def source_sha(*objs) -> str:
    """Fingerprint of the given functions'/modules' SOURCE text — the
    ``sha=`` a kernel passes so its cost records auto-invalidate when
    the kernel is edited. Deliberately source-based (not bytecode):
    a comment-only edit re-races too, which is cheap and safe."""
    h = hashlib.sha256()
    for obj in objs:
        h.update(inspect.getsource(obj).encode())
    return h.hexdigest()[:16]


# -------------------------------------------------------- measurement --

def _time_once(run: Callable[[], object], reps: int = 8) -> float:
    """Marginal seconds per call: `reps` chained calls ended by one host
    fetch, less one call ended by its own fetch."""
    import jax.numpy as jnp

    def fetch(x):
        return float(jnp.asarray(x).reshape(-1)[0])

    fetch(run())  # compile + warmup
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = run()
    fetch(out)
    t_n = time.perf_counter() - t0
    t0 = time.perf_counter()
    fetch(run())
    t_1 = time.perf_counter() - t0
    return max((t_n - t_1) / (reps - 1), 1e-9)


def _race(candidates, make_run, m_measure, m_time, m_race):
    """Time every candidate; returns (best, best seconds, measurements)
    with one ``[candidate, seconds | None | "error text"]`` per candidate
    (None: invalid for the shape). The race's own wall time, the candidates'
    compiles included, is added to ``m_race``."""
    t_race = time.perf_counter()
    best, best_t = None, float("inf")
    measurements = []   # per-candidate provenance for the disk record
    for cand in candidates:
        run = make_run(cand)
        if run is None:                     # invalid for the shape
            measurements.append([list(cand), None])
            continue
        try:
            t = _time_once(run)
        except Exception as e:  # noqa: BLE001 — one candidate the compiler
            # refuses (VMEM, tiling) must not cost the rest; its error is
            # kept, and all of them failing raises in autotune()
            measurements.append(
                [list(cand), f"{type(e).__name__}: {e}"[:500]])
            continue
        m_measure.inc()
        m_time.observe(t)
        measurements.append([list(cand), t])
        if t < best_t:
            best, best_t = cand, t
    m_race.inc(time.perf_counter() - t_race)
    return best, best_t, measurements


def autotune(key: str, candidates: Iterable[Tuple],
             make_run: Callable[[Tuple], Optional[Callable[[], object]]],
             enabled: bool = True, sha: Optional[str] = None) -> Tuple:
    """Pick the fastest candidate for `key`; cached thereafter.

    make_run(candidate) returns a nullary closure executing the kernel with
    that config (returning a fetchable array), or None if the candidate is
    invalid for the shape. With enabled=False the FIRST candidate is
    returned untimed. A candidate that fails to compile or run is recorded
    with its error text in the record's ``measurements``; when EVERY
    candidate fails, nothing is persisted and a RuntimeError carrying the
    errors is raised — a tuned surface never reports an untimed choice as
    a measured one.

    Callers reach this while a jitted function is being traced (the flash
    block choice is made inside the model's forward), where every array a
    candidate makes would be a tracer and nothing could be timed. JAX's
    trace state is thread-local, so candidates are built and timed on a
    worker thread: there their arrays are concrete and their programs
    really run on the device. (``jax.ensure_compile_time_eval()`` is no
    substitute: a pallas kernel traced under it evaluates ``program_id``
    eagerly and fails.)

    ``sha=`` stamps the record with the measured kernel's source
    fingerprint: a later call presenting a different sha invalidates the
    record and re-races (see :func:`lookup`).
    """
    from ..obs import get_registry
    reg = get_registry()
    if key in _memory_cache and sha is None:
        reg.counter("dl4j_autotune_cache_hits_total",
                    "Autotune lookups served from cache",
                    labelnames=("level",)).inc(level="memory")
        return _memory_cache[key]
    cached = lookup(key, sha=sha)
    if cached is not None:
        level = "memory" if key in _memory_cache else "disk"
        reg.counter("dl4j_autotune_cache_hits_total",
                    "Autotune lookups served from cache",
                    labelnames=("level",)).inc(level=level)
        chosen = tuple(cached["choice"])
        _memory_cache[key] = chosen
        return chosen

    candidates = [c for c in candidates]
    if not enabled:
        chosen = candidates[0]
        _memory_cache[key] = chosen
        return chosen

    m_measure = reg.counter("dl4j_autotune_measurements_total",
                            "Candidate configs timed on the device")
    m_time = reg.histogram("dl4j_autotune_candidate_seconds",
                           "Marginal per-call seconds of timed candidates")
    from ..obs.compiles import phase_counters
    m_race = phase_counters()[2]
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=1) as pool:
        best, best_t, measurements = pool.submit(
            _race, candidates, make_run, m_measure, m_time, m_race).result()
    if best is None:
        raise RuntimeError(
            f"autotune {key!r}: no candidate could be timed: "
            + "; ".join(f"{c} -> {why}" for c, why in measurements))
    put(key, best,
        meta={"measured_at": time.time(),
              "best_s": best_t,
              "candidates": len(candidates),
              "measurements": measurements},
        sha=sha)
    return best


# ------------------------------------------- deprecated private shims --
# PR 14's serving/tune.py reached into these; kept so external callers
# keep working one more release. New code: records()/choice()/lookup().

def _disk_cache() -> dict:
    """Deprecated: use :func:`records` (normalized) instead."""
    warnings.warn("autotune._disk_cache is deprecated; use "
                  "autotune.records()", DeprecationWarning, stacklevel=2)
    return _load_store()


def _entry_choice(entry):
    """Deprecated: use :func:`choice`/:func:`lookup` instead."""
    warnings.warn("autotune._entry_choice is deprecated; use "
                  "autotune.choice()/lookup()", DeprecationWarning,
                  stacklevel=2)
    return tuple(_normalize(entry)["choice"])
