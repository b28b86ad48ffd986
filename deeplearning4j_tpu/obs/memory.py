"""Memory census — HBM attribution over named components (ISSUE 12).

Every serving bench row's floor block says decode is MEMORY-bound, and
the two biggest ROADMAP levers (paged KV cache, ZeRO update sharding)
are memory plays: one must prove short requests stop paying ``max_len``
bytes, the other must prove a per-chip memory drop. Neither can be
sized or guarded without attribution — *whose* bytes are on the chip?

This module answers with two sources, combined:

- :func:`tree_bytes` — pytree attribution. Sums leaf ``nbytes`` over a
  named component (params, optimizer state, KV cache, workspace), which
  works on EVERY backend — the CPU tier-1 suite gets real numbers, not
  a silent gap (the ``MetricsListener._poll_memory`` degradation this
  PR fixes). Per-replica attribution reads each leaf's addressable
  shards, so an fsdp-sharded param tree reports what each device
  actually holds, not the logical size.
- :func:`device_memory_stats` — the allocator's own view
  (``device.memory_stats()``: bytes_in_use / peak_bytes_in_use /
  bytes_limit), present on TPU/GPU, gracefully ``None`` on CPU. The
  census carries BOTH: pytree bytes attribute, allocator bytes bound —
  the gap between them is fragmentation + XLA workspace, itself a
  number worth watching.

:func:`emit_census` publishes a census as
``dl4j_mem_component_bytes{component, replica}`` gauges on the process
registry and remembers the latest census per (source, replica) so
``GET /debug/memory`` on the UI server and ``scripts/mem_report.py``
can show the current attribution without re-walking live pytrees.

Label discipline (``scripts/check_metric_names.py`` enforces): the
``dl4j_mem_*`` / ``dl4j_kv_*`` / ``dl4j_compile_*`` plane may label by
``component`` and ``replica`` ONLY — component names are a small fixed
vocabulary (params / optimizer / kv_cache / grads / workspace / total),
never per-request identity.

No jax import at module load — and no package-relative import either:
like the registry, the census must be importable from the UI process
and bench subprocesses, and stay loadable STANDALONE by file path
(without paying the package's jax import chain).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

# the small fixed component vocabulary — emit_census warns (via ValueError)
# on names outside it so dashboards aggregate a stable label set
KNOWN_COMPONENTS = ("params", "optimizer", "kv_cache", "grads",
                    "workspace", "states", "total")

_DEVICE_STAT_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                     "largest_alloc_size")


def format_bytes(v) -> str:
    """Human-readable bytes — the ONE implementation both
    ``scripts/mem_report.py`` and the README table renderer use, so a
    byte count never renders two ways."""
    if v is None:
        return "-"
    v = float(v)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(v) < 1024 or unit == "GiB":
            return f"{int(v)} B" if unit == "B" else f"{v:,.1f} {unit}"
        v /= 1024
    return f"{v:,.1f} GiB"   # unreachable; keeps the signature total


def _leaf_nbytes(x) -> int:
    nb = getattr(x, "nbytes", None)
    if nb is not None:
        return int(nb)
    size = getattr(x, "size", None)
    dt = getattr(x, "dtype", None)
    if size is not None and dt is not None:
        return int(size) * int(getattr(dt, "itemsize", 0) or 0)
    return 0


def tree_bytes(tree) -> int:
    """Total bytes held by a pytree's array leaves (None leaves free)."""
    if tree is None:
        return 0
    import jax
    return sum(_leaf_nbytes(leaf)
               for leaf in jax.tree_util.tree_leaves(tree))


def component_bytes(components: Dict[str, Any]) -> Dict[str, int]:
    """{name: pytree} → {name: bytes}; a ``total`` row is appended."""
    out = {name: tree_bytes(tree) for name, tree in components.items()}
    out["total"] = sum(out.values())
    return out


def per_replica_bytes(tree) -> Dict[str, int]:
    """Bytes each addressable device actually holds of ``tree``.

    A sharded leaf contributes each shard's bytes to that shard's
    device; an unsharded/host leaf contributes everything to replica
    "0". This is what makes the ZeRO per-chip-memory-drop proof a
    gauge read instead of a hand calculation."""
    if tree is None:
        return {"0": 0}
    import jax
    acc: Dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            for sh in shards:
                key = str(getattr(sh.device, "id", 0))
                acc[key] = acc.get(key, 0) + _leaf_nbytes(sh.data)
        else:
            acc["0"] = acc.get("0", 0) + _leaf_nbytes(leaf)
    return acc or {"0": 0}


def device_memory_stats(device=None) -> Optional[Dict[str, float]]:
    """The allocator's view for one device, or None where the backend
    has no ``memory_stats`` (CPU) — callers fall back to pytree sizes,
    they never go blind."""
    try:
        import jax
        dev = device or jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:  # noqa: BLE001 — absence is an expected backend trait
        return None
    if not stats:
        return None
    return {k: float(stats[k]) for k in _DEVICE_STAT_KEYS if k in stats}


# --------------------------------------------------------------- census

# latest census per (source, replica) — what /debug/memory serves
_CENSUSES: Dict[tuple, Dict[str, Any]] = {}
_LOCK = threading.Lock()


def emit_census(components: Dict[str, Any], *, replica: str = "0",
                source: str = "train", registry=None,
                per_replica: bool = False) -> Dict[str, Any]:
    """Attribute ``components`` ({name: pytree}) and publish.

    Sets ``dl4j_mem_component_bytes{component, replica}`` gauges,
    attaches the allocator stats when the backend has them (graceful
    absence on CPU — the pytree numbers stand alone), and records the
    census for ``GET /debug/memory``.

    ``registry`` is a :class:`~.registry.MetricsRegistry` (un-annotated
    on purpose: this module must load standalone by file path, so it
    imports nothing package-relative, not even for a type hint);
    ``None`` means the process-wide registry.

    With ``per_replica=True`` the GAUGES are per-device: each component
    split by the devices its shards actually live on (ParallelWrapper
    wiring — the per-chip number the ZeRO memory-drop proof reads).
    The aggregate numbers live in the returned census record's
    ``component_bytes``; they are deliberately NOT also written under
    ``replica`` — device ids start at "0" and would silently overwrite
    the aggregate row, leaving components that don't sum to ``total``.

    Returns the census record (plain data, JSON-able).
    """
    for name in components:
        if name not in KNOWN_COMPONENTS:
            raise ValueError(
                f"unknown memory component {name!r}: pick from "
                f"{KNOWN_COMPONENTS[:-1]} (a stable label vocabulary — "
                "extend KNOWN_COMPONENTS deliberately)")
    if registry is None:
        from . import get_registry
        registry = get_registry()
    gauge = registry.gauge(
        "dl4j_mem_component_bytes",
        "Device bytes attributed to a named component (pytree census; "
        "the allocator view rides the census record)",
        labelnames=("component", "replica"))
    by_comp = component_bytes(components)
    rep = str(replica)
    census: Dict[str, Any] = {
        "kind": "memcensus", "source": source, "replica": rep,
        "ts": time.time(), "component_bytes": by_comp,
    }
    if per_replica:
        split: Dict[str, Dict[str, int]] = {}
        for name, tree in components.items():
            for dev, nbytes in per_replica_bytes(tree).items():
                split.setdefault(dev, {})
                split[dev][name] = split[dev].get(name, 0) + nbytes
        for dev, comps in split.items():
            comps["total"] = sum(comps.values())
            for name, nbytes in comps.items():
                gauge.set(float(nbytes), component=name, replica=dev)
        census["per_replica_bytes"] = split
    else:
        for name, nbytes in by_comp.items():
            gauge.set(float(nbytes), component=name, replica=rep)
    stats = device_memory_stats()
    census["device"] = stats                  # None on CPU — explicit
    census["device_source"] = "memory_stats" if stats else "pytree"
    with _LOCK:
        _CENSUSES[(source, rep)] = census
    return census


def latest_censuses() -> List[Dict[str, Any]]:
    """Every (source, replica)'s most recent census, stable order."""
    with _LOCK:
        return [_CENSUSES[k] for k in sorted(_CENSUSES)]


def reset_censuses():
    """Drop recorded censuses (tests)."""
    with _LOCK:
        _CENSUSES.clear()


def debug_state() -> Dict[str, Any]:
    """What ``GET /debug/memory`` returns: the latest census per
    source/replica, the live allocator view, and the KV-residency
    accounting of every live scheduler (via its flight recorder's
    ``extra_state`` — the same hook /debug/serving reads)."""
    kv = []
    try:
        from .reqtrace import live_flight_recorders
        for fr in live_flight_recorders():
            if fr.extra_state is None:
                continue
            try:
                state = fr.extra_state()
            except Exception as e:  # noqa: BLE001 — debug must not raise
                state = {"error": repr(e)}
            if "kv" in state:
                kv.append({"replica": fr.replica, **state["kv"]})
    except Exception:  # noqa: BLE001 — debug must not raise
        pass
    return {"censuses": latest_censuses(),
            "device": device_memory_stats(),
            "kv": kv}
