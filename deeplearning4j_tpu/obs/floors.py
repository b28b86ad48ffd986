"""Roofline floor engine — every headline bench row explains itself.

ROADMAP item 5 ("floor-or-lever discipline"): a throughput number without
a hardware floor is indistinguishable from "stopped improving". This
module derives, for any jitted step function, the two quantities a
roofline account needs —

- **flops**: total floating-point work per step,
- **bytes**: HBM/memory traffic per step,

preferring XLA's own cost model (``lowered.compile().cost_analysis()``,
the ground truth the paper-era Caffe-con-Troll proportion-of-peak tables
were built from) and falling back to a jaxpr-walk estimator
(``utils/tracing.trace_ops``: analytic MXU flops keyed on layer shapes,
bytes from per-primitive output sizes) when a backend omits or truncates
the cost model; a floor block then degrades to ``source="estimated"``
rather than crashing a bench row.

Combined with the peak table below (keyed by ``device_kind``, shared
with ``bench.py``'s MFU audit), the costs become a
compute/memory roofline::

    compute_floor_ms = flops / peak_flops
    memory_floor_ms  = bytes / peak_bytes_per_s
    floor_ms         = max(...)          # the binding resource
    pct_of_floor     = floor_ms / measured_step_ms

``pct_of_floor`` ≥ ~0.85 means the row is within the 15% floor-or-lever
band (verdict ``ok``); below it the row owes a named lever (verdict
``lever``). Values > 1 are possible and meaningful: XLA's fusion can
beat the cost model's un-fused byte count (the measured ResNet step runs
*below* the cost-analysis HBM floor — docs/PERF.md).

CPU entries in the peak table are NOMINAL order-of-magnitude host values
so the whole pipeline is exercised by tier-1 CPU tests; a CPU
``pct_of_floor`` is a plumbing check, not a performance claim
(``peaks_nominal: true`` marks such blocks).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

# Attainable peaks of one device, keyed by ``jax.devices()[0].device_kind``
# — THE table: bench.py's MFU audit reads it too. flops keyed by compute
# dtype: f32 matmuls run at ~half the bf16 MXU rate. A kind that is not
# here is an error (:func:`device_peaks`), never silently a v5e.
PEAKS: Dict[str, dict] = {
    "TPU v5 lite": {
        "flops": {"bf16": 197e12, "f32": 98.5e12},
        "bytes_per_s": 819e9,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s "
                  "bf16, 819 GB/s HBM per chip",
    },
    "cpu": {
        # Nominal host-class numbers (order of magnitude for a modern
        # server core count); present so tier-1 CPU tests exercise the
        # floor pipeline end-to-end. Marked nominal in every block.
        "flops": {"bf16": 1.0e12, "f32": 0.5e12},
        "bytes_per_s": 50e9,
        "source": "nominal host values (CI plumbing, not a perf claim)",
        "nominal": True,
    },
}


def device_peaks(device_kind: Optional[str] = None) -> dict:
    """Peak entry for ``device_kind`` (default: the kind of the first
    device jax reports). A kind without an entry raises: a roofline
    against another chip's peaks is a wrong number, not a missing one."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAKS:
        raise ValueError(
            f"no peak table entry for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add its published peaks and their "
            "source to obs/floors.py PEAKS")
    return PEAKS[device_kind]


def estimate_costs(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    """Fallback estimator: analytic flops (jaxpr dot/conv walk — exact
    layer shapes, scan trip counts multiplied) and bytes as the sum of
    every primitive's output size plus the inputs read. Overestimates
    traffic relative to a fused XLA executable (every intermediate is
    counted at memory once), which is the conservative direction for a
    floor: an estimated memory floor is an upper bound on the real one."""
    import math

    import jax

    from ..utils.tracing import trace_ops

    records = trace_ops(fn, *args, **kwargs)
    flops = float(sum(r.flops for r in records))
    bytes_out = float(sum(r.bytes_out for r in records))
    in_bytes = 0.0
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            continue
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 4)
        in_bytes += math.prod(shape or (1,)) * itemsize
    return {"flops": flops, "bytes": bytes_out + in_bytes}


def _cost_analysis_of(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    """{flops, bytes} from XLA's compiled-executable cost model; keys
    absent when the backend omits them. Never raises."""
    import jax

    try:
        lowered = fn.lower(*args, **kwargs) if hasattr(fn, "lower") \
            else jax.jit(fn).lower(*args, **kwargs)
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = dict(ca) if ca else {}
    except Exception:  # noqa: BLE001 — backend withheld the cost model
        return {}
    out = {}
    flops = ca.get("flops")
    if flops is not None and flops > 0:
        out["flops"] = float(flops)
    byts = ca.get("bytes accessed")
    if byts is not None and byts > 0:
        out["bytes"] = float(byts)
    return out


def hlo_costs(fn: Callable, *args, **kwargs) -> Dict[str, Any]:
    """{flops, bytes, source, flops_source, bytes_source} for one step.

    ``fn`` may be a jitted function (its ``.lower`` is used, hitting the
    same lowering the step actually runs) or any traceable callable;
    args may be real arrays or ``jax.ShapeDtypeStruct``s — nothing is
    executed.

    Provenance rules:
    - **bytes**: the compiled executable's "bytes accessed" when
      present (it sees fusion; the estimator can only overcount), else
      the estimator.
    - **flops**: the LARGER of compiled and analytic. XLA's cost
      analysis counts a ``lax.scan`` body ONCE regardless of trip count
      (measured: the 8-block scanned transformer step reports ~10x low,
      which would flip its roofline from compute- to memory-bound),
      while the jaxpr walk multiplies trip counts; taking the max keeps
      whichever accounting actually saw the work.
    - ``source`` is ``"cost_analysis"`` only when BOTH fields come from
      the compiled executable, else ``"estimated"``; per-field
      ``flops_source`` / ``bytes_source`` carry the detail.

    Never raises: a total derivation failure returns ``{"error": ...}``
    for the caller to record."""
    ca = _cost_analysis_of(fn, *args, **kwargs)
    try:
        est = estimate_costs(fn, *args, **kwargs)
    except Exception as e:  # noqa: BLE001 — never crash a bench row
        if not ca:
            return {"error": f"cost derivation failed: "
                             f"{type(e).__name__}: {e}"[:300]}
        est = None
    out: Dict[str, Any] = {}
    ca_fl, est_fl = ca.get("flops"), est["flops"] if est else None
    if ca_fl is not None and (est_fl is None or ca_fl >= est_fl):
        out["flops"], fl_src = ca_fl, "cost_analysis"
    elif est_fl is not None:
        out["flops"], fl_src = est_fl, "estimated"
        if ca_fl is not None:
            out["flops_cost_analysis"] = ca_fl   # the undercount, kept
            # for the record (scan-body-once accounting)
    else:
        return {"error": "no flops from cost_analysis or estimator"}
    if "bytes" in ca:
        out["bytes"], by_src = ca["bytes"], "cost_analysis"
    elif est is not None:
        out["bytes"], by_src = est["bytes"], "estimated"
    else:
        return {"error": "no bytes from cost_analysis or estimator"}
    out["flops_source"], out["bytes_source"] = fl_src, by_src
    out["source"] = ("cost_analysis"
                     if fl_src == by_src == "cost_analysis"
                     else "estimated")
    return out


def floor_block(costs: Dict[str, Any], *, step_ms: Optional[float] = None,
                dtype: str = "bf16", device_kind: Optional[str] = None,
                ok_threshold: float = 0.85) -> Dict[str, Any]:
    """Assemble the ``floor`` block a bench row carries.

    ``costs`` is ``hlo_costs`` output. ``step_ms`` (measured marginal
    step) yields ``pct_of_floor`` + the lever-or-ok verdict; omit it for
    a floor table with no measurement yet (docs use)."""
    if "error" in costs:
        return {"na": costs["error"]}
    block: Dict[str, Any] = {
        "flops": int(costs["flops"]),
        "bytes": int(costs["bytes"]),
        "source": costs.get("source", "estimated"),
    }
    peaks = device_peaks(device_kind)
    peak_flops = peaks["flops"].get(dtype) or peaks["flops"]["f32"]
    block["peak_flops"] = peak_flops
    block["peak_bytes_per_s"] = peaks["bytes_per_s"]
    if peaks.get("nominal"):
        block["peaks_nominal"] = True
    compute_ms = block["flops"] / peak_flops * 1e3
    memory_ms = block["bytes"] / peaks["bytes_per_s"] * 1e3
    block["compute_floor_ms"] = round(compute_ms, 4)
    block["memory_floor_ms"] = round(memory_ms, 4)
    block["floor_ms"] = round(max(compute_ms, memory_ms), 4)
    block["binding_resource"] = ("compute" if compute_ms >= memory_ms
                                 else "memory")
    if step_ms is not None and step_ms > 0 and block["floor_ms"] > 0:
        pct = block["floor_ms"] / step_ms
        block["pct_of_floor"] = round(pct, 4)
        block["verdict"] = "ok" if pct >= ok_threshold else "lever"
    return block


def emit_floor_metrics(config: str, block: Dict[str, Any], registry=None):
    """Mirror a floor block into the dl4j_ registry so a live /metrics
    scrape and the bench artifact read identical names. Returns the
    {name: value} map the bench row embeds; {} for na-blocks."""
    if not block or "floor_ms" not in block:
        return {}
    if registry is None:
        from . import get_registry
        registry = get_registry()
    out = {}
    registry.gauge(
        "dl4j_bench_floor_ms",
        "Roofline floor (max of compute/memory) for a bench row",
        labelnames=("config",)).set(block["floor_ms"], config=config)
    out["dl4j_bench_floor_ms"] = block["floor_ms"]
    if "pct_of_floor" in block:
        registry.gauge(
            "dl4j_bench_pct_of_floor",
            "floor_ms / measured step: 1.0 = at the roofline floor",
            labelnames=("config",)).set(block["pct_of_floor"], config=config)
        out["dl4j_bench_pct_of_floor"] = block["pct_of_floor"]
    return out


def shape_probe(tree):
    """args → ShapeDtypeStructs: lets a builder capture a lowering probe
    BEFORE its buffers are donated (lowering needs shapes, not data)."""
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") else a, tree)
