"""Tracing & profiling — parity with the reference's op profiler / debug path.

Reference counterparts (upstream Eclipse DL4J, per SURVEY.md provenance):
- nd4j ``OpProfiler`` / ``ProfilerConfig`` (op invocation counts, timings,
  bad-value checks) — `nd4j-api/.../profiler/OpProfiler`.
- ``Nd4j.getExecutioner().printEnvironmentInformation()`` and exec debug.
- Performance listener + training UI timing charts.

TPU-native rethink: under ``jit`` everything fuses, so "per-op timing" at
runtime is an XLA concern, not a Python one. The tracer therefore works at
THREE levels, matching how TPU work is actually analysed:

1. **Trace-time op inventory** (`trace_ops`): walk the jaxpr — exact list of
   primitives, shapes, and analytic FLOP counts. Zero execution cost.
2. **Interpreted per-op profile** (`profile_ops`): eval the jaxpr op-by-op
   with host timing — the debug/dev analogue of OpProfiler (not for prod).
3. **XLA-level** (`profile_trace`, `dump_hlo`, `cost_analysis`): the real
   TPU story — jax.profiler traces for tensorboard, compiled-HLO text dump,
   and XLA's own cost model per executable.
"""

from __future__ import annotations

import contextlib
import math
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.extend import core as jcore


# --------------------------------------------------------------------------
# FLOP estimation for the primitives that dominate TPU time (MXU ops).
# --------------------------------------------------------------------------

def _dot_general_flops(eqn) -> int:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    batch = math.prod(lhs.shape[d] for d in lb) if lb else 1
    contract = math.prod(lhs.shape[d] for d in lc) if lc else 1
    m = math.prod(lhs.shape[d] for d in range(len(lhs.shape))
                  if d not in lc and d not in lb)
    n = math.prod(rhs.shape[d] for d in range(len(rhs.shape))
                  if d not in rc and d not in rb)
    return 2 * batch * m * n * contract


def _conv_flops(eqn) -> int:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval  # kernel
    # 2 * output_elements * kernel_spatial * in_features
    dn = eqn.params["dimension_numbers"]
    k_spatial = math.prod(rhs.shape[d] for d in dn.rhs_spec[2:])
    cin = rhs.shape[dn.rhs_spec[1]]
    groups = eqn.params.get("feature_group_count", 1)
    return 2 * math.prod(out.shape) * k_spatial * (cin // max(groups, 1)) * 1


_FLOP_FNS = {
    "dot_general": _dot_general_flops,
    "conv_general_dilated": _conv_flops,
}


@dataclass
class OpRecord:
    """One primitive occurrence (or aggregate) from a traced computation."""
    prim: str
    count: int = 0
    flops: int = 0
    bytes_out: int = 0
    time_s: float = 0.0
    shapes: List[str] = field(default_factory=list)

    def row(self) -> str:
        t = f"{self.time_s * 1e3:10.3f}ms" if self.time_s else " " * 12
        fl = f"{self.flops / 1e9:9.3f}G" if self.flops else " " * 10
        return f"{self.prim:<28}{self.count:>6}  {fl}  {t}  {self.shapes[0] if self.shapes else ''}"


def _walk_jaxpr(jaxpr, agg: Dict[str, OpRecord], depth=0, mult=1):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        # Recurse into higher-order primitives so scan/cond/jit bodies count.
        # A scan body executes `length` times — multiply its contribution, or
        # every scanned model (LSTM over T, per-layer transformer scan)
        # under-counts by the trip count. while_loop trip counts are unknown
        # at trace time: counted once (documented best-effort floor).
        sub_mult = mult
        if name == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1))
        for pname in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr",
                      "branches", "fun_jaxpr"):
            sub = eqn.params.get(pname)
            if sub is None:
                continue
            subs = sub if isinstance(sub, (tuple, list)) else [sub]
            for s in subs:
                inner = s.jaxpr if hasattr(s, "jaxpr") else s
                if hasattr(inner, "eqns"):
                    _walk_jaxpr(inner, agg, depth + 1, sub_mult)
        rec = agg.setdefault(name, OpRecord(prim=name))
        rec.count += mult
        fn = _FLOP_FNS.get(name)
        if fn is not None:
            try:
                rec.flops += mult * fn(eqn)
            except Exception:  # noqa: BLE001 — estimation is best-effort
                pass
        for ov in eqn.outvars:
            aval = getattr(ov, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                rec.bytes_out += mult * math.prod(aval.shape or (1,)) * getattr(
                    aval.dtype, "itemsize", 4)
        if len(rec.shapes) < 3 and eqn.outvars:
            aval = getattr(eqn.outvars[0], "aval", None)
            if aval is not None:
                rec.shapes.append(str(aval))


def trace_ops(fn: Callable, *args, **kwargs) -> List[OpRecord]:
    """Trace `fn` and return aggregated per-primitive records (no execution).

    The TPU analogue of OpProfiler's invocation census: exact op inventory
    with analytic FLOPs for MXU ops (dot_general / conv).
    """
    closed = jax.make_jaxpr(fn, **({"static_argnums": kwargs.pop("static_argnums")}
                                   if "static_argnums" in kwargs else {}))(*args, **kwargs)
    agg: Dict[str, OpRecord] = {}
    _walk_jaxpr(closed.jaxpr, agg)
    return sorted(agg.values(), key=lambda r: (-r.flops, -r.count))


def total_flops(fn: Callable, *args, **kwargs) -> int:
    return sum(r.flops for r in trace_ops(fn, *args, **kwargs))


def format_op_report(records: List[OpRecord], title="op trace") -> str:
    lines = [f"== {title} ==",
             f"{'primitive':<28}{'count':>6}  {'flops':>10}  {'time':>12}  sample shape"]
    lines += [r.row() for r in records]
    lines.append(f"total primitives: {sum(r.count for r in records)}; "
                 f"total flops: {sum(r.flops for r in records) / 1e9:.3f} GFLOP")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# Interpreted per-op profiling (debug mode — runs op-by-op on host).
# --------------------------------------------------------------------------

def profile_ops(fn: Callable, *args) -> List[OpRecord]:
    """Execute `fn` one primitive at a time, timing each (debug analogue of
    OpProfiler's ALL_OPS timing mode). Orders of magnitude slower than jit —
    use for small shapes / debugging only; real profiling is `profile_trace`.
    """
    closed = jax.make_jaxpr(fn)(*args)
    flat_args = jax.tree_util.tree_leaves(args)
    agg: Dict[str, OpRecord] = {}

    def eval_jaxpr(jaxpr, consts, *inputs):
        env: Dict[Any, Any] = {}

        def read(v):
            return v.val if isinstance(v, jcore.Literal) else env[v]

        def write(v, val):
            env[v] = val

        for v, c in zip(jaxpr.constvars, consts):
            write(v, c)
        for v, a in zip(jaxpr.invars, inputs):
            write(v, a)
        for eqn in jaxpr.eqns:
            invals = [read(v) for v in eqn.invars]
            t0 = time.perf_counter()
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            outs = eqn.primitive.bind(*subfuns, *invals, **bind_params)
            outs_flat = outs if eqn.primitive.multiple_results else [outs]
            for o in outs_flat:
                if hasattr(o, "block_until_ready"):
                    o.block_until_ready()
            dt = time.perf_counter() - t0
            rec = agg.setdefault(eqn.primitive.name, OpRecord(prim=eqn.primitive.name))
            rec.count += 1
            rec.time_s += dt
            fl = _FLOP_FNS.get(eqn.primitive.name)
            if fl is not None:
                try:
                    rec.flops += fl(eqn)
                except Exception:  # noqa: BLE001
                    pass
            for v, o in zip(eqn.outvars, outs_flat):
                write(v, o)
        return [read(v) for v in jaxpr.outvars]

    eval_jaxpr(closed.jaxpr, closed.consts, *flat_args)
    return sorted(agg.values(), key=lambda r: -r.time_s)


# --------------------------------------------------------------------------
# jax.profiler hooks — the production path (tensorboard / xprof traces).
# --------------------------------------------------------------------------

@contextlib.contextmanager
def profile_trace(log_dir: str = "runs/profile", host_tracer_level: int = 2):
    """Capture a device+host trace viewable in TensorBoard's profile plugin.
    Wraps jax.profiler.trace; on TPU this records XLA executable timelines."""
    Path(log_dir).mkdir(parents=True, exist_ok=True)
    with jax.profiler.trace(str(log_dir)):
        yield log_dir


def start_profiler_server(port: int = 9999):
    """On-demand profiling: connect tensorboard's capture-profile to this."""
    return jax.profiler.start_server(port)


class StepTimer:
    """Lightweight wall-clock step timer with percentile summary — what the
    PerformanceListener uses under the hood; usable standalone around any
    step function (blocks on the result to include device time)."""

    def __init__(self):
        self.times: List[float] = []

    @contextlib.contextmanager
    def step(self):
        t0 = time.perf_counter()
        yield
        self.times.append(time.perf_counter() - t0)

    def summary(self, skip_first: int = 1) -> Dict[str, float]:
        ts = self.times[skip_first:] or self.times
        if not ts:
            return {}
        arr = np.array(ts)
        return {"mean_s": float(arr.mean()), "p50_s": float(np.percentile(arr, 50)),
                "p90_s": float(np.percentile(arr, 90)), "min_s": float(arr.min()),
                "steps": len(ts)}


# --------------------------------------------------------------------------
# XLA HLO dump + cost analysis.
# --------------------------------------------------------------------------

def dump_hlo(fn: Callable, *args, directory: Optional[str] = None,
             name: str = "computation", optimized: bool = True) -> Dict[str, str]:
    """Lower + compile `fn` and return {stage: text} for StableHLO and
    (optionally) the post-optimization HLO the TPU actually runs.
    If `directory` is given, also writes `<name>.<stage>.txt` files."""
    lowered = jax.jit(fn).lower(*args)
    out = {"stablehlo": lowered.as_text()}
    if optimized:
        compiled = lowered.compile()
        try:
            out["optimized_hlo"] = compiled.as_text()
        except Exception:  # noqa: BLE001 — some backends withhold it
            pass
    if directory:
        d = Path(directory)
        d.mkdir(parents=True, exist_ok=True)
        for stage, text in out.items():
            (d / f"{name}.{stage}.txt").write_text(text)
    return out


def cost_analysis(fn: Callable, *args) -> Dict[str, float]:
    """XLA's own cost model for the compiled executable: flops, bytes
    accessed, transcendentals — the ground truth the analytic estimate in
    `trace_ops` approximates."""
    compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if ca else {}


def memory_analysis(fn: Callable, *args) -> Dict[str, int]:
    """Compiled-executable memory footprint (bytes): args, outputs, temps,
    generated code. Key for fitting models in HBM before touching a chip."""
    compiled = jax.jit(fn).lower(*args).compile()
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes"]
    return {k: getattr(ma, k) for k in keys if hasattr(ma, k)}
