"""Gradient anomaly detection — failure-detection subsystem (SURVEY §2.9).

Reference counterpart: DL4J's FailureTestingListener checks +
ExecDebuggingListener / "gradient issues" diagnostics — catching NaN/Inf
gradients, explosions and dead layers DURING training rather than after a
wasted run. The score-level guard is ``nn.listeners.NanScoreWatchdog``;
this module adds per-parameter-group gradient statistics.

TPU-native shape: the statistics are computed INSIDE the jitted train step
(a handful of scalar reductions, fused into the backward pass by XLA — no
extra HBM traffic worth noticing), the step gates its own param/opt-state
update on grad finiteness (a poisoned batch is a no-op, not a lost run),
and only the tiny stats pytree comes back to host — fetched one step LATE
by the fit loops so dispatch pipelining survives — where the detector
applies thresholds and an EMA explosion test.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp


def grad_stats(grads) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Jit-able: per top-level param-group {l2, max_abs, nonfinite_count}.

    Grouping is by the first pytree level (layer name in MLN/CG params), the
    granularity DL4J reports gradient issues at (per-layer).
    """
    out = {}
    for group, sub in grads.items():
        leaves = jax.tree_util.tree_leaves(sub)
        if not leaves:
            continue
        sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
        mx = jnp.max(jnp.stack([jnp.max(jnp.abs(l.astype(jnp.float32)))
                                for l in leaves]))
        nonfinite = sum(jnp.sum(~jnp.isfinite(l.astype(jnp.float32)))
                        for l in leaves)
        # element count is trace-time static — free, and it lets the
        # numerics plane derive rms = l2/sqrt(size) host-side (ISSUE 13)
        size = jnp.float32(sum(int(l.size) for l in leaves))
        out[str(group)] = {"l2": jnp.sqrt(sq), "max_abs": mx,
                           "nonfinite": nonfinite, "size": size}
    return out


def gate_on_finite(stats, *new_old_pairs):
    """Jit-able: if any gradient element is non-finite, return the old value
    of every (new, old) pytree pair — the whole step becomes a no-op (params,
    opt state AND layer state such as BN running stats), so a poisoned batch
    can be detected without losing the run."""
    ok = sum(s["nonfinite"] for s in stats.values()) == 0
    return tuple(
        jax.tree_util.tree_map(lambda n, o: jnp.where(ok, n, o), new, old)
        for new, old in new_old_pairs)


def stats_and_gate(grads, params, new_params, opt_state, new_opt_state,
                   states, new_states):
    """Jit-able one-stop wiring for step functions: compute grad stats and
    gate every piece of step output on grad finiteness. Used identically by
    MultiLayerNetwork, ComputationGraph and ParallelWrapper steps."""
    stats = grad_stats(grads)
    new_params, new_opt_state, new_states = gate_on_finite(
        stats, (new_params, params), (new_opt_state, opt_state),
        (new_states, states))
    return stats, new_params, new_opt_state, new_states


def maybe_stats_and_gate(gate, grads, params, new_params, opt_state,
                         new_opt_state, states, new_states):
    """Jit-able: :func:`stats_and_gate` when ``gate`` is set (policies
    that must leave a poisoned step bit-identical), plain
    :func:`grad_stats` with the step outputs passed through when it is
    not (observe-only detectors / sentinel policy "warn"). ``gate`` is
    a trace-time Python bool — the step builder (``nn/_fit_common.py``)
    resolves it from the detector's ``gate_updates`` before compiling."""
    if gate:
        return stats_and_gate(grads, params, new_params, opt_state,
                              new_opt_state, states, new_states)
    return grad_stats(grads), new_params, new_opt_state, new_states


class DelayedAnomalyCheck:
    """Host-side: checks each step's stats ONE step late so the fit loop
    never blocks on the step it just dispatched (preserves async pipelining).
    Call push() after each step and flush() when the loop ends."""

    def __init__(self, detector: "GradientAnomalyDetector"):
        self.detector = detector
        self._pending = None

    def push(self, stats, iteration: int):
        if self._pending is not None:
            self.detector.check(jax.device_get(self._pending[0]), self._pending[1])
        self._pending = (stats, iteration)

    def flush(self):
        if self._pending is not None:
            self.detector.check(jax.device_get(self._pending[0]), self._pending[1])
            self._pending = None


@dataclass
class GradientAnomaly:
    kind: str        # "nonfinite" | "explosion" | "vanishing"
    layer: str
    iteration: int
    detail: str

    def __str__(self):
        return (f"[{self.kind}] layer '{self.layer}' at iteration "
                f"{self.iteration}: {self.detail}")


@dataclass
class GradientAnomalyDetector:
    """Host-side thresholds over the in-jit stats.

    - nonfinite: any NaN/Inf gradient element → always an anomaly.
    - explosion: per-layer grad L2 exceeding `explosion_abs`, or exceeding
      `explosion_ratio` × its own EMA (warmup-gated so init noise is ignored).
    - vanishing: per-layer max|g| below `vanishing_abs` for
      `vanishing_patience` consecutive checks (a dead/saturated layer).

    `strict=True` raises FloatingPointError on nonfinite/explosion;
    otherwise anomalies are recorded in `.anomalies` (listener-style).
    """

    explosion_abs: float = 1e4
    explosion_ratio: float = 100.0
    vanishing_abs: float = 1e-10
    vanishing_patience: int = 10
    ema_decay: float = 0.9
    warmup_iters: int = 5
    strict: bool = True
    anomalies: List[GradientAnomaly] = field(default_factory=list)
    _ema: Dict[str, float] = field(default_factory=dict)
    _seen: Dict[str, int] = field(default_factory=dict)
    _dead_streak: Dict[str, int] = field(default_factory=dict)

    def check(self, stats: Dict[str, Dict], iteration: int) -> List[GradientAnomaly]:
        """stats: host-fetched output of grad_stats. Returns new anomalies."""
        new: List[GradientAnomaly] = []
        for layer, s in stats.items():
            l2 = float(s["l2"]); mx = float(s["max_abs"])
            nf = int(s["nonfinite"])
            if nf > 0 or math.isnan(l2) or math.isinf(l2):
                new.append(GradientAnomaly(
                    "nonfinite", layer, iteration,
                    f"{nf} non-finite gradient elements (l2={l2})"))
                continue
            seen = self._seen.get(layer, 0)
            ema = self._ema.get(layer)
            exploded = l2 > self.explosion_abs or (
                ema is not None and seen >= self.warmup_iters
                and ema > 0 and l2 > self.explosion_ratio * ema)
            if exploded:
                new.append(GradientAnomaly(
                    "explosion", layer, iteration,
                    f"grad l2={l2:.3e} (ema={ema if ema is None else f'{ema:.3e}'}, "
                    f"abs threshold={self.explosion_abs:.0e})"))
            self._ema[layer] = l2 if ema is None else (
                self.ema_decay * ema + (1 - self.ema_decay) * l2)
            self._seen[layer] = seen + 1
            if mx < self.vanishing_abs:
                streak = self._dead_streak.get(layer, 0) + 1
                self._dead_streak[layer] = streak
                if streak == self.vanishing_patience:
                    new.append(GradientAnomaly(
                        "vanishing", layer, iteration,
                        f"max|g|={mx:.1e} for {streak} consecutive checks"))
            else:
                self._dead_streak[layer] = 0
        self.anomalies.extend(new)
        if self.strict:
            fatal = [a for a in new if a.kind in ("nonfinite", "explosion")]
            if fatal:
                raise FloatingPointError(
                    "gradient anomaly detected:\n  " + "\n  ".join(map(str, fatal)))
        return new
