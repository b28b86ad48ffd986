"""Perf regression & trend plane (ISSUE 15) — the longitudinal layer.

Every other observability plane (floors, SLO, memory, numerics) explains
a SINGLE capture; nothing watched the numbers *across* captures, so a
regression was invisible until a human reread the README, and the
T=4096 best-XLA bimodality (82–152k tokens/s across sessions,
docs/PERF.md) lived as a prose debt with no machine verdict. This
module is the TVM-autotune discipline (PAPERS.md, arXiv 1802.04799 —
measured, persisted cost records beat one-shot eyeballs) applied to
every headline bench row:

- **Ledger** (``runs/perf_ledger.jsonl``): append-only JSONL every
  ``bench.py`` capture feeds. Appends are a single ``O_APPEND`` write
  of one whole line (atomic at these sizes), and the loader tolerates
  a torn trailing line — the ``obs.spans.load_spans`` discipline. Each
  record is keyed by (row, backend, host fingerprint, git sha) and
  carries the capture's median, relative IQR, raw
  ``step_time_ms_samples``, ``pct_of_floor``, compile/retrace
  counters, and (for inference rows) the slo/memory block scalars.
- **Change detection** (:func:`classify_capture`): verdicts for a new
  capture against the ledger history with noise bands derived from the
  *measured* IQR — the PR 13 ``MeasuredBound`` philosophy applied to
  throughput: the band is ``margin × max(measured rel-IQR, floor)``,
  and the margin is the only judgement call. Verdicts: ``stable`` /
  ``improved`` / ``regressed`` / ``unstable`` / ``bimodal``.
- **Bimodality** (:func:`split_clusters` + :func:`series_split`): a
  largest-gap two-cluster split test over the retained samples, with a
  RECURRENCE requirement — one capture's own sample set splitting, or
  a chronological series that keeps alternating between the modes. A
  series that stepped to a new level and stayed there is a *regime
  change* (baseline = where it settled), never two "clusters" a later
  regression could hide inside. ``bimodal`` rows report per-cluster
  medians instead of a meaningless pooled median; the recorded T=4096
  best-XLA session set (:data:`T4096_BEST_XLA_SAMPLES`) finally gets a
  first-class verdict this way.
- **Attribution** (:func:`attribute`): on ``regressed``, auto-diff the
  floor block (flops/bytes moved → model change), the compile counters
  (retraces appeared), and per-layer profiler spans between baseline
  and current into a ``suspects`` list.
- **Export**: verdict counts and pct-vs-baseline as ``dl4j_trend_*``
  gauges (labels: row / backend / verdict only —
  ``scripts/check_metric_names.py`` enforces it) behind
  ``GET /debug/trend`` on the UI server.

``scripts/perf_gate.py`` is the offline driver: ledger → per-row trend
table, exit 1 on an out-of-band regression vs a pinned baseline
(``runs/perf_baseline.json``). The committed ledger already holds the
r01–r05 history a one-shot backfill once wrote.

No jax import anywhere in this module: like ``obs.memory`` it is
standalone-importable by file path, so the scripts run without pulling
the full package in. The registry export is a lazy, optional import.
"""

from __future__ import annotations

import json
import math
import os
import platform
import statistics
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------- paths

_REPO = Path(__file__).resolve().parents[2]


def ledger_path() -> Path:
    """Default ledger location; ``DL4J_TREND_LEDGER`` overrides (tests)."""
    return Path(os.environ.get("DL4J_TREND_LEDGER",
                               _REPO / "runs" / "perf_ledger.jsonl"))


def baseline_path() -> Path:
    return Path(os.environ.get("DL4J_TREND_BASELINE",
                               _REPO / "runs" / "perf_baseline.json"))


def host_fingerprint() -> str:
    """Coarse host identity: CPU-derived numbers drift with the host
    (README: sandbox CPU is not a stable reference), so off-TPU
    comparisons only pool entries from the SAME fingerprint."""
    return f"{platform.node()}:{platform.machine()}:{os.cpu_count()}"


# ------------------------------------------------------------- the ledger

def append_record(rec: Dict[str, Any],
                  path: Optional[os.PathLike] = None) -> float:
    """Append one record as one whole line with a single ``O_APPEND``
    write — atomic at these sizes, so two bench subprocesses can never
    interleave bytes — and return the elapsed seconds (the <2%-of-a-row
    budget is self-timed and pinned in tests/test_trend.py)."""
    p = Path(path) if path is not None else ledger_path()
    t0 = time.perf_counter()
    p.parent.mkdir(parents=True, exist_ok=True)
    line = json.dumps(rec, separators=(",", ":"),
                      sort_keys=True, default=str) + "\n"
    fd = os.open(p, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    try:
        os.write(fd, line.encode())
    finally:
        os.close(fd)
    return time.perf_counter() - t0


def load_ledger(path: Optional[os.PathLike] = None) -> List[Dict[str, Any]]:
    """Every parseable record, in append order. A torn trailing line (a
    capture process dying mid-write, or a reader racing the writer) is
    skipped, never fatal — the ``load_spans`` discipline."""
    p = Path(path) if path is not None else ledger_path()
    out: List[Dict[str, Any]] = []
    try:
        text = p.read_text()
    except (FileNotFoundError, OSError):
        return out
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue     # torn line
        if isinstance(rec, dict):
            out.append(rec)
    return out


_LOWER_BETTER_UNITS = ("ms",)


def higher_is_better(unit: Optional[str]) -> bool:
    """Polarity from the row's own unit: latency rows ("ms", "ms/step",
    "ms p50 (batch 1)") regress UP, throughput rows regress DOWN."""
    u = (unit or "").strip().lower()
    return not any(u == m or u.startswith(m + "/") or u.startswith(m + " ")
                   for m in _LOWER_BETTER_UNITS)


def ledger_record(row: str, rec: Dict[str, Any],
                  source: str = "bench.py") -> Optional[Dict[str, Any]]:
    """Map a bench record onto the keyed ledger schema. Returns None for
    a record with no measured value (errors / skips never enter the
    ledger — the --refresh never-overwrite-verified discipline)."""
    if not isinstance(rec, dict) or rec.get("value") is None:
        return None
    entry: Dict[str, Any] = {
        "kind": "perf",
        "row": row,
        "backend": rec.get("backend") or "unknown",
        "host": host_fingerprint(),
        "git_sha": rec.get("git_sha"),
        "captured_at": rec.get("captured_at"),
        "unit": rec.get("unit"),
        "value": rec.get("value"),
        "source": source,
    }
    if rec.get("step_time_ms") is not None:
        entry["step_time_ms"] = rec["step_time_ms"]
    # raw retained samples: the sub-ms stability path keeps per-pair
    # step times; TTFT rows keep per-rep wall samples (already ms)
    samples = rec.get("step_time_ms_samples") or rec.get("ttft_ms_samples")
    if samples:
        entry["step_time_ms_samples"] = list(samples)
    for k in ("iqr_rel", "unstable", "bimodal", "cluster_medians_ms",
              "timing_valid", "mfu"):
        if rec.get(k) is not None:
            entry[k] = rec[k]
    fl = rec.get("floor")
    if isinstance(fl, dict) and "na" not in fl:
        entry["floor"] = {k: fl[k] for k in
                          ("flops", "bytes", "pct_of_floor",
                           "binding_resource", "source")
                          if fl.get(k) is not None}
        if fl.get("pct_of_floor") is not None:
            entry["pct_of_floor"] = fl["pct_of_floor"]
    slo = rec.get("slo")
    if isinstance(slo, dict) and "na" not in slo:
        entry["slo"] = {k: slo[k] for k in
                        ("goodput", "itl_p99_ms", "ttft_p99_ms",
                         "error_rate", "met")
                        if slo.get(k) is not None}
    mem = rec.get("memory")
    if isinstance(mem, dict) and "na" not in mem:
        compact = {k: mem[k] for k in
                   ("kv_waste_ratio", "bytes_per_resident_token",
                    "peak_bytes") if mem.get(k) is not None}
        if mem.get("retraces_after_warm") is not None:
            entry["retraces_after_warm"] = mem["retraces_after_warm"]
        if compact:
            entry["memory"] = compact
    if isinstance(rec.get("layers"), dict):
        entry["layers"] = rec["layers"]
    # paged-attention kernel-vs-XLA A/B (ISSUE 17): both arms' rates,
    # the promotion verdict and the fidelity bound ride in the ledger so
    # the trend plane can watch the kernel's margin across captures
    ab = rec.get("paged_kernel_ab")
    if isinstance(ab, dict) and "na" not in ab:
        compact = {k: ab[k] for k in
                   ("verdict", "promoted", "speedup_kernel_over_gather",
                    "fidelity_kl_max", "greedy_match_frac", "cost_record")
                   if ab.get(k) is not None}
        for arm in ("gather", "kernel"):
            a = ab.get(arm)
            if isinstance(a, dict):
                compact[arm] = {k: a[k] for k in
                                ("step_time_ms", "tokens_per_s",
                                 "pct_of_floor") if a.get(k) is not None}
        if compact:
            entry["paged_kernel_ab"] = compact
    return entry


# -------------------------------------------------- two-cluster split test

# Documented cross-session captures of the t4096 b4 best-XLA arm
# (bf16-scores remat-full), tokens/s — the bimodality carried as prose
# ("82–152k across sessions", docs/PERF.md §long-context, VERDICT r5
# item 2) since r5. The recorded session extremes ARE the evidence the
# debt was filed on; the split test below turns them into a first-class
# verdict with per-cluster medians instead of a 1.9×-spread pooled one.
T4096_BEST_XLA_SAMPLES = (82000.0, 152000.0)
T4096_BEST_XLA_ROW = "transformer_long_best_xla"

MIN_REL_GAP = 0.20          # clusters must sit ≥20% apart (≫ any band)
MAX_CLUSTER_REL_SPREAD = 0.10   # and each be internally tight


def split_clusters(values: Sequence[float],
                   min_rel_gap: float = MIN_REL_GAP,
                   max_cluster_rel_spread: float = MAX_CLUSTER_REL_SPREAD,
                   min_cluster: int = 1,
                   ) -> Optional[Dict[str, Any]]:
    """Largest-gap two-cluster split over positive samples. Returns the
    split description when the samples genuinely live in two modes —
    cluster medians ≥ ``min_rel_gap`` apart (relative to their
    midpoint) with each cluster's own spread ≤
    ``max_cluster_rel_spread`` — else None. Ordinary capture noise
    (spread ≪ gap threshold) never splits; a single outlier forms a
    singleton cluster, which is why :func:`classify_capture` only
    calls a row bimodal when the HISTORY splits (a lone new low
    sample is a regression, not a mode), and why callers judging ONE
    capture's sample set (``bench.measure_stable``) pass
    ``min_cluster=2`` — within one capture a mode must RECUR, or a
    lone host-jitter outlier among k samples would read as one."""
    vals = sorted(float(v) for v in values
                  if v is not None and math.isfinite(v) and v > 0)
    if len(vals) < max(2, 2 * min_cluster):
        return None
    gaps = [vals[i + 1] - vals[i] for i in range(len(vals) - 1)]
    i = max(range(len(gaps)), key=gaps.__getitem__)
    lo, hi = vals[:i + 1], vals[i + 1:]
    lo_med, hi_med = statistics.median(lo), statistics.median(hi)
    mid = 0.5 * (lo_med + hi_med)
    if mid <= 0:
        return None
    rel_gap = (hi_med - lo_med) / mid

    def rel_spread(cluster: List[float], med: float) -> float:
        return (cluster[-1] - cluster[0]) / med if med > 0 else math.inf

    if rel_gap < min_rel_gap:
        return None
    if len(lo) < min_cluster or len(hi) < min_cluster:
        return None
    if rel_spread(lo, lo_med) > max_cluster_rel_spread \
            or rel_spread(hi, hi_med) > max_cluster_rel_spread:
        return None
    return {
        "lo_median": lo_med, "hi_median": hi_med,
        "lo_n": len(lo), "hi_n": len(hi),
        "rel_gap": round(rel_gap, 4),
    }


def nearest_cluster(split: Dict[str, Any], value: float) -> float:
    """The cluster median a value belongs to (pct-vs-baseline for a
    bimodal row quotes against its OWN mode, not the pooled median)."""
    lo, hi = split["lo_median"], split["hi_median"]
    return lo if abs(value - lo) <= abs(value - hi) else hi


def cluster_transitions(ordered_values: Sequence[float],
                        split: Dict[str, Any]) -> int:
    """How many times a CHRONOLOGICAL series switches cluster. This is
    what separates bimodality from a regime change: a series that
    visits one mode, moves to the other, and never returns (≤1
    transition — e.g. the r02→r05 doubling of several bench rows) is
    an improvement/regression that STUCK, and its honest baseline is
    the latest regime; a series that keeps alternating (≥2
    transitions) has no single regime — that is ``bimodal``. Without
    this check, every big accepted improvement would pin as a
    'cluster' and a later regression back to the old level would pass
    the gate inside it."""
    assign = [abs(v - split["lo_median"]) > abs(v - split["hi_median"])
              for v in ordered_values]
    return sum(1 for a, b in zip(assign, assign[1:]) if a != b)


def latest_regime(ordered_values: Sequence[float],
                  split: Dict[str, Any]) -> List[float]:
    """The trailing run of same-cluster values — the current regime a
    monotone regime-change series has settled into."""
    vals = list(ordered_values)
    assign = [abs(v - split["lo_median"]) > abs(v - split["hi_median"])
              for v in vals]
    cut = len(vals) - 1
    while cut > 0 and assign[cut - 1] == assign[-1]:
        cut -= 1
    return vals[cut:]


# --------------------------------------------------- noise-aware verdicts

BAND_MARGIN = 1.5     # × the measured rel-IQR — the one judgement call
BAND_MIN = 0.05       # floor: same-config captures repeat within ~1-2%
                      # on ≥10ms rows (docs/PERF.md §LeNet), 5% is slack
UNSTABLE_REL_IQR = 0.25   # bench.py's own sub-ms instability threshold


def noise_band(hist_iqr_rels: Sequence[float],
               cur_iqr_rel: Optional[float] = None,
               band_min: float = BAND_MIN,
               margin: float = BAND_MARGIN) -> float:
    """The MeasuredBound philosophy applied to throughput: the allowed
    relative deviation is ``margin ×`` the measured relative IQR (the
    worse of history and current capture), floored at ``band_min`` so a
    suspiciously quiet history can't make 1% noise a 'regression'."""
    measured = [r for r in list(hist_iqr_rels) + [cur_iqr_rel]
                if isinstance(r, (int, float)) and math.isfinite(r)]
    return margin * max([band_min] + measured)


def series_values(entries: Sequence[Dict[str, Any]]) -> List[float]:
    """Per-capture observations for the split/band tests: an entry
    contributes its retained per-session samples when it has them
    (``value_samples`` — the backfilled T=4096 evidence), else its
    single captured value."""
    out: List[float] = []
    for e in entries:
        samples = e.get("value_samples")
        if samples:
            out.extend(float(s) for s in samples)
        elif e.get("value") is not None:
            out.append(float(e["value"]))
    return out


def series_split(entries: Sequence[Dict[str, Any]]
                 ) -> Tuple[Optional[Dict[str, Any]], Optional[str]]:
    """Is this SERIES bimodal at all? Two ways to earn the verdict:

    - **within-capture**: one entry's own retained sample set splits
      (same sha, same session era — no regime-change reading exists;
      the recorded T=4096 session set and a bimodal ``measure_stable``
      capture both land here);
    - **across-captures**: the chronological per-capture values split
      AND keep alternating (≥2 cluster transitions) — recurrence, not
      a one-way regime change.
    """
    for e in entries:
        sp = split_clusters(e.get("value_samples") or ())
        if sp is not None:
            return sp, "within-capture"
    vals = series_values(entries)
    sp = split_clusters(vals)
    if sp is not None and cluster_transitions(vals, sp) >= 2:
        return sp, "across-captures"
    return None, None


def classify_capture(history: Sequence[float], current: float, *,
                     higher_better: bool = True,
                     cur_iqr_rel: Optional[float] = None,
                     hist_iqr_rels: Sequence[float] = (),
                     band_min: float = BAND_MIN,
                     margin: float = BAND_MARGIN) -> Dict[str, Any]:
    """Judge one new capture against the ledger history.

    Order matters: a history that itself keeps ALTERNATING between two
    modes makes the row ``bimodal`` (the current capture is assigned
    to its nearest cluster and judged against THAT median — the stable
    denominator the pooled median never was); a history that split
    once and stuck (≤1 transition) is a regime change, and the capture
    is judged against the LATEST regime's median; a current capture
    whose own samples are too spread is ``unstable``; otherwise the
    capture is in/out of the measured noise band around the history
    median. A lone new sample far from a tight history is
    ``regressed`` / ``improved``, never ``bimodal`` — one observation
    is an event, two recurrences are a mode."""
    hist = [float(v) for v in history
            if v is not None and math.isfinite(v)]
    out: Dict[str, Any] = {
        "verdict": "no_baseline", "baseline": None,
        "pct_vs_baseline": None, "band_rel": None,
        "n_history": len(hist),
    }
    if not hist:
        return out
    band = noise_band(hist_iqr_rels, cur_iqr_rel, band_min, margin)
    out["band_rel"] = round(band, 4)
    split = split_clusters(hist) if len(hist) >= 2 else None
    if split is not None:
        if cluster_transitions(hist, split) >= 2:
            baseline = nearest_cluster(split, current)
            out.update(verdict="bimodal", baseline=baseline,
                       clusters=[split["lo_median"],
                                 split["hi_median"]],
                       split=split)
            if baseline:
                out["pct_vs_baseline"] = round(
                    (current - baseline) / baseline, 4)
            return out
        # regime change that stuck: judge against where it settled
        hist = latest_regime(hist, split)
    baseline = statistics.median(hist)
    out["baseline"] = baseline
    if baseline:
        out["pct_vs_baseline"] = round((current - baseline) / baseline, 4)
    if cur_iqr_rel is not None and cur_iqr_rel > UNSTABLE_REL_IQR:
        out["verdict"] = "unstable"
        return out
    hist_spread = ((max(hist) - min(hist)) / baseline
                   if baseline and len(hist) > 1 else 0.0)
    if hist_spread > max(2 * band, UNSTABLE_REL_IQR):
        # wildly spread history that does NOT split into clean modes:
        # no stable denominator exists and no band verdict is honest
        out["verdict"] = "unstable"
        return out
    pct = out["pct_vs_baseline"]
    if pct is None or abs(pct) <= band:
        out["verdict"] = "stable"
    elif (pct < 0) == higher_better:
        out["verdict"] = "regressed"
    else:
        out["verdict"] = "improved"
    return out


# -------------------------------------------------- attribution drill-down

FLOOR_DIFF_REL = 0.02      # flops/bytes moved ≥2% → the model changed
LAYER_DIFF_REL = 0.10      # a layer span moved ≥10% → named suspect


def _rel_delta(a, b) -> Optional[float]:
    try:
        a, b = float(a), float(b)
    except (TypeError, ValueError):
        return None
    if not a:
        return None
    return (b - a) / a


def attribute(baseline: Dict[str, Any],
              current: Dict[str, Any]) -> List[str]:
    """The regression drill-down: diff the recorded evidence between
    the baseline and current ledger entries into human-readable
    suspects, most structural first. Order of checks: a floor-block
    move means the PROGRAM changed (different flops/bytes = different
    model — any timing delta follows from that); retraces mean the
    compile cache stopped holding; a layer-span move names the layer;
    an SLO/KV move localizes it to the serving path; an empty list
    falls back to environment suspects (host/sha changed)."""
    suspects: List[str] = []
    bf, cf = baseline.get("floor") or {}, current.get("floor") or {}
    for quantity in ("flops", "bytes"):
        d = _rel_delta(bf.get(quantity), cf.get(quantity))
        if d is not None and abs(d) >= FLOOR_DIFF_REL:
            suspects.append(
                f"model change: floor {quantity}/step moved "
                f"{bf[quantity]:.3g} → {cf[quantity]:.3g} ({d:+.1%}) — "
                "the program being timed is different")
    br = baseline.get("retraces_after_warm") or 0
    cr = current.get("retraces_after_warm") or 0
    if cr > br:
        suspects.append(
            f"retraces appeared: {cr} post-warm compile(s) vs {br} at "
            "baseline — a shape/signature started missing the jit cache")
    bl, cl = baseline.get("layers") or {}, current.get("layers") or {}
    movers = []
    for layer in sorted(set(bl) & set(cl)):
        d = _rel_delta(bl[layer], cl[layer])
        if d is not None and abs(d) >= LAYER_DIFF_REL:
            movers.append((abs(d), layer, d))
    for _, layer, d in sorted(movers, reverse=True)[:3]:
        suspects.append(
            f"layer span {layer!r} moved {d:+.1%} "
            f"({bl[layer]:.3g} → {cl[layer]:.3g} ms)")
    bs, cs = baseline.get("slo") or {}, current.get("slo") or {}
    d = _rel_delta(bs.get("itl_p99_ms"), cs.get("itl_p99_ms"))
    if d is not None and d >= LAYER_DIFF_REL:
        suspects.append(f"serving ITL p99 grew {d:+.1%} "
                        f"({bs['itl_p99_ms']} → {cs['itl_p99_ms']} ms)")
    bm, cm = baseline.get("memory") or {}, current.get("memory") or {}
    d = _rel_delta(bm.get("kv_waste_ratio"), cm.get("kv_waste_ratio"))
    if d is not None and d >= LAYER_DIFF_REL:
        suspects.append(f"kv waste grew {d:+.1%} "
                        f"({bm['kv_waste_ratio']} → "
                        f"{cm['kv_waste_ratio']})")
    if not suspects:
        env = []
        if baseline.get("host") != current.get("host"):
            env.append(f"host changed ({baseline.get('host')} → "
                       f"{current.get('host')})")
        if baseline.get("git_sha") != current.get("git_sha"):
            env.append(f"sha {baseline.get('git_sha')} → "
                       f"{current.get('git_sha')}")
        suspects.append(
            "no attributable change in recorded evidence"
            + (" — " + "; ".join(env) if env else
               " — same host and sha: session noise"))
    return suspects


# ----------------------------------------------------- the trend table

HISTORY_WINDOW = 12    # recent captures the verdict pools


def _comparable(entries: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Off-TPU numbers are only comparable on the SAME host (sandbox
    CPU drifts between sessions — README serving-table caveat): filter
    a non-tpu series to the latest entry's host fingerprint."""
    if not entries:
        return entries
    last = entries[-1]
    if last.get("backend") == "tpu":
        return entries
    host = last.get("host")
    return [e for e in entries if e.get("host") == host]


def trend_table(records: Sequence[Dict[str, Any]],
                window: int = HISTORY_WINDOW) -> Dict[str, Dict[str, Any]]:
    """Replay a ledger into one verdict row per (row, backend) key:
    latest value, history stats, the capture verdict of the LATEST
    entry vs its predecessors, the series-level split, and — when the
    verdict is ``regressed`` — the attribution suspects."""
    groups: Dict[Tuple[str, str], List[Dict[str, Any]]] = {}
    for rec in records:
        if rec.get("kind") != "perf" or rec.get("row") is None:
            continue
        if rec.get("timing_valid") is False:
            # a capture its own MFU audit rejected (or a backfilled
            # pre-methodology record, e.g. the r01 97k-img/s headline)
            # stays in the ledger but never enters a verdict pool
            continue
        groups.setdefault((rec["row"], rec.get("backend") or "unknown"),
                          []).append(rec)
    out: Dict[str, Dict[str, Any]] = {}
    for (row, backend), entries in sorted(groups.items()):
        entries = _comparable(entries)[-window:]
        if not entries:
            continue
        cur = entries[-1]
        hist = entries[:-1]
        hist_vals = series_values(hist)
        cur_vals = series_values([cur])
        cur_val = cur_vals[-1] if cur_vals else None
        unit = cur.get("unit")
        hb = higher_is_better(unit)
        hist_iqrs = [e["iqr_rel"] for e in hist
                     if e.get("iqr_rel") is not None]
        hist_split, hist_split_kind = series_split(hist)
        if cur_val is None:
            verdict: Dict[str, Any] = {"verdict": "no_baseline"}
        elif hist_split is not None:
            # the HISTORY is already bimodal (a within-capture sample
            # split, or recurring alternation): judge the new capture
            # against its nearest mode, never the pooled median
            near = nearest_cluster(hist_split, cur_val)
            verdict = {
                "verdict": "bimodal", "baseline": near,
                "pct_vs_baseline": round((cur_val - near) / near, 4)
                if near else None,
                "clusters": [hist_split["lo_median"],
                             hist_split["hi_median"]],
                "split": {**hist_split, "kind": hist_split_kind},
                "band_rel": round(noise_band(hist_iqrs,
                                             cur.get("iqr_rel")), 4),
                "n_history": len(hist_vals),
            }
        else:
            verdict = classify_capture(
                hist_vals, cur_val, higher_better=hb,
                cur_iqr_rel=cur.get("iqr_rel"),
                hist_iqr_rels=hist_iqrs)
        # series-level split over EVERYTHING retained (incl. the
        # current capture): the "is this row bimodal at all" question
        # the T=4096 debt asks, distinct from the capture verdict —
        # a within-capture sample split or a recurring (alternating)
        # cross-capture split, never a one-way regime change
        split, split_kind = series_split(entries)
        if split is not None and verdict["verdict"] in ("stable",
                                                        "unstable",
                                                        "no_baseline"):
            verdict["verdict"] = "bimodal"
            verdict["clusters"] = [split["lo_median"],
                                   split["hi_median"]]
            verdict["split"] = {**split, "kind": split_kind}
            if cur_val is not None:
                near = nearest_cluster(split, cur_val)
                verdict["baseline"] = near
                verdict["pct_vs_baseline"] = round(
                    (cur_val - near) / near, 4) if near else None
        entry = {
            "row": row, "backend": backend, "unit": unit,
            "value": cur_val,
            "captured_at": cur.get("captured_at"),
            "git_sha": cur.get("git_sha"),
            "n_captures": len(entries),
            "higher_is_better": hb,
            **verdict,
        }
        if verdict["verdict"] == "regressed" and hist:
            entry["suspects"] = attribute(hist[-1], cur)
        out[f"{row}|{backend}"] = entry
    return out


# -------------------------------------------------------------- metrics

def emit_trend_metrics(table: Dict[str, Dict[str, Any]]) -> None:
    """Mirror a replayed trend table into the process registry:
    ``dl4j_trend_pct_vs_baseline{row, backend}`` per row and
    ``dl4j_trend_verdicts{verdict}`` counts. Lazy optional import —
    this module stays standalone-loadable; a process without the obs
    package just skips the mirror. Instruments are re-fetched through
    get-or-create every call (NOT cached): a replay happens once per
    gate/debug request, never per step, and a cached handle would
    survive a registry reset as an orphan."""
    try:
        from deeplearning4j_tpu.obs import get_registry
        reg = get_registry()
    except Exception:  # noqa: BLE001 — standalone script use
        return
    pct_g = reg.gauge("dl4j_trend_pct_vs_baseline",
                      "Latest capture vs ledger baseline (fraction; "
                      "bimodal rows quote vs their nearest cluster)",
                      labelnames=("row", "backend"))
    verdict_g = reg.gauge("dl4j_trend_verdicts",
                          "Rows at each trend verdict after the last "
                          "replay", labelnames=("verdict",))
    counts: Dict[str, int] = {}
    for entry in table.values():
        counts[entry["verdict"]] = counts.get(entry["verdict"], 0) + 1
        if entry.get("pct_vs_baseline") is not None:
            pct_g.set(entry["pct_vs_baseline"],
                      row=entry["row"], backend=entry["backend"])
    for v in ("stable", "improved", "regressed", "unstable", "bimodal",
              "no_baseline"):
        verdict_g.set(counts.get(v, 0), verdict=v)


def debug_state() -> Dict[str, Any]:
    """What ``GET /debug/trend`` returns: the ledger replayed fresh
    (bench captures append from subprocesses, so in-process caching
    would serve stale verdicts) plus verdict counts. Never raises."""
    p = ledger_path()
    try:
        records = load_ledger(p)
        table = trend_table(records)
    except Exception as e:  # noqa: BLE001 — debug must not raise
        return {"ledger_path": str(p), "error": repr(e)}
    counts: Dict[str, int] = {}
    for entry in table.values():
        counts[entry["verdict"]] = counts.get(entry["verdict"], 0) + 1
    try:
        emit_trend_metrics(table)
    except Exception:  # noqa: BLE001 — gauge mirror is decoration
        pass
    return {"ledger_path": str(p), "n_records": len(records),
            "verdict_counts": counts, "rows": table}


# ------------------------------------------------------ README trend cell

def trend_cell(row: str, backend: Optional[str],
               records: Optional[Sequence[Dict[str, Any]]] = None,
               band_min: float = BAND_MIN) -> str:
    """The README trend column: ▲/▼/≈ with % vs the previous
    same-backend capture, tolerant of a missing or partial ledger
    (no ledger / <2 captures → em-dash). The arrow encodes
    BETTER/WORSE, not raw direction — a TTFT row that got 30% slower
    is ▼ even though its millisecond value went up, so a latency
    regression can never render like a throughput gain."""
    try:
        if records is None:
            records = load_ledger()
        entries = [r for r in records
                   if r.get("kind") == "perf" and r.get("row") == row
                   and (backend is None or r.get("backend") == backend)
                   and r.get("value") is not None
                   and r.get("timing_valid") is not False]
        entries = _comparable(entries)
        if len(entries) < 2:
            return "—"
        prev, cur = float(entries[-2]["value"]), float(entries[-1]["value"])
        if not prev:
            return "—"
        pct = (cur - prev) / prev
        if abs(pct) <= band_min:
            return f"≈ ({pct:+.1%})"
        better = (pct > 0) == higher_is_better(entries[-1].get("unit"))
        arrow = "▲" if better else "▼"
        return f"{arrow} {pct:+.1%}"
    except Exception:  # noqa: BLE001 — a decoration must not break the table
        return "—"
