#!/usr/bin/env python
"""Perf regression gate + trend table over the bench ledger (ISSUE 15).

Replays ``runs/perf_ledger.jsonl`` (every ``bench.py`` capture appends
one keyed record; see ``deeplearning4j_tpu/obs/trend.py``) into a
per-row trend table — latest value, verdict vs history
(stable/improved/regressed/unstable/bimodal), pct vs baseline,
attribution suspects on a regression — and gates: **exit 1** when any
row's latest capture is an out-of-band regression vs the pinned
baseline (``runs/perf_baseline.json``), 0 otherwise. The noise band is
derived from the *measured* relative IQR recorded in the ledger (the
MeasuredBound philosophy), never a magic constant.

    python scripts/perf_gate.py                  # table + gate
    python scripts/perf_gate.py --offline        # CI mode (below)
    python scripts/perf_gate.py --update-baseline  # re-pin after an
                                                 #   accepted change
    python scripts/perf_gate.py --json

Modes:

- **--update-baseline**: pin, per (row, backend), the median of the
  recent captures + the measured band (bimodal rows pin BOTH cluster
  medians — the gate then accepts either mode and flags everything
  else).
- **--offline**: CI-safe replay — a missing ledger is a clean exit 0
  (fresh checkout), and the dl4j_trend_* gauge mirror is skipped (no
  package import). Runs in ``scripts/ci_quick.sh`` beside the
  slo/mem/fidelity gates.

What fails the gate: an out-of-band move past the PIN in the bad
direction. An ``unstable`` capture is skipped (its own samples are too
spread to trust either way — re-capture, don't gate noise). A pin
marked ``bimodal`` accepts a landing in EITHER cluster's band. A row
whose pin is unimodal but whose series has since started alternating
still fails when it lands below the pin band — deliberately: until a
human re-pins (``--update-baseline``), a recurring visit to a slower
mode IS slower than the accepted baseline. Rows with no pin report
``no_baseline`` and pass.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

REPO = Path(__file__).resolve().parent.parent

# standalone import by file path: trend.py is jax-free by design, so the gate
# runs in any interpreter without pulling the package in
_spec = importlib.util.spec_from_file_location(
    "_dl4j_obs_trend_standalone",
    REPO / "deeplearning4j_tpu" / "obs" / "trend.py")
trend = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trend)

# ---------------------------------------------------------------- baseline

def update_baseline(ledger: Path, baseline: Path) -> Dict[str, Any]:
    """Pin the current ledger state: per (row, backend) the baseline
    value (median of the LATEST REGIME — a series that improved and
    stuck pins where it settled, so a slide back to the old level
    still gates; BOTH cluster medians when the series is genuinely
    bimodal — the gate then accepts either mode), the measured band,
    unit and polarity. The pin file is what the gate judges against
    until deliberately re-pinned."""
    import statistics
    records = trend.load_ledger(ledger)
    table = trend.trend_table(records)
    rows: Dict[str, Any] = {}
    for key, entry in table.items():
        group = [rec for rec in records
                 if rec.get("kind") == "perf"
                 and rec.get("timing_valid") is not False
                 and rec.get("row") == entry["row"]
                 and (rec.get("backend") or "unknown") == entry["backend"]]
        # same same-host filter trend_table applies: an off-TPU pin
        # must never be a median computed across two machines' speeds
        group = trend._comparable(group)
        vals = trend.series_values(group)[-trend.HISTORY_WINDOW:]
        if not vals:
            continue
        iqrs = [rec["iqr_rel"] for rec in group
                if rec.get("iqr_rel") is not None]
        pin: Dict[str, Any] = {
            "band_rel": round(trend.noise_band(iqrs), 4),
            "unit": entry.get("unit"),
            "higher_is_better": entry.get("higher_is_better", True),
            "n": len(vals),
        }
        split = trend.split_clusters(vals)
        if entry["verdict"] == "bimodal" and entry.get("clusters"):
            pin["clusters"] = entry["clusters"]
            pin["verdict"] = "bimodal"
            pin["value"] = statistics.median(vals)
        elif split is not None:
            # one-way regime change: pin the settled regime
            pin["value"] = statistics.median(
                trend.latest_regime(vals, split))
        else:
            pin["value"] = statistics.median(vals)
        if entry["backend"] != "tpu" \
                and group and group[-1].get("host") is not None:
            pin["host"] = group[-1]["host"]
        rows[key] = pin
    out = {"pinned_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
           "rows": rows}
    baseline.parent.mkdir(parents=True, exist_ok=True)
    tmp = baseline.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(out, indent=1, sort_keys=True) + "\n")
    tmp.replace(baseline)
    return out


def gate(table: Dict[str, Dict[str, Any]],
         pins: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Judge each trend row's LATEST capture against its pin. Returns
    the failures (empty = gate passes). Only an out-of-band move in
    the bad direction fails; a bimodal pin accepts either cluster; an
    unstable capture is skipped (see module docstring)."""
    failures: List[Dict[str, Any]] = []
    for key, entry in table.items():
        pin = (pins.get("rows") or {}).get(key)
        if pin is None or entry.get("value") is None:
            continue
        if entry.get("verdict") == "unstable":
            # the capture's own samples are too spread to trust in
            # either direction — a noise reading must neither trip
            # nor green-light the gate; re-capture instead
            entry["gate"] = "skipped: unstable capture"
            continue
        if entry.get("backend") != "tpu" \
                and pin.get("host") != trend.host_fingerprint():
            # off-TPU numbers are only comparable on the SAME host
            # (README caveat): a pin from another host — or one whose
            # host was never stamped, the backfilled CPU rows — must
            # not let a faster/slower dev machine trip (or mask) the
            # gate. Chip rows gate regardless: v5e perf is not a
            # property of whichever host drove the capture.
            entry["gate"] = "skipped: off-TPU pin from another/unknown host"
            continue
        band = max(pin.get("band_rel") or 0.0, entry.get("band_rel")
                   or 0.0, trend.BAND_MARGIN * trend.BAND_MIN)
        hb = pin.get("higher_is_better", True)
        baselines = pin.get("clusters") or [pin["value"]]
        pcts = [(entry["value"] - b) / b for b in baselines if b]
        if not pcts:
            continue
        # the most favorable pinned mode: a bimodal row passes when it
        # lands in EITHER cluster's band
        pct = min(pcts, key=abs)
        entry["gate_pct_vs_pin"] = round(pct, 4)
        bad = (pct < -band) if hb else (pct > band)
        if bad:
            failures.append({
                "key": key, "value": entry["value"],
                "pinned": baselines, "pct": round(pct, 4),
                "band_rel": round(band, 4),
                "suspects": entry.get("suspects"),
            })
            entry["gate"] = "REGRESSED"
        else:
            entry["gate"] = "ok"
    return failures


# ------------------------------------------------------------------ render

def _fmt_value(v, unit) -> str:
    if v is None:
        return "—"
    u = unit or ""
    if "tokens" in u and v >= 1e3:
        return f"{v / 1e3:,.1f}k tok/s"
    if "ms" in u:
        return f"{v:,.2f} ms"
    return f"{v:,.1f}"


def render(table: Dict[str, Dict[str, Any]],
           failures: List[Dict[str, Any]]) -> str:
    hdr = (f"{'row':<28} {'backend':<8} {'n':>3} {'latest':>14} "
           f"{'vs base':>9} {'band':>7}  verdict")
    lines = [hdr, "-" * len(hdr)]
    for key, e in sorted(table.items()):
        pct = e.get("pct_vs_baseline")
        band = e.get("band_rel")
        verdict = e["verdict"]
        if verdict == "bimodal" and e.get("clusters"):
            lo, hi = e["clusters"]
            verdict = (f"bimodal [{_fmt_value(lo, e.get('unit'))} | "
                       f"{_fmt_value(hi, e.get('unit'))}]")
        if e.get("gate") == "REGRESSED":
            verdict += "  << GATE"
        lines.append(
            f"{e['row']:<28.28} {e['backend']:<8.8} "
            f"{e['n_captures']:>3} "
            f"{_fmt_value(e.get('value'), e.get('unit')):>14} "
            f"{('%+.1f%%' % (100 * pct)) if pct is not None else '—':>9} "
            f"{('±%.0f%%' % (100 * band)) if band is not None else '—':>7}"
            f"  {verdict}")
        for s in e.get("suspects") or []:
            lines.append(f"{'':<13}suspect: {s}")
    if failures:
        lines.append("")
        lines.append(f"perf_gate: {len(failures)} out-of-band "
                     f"regression(s) vs the pinned baseline")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="bench-ledger trend table + perf regression gate")
    ap.add_argument("--ledger", type=Path, default=None,
                    help="ledger path (default runs/perf_ledger.jsonl; "
                         "env DL4J_TREND_LEDGER)")
    ap.add_argument("--baseline", type=Path, default=None,
                    help="pinned-baseline path (default "
                         "runs/perf_baseline.json; env "
                         "DL4J_TREND_BASELINE)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="re-pin the baseline from the current ledger")
    ap.add_argument("--offline", action="store_true",
                    help="CI mode: a missing ledger exits 0; skip the "
                         "dl4j_trend_* gauge mirror")
    ap.add_argument("--json", action="store_true",
                    help="emit the table + failures as JSON")
    args = ap.parse_args(argv)

    ledger = args.ledger or trend.ledger_path()
    baseline = args.baseline or trend.baseline_path()

    records = trend.load_ledger(ledger)
    if not records:
        msg = f"perf_gate: no ledger records at {ledger}"
        if args.offline:
            print(msg + " — offline mode, nothing to gate (ok)")
            return 0
        print(msg + " — run a bench capture first", file=sys.stderr)
        return 1

    table = trend.trend_table(records)

    if args.update_baseline:
        pinned = update_baseline(ledger, baseline)
        print(f"perf_gate: pinned {len(pinned['rows'])} row(s) "
              f"into {baseline}", file=sys.stderr)

    try:
        pins = json.loads(baseline.read_text())
    except (OSError, ValueError):
        pins = {"rows": {}}
    failures = gate(table, pins)

    if not args.offline:
        try:
            trend.emit_trend_metrics(table)
        except Exception:  # noqa: BLE001 — mirror is decoration
            pass

    if args.json:
        print(json.dumps({"rows": table, "failures": failures,
                          "n_records": len(records)}, indent=1,
                         sort_keys=True))
    else:
        print(render(table, failures))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
