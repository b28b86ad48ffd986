"""Perf regression & trend plane suite (ISSUE 15): ledger
append/replay round-trip (atomic, torn-line tolerant), noise-aware
verdict bands from synthetic IQRs, the two-cluster bimodality split on
the recorded T=4096 session set, the committed r01–r05 history, the
injected-regression perf-gate exit-1, attribution suspects, /debug/trend, and the <2%-of-a-row
append budget. Pure host-side — no device work, fast tier-1 set.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from deeplearning4j_tpu.obs import trend

REPO = pathlib.Path(__file__).resolve().parent.parent
GATE = REPO / "scripts" / "perf_gate.py"


def _entry(row="rowA", backend="tpu", value=100.0, **kw):
    return {"kind": "perf", "row": row, "backend": backend,
            "host": None, "unit": "tokens/sec/chip", "value": value,
            "source": "test", **kw}


def _gate(*args, ledger, baseline):
    env = {k: v for k, v in os.environ.items()
           if k not in ("DL4J_TREND_LEDGER", "DL4J_TREND_BASELINE")}
    proc = subprocess.run(
        [sys.executable, str(GATE), "--ledger", str(ledger),
         "--baseline", str(baseline), *args],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    return proc


# ------------------------------------------------------------- the ledger

def test_append_replay_roundtrip(tmp_path):
    p = tmp_path / "ledger.jsonl"
    recs = [_entry(value=float(i), git_sha=f"s{i}") for i in range(7)]
    for r in recs:
        trend.append_record(r, p)
    got = trend.load_ledger(p)
    assert got == recs          # append order preserved, content intact


def test_load_tolerates_torn_trailing_line(tmp_path):
    p = tmp_path / "ledger.jsonl"
    trend.append_record(_entry(value=1.0), p)
    trend.append_record(_entry(value=2.0), p)
    with open(p, "a") as f:
        f.write('{"kind": "perf", "row": "torn", "val')   # dying writer
    got = trend.load_ledger(p)
    assert [r["value"] for r in got] == [1.0, 2.0]
    # and appends after the torn line start on their own line, so one
    # crash can never corrupt subsequent records
    trend.append_record(_entry(value=3.0), p)
    # the torn fragment merges with the next line (no newline between
    # them) — the MERGED line is unparseable and skipped, but records
    # before and nothing else are lost; a clean append then lands
    trend.append_record(_entry(value=4.0), p)
    vals = [r["value"] for r in trend.load_ledger(p)]
    assert vals[:2] == [1.0, 2.0] and 4.0 in vals


def test_append_missing_file_and_dir(tmp_path):
    p = tmp_path / "sub" / "dir" / "ledger.jsonl"
    trend.append_record(_entry(), p)
    assert len(trend.load_ledger(p)) == 1
    assert trend.load_ledger(tmp_path / "absent.jsonl") == []


def test_concurrent_appends_never_tear(tmp_path):
    p = tmp_path / "ledger.jsonl"

    def writer(i):
        for j in range(25):
            trend.append_record(_entry(value=i * 100.0 + j), p)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = trend.load_ledger(p)
    assert len(got) == 100      # every line parsed — no interleaving
    assert len(p.read_text().splitlines()) == 100


def test_append_overhead_under_2pct_of_a_row_capture(tmp_path):
    """The acceptance budget: a ledger append must add <2% to a bench
    row capture. The cheapest real row capture is ≥100 ms of wall
    (compile + warmup + two chained-step timings; even the sub-ms
    lenet row pays seconds), so the pin is mean append < 2 ms."""
    p = tmp_path / "ledger.jsonl"
    rec = _entry(step_time_ms_samples=[0.1] * 5, iqr_rel=0.01,
                 floor={"flops": 1e12, "bytes": 1e9,
                        "pct_of_floor": 0.5})
    trend.append_record(rec, p)          # warm the path
    n = 100
    t0 = time.perf_counter()
    for _ in range(n):
        trend.append_record(rec, p)
    mean_s = (time.perf_counter() - t0) / n
    assert mean_s < 0.002, f"append cost {mean_s * 1e3:.3f} ms/record"


# ------------------------------------------------- verdicts & noise bands

def test_stable_inside_measured_band():
    v = trend.classify_capture([100.0, 101.0, 99.5], 103.0,
                               hist_iqr_rels=[0.02], cur_iqr_rel=0.02)
    assert v["verdict"] == "stable"
    assert v["band_rel"] == pytest.approx(1.5 * 0.05)   # floored band


def test_regressed_and_improved_outside_band():
    hist = [100.0, 101.0, 99.5]
    assert trend.classify_capture(hist, 90.0)["verdict"] == "regressed"
    assert trend.classify_capture(hist, 112.0)["verdict"] == "improved"
    # pct quoted vs the history median
    assert trend.classify_capture(hist, 90.0)["pct_vs_baseline"] == \
        pytest.approx(-0.1, abs=1e-3)


def test_band_scales_with_measured_iqr():
    """A noisier measured history widens the band — the MeasuredBound
    philosophy: same −12% move, two different verdicts depending on
    what the noise actually measured."""
    hist = [100.0, 101.0, 99.5]
    tight = trend.classify_capture(hist, 88.0, hist_iqr_rels=[0.02])
    loose = trend.classify_capture(hist, 88.0, hist_iqr_rels=[0.10])
    assert tight["verdict"] == "regressed"
    assert loose["verdict"] == "stable"
    assert loose["band_rel"] == pytest.approx(0.15)


def test_latency_polarity_flips_verdicts():
    hist = [50.0, 51.0, 50.5]     # ms — lower is better
    up = trend.classify_capture(hist, 60.0, higher_better=False)
    down = trend.classify_capture(hist, 42.0, higher_better=False)
    assert up["verdict"] == "regressed"
    assert down["verdict"] == "improved"
    assert trend.higher_is_better("ms") is False
    assert trend.higher_is_better("ms/step") is False
    assert trend.higher_is_better("ms p50 (batch 1)") is False
    assert trend.higher_is_better("tokens/sec/chip") is True


def test_unstable_current_capture():
    v = trend.classify_capture([100.0, 101.0], 70.0, cur_iqr_rel=0.4)
    assert v["verdict"] == "unstable"


def test_unstable_wild_history_without_clean_modes():
    # wildly spread history that does NOT split into tight clusters:
    # no stable denominator exists
    v = trend.classify_capture([100.0, 160.0, 70.0, 130.0], 100.0)
    assert v["verdict"] == "unstable"


def test_no_baseline():
    assert trend.classify_capture([], 100.0)["verdict"] == "no_baseline"


# ----------------------------------------------- bimodality vs regime change

def test_t4096_recorded_samples_classify_bimodal():
    """The carried ROADMAP-5 debt, adjudicated: the recorded T=4096
    best-XLA session set (82–152k tokens/s, docs/PERF.md) classifies
    ``bimodal`` with per-cluster medians — a first-class machine
    verdict instead of prose."""
    split = trend.split_clusters(trend.T4096_BEST_XLA_SAMPLES)
    assert split is not None
    assert split["lo_median"] == pytest.approx(82000.0)
    assert split["hi_median"] == pytest.approx(152000.0)
    # and through the ledger: a backfilled entry carrying the session
    # samples earns the verdict in the trend table
    table = trend.trend_table([
        _entry(row=trend.T4096_BEST_XLA_ROW,
               value=trend.T4096_BEST_XLA_SAMPLES[-1],
               value_samples=list(trend.T4096_BEST_XLA_SAMPLES))])
    e = table[f"{trend.T4096_BEST_XLA_ROW}|tpu"]
    assert e["verdict"] == "bimodal"
    assert e["clusters"] == [pytest.approx(82000.0),
                             pytest.approx(152000.0)]
    assert e["split"]["kind"] == "within-capture"


def test_unimodal_noise_never_splits():
    assert trend.split_clusters([100.0, 102.0, 98.0, 101.0, 95.0]) is None
    assert trend.split_clusters([100.0]) is None
    assert trend.split_clusters([]) is None


def test_alternating_history_is_bimodal_capture_verdict():
    hist = [150.0, 82.0, 152.0, 80.0, 151.0]    # recurring modes
    v = trend.classify_capture(hist, 83.0)
    assert v["verdict"] == "bimodal"
    # judged against its OWN mode, not the pooled median
    assert v["baseline"] == pytest.approx(81.0)
    assert abs(v["pct_vs_baseline"]) < 0.05


def test_monotone_regime_change_is_not_bimodal():
    """An improvement that STUCK (the r02→r05 doubling) must judge new
    captures against the settled regime — a later slide back to the
    old level is a regression, not a visit to a 'cluster'."""
    hist = [100.0, 101.0, 220.0, 221.0]     # one-way step up
    v = trend.classify_capture(hist, 110.0)
    assert v["verdict"] == "regressed"
    assert v["baseline"] == pytest.approx(220.5)
    ok = trend.classify_capture(hist, 222.0)
    assert ok["verdict"] == "stable"


def test_series_split_requires_recurrence_across_captures():
    # monotone step: NOT bimodal at series level either
    split, kind = trend.series_split(
        [_entry(value=v) for v in (100.0, 101.0, 220.0, 221.0)])
    assert split is None
    # alternation: bimodal
    split, kind = trend.series_split(
        [_entry(value=v) for v in (100.0, 220.0, 101.0, 221.0)])
    assert split is not None and kind == "across-captures"


# ----------------------------------------------------------- attribution

def test_attribution_suspects():
    base = _entry(value=200.0, git_sha="aaa",
                  floor={"flops": 1.0e12, "bytes": 2.0e9},
                  retraces_after_warm=0,
                  layers={"attn": 10.0, "ffn": 5.0},
                  slo={"itl_p99_ms": 20.0})
    cur = _entry(value=150.0, git_sha="bbb",
                 floor={"flops": 1.3e12, "bytes": 2.0e9},
                 retraces_after_warm=3,
                 layers={"attn": 16.0, "ffn": 5.1},
                 slo={"itl_p99_ms": 31.0})
    suspects = trend.attribute(base, cur)
    text = "\n".join(suspects)
    assert "flops" in text and "+30" in text        # model change
    assert "retraces appeared: 3" in text
    assert "attn" in text and "+60" in text         # layer span mover
    assert "ITL p99" in text
    # and the empty-evidence fallback names the environment
    fallback = trend.attribute(_entry(value=200.0, git_sha="aaa"),
                               _entry(value=150.0, git_sha="bbb"))
    assert len(fallback) == 1
    assert "no attributable change" in fallback[0]
    assert "aaa" in fallback[0] and "bbb" in fallback[0]


def test_regressed_table_row_carries_suspects():
    recs = [_entry(value=200.0, retraces_after_warm=0, git_sha="aaa"),
            _entry(value=201.0, retraces_after_warm=0, git_sha="aaa"),
            _entry(value=150.0, retraces_after_warm=2, git_sha="bbb")]
    e = trend.trend_table(recs)["rowA|tpu"]
    assert e["verdict"] == "regressed"
    assert any("retraces appeared" in s for s in e["suspects"])


# ------------------------------------------------------- record mapping

def test_ledger_record_maps_bench_blocks():
    rec = {"value": 6.1, "unit": "tokens/sec/chip", "backend": "cpu",
           "git_sha": "abc1234", "captured_at": "2026-08-04T00:00:00",
           "step_time_ms": 1311.9,
           "step_time_ms_samples": [1300.0, 1320.0],
           "iqr_rel": 0.01, "unstable": False, "mfu": 0.02,
           "floor": {"flops": 8e8, "bytes": 1.6e9, "pct_of_floor": 0.025,
                     "binding_resource": "memory", "source": "estimated",
                     "floor_ms": 2.0},
           "slo": {"goodput": 0.5, "itl_p99_ms": 27672.1,
                   "ttft_p99_ms": 85790.0, "error_rate": 0.0,
                   "met": False, "targets": {"x": 1}},
           "memory": {"kv_waste_ratio": 0.108, "peak_bytes": 3.6e8,
                      "bytes_per_resident_token": 358220.3,
                      "retraces_after_warm": 0, "paged": {"y": 2}}}
    e = trend.ledger_record("inference_decode", rec)
    assert e["row"] == "inference_decode" and e["backend"] == "cpu"
    assert e["pct_of_floor"] == 0.025
    assert e["slo"]["itl_p99_ms"] == 27672.1
    assert e["memory"]["kv_waste_ratio"] == 0.108
    assert e["retraces_after_warm"] == 0
    assert e["step_time_ms_samples"] == [1300.0, 1320.0]
    assert e["host"] == trend.host_fingerprint()
    # errors / valueless records never enter the ledger
    assert trend.ledger_record("x", {"error": "boom"}) is None
    assert trend.ledger_record("x", {"skipped": "time budget"}) is None


def test_measure_stable_inline_bimodal_flag(monkeypatch):
    """Satellite: the sub-ms stability path flags a bimodal sample set
    inline with per-cluster medians (bench.py measure_stable)."""
    import bench
    vals = iter([(1.0e-4, True), (5.0e-4, True), (1.03e-4, True),
                 (5.1e-4, True), (1.01e-4, True)])
    monkeypatch.setattr(bench, "measure_marginal",
                        lambda *a, **kw: next(vals))
    med, valid, stability = bench.measure_stable(None, k=5)
    assert valid and stability is not None
    assert stability["bimodal"] is True
    lo, hi = stability["cluster_medians_ms"]
    assert lo == pytest.approx(0.101, rel=0.05)
    assert hi == pytest.approx(0.505, rel=0.05)
    # a tight sample set stays unimodal
    vals2 = iter([(1.0e-4, True)] * 5)
    monkeypatch.setattr(bench, "measure_marginal",
                        lambda *a, **kw: next(vals2))
    _, _, st2 = bench.measure_stable(None, k=5)
    assert st2["bimodal"] is False and "cluster_medians_ms" not in st2


# ------------------------------------- committed history + perf gate

@pytest.fixture()
def backfilled(tmp_path):
    """A copy of the committed ``runs/perf_ledger.jsonl`` — it already
    holds the r01–r05 history and the recorded T=4096 session set the
    one-shot backfill wrote — with a baseline pinned from it."""
    import shutil
    ledger = tmp_path / "ledger.jsonl"
    baseline = tmp_path / "baseline.json"
    shutil.copy(REPO / "runs" / "perf_ledger.jsonl", ledger)
    proc = _gate("--update-baseline", ledger=ledger, baseline=baseline)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return ledger, baseline, proc


def test_committed_ledger_holds_the_normalized_history(backfilled):
    """What the removed backfill guaranteed about the history is a
    property of the committed file now: the pre-audit r01 headline is
    kept but excluded from verdicts, r2's `dpscale` stays under its own
    key, the headline spans the metric rename, and the sha-less
    dpoverhead capture carries its session's backend."""
    ledger, _, _ = backfilled
    recs = trend.load_ledger(ledger)
    rows = {(r["row"], r.get("round")) for r in recs}
    assert ("resnet50", 1) in rows and ("dpscale", 2) in rows
    r01 = [r for r in recs if r.get("round") == 1][0]
    assert r01["timing_valid"] is False
    assert len([r for r in recs if r["row"] == "resnet50"]) >= 3
    dps = [r for r in recs if r["row"] == "dpoverhead"]
    assert {r["backend"] for r in dps} == {"tpu"}
    assert all(r.get("git_sha") for r in dps)
    assert "dpoverhead|unknown" not in trend.trend_table(recs)
    # nothing newer than 2026-08-01 was measured on a chip
    chip = [r["captured_at"] for r in recs
            if r["backend"] == "tpu" and r.get("captured_at")]
    assert max(chip).startswith("2026-08-01")


def test_backfilled_t4096_row_is_bimodal(backfilled):
    ledger, baseline, _ = backfilled
    table = trend.trend_table(trend.load_ledger(ledger))
    e = table[f"{trend.T4096_BEST_XLA_ROW}|tpu"]
    assert e["verdict"] == "bimodal"
    assert e["clusters"] == [pytest.approx(82000.0),
                             pytest.approx(152000.0)]
    # the pin carries both cluster medians
    pins = json.loads(baseline.read_text())["rows"]
    pin = pins[f"{trend.T4096_BEST_XLA_ROW}|tpu"]
    assert pin.get("verdict") == "bimodal"
    assert pin["clusters"] == [pytest.approx(82000.0),
                               pytest.approx(152000.0)]


def test_gate_green_on_current_capture_red_on_injected(backfilled):
    ledger, baseline, _ = backfilled
    # current state: exit 0
    proc = _gate(ledger=ledger, baseline=baseline)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # inject a synthetic −40% regression on the transformer row
    trend.append_record(
        _entry(row="transformer", value=133051.0, git_sha="deadbee",
               source="test-inject"), ledger)
    proc = _gate(ledger=ledger, baseline=baseline)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "regression" in proc.stdout
    assert "transformer" in proc.stdout
    # a bimodal row landing back in its OTHER pinned cluster passes
    trend.append_record(
        _entry(row=trend.T4096_BEST_XLA_ROW, value=83000.0,
               source="test-inject"), ledger)
    proc = _gate("--json", ledger=ledger, baseline=baseline)
    out = json.loads(proc.stdout)
    keys = {f["key"] for f in out["failures"]}
    assert f"{trend.T4096_BEST_XLA_ROW}|tpu" not in keys
    assert "transformer|tpu" in keys


def test_gate_skips_offtpu_rows_without_host_provenance(backfilled):
    """A CPU row pinned without a host fingerprint (the backfilled
    history) must never gate on a different machine — CPU-derived
    values drift with host perf (README caveat). TPU rows gate
    everywhere."""
    ledger, baseline, _ = backfilled
    # inject a huge apparent CPU regression (as if this dev machine is
    # simply slower than whatever captured the artifact)
    trend.append_record(
        _entry(row="inference_decode", backend="cpu", value=2.0,
               unit="tokens/sec/chip", source="test-inject",
               host=trend.host_fingerprint()), ledger)
    proc = _gate("--json", ledger=ledger, baseline=baseline)
    out = json.loads(proc.stdout)
    assert proc.returncode == 0, proc.stdout
    assert out["rows"]["inference_decode|cpu"]["gate"].startswith(
        "skipped")


def test_gate_skips_unstable_capture(tmp_path):
    """A capture whose own samples are too spread to trust must
    neither trip nor green-light the gate (module-docstring
    contract)."""
    ledger = tmp_path / "ledger.jsonl"
    baseline = tmp_path / "baseline.json"
    for v in (100.0, 101.0, 99.5):
        trend.append_record(_entry(value=v, iqr_rel=0.01), ledger)
    assert _gate("--update-baseline", ledger=ledger,
                 baseline=baseline).returncode == 0
    # out-of-band low, but the capture itself is noise (iqr 50%)
    trend.append_record(_entry(value=60.0, iqr_rel=0.5), ledger)
    proc = _gate("--json", ledger=ledger, baseline=baseline)
    assert proc.returncode == 0, proc.stdout
    out = json.loads(proc.stdout)
    assert out["rows"]["rowA|tpu"]["verdict"] == "unstable"
    assert out["rows"]["rowA|tpu"]["gate"] == "skipped: unstable capture"


def test_update_baseline_pools_same_host_only(tmp_path):
    """An off-TPU pin must be computed from the pinning host's own
    captures — a cross-host median would misjudge the next healthy
    capture on either machine."""
    ledger = tmp_path / "ledger.jsonl"
    baseline = tmp_path / "baseline.json"
    for v in (6.1, 6.15):      # another, faster machine's history
        trend.append_record(_entry(backend="cpu", value=v,
                                   host="other:x86_64:64"), ledger)
    trend.append_record(_entry(backend="cpu", value=3.0,
                               host=trend.host_fingerprint()), ledger)
    assert _gate("--update-baseline", ledger=ledger,
                 baseline=baseline).returncode == 0
    pin = json.loads(baseline.read_text())["rows"]["rowA|cpu"]
    assert pin["value"] == pytest.approx(3.0)   # NOT median(6.1, 6.15, 3)
    assert pin["host"] == trend.host_fingerprint()
    # and a healthy same-host repeat passes the gate
    trend.append_record(_entry(backend="cpu", value=3.05,
                               host=trend.host_fingerprint()), ledger)
    assert _gate(ledger=ledger, baseline=baseline).returncode == 0


def test_inline_split_requires_recurring_modes():
    """min_cluster=2 (the measure_stable call site): a lone outlier
    among k samples is not a second mode."""
    outlier = [1.00e-4, 1.01e-4, 1.02e-4, 1.03e-4, 1.50e-4]
    assert trend.split_clusters(outlier, min_cluster=2) is None
    assert trend.split_clusters(outlier) is not None   # history rule
    recurring = [1.00e-4, 1.5e-4, 1.01e-4, 1.51e-4]
    assert trend.split_clusters(recurring, min_cluster=2) is not None


def test_gate_offline_tolerates_missing_ledger(tmp_path):
    proc = _gate("--offline", ledger=tmp_path / "absent.jsonl",
                 baseline=tmp_path / "absent.json")
    assert proc.returncode == 0
    assert "nothing to gate" in proc.stdout
    # without --offline a missing ledger is an error
    proc = _gate(ledger=tmp_path / "absent.jsonl",
                 baseline=tmp_path / "absent.json")
    assert proc.returncode == 1


def test_committed_ledger_gates_green():
    """The committed runs/perf_ledger.jsonl + pinned baseline must
    replay clean — this is exactly what ci_quick.sh runs."""
    assert (REPO / "runs" / "perf_ledger.jsonl").exists()
    proc = subprocess.run(
        [sys.executable, str(GATE), "--offline"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ------------------------------------------------- gauges + debug + cells

def test_trend_metrics_exported():
    from deeplearning4j_tpu.obs import get_registry
    table = trend.trend_table([
        _entry(value=100.0), _entry(value=101.0), _entry(value=99.0)])
    trend.emit_trend_metrics(table)
    reg = get_registry()
    g = reg.get("dl4j_trend_pct_vs_baseline")
    assert g is not None
    assert g.value(row="rowA", backend="tpu") is not None
    v = reg.get("dl4j_trend_verdicts")
    assert v.value(verdict="stable") >= 1


def test_debug_trend_endpoint(tmp_path, monkeypatch):
    ledger = tmp_path / "ledger.jsonl"
    for val in (100.0, 101.0, 99.5):
        trend.append_record(_entry(value=val), ledger)
    trend.append_record(
        _entry(row=trend.T4096_BEST_XLA_ROW,
               value=trend.T4096_BEST_XLA_SAMPLES[-1],
               value_samples=list(trend.T4096_BEST_XLA_SAMPLES)), ledger)
    monkeypatch.setenv("DL4J_TREND_LEDGER", str(ledger))
    from deeplearning4j_tpu.ui import UIServer
    srv = UIServer(log_dir=str(tmp_path / "ui"), port=0).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/trend",
                timeout=10) as r:
            state = json.loads(r.read())
    finally:
        srv.stop()
    assert state["n_records"] == 4
    assert state["rows"]["rowA|tpu"]["verdict"] == "stable"
    assert state["rows"][f"{trend.T4096_BEST_XLA_ROW}|tpu"][
        "verdict"] == "bimodal"
    assert state["verdict_counts"]["bimodal"] == 1


def test_trend_cell_arrows(tmp_path, monkeypatch):
    recs = [_entry(value=100.0), _entry(value=120.0)]
    assert trend.trend_cell("rowA", "tpu", recs).startswith("▲")
    recs = [_entry(value=100.0), _entry(value=80.0)]
    assert trend.trend_cell("rowA", "tpu", recs).startswith("▼")
    recs = [_entry(value=100.0), _entry(value=101.0)]
    assert trend.trend_cell("rowA", "tpu", recs).startswith("≈")
    # the arrow encodes BETTER/WORSE, not raw direction: a latency
    # (ms) row that got slower is ▼ even though its value went up
    recs = [_entry(value=100.0, unit="ms"), _entry(value=130.0, unit="ms")]
    assert trend.trend_cell("rowA", "tpu", recs) == "▼ +30.0%"
    recs = [_entry(value=100.0, unit="ms"), _entry(value=70.0, unit="ms")]
    assert trend.trend_cell("rowA", "tpu", recs).startswith("▲")
    # tolerant of a missing/partial ledger
    assert trend.trend_cell("rowA", "tpu", []) == "—"
    assert trend.trend_cell("rowA", "tpu",
                            [_entry(value=100.0)]) == "—"
    monkeypatch.setenv("DL4J_TREND_LEDGER", "/nonexistent/x.jsonl")
    assert trend.trend_cell("no_such_row", "tpu") == "—"
