"""Roofline floor engine + bench stability discipline (ISSUE 7).

Acceptance contract: all four headline bench configs (resnet,
transformer, bert, charnn) produce a machine-derived ``floor`` block
(flops, bytes, floor_ms, pct_of_floor, binding_resource) on CPU via
cost_analysis or the estimator; the cost-analysis fallback path records
``source="estimated"`` and never crashes; sub-millisecond rows carry
``median_of_k`` + ``unstable`` fields.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import bench  # noqa: E402  (repo-root module)

from deeplearning4j_tpu.obs import MetricsRegistry, floors  # noqa: E402

FLOOR_KEYS = {"flops", "bytes", "source", "floor_ms", "pct_of_floor",
              "binding_resource", "compute_floor_ms", "memory_floor_ms"}


def _assert_full_floor(block, *, want_verdict=True):
    assert FLOOR_KEYS <= set(block), sorted(block)
    assert block["flops"] > 0 and block["bytes"] > 0
    assert block["floor_ms"] == pytest.approx(
        max(block["compute_floor_ms"], block["memory_floor_ms"]))
    assert block["binding_resource"] in ("compute", "memory")
    assert block["source"] in ("cost_analysis", "estimated")
    assert block["pct_of_floor"] > 0
    if want_verdict:
        assert block["verdict"] in ("ok", "lever")
    assert block.get("peaks_nominal") is True  # CPU peaks are nominal


def _floor_of(run_chain, step_ms=5.0, dtype="f32"):
    costs = run_chain.floor_probe()
    return floors.floor_block(costs, step_ms=step_ms, dtype=dtype)


# ---------------------------------------------------------------------------
# the four headline configs derive a floor on CPU
# ---------------------------------------------------------------------------

def test_floor_charnn_config():
    run_chain, flops = bench.build_charnn(batch=4, seq=12, vocab=20)
    block = _floor_of(run_chain)
    _assert_full_floor(block)
    # cost-analysis flops should be same order as the analytic count
    assert block["flops"] > 0.1 * flops


def test_floor_transformer_config():
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=128, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=16,
                                dtype=jnp.float32)
    run_chain, _ = bench.build_transformer(batch=2, cfg=cfg)
    _assert_full_floor(_floor_of(run_chain))


def test_floor_bert_config():
    from deeplearning4j_tpu.zoo import transformer as tfm
    cfg = tfm.BertConfig(max_seq=16, vocab_size=128, d_model=32, n_heads=2,
                         n_layers=2, d_ff=64)
    run_chain, _ = bench.build_bert(batch=2, cfg=cfg)
    _assert_full_floor(_floor_of(run_chain))


@pytest.mark.slow   # ResNet-50 CPU compile dominates (same as bench step test)
def test_floor_resnet_config():
    run_chain, _ = bench.build_resnet50(batch=2, num_classes=10)
    block = _floor_of(run_chain, step_ms=50.0, dtype="bf16")
    _assert_full_floor(block)


def test_floor_resnet_fit_probe_attached():
    """The headline fit()-path builder carries a floor probe without
    paying the ResNet compile here (probe itself is the slow test)."""
    import deeplearning4j_tpu  # noqa: F401  (import side effects only)
    # tiny MLN stands in for shape: probe attachment is builder-level
    run_chain, _ = bench.build_lenet(batch=4)
    assert callable(run_chain.floor_probe)
    block = _floor_of(run_chain, dtype="bf16")
    _assert_full_floor(block)


# ---------------------------------------------------------------------------
# fallback path: no / partial cost_analysis → estimator, never a crash
# ---------------------------------------------------------------------------

def test_floor_fallback_no_cost_analysis(monkeypatch):
    run_chain, flops = bench.build_charnn(batch=2, seq=8, vocab=11)
    monkeypatch.setattr(floors, "_cost_analysis_of", lambda *a, **k: {})
    costs = run_chain.floor_probe()
    assert costs["source"] == "estimated"
    assert costs["flops"] > 0 and costs["bytes"] > 0
    block = floors.floor_block(costs, step_ms=3.0)
    _assert_full_floor(block)
    assert block["source"] == "estimated"


def test_floor_fallback_partial_cost_analysis(monkeypatch):
    """Backend reports flops but omits bytes: the estimator fills the
    hole and source records the degradation. A compiled flop count
    LARGER than the analytic one is trusted (it saw the real
    executable)."""
    run_chain, flops = bench.build_charnn(batch=2, seq=8, vocab=11)
    big = float(flops * 100)
    monkeypatch.setattr(floors, "_cost_analysis_of",
                        lambda *a, **k: {"flops": big})
    costs = run_chain.floor_probe()
    assert costs["source"] == "estimated"
    assert costs["flops"] == big              # compiled value wins
    assert costs["flops_source"] == "cost_analysis"
    assert costs["bytes_source"] == "estimated"
    assert costs["bytes"] > 0                 # estimator filled it
    _assert_full_floor(floors.floor_block(costs, step_ms=3.0))


def test_floor_scan_undercounted_flops_use_analytic(monkeypatch):
    """XLA cost analysis counts a lax.scan body once regardless of trip
    count; when the compiled flop count lands BELOW the trip-multiplied
    jaxpr walk, the analytic count wins (else a scanned transformer's
    roofline flips from compute- to memory-bound — observed 10x low)."""
    run_chain, _ = bench.build_charnn(batch=2, seq=8, vocab=11)
    monkeypatch.setattr(floors, "_cost_analysis_of",
                        lambda *a, **k: {"flops": 7.0, "bytes": 1e6})
    costs = run_chain.floor_probe()
    assert costs["flops"] > 7.0               # analytic replaced it
    assert costs["flops_source"] == "estimated"
    assert costs["flops_cost_analysis"] == 7.0   # undercount kept
    assert costs["bytes"] == 1e6              # compiled bytes kept
    assert costs["bytes_source"] == "cost_analysis"
    assert costs["source"] == "estimated"


def test_floor_total_failure_never_crashes(monkeypatch):
    """cost_analysis AND the estimator both die → an na-block, not an
    exception, and the bench row still records."""
    monkeypatch.setattr(floors, "_cost_analysis_of", lambda *a, **k: {})

    def boom(*a, **k):
        raise RuntimeError("synthetic estimator failure")
    monkeypatch.setattr(floors, "estimate_costs", boom)

    def bad_probe():
        return floors.hlo_costs(lambda x: x, 1.0)
    bad_probe_chain = lambda n: None  # noqa: E731
    bad_probe_chain.floor_probe = bad_probe
    costs = bad_probe()
    assert "error" in costs
    block = floors.floor_block(costs, step_ms=1.0)
    assert "na" in block and "floor_ms" not in block
    rec = bench._record("synthetic row", "u", 1, (1e-3, True), 10**6,
                        probe=bad_probe_chain)
    assert "na" in rec["floor"]               # row survived floorless


def test_floor_unknown_device_kind_is_an_error():
    """A chip without published peaks in the table is refused — never
    judged against the v5e's."""
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        floors.floor_block({"flops": 1e9, "bytes": 1e6,
                            "source": "cost_analysis"},
                           step_ms=1.0, device_kind="TPU v9 imaginary")
    with pytest.raises(ValueError, match="no peak table entry"):
        floors.device_peaks("tpu")            # a platform is not a kind
    assert floors.device_peaks("TPU v5 lite")["source"].startswith(
        "Google Cloud documentation")
    assert floors.device_peaks()["nominal"] is True      # this CPU run


def test_floor_binding_resource_switches():
    peaks_ok = dict(step_ms=10.0, device_kind="cpu")
    hot = floors.floor_block({"flops": 1e12, "bytes": 1e3,
                              "source": "estimated"}, **peaks_ok)
    assert hot["binding_resource"] == "compute"
    cold = floors.floor_block({"flops": 1e3, "bytes": 1e12,
                               "source": "estimated"}, **peaks_ok)
    assert cold["binding_resource"] == "memory"


# ---------------------------------------------------------------------------
# bench row integration: floor block + registry mirror
# ---------------------------------------------------------------------------

def test_bench_record_embeds_floor_and_metrics():
    from deeplearning4j_tpu.obs import get_registry
    run_chain, flops = bench.build_charnn(batch=2, seq=8, vocab=11)
    rec = bench._record("charnn floor test row", "tokens/sec/chip", 16,
                        (5e-3, True), flops, dtype="f32", probe=run_chain)
    _assert_full_floor(rec["floor"])
    assert rec["metrics"]["dl4j_bench_floor_ms"] == rec["floor"]["floor_ms"]
    assert rec["metrics"]["dl4j_bench_pct_of_floor"] == \
        rec["floor"]["pct_of_floor"]
    reg = get_registry()
    assert reg.gauge("dl4j_bench_floor_ms", labelnames=("config",)).value(
        config="charnn floor test row") == rec["floor"]["floor_ms"]


def test_bench_invalid_timing_floor_has_no_verdict():
    """A timing_valid=False row keeps its flops/bytes floor but must not
    quote a pct_of_floor against a garbage denominator."""
    run_chain, flops = bench.build_charnn(batch=2, seq=8, vocab=11)
    rec = bench._record("charnn invalid timing row", "tokens/sec/chip", 16,
                        (1e-3, False), flops, dtype="f32", probe=run_chain)
    assert rec["timing_valid"] is False
    assert rec["floor"]["flops"] > 0
    assert "pct_of_floor" not in rec["floor"]
    assert "verdict" not in rec["floor"]


# ---------------------------------------------------------------------------
# median-of-k stability for sub-millisecond rows
# ---------------------------------------------------------------------------

def _scripted_marginal(script):
    """Deterministic stand-in for measure_marginal: one (per_step, valid)
    per capture. Wall-clock fakes (time.sleep) are NOT reliable here —
    this host's sleep granularity is coarser than the sub-ms rows under
    test — so the stability logic is tested on scripted samples and the
    real timing path is covered by the bench-config tests."""
    it = iter(script)

    def fake(run_chain, n1, n2, repeats=2):
        return next(it)

    return fake


def test_measure_stable_sub_ms_rows_get_median_fields(monkeypatch):
    monkeypatch.setattr(bench, "measure_marginal",
                        _scripted_marginal([(2e-4, True)] * 4))
    per_step, valid, stab = bench.measure_stable(lambda n: None, k=4)
    assert valid and per_step == pytest.approx(2e-4)
    assert stab["median_of_k"] == 4
    assert stab["unstable"] is False
    assert len(stab["step_time_ms_samples"]) == stab["median_of_k"]
    assert stab["iqr_rel"] < bench.UNSTABLE_REL_IQR


def test_measure_stable_flags_jittery_rows(monkeypatch):
    # 0.1 ms vs 0.5 ms across captures: relative IQR >> the 25% gate
    script = [(1e-4, True), (1e-4, True), (5e-4, True),
              (1e-4, True), (5e-4, True), (5e-4, True)]
    monkeypatch.setattr(bench, "measure_marginal",
                        _scripted_marginal(script))
    per_step, valid, stab = bench.measure_stable(lambda n: None, k=6)
    assert valid and stab is not None
    assert stab["unstable"] is True
    assert per_step == pytest.approx(3e-4)        # median, not first draw
    # an invalid re-capture is dropped, not recorded as a sample
    monkeypatch.setattr(bench, "measure_marginal", _scripted_marginal(
        [(2e-4, True), (1e-9, False), (2e-4, True)]))
    _, _, stab2 = bench.measure_stable(lambda n: None, k=3)
    assert stab2["median_of_k"] == 2


def test_measure_stable_leaves_slow_rows_alone(monkeypatch):
    monkeypatch.setattr(bench, "measure_marginal",
                        _scripted_marginal([(5e-3, True)]))
    per_step, valid, stab = bench.measure_stable(lambda n: None, k=4)
    assert valid and stab is None
    # and an invalid first estimate short-circuits (no stability pass)
    monkeypatch.setattr(bench, "measure_marginal",
                        _scripted_marginal([(1e-9, False)]))
    per_step, valid, stab = bench.measure_stable(lambda n: None, k=4)
    assert not valid and stab is None


def test_record_carries_stability_fields():
    stab = {"median_of_k": 5, "step_time_ms_samples": [0.1] * 5,
            "iqr_rel": 0.31, "unstable": True}
    rec = bench._record("m", "u", 8, (1e-4, True, stab), 10**6)
    assert rec["median_of_k"] == 5
    assert rec["unstable"] is True
    assert rec["iqr_rel"] == 0.31
    # 2-tuple timing (the pre-stability call shape) still works
    rec2 = bench._record("m", "u", 8, (1e-4, True), 10**6)
    assert "median_of_k" not in rec2


# ---------------------------------------------------------------------------
# doc lint: unregistered dl4j_ mentions in docs are rejected
# ---------------------------------------------------------------------------

def test_doc_lint_rejects_unregistered_metric(tmp_path):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))
    try:
        import check_metric_names as cmn
    finally:
        sys.path.pop(0)
    doc = tmp_path / "fake.md"
    doc.write_text("scrape `dl4j_bench_floor_ms` and `dl4j_ghost_metric`, "
                   "histogram series `dl4j_layer_time_ms_bucket`, "
                   "wildcard `dl4j_bench_*`, bogus wildcard `dl4j_nope_*`\n")
    known = {"dl4j_bench_floor_ms", "dl4j_layer_time_ms",
             "dl4j_bench_step_seconds"}
    errors = cmn.check_docs(known, doc_files=[doc])
    joined = "\n".join(errors)
    assert "dl4j_ghost_metric" in joined
    assert "dl4j_nope_*" in joined
    assert "dl4j_layer_time_ms_bucket" not in joined   # suffix resolves
    assert "dl4j_bench_floor_ms" not in joined
    assert len(errors) == 2
    # and the real tree + real docs are clean
    assert cmn.check() == []


def test_floor_metrics_emitted_into_custom_registry():
    reg = MetricsRegistry()
    block = floors.floor_block({"flops": 4e9, "bytes": 2e9,
                                "source": "cost_analysis"},
                               step_ms=100.0, device_kind="TPU v5 lite",
                               dtype="bf16")
    assert block["peak_flops"] == 197e12
    assert "peaks_nominal" not in block
    out = floors.emit_floor_metrics("cfg", block, registry=reg)
    assert out["dl4j_bench_floor_ms"] == block["floor_ms"]
    assert reg.gauge("dl4j_bench_pct_of_floor",
                     labelnames=("config",)).value(config="cfg") == \
        block["pct_of_floor"]
    # na-blocks emit nothing
    assert floors.emit_floor_metrics("cfg", {"na": "x"}, registry=reg) == {}
