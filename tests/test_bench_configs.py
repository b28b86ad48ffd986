"""Every bench.py config's train step compiles and runs (VERDICT r1 weak
item: bench-only code paths were invisible to CI until the round's single
bench run). Tiny shapes on the CPU mesh; same builder code the real bench
uses, so a refactor that breaks a bench surfaces here, not at round end.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import bench  # noqa: E402  (repo-root module)


def _run_one(run_chain):
    loss = float(np.asarray(run_chain(2)).reshape(-1)[0])
    assert np.isfinite(loss), loss
    return loss


def test_bench_lenet_step():
    run_chain, flops = bench.build_lenet(batch=8)
    assert flops > 0
    _run_one(run_chain)


def test_bench_charnn_step():
    run_chain, flops = bench.build_charnn(batch=4, seq=12, vocab=20)
    assert flops > 0
    _run_one(run_chain)


def test_bench_bert_step():
    from deeplearning4j_tpu.zoo import transformer as tfm
    cfg = tfm.BertConfig(max_seq=16, vocab_size=128, d_model=32, n_heads=2,
                         n_layers=2, d_ff=64)
    run_chain, flops = bench.build_bert(batch=2, cfg=cfg)
    assert flops > 0
    _run_one(run_chain)


def test_bench_transformer_step():
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=128, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=16,
                                dtype=jnp.float32)
    run_chain, flops = bench.build_transformer(batch=2, cfg=cfg)
    assert flops > 0
    _run_one(run_chain)


@pytest.mark.slow
def test_bench_resnet50_step():
    run_chain, flops = bench.build_resnet50(batch=2, num_classes=10)
    assert flops > 0
    _run_one(run_chain)


def test_bench_dpoverhead_impl():
    """The dp-overhead config (single fit vs ParallelWrapper dp=8 at equal
    global batch) runs on the virtual mesh and reports finite step times."""
    rec = bench._dpoverhead_impl(batch=64, steps=2)
    assert rec["single_ms"] > 0 and rec["dp8_ms"] > 0
    assert np.isfinite(rec["value"])


def test_bench_record_flags_impossible_mfu(monkeypatch):
    """The MFU audit gate: a derived MFU > 1 marks the record invalid."""
    monkeypatch.setattr(bench, "_peak_flops", lambda dtype="bf16": 197e12)
    rec = bench._record("m", "u", samples_per_step=128,
                        timing=(1e-9, True), flops_per_step=10**9)
    assert rec["mfu"] > 1.0 and rec["timing_valid"] is False
    rec2 = bench._record("m", "u", samples_per_step=128,
                         timing=(1.0, True), flops_per_step=10**12)
    assert rec2["mfu"] < 1.0 and "timing_valid" not in rec2
    # a non-positive marginal time is garbage regardless of MFU
    rec3 = bench._record("m", "u", samples_per_step=128,
                         timing=(1.0, False), flops_per_step=10**9)
    assert rec3["timing_valid"] is False


@pytest.mark.slow
def test_bench_resnet50_fit_path():
    """The fit()-path headline builder runs end-to-end (tiny config)."""
    run_fit, flops = bench.build_resnet50_fit(batch=2, num_classes=10,
                                              n_distinct=2)
    assert flops > 0
    loss = run_fit(2)
    assert loss is not None and np.isfinite(loss)


def test_bench_transformer_long_step():
    """The T=4096-style config (flash+remat-dots) compiles and steps, at
    toy shapes. On the 8-device CI mesh the forced-flash gate falls back
    to the XLA attention path (pallas has no SPMD rule) — the flash
    kernel itself is covered in interpret mode by tests/test_kernels.py;
    remat=dots is engaged either way."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=128, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=32,
                                dtype=jnp.float32, remat=True,
                                remat_policy="dots",
                                use_flash_attention=True)
    run_chain, flops = bench.build_transformer(batch=2, cfg=cfg)
    assert flops > 0
    _run_one(run_chain)


def test_bench_transformer_xlong_step():
    """The benched T=8192-style combination (flash + remat OFF — the
    xlong row) and the flash + save_attn policy both compile and step at
    toy shapes. On the 8-device CI mesh `flash_engages` is False (pallas
    has no SPMD rule), so the analytic flash-flops top-up must NOT be
    added — the traced flops of the forced-flash and no-flash configs
    must agree, keeping the top-up in lockstep with the model's gate."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo import transformer as tfm
    base = dict(vocab_size=128, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                max_seq=32, dtype=jnp.float32)
    # the benched xlong combination: flash forced, remat off
    cfg_benched = tfm.TransformerConfig(use_flash_attention=True,
                                        remat=False, **base)
    run_chain, flops = bench.build_transformer(batch=2, cfg=cfg_benched)
    assert flops > 0
    _run_one(run_chain)
    # the save_attn policy combination (T=1024-row style remat)
    kw = dict(remat=True, remat_policy="save_attn", **base)
    cfg = tfm.TransformerConfig(use_flash_attention=True, **kw)
    run_chain, flops = bench.build_transformer(batch=2, cfg=cfg)
    assert flops > 0
    _run_one(run_chain)
    _, flops_noflash = bench.build_transformer(
        batch=2, cfg=tfm.TransformerConfig(use_flash_attention=False, **kw))
    assert tfm.flash_engages(cfg, cfg.max_seq) == (jax.device_count() == 1)
    if tfm.flash_engages(cfg, cfg.max_seq):
        assert flops > flops_noflash
    else:
        assert flops == flops_noflash


def test_bench_lenet_scan_step():
    run_chain, flops = bench.build_lenet_scan(batch=8)
    assert flops > 0
    loss = run_chain(3)
    assert loss is not None and float(loss) == float(loss)


@pytest.mark.slow   # ~95s: the ResNet fit_scanned epoch compile dominates
def test_bench_resnet50_fitscan_parts():
    """build_resnet50_fit(return_parts=True) feeds the fitscan config; the
    scanned entry point runs on the tiny-config CI path."""
    run_fit, flops, net, dss = bench.build_resnet50_fit(
        batch=2, num_classes=10, n_distinct=2, return_parts=True)
    assert flops > 0 and hasattr(net, "fit_scanned")
    loss = net.fit_scanned([dss[0], dss[1]])
    assert float(loss) == float(loss)


@pytest.mark.parametrize("argv", [[], ["--refresh", "lenet"],
                                  ["--model", "lenet", "2", "2"]],
                         ids=["full", "refresh", "model"])
def test_bench_no_tpu_exits_nonzero_artifact_untouched(tmp_path, argv):
    """No TPU -> non-zero exit from the full run, --refresh and a --model
    child alike; no record on stdout (a CPU timing is never printed under
    a chip metric's name) and the artifact on disk is byte-identical."""
    import json as _json
    import os
    import subprocess
    import sys

    prev = {"headline": {"metric": "m", "value": 123.0, "git_sha": "abc"},
            "secondary": {"lenet": {"value": 5.0}}}
    art = tmp_path / "bench_secondary.json"
    art.write_text(_json.dumps(prev))
    before = art.read_bytes()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DL4J_TPU_BENCH_ARTIFACT=str(art),
               DL4J_TREND_LEDGER=str(tmp_path / "ledger.jsonl"))
    repo = os.path.dirname(os.path.abspath(bench.__file__))
    proc = subprocess.run([sys.executable, "bench.py", *argv], cwd=repo,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == bench.NO_TPU_RC != 0, proc.stderr[-500:]
    assert proc.stdout.strip() == ""          # no metric, no record
    assert "no TPU" in proc.stderr
    assert art.read_bytes() == before
    assert not (tmp_path / "ledger.jsonl").exists()


def test_bench_main_failed_headline_exits_nonzero(tmp_path, monkeypatch,
                                                  capsys):
    """A headline child that fails for any other reason also ends the run
    non-zero before anything is written."""
    art = tmp_path / "bench_secondary.json"
    monkeypatch.setenv("DL4J_TPU_BENCH_ARTIFACT", str(art))
    monkeypatch.setattr(bench, "_run_row_subprocess",
                        lambda name, *a: {"error": "synthetic crash"})
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    assert bench.main() == 1
    assert capsys.readouterr().out == ""
    assert not art.exists()


def test_bench_refresh_rows_isolated(tmp_path, monkeypatch, capsys):
    """--refresh semantics without a chip: unknown rows never touch the
    artifact; a row whose subprocess fails records an error entry while
    every other row's record (and the headline) survives, and a stale
    _incomplete marker from a crashed full run is cleared."""
    import json as _json
    import bench

    art = tmp_path / "bench_secondary.json"
    prev = {"headline": {"metric": "m", "value": 100.0, "git_sha": "abc"},
            "secondary": {"lenet": {"value": 5.0, "git_sha": "abc"},
                          "_incomplete": "run in progress"}}
    art.write_text(_json.dumps(prev))
    monkeypatch.setenv("DL4J_TPU_BENCH_ARTIFACT", str(art))

    # unknown row: message, artifact byte-identical
    before = art.read_text()
    bench._refresh_rows(["nosuchrow"])
    assert art.read_text() == before

    # the headline row is not refreshable in place
    bench._refresh_rows(["resnet50"])
    assert art.read_text() == before

    # a failing re-capture of a VERIFIED row keeps the previous record
    # (never overwrite a good capture with an error entry)
    monkeypatch.setitem(bench.CONFIGS, "lenet", lambda b, s: {})
    monkeypatch.setitem(bench.DEFAULTS, "lenet", (1, 1))
    with monkeypatch.context() as m:
        m.setattr(bench, "_run_row_subprocess",
                  lambda name: {"error": "synthetic subprocess failure"})
        bench._refresh_rows(["lenet"])
    disk = _json.loads(art.read_text())
    assert disk == prev  # untouched: failed refresh never persisted

    # a row that exists in-process but fails in the fresh subprocess,
    # with NO previous record: the error entry is recorded
    monkeypatch.setitem(bench.CONFIGS, "synthetic_fail", lambda b, s: {})
    monkeypatch.setitem(bench.DEFAULTS, "synthetic_fail", (1, 1))
    bench._refresh_rows(["synthetic_fail"])
    disk = _json.loads(art.read_text())
    assert disk["headline"]["value"] == 100.0           # headline kept
    assert disk["secondary"]["lenet"]["value"] == 5.0   # other rows kept
    assert "error" in disk["secondary"]["synthetic_fail"]
    assert "_incomplete" not in disk["secondary"]       # marker cleared


def test_bench_slo_serve_block_tiny_engine():
    """The `slo` + `memory` blocks every inference row now embeds
    (ISSUE 11 + 12): ONE real mixed-length scheduler serve at CI scale
    yields goodput / ITL p99 / TTFT p99 with the targets riding along,
    beside the KV-waste attribution that sizes the paged-KV PR."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.serving import GenerationEngine
    from deeplearning4j_tpu.zoo import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=32,
                                dtype=jnp.float32, attn_scores_bf16=False)
    eng = GenerationEngine(cfg, tfm.init_params(jax.random.PRNGKey(0),
                                                cfg))
    block, mem = bench._serve_blocks(eng, slots=2, n_requests=4,
                                     new_tokens=4, prompt_len=6)
    assert 0.0 <= block["goodput"] <= 1.0
    assert block["itl_p99_ms"] > 0 and block["ttft_p99_ms"] > 0
    assert block["requests"] == 4
    # mixed budgets: request i generates new_tokens + (i % 3) tokens,
    # each contributing (tokens - 1) inter-token gaps
    assert block["itl_samples"] == sum(4 + (i % 3) - 1 for i in range(4))
    assert block["targets"]["quantile"] == 0.99
    assert isinstance(block["met"], bool)
    assert mem["params_bytes"] > 0 and mem["kv_allocated_bytes"] > 0
    assert 0.0 < mem["kv_waste_ratio"] < 1.0
    assert mem["bytes_per_resident_token"] > 0
    assert mem["retraces_after_warm"] == 0
    assert mem["source"] in ("memory_stats", "pytree")
    # the offline TTFT-row derivation shares _slo_compact
    from deeplearning4j_tpu.obs import SLOConfig, SLOTracker
    tr = SLOTracker(SLOConfig(), registry=False)
    for s in (0.01, 0.02):
        tr.observe_summary({"status": "finish", "ttft_s": s, "itl_s": []})
    compact = bench._slo_compact(tr.report())
    assert compact["goodput"] == 1.0 and compact["itl_p99_ms"] is None


def test_bench_inference_helpers_and_refresh_routing(tmp_path, monkeypatch):
    """Serving bench surface at CI scale (ISSUE 10): the latency-sweep
    helper drives a live ParallelInference at tiny shapes, records are
    stamped with their device, and --refresh routes inference_* rows
    into the artifact's `inference` section without touching
    secondary."""
    import json as _json
    import bench
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import ParallelInference
    from deeplearning4j_tpu.serving import FunctionalInferenceModel
    from deeplearning4j_tpu.zoo import transformer as tfm

    # latency sweep through the functional-adapter front end
    cfg = tfm.BertConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                         d_ff=32, max_seq=8, dtype=jnp.float32)
    params = tfm.bert_init(jax.random.PRNGKey(0), cfg)
    model = FunctionalInferenceModel(
        params, lambda p, ids: tfm.bert_forward(p, cfg, ids)[0])
    pi = ParallelInference(model, max_batch=8)

    def make_batch(b):
        return np.random.default_rng(0).integers(
            0, 32, (b, 8)).astype(np.int32)

    stats = bench._latency_sweep(pi, make_batch, iters=3, batches=(1, 2))
    assert stats["p50_ms"] > 0 and stats["p99_ms"] >= stats["p50_ms"]
    assert stats["best_batch"] in (1, 2)
    assert stats["best_batch_throughput"] > 0

    # every record names the device it ran on
    stamped = bench._stamp({})
    assert stamped["backend"] == "cpu" and stamped["device_kind"] == "cpu"
    assert stamped["device_count"] == jax.device_count()

    # --refresh routing: inference rows land in the `inference` section
    art = tmp_path / "bench_secondary.json"
    prev = {"headline": {"metric": "m", "value": 100.0, "git_sha": "abc"},
            "secondary": {"lenet": {"value": 5.0}}}
    art.write_text(_json.dumps(prev))
    monkeypatch.setenv("DL4J_TPU_BENCH_ARTIFACT", str(art))
    assert "inference_decode" in bench.INFERENCE_ROWS
    with monkeypatch.context() as m:
        m.setattr(bench, "_run_row_subprocess",
                  lambda name: {"value": 42.0, "metric": name})
        bench._refresh_rows(["inference_decode"])
    disk = _json.loads(art.read_text())
    assert disk["inference"]["inference_decode"]["value"] == 42.0
    assert disk["secondary"] == {"lenet": {"value": 5.0}}  # untouched
    assert disk["headline"]["value"] == 100.0
