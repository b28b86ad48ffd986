"""CPU rehearsal of ``run.py`` for the GLM-4.7-Flash cell at a tiny width:
the whole run but the look for a chip, through the ``glm_train`` driver; the
faults a training cell can have, planted under the timed path (an unchanged
state, half of the row, the prediction loss left out), come out ``correct:
false``; and the control (the reference in float8) fails the comparison. As
``test_rehearsal_zaya.py`` does it: a tiny copy of the benchmark's data
files in a temporary checkout; the limits are the test's own at this width."""
import importlib
import importlib.util
import json
import shutil
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
REAL, CELL = "glm47flash-train-b1-t8192", "tiny-glm-train"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout_glm")
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        ".state", "__pycache__", "tests", "tools"))
    (root / "deeplearning4j_tpu").symlink_to(REPO / "deeplearning4j_tpu")
    b = root / "benchmark"
    c = json.loads((b / "configs" / "glm-4.7-flash.json").read_text())
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
             qk_rope_head_dim=4, v_head_dim=16, intermediate_size=96,
             moe_intermediate_size=32, n_routed_experts=4,
             num_hidden_layers=3, vocab_size=211,
             max_position_embeddings=128)
    c["published"]["n_routed_experts"] = 16
    (b / "configs" / "tiny-glm.json").write_text(json.dumps(c))
    t = json.loads((b / "traffic" / "lm-b1-t8192.json").read_text())
    t.update(batch=1, seq=128, pool_batches=8, trace_from_step=5, trace_steps=5)
    (b / "traffic" / "tiny-glm.json").write_text(json.dumps(t))
    # the cell's limits do not carry over to this width (LIMITS below)
    limits = json.loads((b / "limits" / f"{REAL}.json").read_text())
    for name, limit in LIMITS.items():
        limits.setdefault(name, {})["limit"] = limit
    (b / "limits" / f"{CELL}.json").write_text(json.dumps(limits))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-glm", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny-glm.json", "why": "t"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-glm",
                           "traffic": "tiny-glm", "chips": 1, "why": "t"}]
    for kind in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[kind]:
            if "workloads" not in m:
                kept.append(m)
            elif REAL in m["workloads"]:
                kept.append({**m, "workloads": [CELL]})
        bench[kind] = kept
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location("bench_run_glm_under_test",
                                                  b / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


#: at this width (128 tokens a step), seeds 1 to 6 on the CPU, the reference
#: handed the program's choices: by the worst gradient leaf the program reads
#: 3.4e-3 to 7.7e-3, the float8 control 4.1e-2 to 6.5e-2, half of the row
#: over 0.77, the prediction loss left out 1 (the module's leaves get no
#: gradient, and their change reads 1 as an unchanged state's does); the
#: whole loss at step 1 reads up to 1.5e-4 in the program, from 5.5e-4 in the
#: control, 0.23 without the prediction loss; its predicted-token part up to
#: 2.2e-4 in the program and from 2.1e-3 in the control; the worst leaf of
#: the change up to 5.1e-3 in the program, 0.13 on half of the row; 0.5 to
#: 0.6 % of the program's assignments are not in the reference's own top-4
LIMITS = {"loss_gap_step1": 3e-4, "loss_gap_step2": None,
          "loss_gap_step3": None, "mtp_loss_gap_step1": 1e-3,
          "grad_norm_gap": 1.5e-2, "grad_norm_gap_global": None,
          "delta_norm_gap": 0.05, "delta_norm_gap_global": None,
          "choice_mismatch_share": 0.05}


def _run(run, capsys, trace=0, seed=2147483659):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace)],
                  require=lambda chips, peaks: jax.devices())
    out, err = capsys.readouterr()
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result)[-1] == "compared"
    assert err.strip().splitlines()[-1] == \
        f"correct: {json.dumps(result['correct'])}"
    return result


def test_sound_run_is_correct(checkout, capsys):
    r = _run(checkout, capsys)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"lm_train_tokens_per_s", "setup_s"}
    held = {k: c for k, c in r["compared"].items() if c["limit"] is not None}
    assert {"loss_gap_step1", "mtp_loss_gap_step1", "grad_norm_gap",
            "delta_norm_gap", "choice_mismatch_share"} <= set(held)
    assert all(c["value"] <= c["limit"] for c in held.values())


def test_traced_run_reports_the_counters_and_no_device_share(checkout, capsys):
    r = _run(checkout, capsys, trace=1)
    m = r["metrics"]
    # the counters the cell shares with the moe cell, under that cell's names
    assert m["compiles_in_window.moe"]["value"] == 0
    assert m["moe_dropped.moe"]["value"] == 0
    assert m["expert_load_max_over_mean.moe"]["value"] >= 1.0
    assert m["peak_hbm_gb.moe"]["value"] >= 0.0
    # no TPU plane in a CPU trace: the trace readers find nothing, say nothing
    for name in ("idle_pct.moe", "mfu_pct.glm", "flash_roofline_pct.glm",
                 "experts_roofline_pct.glm", "moe_time_share_pct.moe",
                 "attn_time_share_pct.moe", "mla_time_share_pct.glm",
                 "head_time_share_pct.lm", "mtp_time_share_pct.glm"):
        assert name not in m
    from deeplearning4j_tpu.obs import get_registry
    reg = get_registry()
    assert reg.get("dl4j_lm_main_loss").value() > 1.0
    assert reg.get("dl4j_lm_mtp_loss").value() > 1.0


def _fault(monkeypatch, fault):
    from drivers import glm_train
    real = glm_train.build_step

    def build_step(cfg, config):
        import dataclasses
        if fault == "no_prediction_loss":   # the module's weight set to 0
            return real(dataclasses.replace(cfg, predict_weight=0.0), config)
        opt, step = real(cfg, config)
        inner = step.__wrapped__

        def unchanged(params, opt_state, ids, tgt):
            return (params, opt_state) + inner(params, opt_state, ids, tgt)[2:]

        def half(params, opt_state, ids, tgt):
            n = ids.shape[1] // 2       # a batch of one: half of the row
            out = inner(params, opt_state, ids[:, :n], tgt[:, :n])
            told = dict(out[3], choices=jax.numpy.tile(
                out[3]["choices"], (1, 1, 2)))      # the shape the cell has
            return out[:3] + (told,)

        return opt, jax.jit({"unchanged": unchanged, "half": half}[fault],
                            donate_argnums=(0, 1))

    monkeypatch.setattr(glm_train, "build_step", build_step)


@pytest.mark.parametrize("fault", ["unchanged", "half", "no_prediction_loss"])
def test_fault_is_not_correct(checkout, capsys, monkeypatch, fault):
    _fault(monkeypatch, fault)
    r = _run(checkout, capsys)
    assert r["correct"] is False
    failing = [k for k, c in r["compared"].items()
               if c["limit"] is not None and c["value"] > c["limit"]]
    assert failing, r["compared"]
    if fault == "unchanged":
        assert r["compared"]["delta_norm_gap"]["value"] == pytest.approx(1.0)
    if fault == "no_prediction_loss":   # the whole loss lacks 0.3 x the part
        assert "loss_gap_step1" in failing
        assert r["compared"]["loss_gap_step1"]["value"] > 0.1


def test_float8_control_half_row_and_no_prediction_loss_are_not_correct(
        checkout):
    """The reference put in the program's place, computed in float8, on half
    of the one row's positions, or without the prediction loss, against the
    reference itself: each has to fail the comparison."""
    import compare
    run = checkout
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    _, config, traffic = run.find_cell(bench, CELL)
    driver = importlib.import_module(f"drivers.{traffic['driver']}")
    st = driver.setup(config, traffic, 3, run.Probe(False, traffic))
    driver.release(st)
    want = driver.reference_readings(st)
    limits = run.load_json(run.HERE / "limits" / f"{CELL}.json")

    def judged(readings):
        return compare.judge(driver.gaps_of(readings, want), limits)

    assert judged(st.readings)[0]
    ok, compared = judged(driver.reference_readings(
        st, product=driver.CONTROL_PRODUCT))
    assert not ok and compared["grad_norm_gap"]["value"] > LIMITS["grad_norm_gap"]
    # what tools/readings.py plants at a batch of one: slice(0, 0)
    ok, compared = judged(driver.reference_readings(st, rows=slice(0, 0)))
    assert not ok, compared
    ok, compared = judged(driver.reference_readings(st, predict_weight=0.0))
    assert not ok and compared["loss_gap_step1"]["value"] > 0.1
    # the predicted-token part itself is the same at step 1: it is the WHOLE
    # loss and the gradients that lack it
    assert compared["mtp_loss_gap_step1"]["value"] < 1e-6
    assert compared["grad_norm_gap"]["value"] > LIMITS["grad_norm_gap"]
    # left to its own top-k the reference finds nothing to disagree with;
    # handed the program's, a few assignments at most (ties within bf16)
    own = driver.reference_readings(st, handed=False)
    assert own["choice_mismatch"] == 0.0 <= want["choice_mismatch"] < 0.05


def test_a_router_that_takes_other_experts_is_not_correct(checkout, capsys,
                                                          monkeypatch):
    """The gradients are compared on the program's own choices, so the
    choices are held to the reference's top-k by their own number."""
    from drivers import glm_train
    real = glm_train.build_step

    def build_step(cfg, config):
        opt, step = real(cfg, config)
        inner = step.__wrapped__

        def biased(params, opt_state, ids, tgt):
            # expert 0 into every token's choice: a bias the reference lacks
            blocks = dict(params["blocks"])
            blocks["router_beta"] = blocks["router_beta"].at[:, 0].add(5.0)
            out = inner(dict(params, blocks=blocks), opt_state, ids, tgt)
            kept = dict(out[0], blocks=dict(
                out[0]["blocks"], router_beta=params["blocks"]["router_beta"]))
            return (kept,) + out[1:]

        return opt, jax.jit(biased, donate_argnums=(0, 1))

    monkeypatch.setattr(glm_train, "build_step", build_step)
    r = _run(checkout, capsys)
    assert r["correct"] is False
    c = r["compared"]["choice_mismatch_share"]
    assert c["value"] > 0.1 > c["limit"]
