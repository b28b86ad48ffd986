"""CPU rehearsal of ``run.py`` for the Qwen3-Next cell at a tiny width: the
whole run but the look for a chip, through the ``qwen3next_train`` driver;
the faults a training cell can have, planted under the timed path (an
unchanged state, half of the row), come out ``correct: false``; and so do
the control (the reference in float8) and this model's own faults (the
decay left out, the delta correction left out, attention's output gate left
out, the shared expert's gate left out), each the reference put in the
program's place. As ``test_rehearsal_glm.py`` does it: a tiny copy of the
benchmark's data files in a temporary checkout; the limits are the test's
own at this width."""
import importlib
import importlib.util
import json
import shutil
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
REAL, CELL = "qwen3next-train-b1-t8192", "tiny-qwen3next-train"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout_qwen3next")
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        ".state", "__pycache__", "tests", "tools"))
    (root / "deeplearning4j_tpu").symlink_to(REPO / "deeplearning4j_tpu")
    b = root / "benchmark"
    c = json.loads((b / "configs" / "qwen3-next-80b-a3b.json").read_text())
    # key heads of 64: at 8 a head's L2 norm often divides by a near-zero
    # length, and bf16's rounding then moves the gradient by tens of per
    # cent (at 128, 0.5 %); 16 value heads in each of 3 layers, so that a
    # few decay slowly under the published draw (A_log = log U(0, 16)), as
    # the cell's 96 do, and the delta correction matters
    c.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
             head_dim=32, linear_num_key_heads=2, linear_key_head_dim=64,
             linear_num_value_heads=16, linear_value_head_dim=16,
             moe_intermediate_size=16, shared_expert_intermediate_size=16,
             num_experts=4, num_experts_per_tok=4, vocab_size=211,
             max_position_embeddings=256,
             # float32: at this width bf16's rounding moves the program's
             # worst gradient leaf by 3 to 10 %, as much as the faults do
             compute_dtype="float32")
    c["published"]["num_experts"] = 16
    (b / "configs" / "tiny-qwen3next.json").write_text(json.dumps(c))
    t = json.loads((b / "traffic" / "lm-b1-t8192-gdn.json").read_text())
    t.update(batch=1, seq=128, pool_batches=8, trace_from_step=5, trace_steps=5)
    (b / "traffic" / "tiny-qwen3next.json").write_text(json.dumps(t))
    # the cell's limits do not carry over to this width (LIMITS below)
    limits = json.loads((b / "limits" / f"{REAL}.json").read_text())
    for name, limit in LIMITS.items():
        limits.setdefault(name, {})["limit"] = limit
    (b / "limits" / f"{CELL}.json").write_text(json.dumps(limits))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-qwen3next", "source": "test",
                         "reduced": [], "why": "t",
                         "file": "benchmark/configs/tiny-qwen3next.json"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-qwen3next",
                           "traffic": "tiny-qwen3next", "chips": 1, "why": "t"}]
    for kind in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[kind]:
            if "workloads" not in m:
                kept.append(m)
            elif REAL in m["workloads"]:
                kept.append({**m, "workloads": [CELL]})
        bench[kind] = kept
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location(
        "bench_run_qwen3next_under_test", b / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


#: at this width (128 tokens a step), in float32, on the CPU, seed 3 (and 4
#: for the program), the reference handed the program's choices: the program
#: reads 0 / 8.2e-8 at step 1, 4.6e-6 / 2.0e-6 by the worst gradient leaf,
#: 6.6e-6 / 2.1e-6 by the worst leaf of the change; the float8 control 3.3e-3,
#: 0.149, 1.1e-2; half of the row 7.5e-3, 0.571, 0.219; the decay left out
#: 1.9e-3, 0.723, 0.295; the delta correction left out 3.0e-4, 0.122, 4.1e-3;
#: attention's gate left out 1.2e-3, 0.552, 0.182; the shared gate left out
#: 8.5e-3, 1, 0.244
LIMITS = {"loss_gap_step1": 1e-4, "loss_gap_step2": None,
          "loss_gap_step3": None, "grad_norm_gap": 1e-3,
          "grad_norm_gap_global": None, "delta_norm_gap": 1e-3,
          "delta_norm_gap_global": None, "choice_mismatch_share": 0.05}


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
    assert {"loss_gap_step1", "grad_norm_gap", "delta_norm_gap",
            "choice_mismatch_share"} <= set(held)
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
    for name in ("idle_pct.moe", "mfu_pct.qwen3next", "moe_time_share_pct.moe",
                 "gdn_time_share_pct.qwen3next", "gdn_roofline_pct.qwen3next",
                 "head_time_share_pct.lm", "unscoped_time_share_pct.lm"):
        assert name not in m


def _fault(monkeypatch, fault):
    from drivers import qwen3next_train
    real = qwen3next_train.build_step

    def build_step(cfg, config):
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

    monkeypatch.setattr(qwen3next_train, "build_step", build_step)


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_fault_is_not_correct(checkout, capsys, monkeypatch, fault):
    _fault(monkeypatch, fault)
    r = _run(checkout, capsys)
    assert r["correct"] is False
    failing = [k for k, c in r["compared"].items()
               if c["limit"] is not None and c["value"] > c["limit"]]
    assert failing, r["compared"]
    if fault == "unchanged":
        assert r["compared"]["delta_norm_gap"]["value"] == pytest.approx(1.0)


def test_control_half_row_and_the_models_own_faults_are_not_correct(checkout):
    """The reference put in the program's place, computed in float8, on half
    of the one row's positions, or with one of this model's own faults,
    against the reference itself: each has to fail the comparison."""
    import compare
    run = checkout
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    _, config, traffic = run.find_cell(bench, CELL)
    driver = importlib.import_module(f"drivers.{traffic['driver']}")
    st = driver.setup(config, traffic, 3, run.Probe(False, traffic))
    driver.release(st)
    want = driver.reference_readings(st)
    limits = run.load_json(run.HERE / "limits" / f"{CELL}.json")

    def failing(readings):
        ok, compared = compare.judge(driver.gaps_of(readings, want), limits)
        return not ok and [k for k, c in compared.items()
                           if c["limit"] is not None
                           and c["value"] > c["limit"]]

    assert not failing(st.readings)
    assert failing(driver.reference_readings(
        st, product=driver.CONTROL_PRODUCT))
    # what tools/readings.py plants at a batch of one: slice(0, 0)
    assert failing(driver.reference_readings(st, rows=slice(0, 0)))
    for fault in ("no_decay", "no_delta", "no_attn_gate", "no_shared_gate"):
        assert failing(driver.reference_readings(st, fault=fault)), fault
    # left to its own top-k the reference finds nothing to disagree with;
    # handed the program's, a few assignments at most (ties within bf16)
    own = driver.reference_readings(st, handed=False)
    assert own["choice_mismatch"] == 0.0 <= want["choice_mismatch"] < 0.05


def test_a_router_that_takes_other_experts_is_not_correct(checkout, capsys,
                                                          monkeypatch):
    """The gradients are compared on the program's own choices, so the
    choices are held to the reference's top-k by their own number."""
    from drivers import qwen3next_train
    real = qwen3next_train.build_step

    def build_step(cfg, config):
        opt, step = real(cfg, config)
        inner = step.__wrapped__

        def biased(params, opt_state, ids, tgt):
            # expert 0 into every token's choice: a router the reference lacks
            blocks = dict(params["blocks"])
            blocks["router"] = blocks["router"].at[:, :, 0].add(
                3.0 * jax.numpy.sign(params["blocks"]["router"][:, :, 0]))
            out = inner(dict(params, blocks=blocks), opt_state, ids, tgt)
            kept = dict(out[0], blocks=dict(
                out[0]["blocks"], router=params["blocks"]["router"]))
            return (kept,) + out[1:]

        return opt, jax.jit(biased, donate_argnums=(0, 1))

    monkeypatch.setattr(qwen3next_train, "build_step", build_step)
    r = _run(checkout, capsys)
    assert r["correct"] is False
    c = r["compared"]["choice_mismatch_share"]
    assert c["value"] > c["limit"]
