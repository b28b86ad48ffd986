"""CPU rehearsal of ``run.py`` for the ZAYA1 cell at a tiny width: the whole
run but the look for a chip, through the ``zaya_train`` driver; the faults a
training cell can have, planted under the timed path, come out ``correct:
false``; and the control (the reference in float8) fails the comparison. As
``test_rehearsal_moe.py`` does it: a tiny copy of the benchmark's data files
in a temporary checkout; the limits are the test's own at this width."""
import importlib
import importlib.util
import json
import shutil
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
REAL, CELL = "zaya1-train-b1-t32768", "tiny-zaya-train"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout_zaya")
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        ".state", "__pycache__", "tests", "tools"))
    (root / "deeplearning4j_tpu").symlink_to(REPO / "deeplearning4j_tpu")
    b = root / "benchmark"
    c = json.loads((b / "configs" / "zaya1-8b.json").read_text())
    c.update(hidden_size=64, head_dim=16, num_attention_heads=4,
             num_key_value_heads=2, moe_intermediate_size=32, num_experts=4,
             router_hidden_size=16, vocab_size=211, max_position_embeddings=128)
    c["published"]["num_experts"] = 8
    (b / "configs" / "tiny-zaya.json").write_text(json.dumps(c))
    t = json.loads((b / "traffic" / "lm-b1-t32768.json").read_text())
    t.update(batch=1, seq=128, pool_batches=8, trace_from_step=5, trace_steps=5)
    (b / "traffic" / "tiny-zaya.json").write_text(json.dumps(t))
    # the cell's limits do not carry over to this width (LIMITS below)
    limits = json.loads((b / "limits" / f"{REAL}.json").read_text())
    for name, limit in LIMITS.items():
        limits.setdefault(name, {})["limit"] = limit
    (b / "limits" / f"{CELL}.json").write_text(json.dumps(limits))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-zaya", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny-zaya.json", "why": "t"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-zaya",
                           "traffic": "tiny-zaya", "chips": 1, "why": "t"}]
    for kind in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[kind]:
            if "workloads" not in m:
                kept.append(m)
            elif REAL in m["workloads"]:
                kept.append({**m, "workloads": [CELL]})
        bench[kind] = kept
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location("bench_run_zaya_under_test",
                                                  b / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


#: at this width (128 tokens a step), seeds 1 to 6 on the CPU, the reference
#: handed the program's choices: by the worst gradient leaf the program reads
#: 1.3e-3 to 2.3e-3, the float8 control 2.3e-2 to 3.0e-2, half of the row
#: over 0.5 (left to its own argmax, with an earlier draw of wo, the program
#: read up to 5.9e-2 and the control from 6.4e-2: not told apart); the worst
#: leaf of the change reads up to 7.7e-3 in the program and 1 for an
#: unchanged state; 0 to 0.6 % of the program's choices are not the
#: reference's own; the loss at step 1 reads up to 1.3e-4 in the program and
#: 2.4e-3 in the control (steps 2 and 3: not read at this width)
LIMITS = {"loss_gap_step1": 1e-3, "loss_gap_step2": None,
          "loss_gap_step3": None,
          "grad_norm_gap": 1e-2, "grad_norm_gap_global": None,
          "delta_norm_gap": 0.2, "delta_norm_gap_global": None,
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
    assert held and all(c["value"] <= c["limit"] for c in held.values())


def test_traced_run_reports_the_counters_and_no_device_share(checkout, capsys):
    r = _run(checkout, capsys, trace=1)
    m = r["metrics"]
    assert m["compiles_in_window.zaya"]["value"] == 0
    assert m["moe_dropped.zaya"]["value"] == 0
    assert m["expert_load_max_over_mean.zaya"]["value"] >= 1.0
    assert 0.0 <= m["moe_skipped_pct.zaya"]["value"] <= 100.0
    # no TPU plane in a CPU trace: the trace readers find nothing, say nothing
    for name in ("idle_pct.zaya", "mfu_pct.zaya", "flash_roofline_pct.zaya",
                 "experts_roofline_pct.zaya", "moe_time_share_pct.zaya",
                 "attn_time_share_pct.zaya", "cca_time_share_pct.zaya"):
        assert name not in m


def _fault(monkeypatch, fault):
    from drivers import zaya_train
    real = zaya_train.build_step

    def build_step(cfg, config):
        opt, step = real(cfg, config)
        inner = step.__wrapped__

        def unchanged(params, opt_state, ids, tgt):
            return (params, opt_state) + inner(params, opt_state, ids, tgt)[2:]

        def half(params, opt_state, ids, tgt):
            n = ids.shape[1] // 2       # a batch of one: half of the row
            return inner(params, opt_state, ids[:, :n], tgt[:, :n])

        return opt, jax.jit({"unchanged": unchanged, "half": half}[fault],
                            donate_argnums=(0, 1))

    monkeypatch.setattr(zaya_train, "build_step", build_step)


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_fault_is_not_correct(checkout, capsys, monkeypatch, fault):
    _fault(monkeypatch, fault)
    r = _run(checkout, capsys)
    assert r["correct"] is False
    failing = [k for k, c in r["compared"].items()
               if c["limit"] is not None and c["value"] > c["limit"]]
    assert failing, r["compared"]
    if fault == "unchanged":
        gap = [k for k in r["compared"] if k.startswith("delta_norm_gap")
               and r["compared"][k]["limit"] is not None][0]
        assert r["compared"][gap]["value"] == pytest.approx(1.0)


def test_float8_control_and_the_half_row_are_not_correct(checkout):
    """The reference put in the program's place, computed in float8 or on
    half of the one row's positions, against the reference itself: each has
    to fail the comparison."""
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
        gaps = compare.training_gaps(readings, want)
        gaps["choice_mismatch_share"] = want["choice_mismatch"]
        return compare.judge(gaps, limits)

    assert judged(st.readings)[0]
    ok, compared = judged(driver.reference_readings(
        st, product=driver.CONTROL_PRODUCT))
    assert not ok and compared["grad_norm_gap"]["value"] > LIMITS["grad_norm_gap"]
    # what tools/readings.py plants at a batch of one: slice(0, 0)
    ok, compared = judged(driver.reference_readings(st, rows=slice(0, 0)))
    assert not ok, compared
    # left to its own argmax the reference finds nothing to disagree with;
    # handed the program's, a few tokens at most (ties within bf16's rounding)
    own = driver.reference_readings(st, handed=False)
    assert own["choice_mismatch"] == 0.0 <= want["choice_mismatch"] < 0.05


@pytest.mark.parametrize("first_step", [0, 1], ids=["from_step_1",
                                                    "from_step_2"])
def test_a_router_that_takes_other_experts_is_not_correct(checkout, capsys,
                                                          monkeypatch,
                                                          first_step):
    """The gradients are compared on the program's own choices, so the
    choices are held to the reference's argmax by their own number, over
    every step followed: a fault that sets in after the first update shows."""
    from drivers import zaya_train
    real = zaya_train.build_step

    def build_step(cfg, config):
        opt, step = real(cfg, config)
        inner = step.__wrapped__

        def biased(params, opt_state, ids, tgt):
            # every token to expert 0: a bias the reference does not have
            bias = 5.0 * (opt_state[0].count >= first_step)
            blocks = dict(params["blocks"])
            blocks["router_beta"] = blocks["router_beta"].at[:, 0].add(bias)
            out = inner(dict(params, blocks=blocks), opt_state, ids, tgt)
            kept = dict(out[0], blocks=dict(
                out[0]["blocks"], router_beta=params["blocks"]["router_beta"]))
            return (kept,) + out[1:]

        return opt, jax.jit(biased, donate_argnums=(0, 1))

    monkeypatch.setattr(zaya_train, "build_step", build_step)
    r = _run(checkout, capsys)
    assert r["correct"] is False
    c = r["compared"]["choice_mismatch_share"]
    assert c["value"] > 0.5 > c["limit"]
