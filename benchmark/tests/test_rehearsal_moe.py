"""CPU rehearsal of ``run.py`` for the sparse-expert cell at a tiny width:
the whole run but the look for a chip, through the ``moe_train`` driver; the
faults a training cell can have, planted under the timed path, come out
``correct: false``; and the control (the reference in float8) fails the
comparison. As ``test_rehearsal.py`` does it for the first two cells: a tiny
copy of the benchmark's data files in a temporary checkout; the limits are
the test's own at this width."""
import importlib
import importlib.util
import json
import shutil
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
REAL, CELL = "smallthinker-train-b2-t8192", "tiny-moe-train"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout_moe")
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        ".state", "__pycache__", "tests", "tools"))
    (root / "deeplearning4j_tpu").symlink_to(REPO / "deeplearning4j_tpu")
    b = root / "benchmark"
    c = json.loads((b / "configs" / "smallthinker-21b-a3b.json").read_text())
    c.update(hidden_size=64, head_dim=16, num_attention_heads=3,
             num_key_value_heads=1, moe_ffn_hidden_size=32,
             moe_num_primary_experts=4, vocab_size=211,
             sliding_window_size=24, max_position_embeddings=64)
    c["published"]["moe_num_primary_experts"] = 16
    (b / "configs" / "tiny-moe.json").write_text(json.dumps(c))
    t = json.loads((b / "traffic" / "lm-b2-t8192.json").read_text())
    t.update(batch=4, seq=64, pool_batches=8, trace_from_step=5, trace_steps=5)
    (b / "traffic" / "tiny-moe.json").write_text(json.dumps(t))
    # the cell's limits do not carry over: at this width the program reads
    # 8.5e-4 to 2.2e-3 by the worst gradient leaf and the float8 control
    # 1.1e-2 to 1.4e-2, and the norm over all leaves (which the cell
    # compares) does not tell them apart: 1.3e-4 to 3.4e-4 against 6.7e-4 to
    # 5.8e-3
    limits = json.loads((b / "limits" / f"{REAL}.json").read_text())
    limits["grad_norm_gap"]["limit"] = 5e-3
    limits["grad_norm_gap_global"]["limit"] = None
    limits["delta_norm_gap"]["limit"] = 6e-3
    (b / "limits" / f"{CELL}.json").write_text(json.dumps(limits))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-moe", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny-moe.json", "why": "t"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-moe",
                           "traffic": "tiny-moe", "chips": 1, "why": "t"}]
    for kind in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[kind]:
            if "workloads" not in m:
                kept.append(m)
            elif REAL in m["workloads"]:
                kept.append({**m, "workloads": [CELL]})
        bench[kind] = kept
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location("bench_run_moe_under_test",
                                                  b / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


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
    assert m["compiles_in_window.moe"]["value"] == 0
    assert m["moe_dropped.moe"]["value"] == 0
    assert m["expert_load_max_over_mean.moe"]["value"] >= 1.0
    # no TPU plane in a CPU trace: the trace readers find nothing, say nothing
    for name in ("idle_pct.moe", "mfu_pct.moe", "flash_roofline_pct.moe",
                 "experts_roofline_pct.moe", "moe_time_share_pct.moe",
                 "attn_time_share_pct.moe"):
        assert name not in m


def _fault(monkeypatch, fault):
    from drivers import moe_train
    real = moe_train.build_step

    def build_step(cfg, config):
        opt, step = real(cfg, config)
        inner = step.__wrapped__

        def unchanged(params, opt_state, ids, tgt):
            return (params, opt_state) + inner(params, opt_state, ids, tgt)[2:]

        def half(params, opt_state, ids, tgt):
            n = ids.shape[0] // 2
            return inner(params, opt_state, ids[:n], tgt[:n])

        return opt, jax.jit({"unchanged": unchanged, "half": half}[fault],
                            donate_argnums=(0, 1))

    monkeypatch.setattr(moe_train, "build_step", build_step)


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


def test_float8_control_is_not_correct(checkout):
    """The reference put in the program's place, computed in float8, against
    the reference itself: it has to fail the comparison."""
    import compare
    run = checkout
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    _, config, traffic = run.find_cell(bench, CELL)
    driver = importlib.import_module(f"drivers.{traffic['driver']}")
    st = driver.setup(config, traffic, 3, run.Probe(False, traffic))
    driver.release(st)
    want = driver.reference_readings(st)
    limits = run.load_json(run.HERE / "limits" / f"{CELL}.json")
    ok, _ = compare.judge(compare.training_gaps(st.readings, want), limits)
    assert ok
    control = driver.reference_readings(st, product=driver.CONTROL_PRODUCT)
    ok, compared = compare.judge(compare.training_gaps(control, want), limits)
    assert not ok, compared
