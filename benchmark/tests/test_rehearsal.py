"""CPU rehearsal of ``run.py`` at a tiny width: the whole run but the look
for a chip, through both drivers; the faults a training cell can have, planted
under the timed path, come out ``correct: false``; and the control (the
reference in float8) fails the comparison.

A tiny copy of the benchmark's data files is laid out in a temporary
checkout (``BENCHMARK.json``, ``benchmark/``, a link to the package), so
``run.py`` itself needs no switch that lets it measure off the chip. Limits
at the tiny size are the test's own where the cell's do not carry over (a
batch of 32 small images is noisier than 256 large ones).
"""
import importlib.util
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
LM, FIT = "tiny-lm-train", "tiny-resnet-fit"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _limits(real: str, overrides: dict) -> dict:
    limits = json.loads((BENCH / "limits" / f"{real}.json").read_text())
    for name, value in overrides.items():
        limits[name]["limit"] = value
    return limits


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        ".state", "__pycache__", "tests", "tools"))
    (root / "deeplearning4j_tpu").symlink_to(REPO / "deeplearning4j_tpu")
    b = root / "benchmark"
    lm = json.loads((b / "configs" / "gpt2-medium.json").read_text())
    lm.update(n_embd=64, n_layer=2, n_head=4, head_dim=16, vocab_size=257,
              n_positions=32, n_ctx=32, n_inner=256)
    (b / "configs" / "tiny-lm.json").write_text(json.dumps(lm))
    rn = json.loads((b / "configs" / "resnet50-imagenet.json").read_text())
    rn.update(input_shape=[64, 64, 3], num_classes=10,
              stages=[[2, [16, 16, 64]], [2, [32, 32, 128]]])
    (b / "configs" / "tiny-resnet.json").write_text(json.dumps(rn))
    t = json.loads((b / "traffic" / "lm-b16-t1024.json").read_text())
    t.update(batch=8, seq=32, pool_batches=8, trace_from_step=5, trace_steps=5)
    (b / "traffic" / "tiny-lm.json").write_text(json.dumps(t))
    t = json.loads((b / "traffic" / "fit-b256-host-f32.json").read_text())
    t.update(batch=32, pool_batches=4, trace_from_step=2, trace_steps=2)
    (b / "traffic" / "tiny-fit.json").write_text(json.dumps(t))
    (b / "limits" / f"{LM}.json").write_text(json.dumps(
        _limits("gpt2m-train-b16", {})))
    (b / "limits" / f"{FIT}.json").write_text(json.dumps(
        _limits("resnet50-fit-b256", {"grad_norm_gap_global": 6e-3,
                                      "delta_norm_gap_global": 1e-2})))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = {"gpt2m-train-b16": LM, "resnet50-fit-b256": FIT}
    bench["configs"] = [
        {"name": "tiny-lm", "source": "test", "file": "benchmark/configs/tiny-lm.json",
         "reduced": [], "why": "test"},
        {"name": "tiny-resnet", "source": "test",
         "file": "benchmark/configs/tiny-resnet.json", "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": LM, "config": "tiny-lm", "traffic": "tiny-lm", "chips": 1, "why": "t"},
        {"name": FIT, "config": "tiny-resnet", "traffic": "tiny-fit", "chips": 1,
         "why": "t"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [names[w] for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location("bench_run_under_test",
                                                  b / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


@pytest.fixture(autouse=True)
def tiny_resnet(monkeypatch):
    """The zoo's ResNet50 fixes its stages in a class attribute; the tiny
    configuration's stages go in through a subclass."""
    from drivers import cg_fit

    def build_net(config):
        from deeplearning4j_tpu.zoo.resnet import ResNet50

        class Tiny(ResNet50):
            STAGES = tuple((n, tuple(f)) for n, f in config["stages"])
        return Tiny(num_classes=config["num_classes"],
                    input_shape=tuple(config["input_shape"]),
                    compute_dtype=jnp.dtype(config["compute_dtype"])).init()

    monkeypatch.setattr(cg_fit, "build_net", build_net)
    return build_net


def _run(run, capsys, workload, trace=0, seed=2147483659):
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace)],
                  require=lambda chips, peaks: jax.devices())
    out, err = capsys.readouterr()
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(result)
    assert list(result)[-1] == "compared"
    assert err.strip().splitlines()[-1] == \
        f"correct: {json.dumps(result['correct'])}"
    return result


@pytest.mark.parametrize("workload,e2e", [(LM, "lm_train_tokens_per_s"),
                                          (FIT, "fit_samples_per_s")])
def test_sound_run_is_correct_and_prints_the_contract_keys(
        checkout, capsys, workload, e2e):
    r = _run(checkout, capsys, workload)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {e2e, "setup_s"}
    assert all(set(m) == {"value", "unit"} for m in r["metrics"].values())
    assert set(r["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    compared = {k: c for k, c in r["compared"].items() if c["limit"] is not None}
    assert len(compared) == 2
    assert all(c["value"] <= c["limit"] for c in compared.values())


@pytest.mark.parametrize("workload", [LM, FIT])
def test_traced_run_reports_per_layer_metrics_it_can_read(
        checkout, capsys, workload):
    r = _run(checkout, capsys, workload, trace=1)
    suffix = ".lm" if workload == LM else ".fit"
    # no TPU plane in a CPU trace: the trace readers find nothing and say
    # nothing; the counters are there
    assert f"compiles_in_window{suffix}" in r["metrics"]
    assert r["metrics"][f"compiles_in_window{suffix}"]["value"] == 0
    assert f"idle_pct{suffix}" not in r["metrics"]
    assert f"mfu_pct{suffix}" not in r["metrics"]      # no peak for a CPU
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_refuses_without_a_chip(checkout, capsys):
    with pytest.raises(SystemExit) as e:
        checkout.main(["--workload", LM, "--seed", "1", "--seconds", "1"])
    assert e.value.code == 3
    assert capsys.readouterr().out == ""


# ------------------------------------------------------------ the faults --

def _lm_fault(monkeypatch, fault):
    from drivers import lm_train
    real = lm_train.build_step

    def build_step(cfg, config):
        opt, step = real(cfg, config)
        inner = step.__wrapped__

        def unchanged(params, opt_state, ids, tgt):
            return params, opt_state, inner(params, opt_state, ids, tgt)[2]

        def half(params, opt_state, ids, tgt):
            n = ids.shape[0] // 2
            return inner(params, opt_state, ids[:n], tgt[:n])

        return opt, jax.jit({"unchanged": unchanged, "half": half}[fault],
                            donate_argnums=(0, 1))

    monkeypatch.setattr(lm_train, "build_step", build_step)


def _fit_fault(monkeypatch, fault, build_tiny):
    from drivers import cg_fit

    def build_net(config):
        net = build_tiny(config)
        get_real = net._get_train_step

        def get_step():
            inner = get_real().__wrapped__

            def unchanged(params, states, opt_state, inputs, labels, rng, fm, lm):
                out = inner(params, states, opt_state, inputs, labels, rng, fm, lm)
                return (params, states, opt_state) + tuple(out[3:])

            def half(params, states, opt_state, inputs, labels, rng, fm, lm):
                cut = lambda d: {k: v[: v.shape[0] // 2] for k, v in d.items()}
                return inner(params, states, opt_state, cut(inputs),
                             cut(labels), rng, fm, lm)

            if not hasattr(net, "_broken_step"):
                net._broken_step = jax.jit(
                    {"unchanged": unchanged, "half": half}[fault])
            return net._broken_step

        net._get_train_step = get_step
        return net

    monkeypatch.setattr(cg_fit, "build_net", build_net)


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_lm_fault_is_not_correct(checkout, capsys, monkeypatch, fault):
    _lm_fault(monkeypatch, fault)
    r = _run(checkout, capsys, LM)
    assert r["correct"] is False
    failing = [k for k, c in r["compared"].items()
               if c["limit"] is not None and c["value"] > c["limit"]]
    assert failing, r["compared"]
    if fault == "unchanged":
        assert r["compared"]["delta_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_fit_fault_is_not_correct(checkout, capsys, monkeypatch, tiny_resnet,
                                  fault):
    _fit_fault(monkeypatch, fault, tiny_resnet)
    r = _run(checkout, capsys, FIT)
    assert r["correct"] is False
    if fault == "unchanged":
        assert r["compared"]["delta_norm_gap_global"]["value"] == \
            pytest.approx(1.0)
    else:
        assert r["compared"]["grad_norm_gap_global"]["value"] > 0.1


# ----------------------------------------------------------- the control --

@pytest.mark.parametrize("workload", [LM, FIT])
def test_float8_control_is_not_correct(checkout, workload):
    """The reference put in the program's place, computed in float8, against
    the reference itself: it has to fail the comparison."""
    import importlib

    import compare
    run = checkout
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    _, config, traffic = run.find_cell(bench, workload)
    driver = importlib.import_module(f"drivers.{traffic['driver']}")
    st = driver.setup(config, traffic, 3, run.Probe(False, traffic))
    driver.release(st)
    want = driver.reference_readings(st)
    limits = run.load_json(run.HERE / "limits" / f"{workload}.json")
    ok, _ = compare.judge(compare.training_gaps(st.readings, want), limits)
    assert ok
    control = driver.reference_readings(st, product=driver.CONTROL_PRODUCT)
    ok, compared = compare.judge(compare.training_gaps(control, want), limits)
    assert not ok, compared
