"""Job-level orchestration driver (VERDICT r4 missing item 3): the
Spark-scaleout analogue — SparkDl4jMultiLayer + ParameterAveragingTrainingMaster
over the socket hub: partitioning, averaging rounds, worker-failure
tolerance, between-round checkpointing, and a real 2-process run.
Reference: deeplearning4j-scaleout/spark TrainingMaster +
SparkDl4jMultiLayer.fit."""

import json
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import numpy as np
import pytest

# Slow: each job provisions a socket hub + N worker replicas with their
# own jitted fits (~20s/module) — outside the tier-1 truncation budget;
# runs in the full (slow-inclusive) suite. Tier-1 scaleout coverage
# (rounds, trace stitching, metrics) lives in tests/test_obs.py.
pytestmark = pytest.mark.slow

from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import (DenseLayer, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.parallel import (ParameterAveragingTrainingMaster,
                                         SparkDl4jMultiLayer)
from deeplearning4j_tpu.train import Sgd

REPO = Path(__file__).resolve().parent.parent


def _net(seed=11):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(5e-2))
            .list()
            .layer(DenseLayer(n_in=6, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n_batches=8, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        x = rng.normal(size=(batch, 6)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)]
        out.append(DataSet(x, y))
    return out


def test_spark_fit_runs_rounds_and_trains():
    net = _net()
    datasets = _data()
    x_all = np.concatenate([np.asarray(d.features) for d in datasets])
    y_all = np.concatenate([np.asarray(d.labels) for d in datasets])
    score0 = net.clone().score(DataSet(x_all, y_all))

    tm = ParameterAveragingTrainingMaster(
        n_workers=2, averaging_frequency=2, epochs_per_fit=3,
        worker_timeout=60.0)
    spark = SparkDl4jMultiLayer(net, tm)
    trained = spark.fit(datasets)
    assert trained is net
    assert spark.rounds >= 2          # 4 batches/worker × 3 epochs, freq 2
    assert spark.dropped_workers == []
    assert net.score(DataSet(x_all, y_all)) < score0


def test_spark_param_averaging_freq1_matches_sequential_two_workers():
    """freq=1 Sgd averaging == training on averaged gradients: with the
    SAME batch given to both workers, the averaged params equal one
    worker's params (both replicas walk identical trajectories) — the
    equivalence anchor the in-mesh ParameterAveragingTrainer also pins."""
    datasets = _data(n_batches=2, seed=3)
    same = [datasets[0], datasets[0]]    # worker 0 and 1 get THE SAME batch

    net = _net(seed=7)
    tm = ParameterAveragingTrainingMaster(
        n_workers=2, averaging_frequency=1, epochs_per_fit=1,
        worker_timeout=60.0)
    SparkDl4jMultiLayer(net, tm).fit(same)

    solo = _net(seed=7)
    solo.fit(datasets[0])
    np.testing.assert_allclose(np.asarray(net.params_flat()),
                               np.asarray(solo.params_flat()),
                               rtol=1e-6, atol=1e-7)


def test_spark_tolerates_worker_failure():
    net = _net()
    datasets = _data()
    tm = ParameterAveragingTrainingMaster(
        n_workers=2, averaging_frequency=2, epochs_per_fit=2,
        worker_timeout=15.0)
    spark = SparkDl4jMultiLayer(net, tm)
    with pytest.warns(UserWarning, match="failed mid-job"):
        spark.fit(datasets, fail_worker=1, fail_after_steps=1)
    assert spark.dropped_workers == [1]
    assert spark.rounds >= 1          # survivor kept averaging


def test_spark_all_workers_fail_raises():
    tm1 = ParameterAveragingTrainingMaster(
        n_workers=1, averaging_frequency=5, epochs_per_fit=1,
        worker_timeout=10.0)
    with pytest.raises(RuntimeError, match="no averaged parameters"):
        with pytest.warns(UserWarning):
            SparkDl4jMultiLayer(_net(), tm1).fit(
                _data(n_batches=2), fail_worker=0, fail_after_steps=1)


def test_spark_checkpoints_between_rounds_and_resume(tmp_path):
    net = _net()
    datasets = _data()
    tm = ParameterAveragingTrainingMaster(
        n_workers=2, averaging_frequency=2, epochs_per_fit=2,
        worker_timeout=60.0, checkpoint_dir=str(tmp_path / "ck"))
    spark = SparkDl4jMultiLayer(net, tm)
    spark.fit(datasets)
    ck = tmp_path / "ck"
    assert (ck / "latest.zip").exists()
    assert int((ck / "round.txt").read_text()) == spark.rounds

    # resume: restored net continues training through a fresh job
    from deeplearning4j_tpu.serde import ModelSerializer
    resumed = ModelSerializer.restore_multi_layer_network(str(ck / "latest.zip"))
    tm2 = ParameterAveragingTrainingMaster(
        n_workers=2, averaging_frequency=2, epochs_per_fit=1,
        worker_timeout=60.0)
    SparkDl4jMultiLayer(resumed, tm2).fit(datasets)


WORKER = textwrap.dedent("""
    import json, sys
    import numpy as np
    sys.path.insert(0, {repo!r})
    from deeplearning4j_tpu.data import DataSet
    from deeplearning4j_tpu.nn import (DenseLayer, MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.parallel import worker_main
    from deeplearning4j_tpu.train import Sgd

    port = int(sys.argv[1]); wid = int(sys.argv[2]); out = sys.argv[3]
    conf = (NeuralNetConfiguration.builder().seed(11).updater(Sgd(5e-2))
            .list()
            .layer(DenseLayer(n_in=6, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(wid)      # each process: its own partition
    ds = [DataSet(rng.normal(size=(16, 6)).astype("float32"),
                  np.eye(3, dtype="float32")[rng.integers(0, 3, 16)])
          for _ in range(4)]
    worker_main(("127.0.0.1", port), net, ds, averaging_frequency=2,
                epochs=1, worker_id=wid)
    np.savez(out, w=np.asarray(net.params_flat()))
""").format(repo=str(REPO))


@pytest.mark.slow
def test_two_process_spark_job(tmp_path):
    """Real process boundary: two subprocess workers + in-proc hub — the
    multi-host path (workers share nothing but the master address)."""
    from deeplearning4j_tpu.parallel import ParamAveragingHub

    hub = ParamAveragingHub(n_workers=2, worker_timeout=120.0).start()
    port = hub.address[1]
    procs, outs = [], []
    for wid in range(2):
        out = tmp_path / f"w{wid}.npz"
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", WORKER, str(port), str(wid), str(out)],
            cwd=str(REPO)))
    for p in procs:
        assert p.wait(timeout=300) == 0
    final = hub.result(timeout=30)
    assert final is not None and hub.rounds >= 2
    w0 = np.load(outs[0])["w"]
    w1 = np.load(outs[1])["w"]
    # both workers ended on the same averaged params (last round synced all)
    np.testing.assert_allclose(w0, w1, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# Fault-injection matrix (ISSUE 8 acceptance): each failure schedule
# completes the job, covers every partition, and lands within tolerance
# of the uninterrupted run's loss.
# ---------------------------------------------------------------------------

def _job_score(net, datasets):
    x = np.concatenate([np.asarray(d.features) for d in datasets])
    y = np.concatenate([np.asarray(d.labels) for d in datasets])
    return float(net.score(DataSet(x, y)))


_MATRIX_TM = dict(n_workers=4, averaging_frequency=2, epochs_per_fit=2,
                  worker_timeout=20.0)
# averaging over different (but complete) lease schedules is not
# bit-identical to the clean run — partial fits from the killed worker
# and reassignment reorderings shift the trajectory slightly
_LOSS_TOL = 0.15


def _clean_loss(datasets, **overrides):
    net = _net()
    tm = ParameterAveragingTrainingMaster(**{**_MATRIX_TM, **overrides})
    SparkDl4jMultiLayer(net, tm).fit(datasets)
    return _job_score(net, datasets)


def test_fault_matrix_worker_kill_rejoins_and_job_completes():
    """Kill one of four workers mid-job with re-provisioning on: the
    replacement rejoins under the same id, every partition is consumed,
    and the final loss matches the uninterrupted run within tolerance."""
    datasets = _data()
    clean = _clean_loss(datasets)
    net = _net()
    spark = SparkDl4jMultiLayer(
        net, ParameterAveragingTrainingMaster(**_MATRIX_TM))
    with pytest.warns(UserWarning, match="failed mid-job"):
        spark.fit(datasets, fail_worker=2, fail_after_steps=1,
                  respawn_failed=True)
    assert 2 in spark.dropped_workers
    assert spark.rejoins >= 1                 # the replacement re-attached
    counts = spark.lease_table.counts()
    assert spark.lease_table.all_done() and counts["leased"] == 0
    loss = _job_score(net, datasets)
    assert abs(loss - clean) < _LOSS_TOL, (loss, clean)


def test_fault_matrix_worker_kill_no_rejoin_leases_reassigned():
    """Kill one worker with NO replacement: its leases flow to the
    survivors — no partition is lost, loss stays within tolerance."""
    datasets = _data()
    clean = _clean_loss(datasets)
    net = _net()
    spark = SparkDl4jMultiLayer(
        net, ParameterAveragingTrainingMaster(**_MATRIX_TM))
    with pytest.warns(UserWarning, match="failed mid-job"):
        spark.fit(datasets, fail_worker=1, fail_after_steps=1)
    assert spark.dropped_workers == [1] and spark.rejoins == 0
    counts = spark.lease_table.counts()
    assert spark.lease_table.all_done() and counts["leased"] == 0
    assert counts["reassigned"] >= 1          # survivors took the orphans
    loss = _job_score(net, datasets)
    assert abs(loss - clean) < _LOSS_TOL, (loss, clean)


def test_fault_matrix_master_kill_restart_from_checkpoint(tmp_path):
    """Kill the master between rounds: fit raises MasterDiedError leaving
    the interrupted-job stamp; a second fit against the same
    checkpoint_dir resumes (params + round numbering + lease table),
    completes the remaining partitions, and clears the stamp."""
    from deeplearning4j_tpu.parallel import MasterDiedError, read_resume_state

    datasets = _data()
    clean = _clean_loss(datasets,
                        checkpoint_dir=str(tmp_path / "ck_clean"))
    ck = tmp_path / "ck"
    kwargs = dict(_MATRIX_TM, checkpoint_dir=str(ck), worker_timeout=10.0,
                  worker_retries=2, worker_backoff=0.1)
    net = _net()
    spark = SparkDl4jMultiLayer(net, ParameterAveragingTrainingMaster(**kwargs))
    with pytest.raises(MasterDiedError):
        spark.fit(datasets, fail_master_after_rounds=1)
    stamp = read_resume_state(ck)
    assert stamp is not None and stamp[0] == spark.rounds >= 1
    assert not spark.lease_table.all_done()   # the job IS interrupted

    net2 = _net(seed=99)     # params come from the checkpoint, not seed
    spark2 = SparkDl4jMultiLayer(net2,
                                 ParameterAveragingTrainingMaster(**kwargs))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")       # late drops of run-1 ghosts
        spark2.fit(datasets)
    assert spark2.resumed
    assert spark2.rounds > spark.rounds       # round numbering continued
    assert spark2.lease_table.all_done()
    # union of run 1's checkpointed completions and run 2's covers all —
    # run 2 started from exactly the items the stamp recorded
    assert not (ck / "leases.json").exists()  # completed job clears stamp
    assert int((ck / "round.txt").read_text()) == spark2.rounds
    loss = _job_score(net2, datasets)
    assert abs(loss - clean) < _LOSS_TOL, (loss, clean)


def test_spark_computation_graph_alias_trains_cg():
    """SparkComputationGraph is the same driver — CG nets satisfy the
    clone/params_flat/fit contract."""
    from deeplearning4j_tpu.nn import (ComputationGraph,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel import SparkComputationGraph

    gb = (NeuralNetConfiguration.builder().seed(2).updater(Sgd(5e-2))
          .graph_builder()
          .add_inputs("in")
          .add_layer("d", DenseLayer(n_in=6, n_out=8, activation="tanh"),
                     "in")
          .add_layer("out", OutputLayer(n_in=8, n_out=3,
                                        activation="softmax", loss="mcxent"),
                     "d")
          .set_outputs("out"))
    cg = ComputationGraph(gb.build()).init([(6,)])
    datasets = _data(n_batches=4)
    x = np.concatenate([np.asarray(d.features) for d in datasets])
    y = np.concatenate([np.asarray(d.labels) for d in datasets])
    s0 = cg.clone().score(DataSet(x, y))
    tm = ParameterAveragingTrainingMaster(
        n_workers=2, averaging_frequency=2, epochs_per_fit=3,
        worker_timeout=60.0)
    spark = SparkComputationGraph(cg, tm)
    spark.fit(datasets)
    assert spark.rounds >= 1
    assert cg.score(DataSet(x, y)) < s0
