"""Parallelism tests on the 8-device virtual CPU mesh (SURVEY.md §4):
dp fit == single-device fit; ring attention == full attention;
pipeline loss == single-device loss; fsdp sharding round-trips."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

# Whole module is slow: every test compiles multi-device XLA programs on
# the 8-way virtual CPU mesh (~7 min total) — far past the tier-1
# truncation budget. Run explicitly or via the full (slow-inclusive)
# suite; the cheap telemetry-level parallel coverage lives in
# tests/test_obs.py.
pytestmark = pytest.mark.slow

from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh, shard_params_fsdp
from deeplearning4j_tpu.parallel.pipeline import (make_pipeline_loss,
                                                  place_params_for_pipeline)
from deeplearning4j_tpu.parallel.ring_attention import ring_attention
from deeplearning4j_tpu.zoo import transformer as tfm


def test_mesh_spec_validation(devices8):
    mesh = make_mesh(dp=2, tp=4)
    assert mesh.shape == {"dp": 2, "tp": 4}
    with pytest.raises(ValueError):
        make_mesh(dp=3)
    with pytest.raises(ValueError):
        MeshSpec({"bogus": 8})


def test_dp_fit_matches_single_device(devices8):
    """ParallelWrapper (dp=8) reaches the same solution as 1-device fit."""
    from deeplearning4j_tpu.data import IrisDataSetIterator
    from deeplearning4j_tpu.nn import (DenseLayer, MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    from deeplearning4j_tpu.train import Sgd

    def build():
        conf = (NeuralNetConfiguration.builder().seed(5).updater(Sgd(0.5))
                .list()
                .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
                .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                                   loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init((4,))

    # 144 examples → divisible by 8; dp gradients == single-device gradients
    it = IrisDataSetIterator(batch_size=144, num_examples=144)
    single = build()
    single.fit(it, epochs=10)
    it.reset()
    par = build()
    pw = ParallelWrapper(par, mesh=make_mesh(dp=8))
    pw.fit(it, epochs=10)
    w_single = np.asarray(single.params["layer_0"]["W"])
    w_par = np.asarray(par.params["layer_0"]["W"])
    np.testing.assert_allclose(w_par, w_single, rtol=1e-4, atol=1e-5)


def test_ring_attention_exact(devices8):
    mesh = make_mesh(dp=2, sp=4)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((4, 32, 2, 8)).astype(np.float32))
               for _ in range(3))
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=True)
    got = ring_attention(mesh, q, k, v, causal=True)
    assert float(jnp.abs(ref - got).max()) < 2e-5
    # non-causal too
    ref2 = jax.nn.dot_product_attention(q, k, v, is_causal=False)
    got2 = ring_attention(mesh, q, k, v, causal=False)
    assert float(jnp.abs(ref2 - got2).max()) < 2e-5


def test_ring_attention_long_context(devices8):
    """SURVEY §7 long-context scale: exact at T=4096 (vs full attention)
    and a T=16384 run whose first sequence-block must equal LOCAL causal
    attention (causality masks every other block) — validates the ring at
    lengths where materializing the T² score matrix would be impossible
    on-device."""
    mesh = make_mesh(sp=8)
    rng = np.random.default_rng(1)

    t = 4096
    q, k, v = (jnp.asarray(rng.standard_normal((1, t, 2, 8)), jnp.float32)
               for _ in range(3))
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=True)
    got = ring_attention(mesh, q, k, v, causal=True)
    assert float(jnp.abs(ref - got).max()) < 5e-5

    t = 16384
    q, k, v = (jnp.asarray(rng.standard_normal((1, t, 1, 8)), jnp.float32)
               for _ in range(3))
    out = ring_attention(mesh, q, k, v, causal=True)
    assert out.shape == (1, t, 1, 8)
    assert bool(jnp.isfinite(out).all())
    blk = t // 8
    local = jax.nn.dot_product_attention(q[:, :blk], k[:, :blk], v[:, :blk],
                                         is_causal=True)
    assert float(jnp.abs(out[:, :blk] - local).max()) < 5e-5


def test_pipeline_matches_single(devices8):
    cfg = tfm.TransformerConfig(vocab_size=61, d_model=16, n_heads=2,
                                n_layers=4, d_ff=32, max_seq=8,
                                dtype=jnp.float32, remat=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 61)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, 61)
    ref = float(tfm.lm_loss(params, cfg, ids, tgt))
    mesh = make_mesh(pp=2, dp=2, tp=2)
    pp_params = place_params_for_pipeline(mesh, params)
    loss = float(make_pipeline_loss(mesh, cfg)(
        pp_params, ids.reshape(2, 2, 8), tgt.reshape(2, 2, 8)))
    assert abs(loss - ref) < 2e-4, (loss, ref)


def test_tp_sharded_step_matches_single(devices8):
    """dp2×tp2×sp2 jitted train step computes the same loss as 1 device."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=16, n_heads=2,
                                n_layers=2, d_ff=32, max_seq=8,
                                dtype=jnp.float32, remat=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 64)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, 64)
    ref = float(tfm.lm_loss(params, cfg, ids, tgt))
    mesh = make_mesh(dp=2, tp=2, sp=2)
    sh = tfm.shardings_for(mesh, cfg)
    p_sh = jax.tree_util.tree_map(jax.device_put, params, sh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    dsh = NamedSharding(mesh, P("dp", "sp"))
    loss = float(jax.jit(lambda p, i, t: tfm.lm_loss(p, cfg, i, t))(
        p_sh, jax.device_put(ids, dsh), jax.device_put(tgt, dsh)))
    assert abs(loss - ref) < 2e-4, (loss, ref)


def test_fsdp_sharding(devices8):
    mesh = make_mesh(fsdp=8)
    params = {"big": jnp.zeros((16, 1024 * 16)), "small": jnp.zeros((4,))}
    sh = shard_params_fsdp(mesh, params)
    placed = jax.tree_util.tree_map(jax.device_put, params, sh)
    # big is sharded (each device holds 1/8), small replicated
    assert placed["big"].sharding.spec == jax.sharding.PartitionSpec(None, "fsdp")
    assert placed["small"].sharding.spec == jax.sharding.PartitionSpec()


def test_moe_forward_and_balance():
    cfg = tfm.TransformerConfig(vocab_size=61, d_model=16, n_heads=2,
                                n_layers=2, d_ff=32, max_seq=8, n_experts=4,
                                expert_top_k=2, dtype=jnp.float32, remat=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 61)
    logits, aux = tfm.forward(params, cfg, ids)
    assert logits.shape == (4, 8, 61)
    assert float(aux) > 0.0  # load-balance loss is live


def test_parameter_averaging_freq1_sgd_matches_sync_dp():
    """averaging params after ONE local Sgd step == stepping on the
    averaged gradient: freq=1 ParameterAveragingTrainer must equal the
    synchronous ParallelWrapper result (ParameterAveragingTrainingMaster
    semantics check)."""
    from deeplearning4j_tpu.parallel import (ParameterAveragingTrainer,
                                             ParallelWrapper, make_mesh)
    from deeplearning4j_tpu.train import Sgd
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator

    def build():
        conf = (NeuralNetConfiguration.builder().seed(11).updater(Sgd(5e-2))
                .list()
                .layer(DenseLayer(n_in=6, n_out=16, activation="tanh"))
                .layer(OutputLayer(n_in=16, n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(6))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(3)
    X = rng.standard_normal((64, 6)).astype(np.float32)
    Y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 64)]
    # 8 microbatches of 8: one param-avg round at freq=1 over dp=8 equals
    # one sync step on the concatenated batch ONLY for linear updaters —
    # compare against ParallelWrapper stepping per microbatch group
    it1 = ListDataSetIterator([DataSet(X[i * 8:(i + 1) * 8],
                                       Y[i * 8:(i + 1) * 8])
                               for i in range(8)], batch_size=8)
    net_pa = build()
    pa = ParameterAveragingTrainer(net_pa, mesh=make_mesh(dp=8),
                                   averaging_frequency=1)
    pa.fit(it1, epochs=1)
    assert pa._round is not None   # the shard_map ROUND ran, not the tail

    net_pw = build()
    pw = ParallelWrapper(net_pw, mesh=make_mesh(dp=8))
    # same data as ONE sharded batch of 64 (dp=8 x 8 per shard): gradient
    # mean over the whole batch == mean of the 8 microbatch gradients
    it2 = ListDataSetIterator([DataSet(X, Y)], batch_size=None)
    pw.fit(it2, epochs=1)

    for k in net_pa.params:
        for name in net_pa.params[k]:
            np.testing.assert_allclose(
                np.asarray(net_pa.params[k][name]),
                np.asarray(net_pw.params[k][name]), rtol=2e-4, atol=2e-5)


def test_parameter_averaging_freq_gt1_converges():
    from deeplearning4j_tpu.parallel import ParameterAveragingTrainer, make_mesh
    from deeplearning4j_tpu.train import Adam
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator

    conf = (NeuralNetConfiguration.builder().seed(4).updater(Adam(2e-2))
            .list()
            .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(5)
    X = rng.standard_normal((128, 4)).astype(np.float32)
    W = rng.standard_normal((4, 3))
    Y = np.eye(3, dtype=np.float32)[(X @ W).argmax(1)]
    batches = [DataSet(X[i * 8:(i + 1) * 8], Y[i * 8:(i + 1) * 8])
               for i in range(16)]   # 16 = one round of dp8 * freq2
    it = ListDataSetIterator(batches, batch_size=8)
    pa = ParameterAveragingTrainer(net, mesh=make_mesh(dp=8),
                                   averaging_frequency=2)
    from deeplearning4j_tpu.data.dataset import DataSet as DS
    s0 = net.score(DS(X, Y))
    for _ in range(15):
        pa.fit(it, epochs=1)
    assert net.score(DS(X, Y)) < s0 * 0.5
    # replicas were averaged back into a single consistent copy
    out = net.output(X)
    assert out.shape == (128, 3)


def test_parameter_averaging_respects_label_masks():
    """Masked DataSets must flow into the local steps (not be dropped):
    training with a labels mask that zeroes half the timesteps must give
    different parameters than training with the mask ignored."""
    from deeplearning4j_tpu.parallel import ParameterAveragingTrainer, make_mesh
    from deeplearning4j_tpu.train import Sgd
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration,
                                       RnnOutputLayer, SimpleRnn)
    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator

    def build():
        conf = (NeuralNetConfiguration.builder().seed(2).updater(Sgd(5e-2))
                .list()
                .layer(SimpleRnn(n_in=3, n_out=8, activation="tanh"))
                .layer(RnnOutputLayer(n_in=8, n_out=2, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(InputType.recurrent(3, 6))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(7)
    X = rng.standard_normal((64, 6, 3)).astype(np.float32)
    Y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (64, 6))]
    M = np.zeros((64, 6), np.float32)
    M[:, :3] = 1.0
    mk = lambda use_mask: ListDataSetIterator(  # noqa: E731
        [DataSet(X[i*8:(i+1)*8], Y[i*8:(i+1)*8],
                 labels_mask=M[i*8:(i+1)*8] if use_mask else None)
         for i in range(8)], batch_size=8)

    net_m = build()
    ParameterAveragingTrainer(net_m, mesh=make_mesh(dp=8),
                              averaging_frequency=1).fit(mk(True), epochs=1)
    net_u = build()
    ParameterAveragingTrainer(net_u, mesh=make_mesh(dp=8),
                              averaging_frequency=1).fit(mk(False), epochs=1)
    w_m = np.asarray(net_m.params["layer_1"]["W"])
    w_u = np.asarray(net_u.params["layer_1"]["W"])
    assert not np.allclose(w_m, w_u), "labels mask was silently dropped"


# ------------------------------------------------- r3: generic tp / pp ----
def _tp_mlp(cls1, cls2, seed=7):
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.train import Adam
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .list()
            .layer(cls1(n_in=32, n_out=64, activation="relu"))
            .layer(cls2(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init((32,))


def test_tp_mln_matches_single_device(devices8):
    """VERDICT r2 item 4: Column/RowParallelDense in a user-built MLN under
    dp2 x tp2 track the single-device trajectory exactly, with W actually
    tp-sharded."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn import DenseLayer
    from deeplearning4j_tpu.parallel import (ColumnParallelDense,
                                             ParallelWrapper,
                                             RowParallelDense, make_mesh)

    rng = np.random.default_rng(0)
    X = rng.random((64, 32), np.float32)
    Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 64)]
    ds = DataSet(jnp.asarray(X), jnp.asarray(Y))

    net1 = _tp_mlp(DenseLayer, DenseLayer)
    losses1 = [net1.fit(ds) for _ in range(5)]

    net2 = _tp_mlp(ColumnParallelDense, RowParallelDense)
    pw = ParallelWrapper(net2, mesh=make_mesh(jax.devices()[:4], dp=2, tp=2))
    losses2 = [pw.fit([ds]) for _ in range(5)]
    np.testing.assert_allclose(losses1, losses2, atol=1e-5)
    spec = net2.params["layer_0"]["W"].sharding.spec
    assert tuple(spec) == (None, "tp"), spec
    spec1 = net2.params["layer_1"]["W"].sharding.spec
    assert spec1 and spec1[0] == "tp", spec1  # jax drops trailing Nones


def test_tp_computation_graph_matches_single_device(devices8):
    """A ComputationGraph MLP under dp2 x tp2: network_param_shardings
    resolves node-keyed params; the jitted loss matches single-device."""
    from deeplearning4j_tpu.nn.computation_graph import (
        ComputationGraphConfiguration)
    from deeplearning4j_tpu.nn import NeuralNetConfiguration, OutputLayer
    from deeplearning4j_tpu.parallel import (ColumnParallelDense,
                                             RowParallelDense, make_mesh,
                                             network_param_shardings)
    from deeplearning4j_tpu.train import Adam
    from jax.sharding import NamedSharding, PartitionSpec as P

    g = (NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-3))
         .graph_builder()
         .add_inputs("in")
         .add_layer("h1", ColumnParallelDense(n_in=16, n_out=32,
                                              activation="relu"), "in")
         .add_layer("h2", RowParallelDense(n_out=16, activation="relu"), "h1")
         .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                       loss="mcxent"), "h2")
         .set_outputs("out")
         .build())
    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
    net = ComputationGraph(g).init([(16,)])

    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.random((32, 16), np.float32))
    Y = jnp.asarray(np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)])
    inputs = {"in": X}
    labels = {"out": Y}
    ref = float(net._loss(net.params, net.states, inputs, labels,
                          None, None, None)[0])

    mesh = make_mesh(jax.devices()[:4], dp=2, tp=2)
    shardings = network_param_shardings(mesh, net)
    assert tuple(shardings["h1"]["W"].spec) == (None, "tp")
    assert tuple(shardings["h2"]["W"].spec) == ("tp", None)
    params = jax.tree_util.tree_map(jax.device_put, net.params, shardings)
    batch_sh = NamedSharding(mesh, P("dp"))
    X_sh = jax.device_put(X, batch_sh)
    Y_sh = jax.device_put(Y, batch_sh)

    @jax.jit
    def loss_fn(params, x, y):
        return net._loss(params, net.states, {"in": x}, {"out": y},
                         None, None, None)[0]

    got = float(loss_fn(params, X_sh, Y_sh))
    np.testing.assert_allclose(got, ref, atol=1e-5)
    # gradients flow and stay sharded
    g2 = jax.jit(jax.grad(loss_fn))(params, X_sh, Y_sh)
    assert np.isfinite(float(jnp.abs(g2["h1"]["W"]).sum()))


def test_tp_sharded_attention_compiles(devices8):
    """ShardedSelfAttention (Megatron head sharding) runs under tp2 and
    matches the unsharded layer's output."""
    from deeplearning4j_tpu.nn import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.base import Ctx
    from deeplearning4j_tpu.parallel import ShardedSelfAttention, make_mesh
    from deeplearning4j_tpu.parallel.tp import layer_param_shardings

    layer = ShardedSelfAttention(n_in=16, n_out=16, n_heads=4)
    params, state, _ = layer.init(jax.random.PRNGKey(0), (6, 16))
    x = jnp.asarray(np.random.default_rng(0).random((4, 6, 16), np.float32))
    ref, _ = SelfAttentionLayer.apply(layer, params, state, x, Ctx())

    mesh = make_mesh(jax.devices()[:2], tp=2)
    sh = layer_param_shardings(mesh, layer, params)
    assert tuple(sh["Wq"].spec) == (None, "tp")
    p_sh = jax.tree_util.tree_map(jax.device_put, params, sh)
    got, _ = jax.jit(lambda p, x: layer.apply(p, state, x, Ctx()))(p_sh, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def _pp_mlp():
    from deeplearning4j_tpu.nn import (DenseLayer, MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.train import Adam
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_in=16, n_out=48, activation="relu"))
            .layer(DenseLayer(n_out=24, activation="relu"))
            .layer(DenseLayer(n_out=24, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init((16,))


def test_generic_pipeline_partitioner_balance():
    from deeplearning4j_tpu.parallel import partition_layers
    net = _pp_mlp()
    stages = partition_layers(net, 2)
    assert [i for s in stages for i in s] == [0, 1, 2, 3]
    assert all(s for s in stages)
    with pytest.raises(ValueError):
        partition_layers(net, 9)


def test_generic_pipeline_loss_matches_single_device(devices8):
    """VERDICT r2 item 4: the generic MLN pipeline (pp2, and pp2 x dp2)
    reproduces the single-device loss exactly and trains."""
    from deeplearning4j_tpu.parallel import (make_mln_pipeline_loss,
                                             make_mln_pipeline_train_step,
                                             make_mesh, microbatches)

    net = _pp_mlp()
    rng = np.random.default_rng(0)
    X = rng.random((32, 16), np.float32)
    Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 32)]
    x_mb, y_mb = microbatches(X, Y, 8)
    ref = np.mean([float(net._loss(net.params, net.states,
                                   jnp.asarray(x_mb[i]), jnp.asarray(y_mb[i]),
                                   None, None, None)[0]) for i in range(4)])

    mesh = make_mesh(jax.devices()[:2], pp=2)
    loss_fn = make_mln_pipeline_loss(mesh, net, microbatch=8)
    pl = float(loss_fn(net.params, jnp.asarray(x_mb), jnp.asarray(y_mb)))
    np.testing.assert_allclose(pl, ref, atol=1e-5)

    mesh4 = make_mesh(jax.devices()[:4], pp=2, dp=2)
    loss4 = make_mln_pipeline_loss(mesh4, net, microbatch=8)
    pl4 = float(loss4(net.params, jnp.asarray(x_mb), jnp.asarray(y_mb)))
    np.testing.assert_allclose(pl4, ref, atol=1e-5)

    opt = optax.adam(1e-2)
    step = make_mln_pipeline_train_step(mesh, net, opt, microbatch=8)
    p, o = jax.tree_util.tree_map(jnp.copy, net.params), opt.init(net.params)
    first = last = None
    for _ in range(10):
        p, o, l = step(p, o, jnp.asarray(x_mb), jnp.asarray(y_mb))
        first = first if first is not None else float(l)
        last = float(l)
    assert last < first


def test_tp_row_sharded_embedding(devices8):
    """RowShardedEmbedding: vocab-sharded table matches the unsharded
    lookup through a jitted step on a tp mesh."""
    from deeplearning4j_tpu.nn.layers.base import Ctx
    from deeplearning4j_tpu.parallel import (RowShardedEmbeddingSequence,
                                             make_mesh)
    from deeplearning4j_tpu.parallel.tp import layer_param_shardings

    layer = RowShardedEmbeddingSequence(n_in=32, n_out=12)
    params, state, _ = layer.init(jax.random.PRNGKey(0), (6,))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 32, (4, 6)))
    ref, _ = layer.apply(params, state, ids, Ctx())

    mesh = make_mesh(jax.devices()[:4], tp=4)
    sh = layer_param_shardings(mesh, layer, params)
    assert tuple(sh["W"].spec) == ("tp", None)
    p_sh = jax.tree_util.tree_map(jax.device_put, params, sh)
    got, _ = jax.jit(lambda p: layer.apply(p, state, ids, Ctx()))(p_sh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


def test_tp_channel_sharded_conv_pair(devices8):
    """ChannelSharded (column) ⊗ InputChannelSharded (row) conv pairing
    matches the unsharded stack — the CNN analogue of Megatron f/g."""
    from deeplearning4j_tpu.nn.layers.base import Ctx
    from deeplearning4j_tpu.parallel import (ChannelShardedConvolution,
                                             InputChannelShardedConvolution,
                                             make_mesh)
    from deeplearning4j_tpu.parallel.tp import layer_param_shardings

    c1 = ChannelShardedConvolution(n_out=8, kernel_size=(3, 3),
                                   convolution_mode="same",
                                   activation="relu")
    c2 = InputChannelShardedConvolution(n_out=4, kernel_size=(3, 3),
                                        convolution_mode="same",
                                        activation="identity")
    p1, s1, shape1 = c1.init(jax.random.PRNGKey(0), (8, 8, 3))
    p2, s2, _ = c2.init(jax.random.PRNGKey(1), shape1)
    x = jnp.asarray(np.random.default_rng(0).random((2, 8, 8, 3), np.float32))

    def fwd(p1_, p2_, x_):
        h, _ = c1.apply(p1_, s1, x_, Ctx())
        y, _ = c2.apply(p2_, s2, h, Ctx())
        return y

    ref = fwd(p1, p2, x)
    mesh = make_mesh(jax.devices()[:2], tp=2)
    sh1 = layer_param_shardings(mesh, c1, p1)
    sh2 = layer_param_shardings(mesh, c2, p2)
    assert tuple(sh1["W"].spec) == (None, None, None, "tp")
    assert tuple(sh2["W"].spec) == (None, None, "tp", None)
    p1s = jax.tree_util.tree_map(jax.device_put, p1, sh1)
    p2s = jax.tree_util.tree_map(jax.device_put, p2, sh2)
    got = jax.jit(fwd)(p1s, p2s, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

    # depthwise/grouped row-sharding is rejected loudly
    bad = InputChannelShardedConvolution(n_out=4, kernel_size=(3, 3),
                                         groups=2)
    pb, sb, _ = bad.init(jax.random.PRNGKey(2), (8, 8, 4))
    with pytest.raises(ValueError, match="group"):
        layer_param_shardings(mesh, bad, pb)


def _pp_bn_net():
    from deeplearning4j_tpu.nn import (BatchNormalization, DenseLayer,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.train import Adam
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(BatchNormalization())
            .layer(DenseLayer(n_out=12, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init((8,))


def test_generic_pipeline_batchnorm(devices8):
    """Pipeline v2 (VERDICT r3 item 6): BatchNorm inside the generic
    pipeline — loss AND running stats match the sequential microbatched
    loop (GPipe per-microbatch BN semantics)."""
    from deeplearning4j_tpu.nn.layers.base import Ctx
    from deeplearning4j_tpu.parallel import (make_mln_pipeline_loss,
                                             make_mln_pipeline_train_step,
                                             make_mesh, microbatches)
    net = _pp_bn_net()
    rng = np.random.default_rng(0)
    X = rng.random((16, 8), np.float32)
    Y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)]
    x_mb, y_mb = microbatches(X, Y, 4)

    # sequential oracle: run microbatches one by one, carrying BN stats
    states = net.states
    losses = []
    for m in range(4):
        loss, states = net._loss(net.params, states, jnp.asarray(x_mb[m]),
                                 jnp.asarray(y_mb[m]), None, None, None)
        losses.append(float(loss))
    ref_loss = float(np.mean(losses))

    mesh = make_mesh(jax.devices()[:2], pp=2)
    loss_fn = make_mln_pipeline_loss(mesh, net, microbatch=4)
    pl, new_states = loss_fn(net.params, net.states, jnp.asarray(x_mb),
                             jnp.asarray(y_mb))
    np.testing.assert_allclose(float(pl), ref_loss, atol=1e-5)
    for key in states:
        for leaf_name, want in states[key].items():
            got = new_states[key][leaf_name]
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=f"{key}.{leaf_name}")

    # stateful train step runs and the loss decreases
    opt = optax.adam(1e-2)
    step = make_mln_pipeline_train_step(mesh, net, opt, microbatch=4)
    p = jax.tree_util.tree_map(jnp.copy, net.params)
    s = jax.tree_util.tree_map(jnp.copy, net.states)
    o = opt.init(p)
    first = last = None
    for _ in range(10):
        p, s, o, l = step(p, s, o, jnp.asarray(x_mb), jnp.asarray(y_mb))
        first = first if first is not None else float(l)
        last = float(l)
    assert last < first
    # stats actually moved
    assert not np.allclose(np.asarray(s["layer_1"]["mean"]),
                           np.asarray(net.states["layer_1"]["mean"]))


def test_cg_pipeline_linear_chain(devices8):
    """make_cg_pipeline_train_step: a linear-chain ComputationGraph rides
    the generic pipeline; loss matches the CG's own loss on the same data,
    and a branchy CG is rejected loudly."""
    from deeplearning4j_tpu.nn import (DenseLayer, NeuralNetConfiguration,
                                       OutputLayer)
    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
    from deeplearning4j_tpu.nn.vertices import MergeVertex
    from deeplearning4j_tpu.parallel import (make_cg_pipeline_train_step,
                                             make_mesh, microbatches)
    from deeplearning4j_tpu.train import Adam

    gb = (NeuralNetConfiguration.builder().seed(6).updater(Adam(1e-3))
          .graph_builder()
          .add_inputs("in")
          .add_layer("d1", DenseLayer(n_in=16, n_out=32, activation="relu"),
                     "in")
          .add_layer("d2", DenseLayer(n_out=16, activation="relu"), "d1")
          .add_layer("out", OutputLayer(n_out=4, activation="softmax",
                                        loss="mcxent"), "d2")
          .set_outputs("out"))
    cg = ComputationGraph(gb.build()).init([(16,)])
    rng = np.random.default_rng(0)
    X = rng.random((16, 16), np.float32)
    Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]
    x_mb, y_mb = microbatches(X, Y, 4)
    mesh = make_mesh(jax.devices()[:2], pp=2)
    opt = optax.adam(1e-2)
    step, view = make_cg_pipeline_train_step(mesh, cg, opt, microbatch=4)
    p, o = jax.tree_util.tree_map(jnp.copy, view.params), \
        opt.init(view.params)
    first = last = None
    for _ in range(10):
        p, o, l = step(p, o, jnp.asarray(x_mb), jnp.asarray(y_mb))
        first = first if first is not None else float(l)
        last = float(l)
    assert last < first
    # round-trip the keys back onto the graph
    back = view.to_graph(p)
    assert set(back) == {"d1", "d2", "out"}

    # branchy CG rejected
    gb2 = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-3))
           .graph_builder()
           .add_inputs("in")
           .add_layer("a", DenseLayer(n_in=16, n_out=8, activation="relu"),
                      "in")
           .add_layer("b", DenseLayer(n_in=16, n_out=8, activation="relu"),
                      "in")
           .add_vertex("m", MergeVertex(), "a", "b")
           .add_layer("out", OutputLayer(n_out=4, activation="softmax",
                                         loss="mcxent"), "m")
           .set_outputs("out"))
    cg2 = ComputationGraph(gb2.build()).init([(16,)])
    with pytest.raises(ValueError, match="linear chain|layer chain"):
        make_cg_pipeline_train_step(mesh, cg2, opt, microbatch=4)


def test_generic_pipeline_pp_sharded_params(devices8):
    """shard_params_pp: at-rest 1/pp layout (ZeRO-3 over pp) feeds the same
    pipelined step and produces the same loss."""
    from deeplearning4j_tpu.parallel import (make_mln_pipeline_loss,
                                             make_mesh, microbatches,
                                             shard_params_pp)
    net = _pp_mlp()
    rng = np.random.default_rng(0)
    X = rng.random((32, 16), np.float32)
    Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 32)]
    x_mb, y_mb = microbatches(X, Y, 8)
    mesh = make_mesh(jax.devices()[:2], pp=2)
    loss_fn = make_mln_pipeline_loss(mesh, net, microbatch=8)
    ref = float(loss_fn(net.params, jnp.asarray(x_mb), jnp.asarray(y_mb)))

    p_sh = shard_params_pp(mesh, net.params, min_size=64)
    # the big W leaves really are partitioned over pp
    w0 = p_sh["layer_0"]["W"]
    assert "pp" in tuple(a for a in (w0.sharding.spec or ()) if a)
    got = float(loss_fn(p_sh, jnp.asarray(x_mb), jnp.asarray(y_mb)))
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_parallel_inference_does_not_mutate_net(devices8):
    """ParallelInference must not re-place the trainer's arrays (review
    finding, r3): a ParallelWrapper compiled on one mesh keeps working
    after a ParallelInference is built on another."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn import DenseLayer
    from deeplearning4j_tpu.parallel import (ColumnParallelDense,
                                             ParallelInference,
                                             ParallelWrapper,
                                             RowParallelDense, make_mesh)

    net = _tp_mlp(ColumnParallelDense, RowParallelDense)
    pw = ParallelWrapper(net, mesh=make_mesh(jax.devices()[:4], dp=2, tp=2))
    rng = np.random.default_rng(0)
    X = rng.random((16, 32), np.float32)
    Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]
    ds = DataSet(jnp.asarray(X), jnp.asarray(Y))
    pw.fit([ds])
    pi = ParallelInference(net, mesh=make_mesh(jax.devices()[4:8], dp=4))
    out = pi.output(X[:5])
    assert out.shape == (5, 4)
    # trainer still works on its own mesh after inference construction
    loss = pw.fit([ds])
    assert np.isfinite(loss)
    # refresh picks up newly trained params
    out2 = pi.refresh().output(X[:5])
    assert np.isfinite(out2).all()


def test_parallel_wrapper_pads_to_batch_axes_only(devices8):
    """Partial batches pad to the dp extent, not mesh.size (review finding,
    r3): a 6-row batch on dp2×tp2 needs no padding and must match the
    single-device loss."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.parallel import (ColumnParallelDense,
                                             ParallelWrapper,
                                             RowParallelDense, make_mesh)

    rng = np.random.default_rng(0)
    X = rng.random((6, 32), np.float32)
    Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 6)]
    ds = DataSet(jnp.asarray(X), jnp.asarray(Y))
    net1 = _tp_mlp(ColumnParallelDense, RowParallelDense)
    ref = float(net1._loss(net1.params, net1.states, jnp.asarray(X),
                           jnp.asarray(Y), None, None, None)[0])
    net2 = _tp_mlp(ColumnParallelDense, RowParallelDense)
    pw = ParallelWrapper(net2, mesh=make_mesh(jax.devices()[:4], dp=2, tp=2))
    loss = pw.fit([ds])
    np.testing.assert_allclose(loss, ref, atol=1e-5)


def test_sharded_attention_rejects_uneven_heads(devices8):
    from deeplearning4j_tpu.parallel import ShardedSelfAttention, make_mesh
    from deeplearning4j_tpu.parallel.tp import layer_param_shardings
    layer = ShardedSelfAttention(n_in=12, n_out=12, n_heads=3)
    params, _, _ = layer.init(jax.random.PRNGKey(0), (4, 12))
    with pytest.raises(ValueError, match="divisible by tp"):
        layer_param_shardings(make_mesh(jax.devices()[:2], tp=2),
                              layer, params)


def _small_cg(seed=7, remat=None):
    """Residual conv CG used by the ParallelWrapper/Inference CG tests."""
    from deeplearning4j_tpu.nn import (ActivationLayer, BatchNormalization,
                                       ComputationGraph, ConvolutionLayer,
                                       ElementWiseVertex, InputType,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.train import Sgd
    b = NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
    g = b.graph_builder().add_inputs("in")
    g.add_layer("c1", ConvolutionLayer(n_out=8, kernel_size=(3, 3),
                                       convolution_mode="same",
                                       activation="identity"), "in")
    g.add_layer("bn1", BatchNormalization(activation="relu"), "c1")
    g.add_layer("c2", ConvolutionLayer(n_out=8, kernel_size=(3, 3),
                                       convolution_mode="same",
                                       activation="identity"), "bn1")
    g.add_layer("bn2", BatchNormalization(activation="identity"), "c2")
    g.add_vertex("add", ElementWiseVertex(op="add"), "bn2", "bn1")
    g.add_layer("act", ActivationLayer(activation="relu"), "add")
    g.add_layer("out", OutputLayer(n_out=5, activation="softmax",
                                   loss="mcxent"), "act")
    g.set_outputs("out")
    g.set_input_types(InputType.convolutional(8, 8, 3))
    net = ComputationGraph(g.build()).init()
    net.remat_segments = remat
    return net


def test_parallel_wrapper_computation_graph(devices8):
    """ParallelWrapper is a drop-in for ComputationGraph.fit too (its array
    x/y calling convention must reach CG._loss — regression: dict(inputs)
    blew up on the raw batch array). dp-8 trajectory == single-device."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.standard_normal((64, 8, 8, 3)).astype(np.float32))
    Y = jnp.asarray(np.eye(5, dtype=np.float32)[rng.integers(0, 5, 64)])
    ds = DataSet(X, Y)
    single = _small_cg()
    for _ in range(4):
        single.fit([ds])
    par = _small_cg()
    pw = ParallelWrapper(par, mesh=make_mesh(dp=8))
    for _ in range(4):
        pw.fit([ds])
    for k in single.params:
        for pk, a in single.params[k].items():
            np.testing.assert_allclose(np.asarray(a),
                                       np.asarray(par.params[k][pk]),
                                       rtol=2e-4, atol=1e-5)


def test_parallel_wrapper_computation_graph_remat(devices8):
    """remat_segments composes with ParallelWrapper (checkpointed segments
    inside the dp-sharded jitted step)."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

    rng = np.random.default_rng(4)
    X = jnp.asarray(rng.standard_normal((32, 8, 8, 3)).astype(np.float32))
    Y = jnp.asarray(np.eye(5, dtype=np.float32)[rng.integers(0, 5, 32)])
    ds = DataSet(X, Y)
    plain = _small_cg()
    pw1 = ParallelWrapper(plain, mesh=make_mesh(dp=8))
    l1 = pw1.fit([ds])
    remat = _small_cg(remat=3)
    pw2 = ParallelWrapper(remat, mesh=make_mesh(dp=8))
    l2 = pw2.fit([ds])
    np.testing.assert_allclose(l1, l2, rtol=1e-6)


def test_parallel_inference_computation_graph(devices8):
    """ParallelInference serves a ComputationGraph (3-tuple _forward)."""
    from deeplearning4j_tpu.parallel import ParallelInference, make_mesh

    rng = np.random.default_rng(5)
    X = rng.standard_normal((24, 8, 8, 3)).astype(np.float32)
    net = _small_cg()
    want = np.asarray(net.output(jnp.asarray(X)))
    pi = ParallelInference(net, mesh=make_mesh(dp=8))
    got = pi.output(X)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_parallel_wrapper_multidataset_cg(devices8):
    """Multi-input/multi-output CG trains through ParallelWrapper with
    MultiDataSet batches (tuple features/labels reach CG._as_input_dict),
    and ParallelInference returns per-output arrays."""
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.nn import (ComputationGraph, DenseLayer,
                                       MergeVertex, NeuralNetConfiguration,
                                       OutputLayer)
    from deeplearning4j_tpu.parallel import (ParallelInference,
                                             ParallelWrapper, make_mesh)
    from deeplearning4j_tpu.train import Sgd

    def build():
        b = NeuralNetConfiguration.builder().seed(11).updater(Sgd(0.1))
        g = b.graph_builder().add_inputs("a", "b")
        g.add_layer("da", DenseLayer(n_in=6, n_out=8, activation="tanh"), "a")
        g.add_layer("db", DenseLayer(n_in=4, n_out=8, activation="tanh"), "b")
        g.add_vertex("m", MergeVertex(), "da", "db")
        g.add_layer("o1", OutputLayer(n_in=16, n_out=3, activation="softmax",
                                      loss="mcxent"), "m")
        g.add_layer("o2", OutputLayer(n_in=16, n_out=2, activation="softmax",
                                      loss="mcxent"), "m")
        g.set_outputs("o1", "o2")
        return ComputationGraph(g.build()).init([(6,), (4,)])

    rng = np.random.default_rng(0)
    xa = rng.standard_normal((32, 6)).astype(np.float32)
    xb = rng.standard_normal((32, 4)).astype(np.float32)
    y1 = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]
    y2 = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 32)]
    mds = MultiDataSet([xa, xb], [y1, y2])

    single = build()
    for _ in range(3):
        single.fit([mds])
    par = build()
    pw = ParallelWrapper(par, mesh=make_mesh(dp=8))
    for _ in range(3):
        pw.fit([mds])
    for k in single.params:
        for pk, a in single.params[k].items():
            np.testing.assert_allclose(np.asarray(a),
                                       np.asarray(par.params[k][pk]),
                                       rtol=2e-4, atol=1e-5)
    # multi-input serving + multi-output unpadding (24 rows pads to 32 on
    # dp=8): per-output arrays must match the net's own output()
    pi = ParallelInference(single, mesh=make_mesh(dp=8))
    got = pi.output([xa[:24], xb[:24]])
    want = single.output(jnp.asarray(xa[:24]), jnp.asarray(xb[:24]))
    assert isinstance(got, list) and len(got) == 2
    for g_arr, w_arr in zip(got, want):
        np.testing.assert_allclose(g_arr, np.asarray(w_arr), rtol=1e-5,
                                   atol=1e-6)


def test_ring_attention_flash_path_exact(devices8):
    """Ring with the flash-kernel local attention (interpret mode on CPU)
    == full attention, forward AND gradients. The grad check exercises the
    lse cotangent path of flash_attention_lse (the merge weights partials
    by exp(lse_i - lse), so dLSE is live)."""
    mesh = make_mesh(dp=2, sp=4)
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 32, 2, 8)), jnp.float32)
               for _ in range(3))

    for causal in (True, False):
        ref = jax.nn.dot_product_attention(q, k, v, is_causal=causal)
        got = ring_attention(mesh, q, k, v, causal=causal, use_flash=True,
                             interpret=True)
        assert float(jnp.abs(ref - got).max()) < 2e-5, causal

    def loss_ring(q_, k_, v_):
        return jnp.sum(ring_attention(mesh, q_, k_, v_, causal=True,
                                      use_flash=True, interpret=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(jax.nn.dot_product_attention(
            q_, k_, v_, is_causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        assert float(jnp.abs(a - b).max()) < 5e-4


def test_ring_attention_xla_path_grads(devices8):
    """The reworked XLA ring (out/lse merge + cond-skipped masked hops)
    matches full-attention gradients too."""
    mesh = make_mesh(dp=2, sp=4)
    rng = np.random.default_rng(8)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 32, 2, 8)), jnp.float32)
               for _ in range(3))

    def loss_ring(q_, k_, v_):
        return jnp.sum(ring_attention(mesh, q_, k_, v_, causal=True,
                                      use_flash=False) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(jax.nn.dot_product_attention(
            q_, k_, v_, is_causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        assert float(jnp.abs(a - b).max()) < 5e-5


@pytest.mark.parametrize("fused", [False, True],
                         ids=["unfused", "fused_loss"])
def test_ring_train_step_matches_monolithic(devices8, fused):
    """MODEL-level ring sequence parallelism: tfm.make_ring_train_step
    (full train step under shard_map over dp2 x sp4 — ring attention,
    global position offsets per sequence shard, pmean'd loss/grads)
    matches the monolithic single-device step: same loss, same updated
    params, for two consecutive steps. ``fused_loss``: the chunked head's
    ``custom_vjp`` inside ``shard_map`` (each shard's 16 rows in chunks of
    6, a pad chunk among them) against the monolithic UNFUSED step."""
    import dataclasses
    mesh = make_mesh(dp=2, sp=4)
    cfg = tfm.TransformerConfig(
        vocab_size=61, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq=32, dtype=jnp.float32, remat=False, fused_loss=fused,
        loss_chunk=6, use_ring_attention=True)
    cfg_mono = dataclasses.replace(cfg, use_ring_attention=False,
                                   fused_loss=False)
    rng = np.random.default_rng(11)
    ids = jnp.asarray(rng.integers(0, 61, (4, 32)))
    tgt = jnp.asarray(rng.integers(0, 61, (4, 32)))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(1e-2)

    ring_step = tfm.make_ring_train_step(cfg, opt, mesh)
    mono_step = jax.jit(tfm.make_train_step(cfg_mono, opt))

    # independent buffer copies: ring_step donates its params/opt_state
    p_r = jax.tree_util.tree_map(jnp.copy, params)
    p_m = jax.tree_util.tree_map(jnp.copy, params)
    o_r, o_m = opt.init(p_r), opt.init(p_m)
    for i in range(2):
        p_r, o_r, loss_r = ring_step(p_r, o_r, ids, tgt)
        p_m, o_m, loss_m = mono_step(p_m, o_m, ids, tgt)
        assert abs(float(loss_r) - float(loss_m)) < 1e-5, (i, loss_r, loss_m)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
        p_r, p_m)

    # config guards: ring flag required; MoE refuses loudly; a global T
    # past the position table is rejected instead of silently clamping
    with pytest.raises(ValueError):
        tfm.make_ring_train_step(cfg_mono, opt, mesh)
    with pytest.raises(NotImplementedError):
        tfm.make_ring_train_step(
            dataclasses.replace(cfg, n_experts=4), opt, mesh)
    with pytest.raises(ValueError, match="exceeds"):
        too_long = jnp.zeros((4, 64), jnp.int32)
        tfm.make_ring_train_step(cfg, opt, mesh)(p_r, o_r, too_long, too_long)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_fused_loss_with_the_head_sharded_by_rows(devices8, tied):
    """The chunked head's ``custom_vjp`` under GSPMD: with the table's
    vocabulary rows split over 'tp' (and the batch over dp, sp),
    ``lm_loss(fused_loss=True)`` gives the single-device loss and
    gradients."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=16, n_heads=2,
                                n_layers=2, d_ff=32, max_seq=8,
                                dtype=jnp.float32, remat=False,
                                fused_loss=True, loss_chunk=8,
                                tie_embeddings=tied)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 64)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, 64)
    fn = jax.value_and_grad(lambda p, i, t: tfm.lm_loss(p, cfg, i, t))
    want, g_want = fn(params, ids, tgt)
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh(dp=2, tp=2, sp=2)
    sh = tfm.shardings_for(mesh, cfg)
    assert sh["embed" if tied else "head"].spec in (P("tp", None),
                                                    P(None, "tp"))
    dsh = NamedSharding(mesh, P("dp", "sp"))
    got, g_got = jax.jit(fn)(
        jax.tree_util.tree_map(jax.device_put, params, sh),
        jax.device_put(ids, dsh), jax.device_put(tgt, dsh))
    assert abs(float(got) - float(want)) < 1e-5
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6),
        g_got, g_want)


def test_param_averaging_computation_graph(devices8):
    """ParameterAveragingTrainer drives a ComputationGraph (array x/y reach
    CG._loss via the normalization shim); MultiDataSet rejects loudly."""
    from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
    from deeplearning4j_tpu.parallel import (ParameterAveragingTrainer,
                                             make_mesh)

    rng = np.random.default_rng(12)
    X = rng.standard_normal((64, 8, 8, 3)).astype(np.float32)
    Y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 64)]
    net = _small_cg(seed=21)
    tr = ParameterAveragingTrainer(net, mesh=make_mesh(dp=8),
                                   averaging_frequency=1)
    loss = tr.fit([DataSet(X, Y)] * 4)
    assert loss is not None and np.isfinite(loss)

    mds = MultiDataSet([X, X], [Y])
    with pytest.raises(NotImplementedError, match="MultiDataSet"):
        tr.fit([mds] * 2)


def test_parallel_wrapper_fit_scanned_matches_fit(devices8):
    """ParallelWrapper.fit_scanned == ParallelWrapper.fit: same parameter
    trajectory (same step math, same rng chain), one dispatch per epoch."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn import (DenseLayer, MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh
    from deeplearning4j_tpu.train import Sgd

    def build():
        conf = (NeuralNetConfiguration.builder().seed(13).updater(Sgd(0.2))
                .list()
                .layer(DenseLayer(n_in=6, n_out=12, activation="tanh"))
                .layer(OutputLayer(n_in=12, n_out=3, activation="softmax",
                                   loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init((6,))

    rng = np.random.default_rng(2)
    dss = [DataSet(jnp.asarray(rng.standard_normal((16, 6)).astype(np.float32)),
                   jnp.asarray(np.eye(3, dtype=np.float32)[
                       rng.integers(0, 3, 16)]))
           for _ in range(4)]
    a = build()
    pw_a = ParallelWrapper(a, mesh=make_mesh(dp=8))
    for _ in range(3):
        pw_a.fit(dss)
    b = build()
    pw_b = ParallelWrapper(b, mesh=make_mesh(dp=8))
    last = pw_b.fit_scanned(dss, epochs=3)
    assert np.isfinite(last)
    for k in a.params:
        for pk, v in a.params[k].items():
            np.testing.assert_allclose(np.asarray(v),
                                       np.asarray(b.params[k][pk]),
                                       rtol=2e-5, atol=1e-6)

    # rejection: ragged shapes
    ragged = dss + [DataSet(jnp.zeros((8, 6)), jnp.zeros((8, 3)))]
    with pytest.raises(ValueError, match="equally-shaped"):
        pw_b.fit_scanned(ragged)
    # rejection: batch must divide the dp extent
    with pytest.raises(ValueError, match="divide"):
        pw_b.fit_scanned([DataSet(jnp.zeros((6, 6)), jnp.zeros((6, 3)))])
    # epochs=0 is a graceful no-op, like fit()
    assert pw_b.fit_scanned(dss, epochs=0) is None


def _constrained_mlp():
    from deeplearning4j_tpu.nn import (DenseLayer, MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.train import MaxNormConstraint, Sgd
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.5))
            .constrain_weights(MaxNormConstraint(0.5, dims=0))
            .list()
            .layer(DenseLayer(n_in=6, n_out=12, activation="tanh"))
            .layer(OutputLayer(n_in=12, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init((6,))


def _mlp_batches(n=4, rows=16):
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.default_rng(2)
    return [DataSet(jnp.asarray(rng.standard_normal((rows, 6)).astype(np.float32)),
                    jnp.asarray(np.eye(3, dtype=np.float32)[
                        rng.integers(0, 3, rows)]))
            for _ in range(n)]


def test_parallel_wrapper_applies_weight_constraints(devices8):
    """The dp step is the net's own step (ISSUE 30): a max-norm constraint
    holds under ParallelWrapper.fit, and the parameters end where the
    net's own fit leaves them."""
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

    dss = _mlp_batches()
    single = _constrained_mlp()
    single.fit(dss, epochs=3)
    par = _constrained_mlp()
    ParallelWrapper(par, mesh=make_mesh(dp=8)).fit(dss, epochs=3)
    for key in ("layer_0", "layer_1"):
        w = np.asarray(par.params[key]["W"])
        assert np.linalg.norm(w, axis=0).max() <= 0.5 + 1e-5
        # the constraint binds: the test would pass without it otherwise
        assert np.linalg.norm(w, axis=0).max() >= 0.5 - 1e-4
        for pk, v in single.params[key].items():
            np.testing.assert_allclose(np.asarray(par.params[key][pk]),
                                       np.asarray(v), rtol=2e-4, atol=1e-5)


def test_parallel_wrapper_calls_on_epoch_end(devices8):
    """Once an epoch, after that epoch's last iteration_done, with the
    wrapped net as the model, like the net's own fit."""
    from deeplearning4j_tpu.nn.listeners import TrainingListener
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

    class Order(TrainingListener):
        def __init__(self):
            self.events = []

        def iteration_done(self, model, iteration, epoch, score):
            self.events.append(("iteration", iteration, epoch))

        def on_epoch_end(self, model):
            self.events.append(("epoch_end", model._step_count,
                                model.epoch_count))

    dss = _mlp_batches(n=3)
    want = []
    for fit_of in (lambda net: net.fit,
                   lambda net: ParallelWrapper(net, mesh=make_mesh(dp=8)).fit):
        net = _constrained_mlp()
        net.set_listeners(Order())
        fit_of(net)(dss, epochs=2)
        want.append(net.listeners[0].events)
    assert want[0] == want[1] == (
        [("iteration", i, 0) for i in (1, 2, 3)] + [("epoch_end", 3, 1)]
        + [("iteration", i, 1) for i in (4, 5, 6)] + [("epoch_end", 6, 2)])


def test_generic_pipeline_dropout_rng(devices8):
    """Dropout in the generic pipeline: rng engages per-microbatch masks
    (loss changes vs rng=None and varies across keys); rng=None keeps the
    old deterministic behavior; dropout=0 nets ignore the key entirely."""
    from deeplearning4j_tpu.nn import (DenseLayer, MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.parallel import make_mln_pipeline_loss, make_mesh

    def build(dropout):
        conf = (NeuralNetConfiguration.builder().seed(9)
                .list()
                .layer(DenseLayer(n_in=12, n_out=24, activation="relu"))
                .layer(DenseLayer(n_out=24, activation="relu",
                                  dropout=dropout))
                .layer(DenseLayer(n_out=12, activation="relu"))
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init((12,))

    mesh = make_mesh(jax.devices()[:2], pp=2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 8, 12)), jnp.float32)
    y = jnp.asarray(np.eye(4, dtype=np.float32)[
        rng.integers(0, 4, (4, 8))])

    net = build(dropout=0.5)
    loss_fn = make_mln_pipeline_loss(mesh, net, microbatch=8)
    base = float(loss_fn(net.params, x, y))
    la = float(loss_fn(net.params, x, y, jax.random.PRNGKey(1)))
    lb = float(loss_fn(net.params, x, y, jax.random.PRNGKey(2)))
    assert la != base and lb != base and la != lb

    # gradient flows through the dropout path
    g = jax.grad(lambda p: loss_fn(p, x, y, jax.random.PRNGKey(1)))(
        net.params)
    assert any(float(jnp.abs(l).max()) > 0
               for l in jax.tree_util.tree_leaves(g))

    # a dropout-free net gives the same loss with and without a key
    net0 = build(dropout=0.0)
    fn0 = make_mln_pipeline_loss(mesh, net0, microbatch=8)
    np.testing.assert_allclose(
        float(fn0(net0.params, x, y)),
        float(fn0(net0.params, x, y, jax.random.PRNGKey(3))), rtol=1e-6)
