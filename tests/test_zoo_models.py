"""Zoo model smoke tests (SURVEY.md §2.7): every model builds, forwards
with the right output shape at reduced input size, and the detection /
segmentation heads train a step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import zoo


def _forward(model, x):
    net = model.init()
    return net, net.output(jnp.asarray(x))


def test_tiny_yolo_builds_and_fits():
    m = zoo.TinyYOLO(num_classes=3, input_shape=(64, 64, 3))
    net = m.init()
    x = np.random.default_rng(0).standard_normal((1, 64, 64, 3)).astype(np.float32)
    y = net.output(jnp.asarray(x))
    # 64 -> /32 = 2x2 grid, 5 anchors * (5+3)
    assert y.shape == (1, 2, 2, 5 * 8)
    lab = np.zeros((1, 2, 2, 4 + 3), np.float32)
    lab[0, 1, 1, :4] = [1.1, 1.2, 1.9, 1.8]
    lab[0, 1, 1, 4] = 1.0
    from deeplearning4j_tpu.data import DataSet
    l0 = net.fit(DataSet(jnp.asarray(x), jnp.asarray(lab)))
    assert np.isfinite(l0)


def test_yolo2_passthrough_shapes():
    m = zoo.YOLO2(num_classes=4, input_shape=(64, 64, 3))
    net, y = _forward(m, np.zeros((1, 64, 64, 3), np.float32))
    assert y.shape == (1, 2, 2, 5 * (5 + 4))


def test_unet_shapes_and_fit():
    m = zoo.UNet(input_shape=(64, 64, 3))
    net = m.init()
    x = np.random.default_rng(0).standard_normal((1, 64, 64, 3)).astype(np.float32)
    y = net.output(jnp.asarray(x))
    assert y.shape == (1, 64, 64, 1)
    assert np.all((np.asarray(y) >= 0) & (np.asarray(y) <= 1))  # sigmoid
    from deeplearning4j_tpu.data import DataSet
    mask = (np.random.default_rng(1).random((1, 64, 64, 1)) > 0.5).astype(np.float32)
    l0 = net.fit(DataSet(jnp.asarray(x), jnp.asarray(mask)))
    assert np.isfinite(l0)


def test_xception_small():
    m = zoo.Xception(num_classes=7, input_shape=(71, 71, 3))
    net, y = _forward(m, np.zeros((1, 71, 71, 3), np.float32))
    assert y.shape == (1, 7)
    assert np.allclose(np.asarray(y).sum(), 1.0, atol=1e-4)


def test_inception_resnet_v1_small():
    m = zoo.InceptionResNetV1(num_classes=5, input_shape=(64, 64, 3),
                              blocks_a=1, blocks_b=1, blocks_c=1)
    net, y = _forward(m, np.zeros((1, 64, 64, 3), np.float32))
    assert y.shape == (1, 5)


def test_facenet_nn4_small():
    m = zoo.FaceNetNN4Small2(num_classes=5, input_shape=(64, 64, 3))
    net, y = _forward(m, np.zeros((1, 64, 64, 3), np.float32))
    assert y.shape == (1, 5)


def test_nasnet_small():
    m = zoo.NASNet(num_classes=6, input_shape=(32, 32, 3),
                   penultimate_filters=96, cells_per_stack=1)
    net, y = _forward(m, np.zeros((1, 32, 32, 3), np.float32))
    assert y.shape == (1, 6)


def test_squeezenet_and_darknet_build():
    net, y = _forward(zoo.SqueezeNet(num_classes=4, input_shape=(67, 67, 3)),
                      np.zeros((1, 67, 67, 3), np.float32))
    assert y.shape == (1, 4)
    net, y = _forward(zoo.Darknet19(num_classes=4, input_shape=(64, 64, 3)),
                      np.zeros((1, 64, 64, 3), np.float32))
    assert y.shape == (1, 4)


def test_text_generation_sampling():
    """Char-RNN sampling via streamed rnn_time_step: prime on a seed, sample
    greedily-ish, and verify the streamed distributions equal output() on
    the growing prefix (state correctness), not just shape."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo import TextGenerationLSTM

    zm = TextGenerationLSTM(num_classes=11, input_shape=(6, 11), units=16)
    net = zm.init()
    rng = np.random.default_rng(0)
    seed = np.eye(11, dtype=np.float32)[rng.integers(0, 11, (2, 4))]
    toks = zm.generate(net, seed, n_steps=5, temperature=0.8)
    assert toks.shape == (2, 5)
    assert int(toks.min()) >= 0 and int(toks.max()) < 11

    # state correctness: streamed prime distribution == full forward's last
    net.rnn_clear_previous_state()
    streamed = np.asarray(net.rnn_time_step(jnp.asarray(seed)))[:, -1]
    full = np.asarray(net.output(jnp.asarray(seed)))[:, -1]
    np.testing.assert_allclose(streamed, full, atol=1e-5)


def test_transformer_fused_loss_matches_naive():
    """Chunked fused cross-entropy == naive log_softmax loss (values and
    gradients), incl. non-dividing chunk sizes and tied embeddings."""
    from dataclasses import replace
    import jax
    from deeplearning4j_tpu.zoo import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=128, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=16,
                                dtype=jnp.float32, remat=False,
                                fused_loss=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 128)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 128)
    ref = float(tfm.lm_loss(params, cfg, ids, tgt))
    gref = jax.grad(lambda p: tfm.lm_loss(p, cfg, ids, tgt))(params)
    cfg_f = replace(cfg, fused_loss=True, loss_chunk=24)  # pad path
    got = float(tfm.lm_loss(params, cfg_f, ids, tgt))
    gfus = jax.grad(lambda p: tfm.lm_loss(p, cfg_f, ids, tgt))(params)
    assert abs(ref - got) < 1e-5
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                atol=2e-5), gref, gfus)
    cfg_t = replace(cfg, tie_embeddings=True, fused_loss=True, loss_chunk=16)
    cfg_tn = replace(cfg, tie_embeddings=True, fused_loss=False)
    pt = tfm.init_params(jax.random.PRNGKey(0), cfg_t)
    assert abs(float(tfm.lm_loss(pt, cfg_t, ids, tgt))
               - float(tfm.lm_loss(pt, cfg_tn, ids, tgt))) < 1e-5


def _naive_ce(x, head, targets, weights, bias):
    logits = x.astype(jnp.float32) @ head.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               targets[:, None], -1)[:, 0]
    return (nll if weights is None else nll * weights).sum()


@pytest.mark.parametrize("case", [
    "chunk_divides", "pad_path", "weights_with_zeros", "weights_and_bias",
    "bf16_operands", "cotangent_of_3", "inside_checkpoint",
    "jit_donated_arguments"])
def test_chunked_ce_value_and_every_cotangent_match_naive(case):
    """``_chunked_ce`` forms its gradient in its forward (a ``custom_vjp``):
    the value and the cotangent of every float operand equal the naive f32
    ``log_softmax`` form's, whatever wraps the call."""
    from deeplearning4j_tpu.zoo import transformer as tfm

    n, d, v = 64, 32, 100
    dtype = jnp.bfloat16 if case == "bf16_operands" else jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    ops = {"x": jax.random.normal(ks[0], (n, d)).astype(dtype),
           "head": (jax.random.normal(ks[1], (d, v)) / 6).astype(dtype)}
    targets = jax.random.randint(ks[2], (n,), 0, v)
    if case in ("weights_with_zeros", "weights_and_bias"):
        ops["weights"] = 1.5 * (jax.random.uniform(ks[3], (n,)) > 0.3)
        assert 0 < int((ops["weights"] == 0).sum()) < n
    if case == "weights_and_bias":
        ops["bias"] = jax.random.normal(ks[4], (v,))
    chunk = 24 if case == "pad_path" else 16
    scale = 3.0 if case == "cotangent_of_3" else 1.0

    def chunked(ops):
        return scale * tfm._chunked_ce(
            ops["x"], ops["head"], targets, chunk,
            weights=ops.get("weights"), bias=ops.get("bias"))

    def naive(ops):
        return scale * _naive_ce(ops["x"], ops["head"], targets,
                                 ops.get("weights"), ops.get("bias"))

    fn = jax.value_and_grad(
        jax.checkpoint(chunked) if case == "inside_checkpoint" else chunked)
    want, g_want = jax.value_and_grad(naive)(ops)
    if case == "jit_donated_arguments":
        fn = jax.jit(fn, donate_argnums=0)
        got, g_got = fn(jax.tree_util.tree_map(jnp.copy, ops))
    else:
        got, g_got = fn(ops)
    # the primal alone (nothing differentiates) is the same number
    assert float(chunked(ops)) == pytest.approx(float(got), rel=1e-6)
    # bf16: the gradients are rounded to bf16 once (2**-8 relative) and
    # dhead is carried in bf16 over 4 chunks; measured 4.7e-3 of the largest
    rel = 1e-2 if dtype == jnp.bfloat16 else 2e-6
    assert float(got) == pytest.approx(float(want), rel=1e-6 if
                                       dtype == jnp.float32 else 1e-3)
    assert set(g_got) == set(ops)
    for name in ops:
        a, b = (np.asarray(g[name], np.float32) for g in (g_got, g_want))
        assert g_got[name].dtype == ops[name].dtype, name
        assert np.abs(b).max() > 0, name
        assert np.abs(a - b).max() <= rel * np.abs(b).max(), name
    if case == "cotangent_of_3":
        _, g_one = jax.value_and_grad(
            lambda o: tfm._chunked_ce(o["x"], o["head"], targets, chunk))(ops)
        for name in ops:
            np.testing.assert_allclose(np.asarray(g_got[name]),
                                       3.0 * np.asarray(g_one[name]),
                                       rtol=1e-6)


def test_transformer_bf16_scores_attention_close_to_xla():
    """attn_scores_bf16: same math as the stock XLA path up to the bf16
    score quantization — outputs close, loss finite, grads flow."""
    from dataclasses import replace
    import jax
    from deeplearning4j_tpu.zoo import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=32,
                                dtype=jnp.bfloat16, remat=False,
                                fused_loss=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 64)
    cfg_b = replace(cfg, attn_scores_bf16=True)
    lf = float(tfm.lm_loss(params, cfg, ids, tgt))
    lb = float(tfm.lm_loss(params, cfg_b, ids, tgt))
    assert abs(lf - lb) / max(abs(lf), 1e-6) < 0.05, (lf, lb)
    logits_f, _ = tfm.forward(params, cfg, ids)
    logits_b, _ = tfm.forward(params, cfg_b, ids)
    np.testing.assert_allclose(np.asarray(logits_f, np.float32),
                               np.asarray(logits_b, np.float32),
                               atol=0.15, rtol=0.1)
    g = jax.grad(lambda p: tfm.lm_loss(p, cfg_b, ids, tgt))(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    # causality: future-token perturbation cannot change earlier logits
    ids2 = ids.at[:, -1].set((ids[:, -1] + 1) % 64)
    l2, _ = tfm.forward(params, cfg_b, ids2)
    np.testing.assert_allclose(np.asarray(logits_b, np.float32)[:, :-1],
                               np.asarray(l2, np.float32)[:, :-1],
                               atol=1e-4)


def test_resnet50_s2d_stem_exact_equivalence():
    """r4 TPU stem optimization: space-to-depth(2) input + folded 4x4x12
    stem kernel computes the bit-identical function of the 7x7/s2 SAME
    stem (MLPerf-style equivalent transformation)."""
    import numpy as np
    from deeplearning4j_tpu.zoo.resnet import (ResNet50,
                                               fold_stem_weights_s2d)

    std = ResNet50(num_classes=10, input_shape=(64, 64, 3), seed=5).init()
    s2d = ResNet50(num_classes=10, input_shape=(64, 64, 3), seed=5,
                   stem_space_to_depth=True).init()
    for name, p in std.params.items():
        if name == "stem_conv":
            s2d.params[name]["W"] = fold_stem_weights_s2d(p["W"])
        else:
            for k, v in p.items():
                s2d.params[name][k] = v
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 64, 64, 3)),
                    jnp.float32)
    o1 = np.asarray(std.output(x))
    o2 = np.asarray(s2d.output(x))
    assert np.abs(o1 - o2).max() < 2e-5


def test_resnet50_remat_segments_plumbing():
    """ResNet50(remat_segments=n) reaches the CG attribute, the segment
    plan covers the whole 224-node graph with single-tensor boundaries,
    and the remat train loss equals the monolithic one (small input)."""
    import numpy as np
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    net = ResNet50(num_classes=10, input_shape=(32, 32, 3), seed=3,
                   remat_segments=8).init()
    assert net.remat_segments == 8
    plan = net._segment_plan(8, ["in"])
    flat = [nm for seg in plan for _, nm in seg["nodes"]]
    assert flat == list(net.conf.topo_order)
    assert max(len(s["carry_in"]) for s in plan) == 1  # residual-chain cuts

    plain = ResNet50(num_classes=10, input_shape=(32, 32, 3), seed=3).init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, 2)])
    l_rm, _ = net._loss(net.params, net.states, {"in": x}, {"out": y},
                        None, None, None)
    l_pl, _ = plain._loss(plain.params, plain.states, {"in": x}, {"out": y},
                          None, None, None)
    assert float(l_rm) == pytest.approx(float(l_pl), abs=1e-6)
