"""The Qwen3-Next-style model at tiny widths on the CPU: the program
(``zoo.transformer`` with gated DeltaNet layers beside a gated attention
layer in one period, softmax-routed experts of which a share is held, a gated
shared expert, zero-centred norms) against the benchmark's plain reference
(``benchmark/reference/qwen3_next.py``, which imports nothing of the package
and runs the delta rule token by token), on seeded weights."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from drivers import qwen3next_train                              # noqa: E402
from reference import qwen3_next as ref                          # noqa: E402

from deeplearning4j_tpu.kernels import gated_delta                # noqa: E402
from deeplearning4j_tpu.zoo import transformer as tfm            # noqa: E402


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def tiny(**over) -> dict:
    """A configuration file's keys at a tiny width: a period of three gated
    DeltaNet layers (2 key heads of 4, 4 value heads of 4, 4 taps) and one
    attention layer (2 heads of 8 on 1 K/V head, a quarter of each head
    rotated); 8 experts published of which 3 (ids 2-4) are held, top-3, of
    width 6, and a shared one of 6; width 32."""
    config = dict(
        hidden_size=32, num_attention_heads=2, num_key_value_heads=1,
        head_dim=8, partial_rotary_factor=0.25, full_attention_interval=4,
        num_hidden_layers=4, linear_num_key_heads=2, linear_key_head_dim=4,
        linear_num_value_heads=4, linear_value_head_dim=4,
        linear_conv_kernel_dim=4, intermediate_size=40,
        moe_intermediate_size=6, shared_expert_intermediate_size=6,
        num_experts=3, first_expert_held=2, num_experts_per_tok=3,
        published={"num_experts": 8}, norm_topk_prob=True,
        decoder_sparse_step=1, mlp_only_layers=[], rope_scaling=None,
        use_sliding_window=False, rope_theta=1e4, rms_norm_eps=1e-6,
        max_position_embeddings=128, vocab_size=50,
        tie_word_embeddings=False, compute_dtype="float32",
        param_dtype="float32",
        program={"fused_loss": True, "remat": True,
                 "remat_policy": "save_attn", "loss_chunk": 16})
    config.update(over)
    return config


def _both(config):
    return ref.sizes_of(config), qwen3next_train.program_config(config)


def _weights(seed, sz, noise=0.1):
    """The reference's draw with every leaf moved off its initial value, so
    that the norm scales are all live."""
    def moved(tree):
        leaves, tree = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
        return tree.unflatten([a + noise * jax.random.normal(k, a.shape)
                               for a, k in zip(leaves, keys)])

    return jax.jit(moved)(ref.make_weights(seed, sz))


def _batch(sz, seed=3, batch=1, seq=16):
    ids, tgt = ref.make_batches(seed, 1, batch, seq, sz["vocab"])
    return jnp.asarray(ids[0]), jnp.asarray(tgt[0])


def _leaves(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def _grads(cfg, sz):
    """(program, reference) value_and_grad, one compile a side; the
    reference's aux is the assignments it was handed that its own top-k
    lacks."""
    prog = jax.jit(jax.value_and_grad(lambda p, i, t: tfm._lm_loss_stats(
        p, cfg, i, t), has_aux=True))

    def ref_loss(p, i, t, c):
        rows = ref.unstack(p, sz)
        return ref.row_loss(rows, i[0], t[0], sz, choices=c[:, :, 0])

    return prog, jax.jit(jax.value_and_grad(ref_loss, has_aux=True))


def test_loss_and_every_gradient_leaf_match_the_reference():
    """Two periods (the g-th DeltaNet layer reads row g of the gdn_* leaves
    and the a-th attention layer row a of attention's, in both trees) at 80
    positions (two of the program's chunks of 64, the second padded), a
    share of the experts. Within 1e-5 of the loss and 1e-3 of the worst
    leaf's largest entry: float32 on both sides, the program's delta rule
    chunk-wise and the reference's token by token, which sum in other orders
    (read here: under 2e-6 and 3e-5)."""
    sz, cfg = _both(tiny(num_hidden_layers=8))
    params = _weights(3, sz)
    ids, tgt = _batch(sz, seq=80)
    prog, want = _grads(cfg, sz)
    (got, told), g_got = prog(params, ids, tgt)
    took = told["choices"].reshape(sz["layers"], sz["top_k"], *ids.shape)
    (l_want, other), g_want = want(params, ids, tgt, took)
    assert abs(float(got) - float(l_want)) <= 1e-5 * abs(float(l_want))
    got_l, want_l = _leaves(g_got), _leaves(g_want)
    assert sorted(got_l) == sorted(want_l)
    for name, w in want_l.items():
        gap = float(jnp.max(jnp.abs(got_l[name] - w))) / float(
            jnp.max(jnp.abs(w)))
        assert gap <= 1e-3, (name, gap)
    load = np.asarray(told["load"])
    assert load.shape == (sz["layers"], 4) and (load[:, 2] == 0).all()
    # handed the program's choices, the float32 reference finds its own
    # top-k agreeing
    assert float(other) == 0.0


#: (B, T, dtype of q, k, v, id suffix) of the kernel's cases against the
#: recurrence; the first keeps the ids the rule's test had before it was cut
#: into cases
RULE_SHAPES = [(1, 37, jnp.float32, ""), (2, 200, jnp.float32, "-b2_t200"),
               (2, 200, jnp.bfloat16, "-b2_t200_bf16")]


@pytest.mark.parametrize(
    "strong,b,t,dtype",
    [(s, b, t, d) for b, t, d, _ in RULE_SHAPES for s in (False, True)],
    ids=[n + x for *_, x in RULE_SHAPES for n in ("weak", "strongest")])
def test_chunked_rule_matches_the_recurrence(strong, b, t, dtype):
    """``kernels.gated_delta`` (interpret mode, chunks of 64) over sequences
    that are no multiple of 64 (T 37: one padded chunk; T 200: four, the
    last padded), one and two sequences, two value heads a key head,
    against the reference's token-by-token scan in float32, at a weak decay
    and at the strongest the draw allows (A_log = log 16, a large: exp of
    the cumulative decay underflows inside a chunk, which a form with exp(G)
    and exp(-G) apart would turn into inf * 0).

    float32 q, k, v: within 1e-5 of the largest output, 1e-4 of each
    largest gradient. bfloat16 q, k, v (as training feeds them; both sides
    read the same rounded values): every product then takes bf16 operands
    in one pass, within 2.5e-2 of each largest. Readings at B 2, T 200,
    seeds 7 to 9 (output and the five gradients): the kernel 3.2e-3 to
    1.47e-2; the chunked XLA rule it replaced, its products' operands
    rounded to bf16 as the chip's default precision does, 2.9e-3 to 1.86e-2;
    the recurrence with every product in scaled float8
    (``reference.lowprec.FP8``, the benchmark's control) 4.7e-2 to 0.156."""
    key = jax.random.split(jax.random.PRNGKey(7), 6)
    hk, hv, dk, dv = 2, 4, 4, 5

    def draw(i, shape):     # in the dtype the rule reads, the reference too
        return jax.random.normal(key[i], shape).astype(dtype).astype(
            jnp.float32)

    q = draw(0, (b, t, hk, dk))                     # the rule norms them
    k = draw(1, (b, t, hk, dk))
    v = draw(2, (b, t, hv, dv))
    beta = jax.nn.sigmoid(jax.random.normal(key[3], (b, t, hv)))
    a = jax.random.normal(key[4], (b, t, hv)) + (30.0 if strong else -4.0)
    g = -(16.0 if strong else 0.1) * jax.nn.softplus(a + 1.0)
    w = jax.random.normal(key[5], (b, t, hv, dv))

    def with_grads(f):
        out, pull = jax.vjp(f, q, k, v, g, beta)
        return out, pull(w)

    def recurrence(q, k, *x):
        return jax.vmap(lambda q, k, *x: ref.recurrence(
            ref._unit(q) / np.sqrt(dk), ref._unit(k), *x))(q, k, *x)

    def kernel(q, k, v, g, beta):
        qkv = jnp.concatenate([a.reshape(b, t, -1) for a in (q, k, v)], -1)
        return gated_delta.gated_delta_rule(
            qkv.astype(dtype), g, beta, hk, dk, dv).reshape(b, t, hv, dv)

    want, g_ref = jax.jit(lambda: with_grads(recurrence))()
    got, g_got = jax.jit(lambda: with_grads(kernel))()
    exact = dtype == jnp.float32
    top = float(jnp.max(jnp.abs(want)))
    assert got.shape == want.shape and top > 0.1
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=(1e-5 if exact else 2.5e-2) * top)
    for mine, theirs in zip(g_got, g_ref):
        assert bool(jnp.all(jnp.isfinite(mine)))
        np.testing.assert_allclose(mine, theirs, rtol=0, atol=(
            1e-4 if exact else 2.5e-2) * float(jnp.max(jnp.abs(theirs))))


def test_the_shares_parts_add_up_to_the_uncut_layer():
    """The share test: the routed parts of two shares (experts 0-3
    and 4-7 of 8) sum to what the uncut program gives, and with the
    gated DeltaNet mixer and the gated shared expert (held whole by every
    share) counted once, to the uncut layer of the reference."""
    full_sz, full_cfg = _both(tiny(num_experts=8, first_expert_held=0))
    blk = ref.unstack(_weights(11, full_sz), full_sz)["layers"][0]  # DeltaNet
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 32), jnp.float32)

    @jax.jit
    def alike(blk):     # what every share computes whole
        x1 = x + tfm._gated_deltanet(full_cfg,
                                     tfm._norm(full_cfg, x, blk["ln1"]), blk)
        u = tfm._norm(full_cfg, x1, blk["ln2"])
        shared = tfm._dense_mlp(full_cfg, u, blk["ws_in"], blk["ws_out"]) \
            * jax.nn.sigmoid(u @ blk["ws_gate"])
        return x1, u, shared

    def routed(cfg, u, w_in, w_out):
        chosen, weight = tfm._route_top_k(
            cfg, tfm._router_logits(u, blk["router"]))
        return tfm._moe_share(cfg, u, chosen, weight, w_in, w_out)[:2]

    routed = jax.jit(routed, static_argnums=0)
    x1, u, shared = alike(blk)
    y_full, stats_full = routed(full_cfg, u, blk["we_in"], blk["we_out"])
    y_sum, local = 0.0, 0.0
    for share in range(2):
        cfg = _both(tiny(num_experts=4, first_expert_held=4 * share))[1]
        mine = slice(4 * share, 4 * share + 4)
        y, stats = routed(cfg, u, blk["we_in"][mine], blk["we_out"][mine])
        y_sum, local = y_sum + y, local + float(stats[1])
    np.testing.assert_allclose(y_sum, y_full, rtol=1e-6, atol=1e-6)
    want_x, _ = jax.jit(lambda b: ref.layer_fn(x[0], b, full_sz))(blk)
    np.testing.assert_allclose((x1 + y_sum + shared)[0], want_x,
                               rtol=1e-5, atol=1e-5)
    assert local == 24 * 3 == float(stats_full[1])


def test_init_params_draws_the_tree_the_reference_draws():
    sz, cfg = _both(tiny())
    mine = _leaves(jax.jit(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                   cfg))())
    theirs = _leaves(ref.make_weights(0, sz))
    assert {k: (v.shape, v.dtype) for k, v in mine.items()} == \
        {k: (v.shape, v.dtype) for k, v in theirs.items()}
    assert mine["['blocks']['wqkv']"].shape == (1, 32, 2 * 16 + 2 * 8)
    assert mine["['blocks']['gdn_wqkvz']"].shape == (3, 32, 2 * 8 + 2 * 16)
    assert mine["['blocks']['ws_gate']"].shape == (4, 32, 1)
    for tree in (mine, theirs):
        for name, a in tree.items():
            if "['ln" in name or name.endswith("_norm']") \
                    and "gdn" not in name:
                assert float(jnp.max(jnp.abs(a))) == 0.0, name  # 1 + w
            if name.endswith(("['gdn_norm']", "['gdn_dt_bias']")):
                assert float(jnp.min(a)) == float(jnp.max(a)) == 1.0, name
            if name.endswith("['gdn_a_log']"):
                assert float(jnp.max(a)) <= np.log(16.0)
    assert sorted(_leaves(tfm.param_pspecs(cfg))) == sorted(mine)
    norms = jax.eval_shape(lambda: ref.stacked_norms(
        ref.unstack(ref.make_weights(0, sz), sz)))
    assert sorted(norms) == sorted(theirs)


def test_the_published_share_counts_what_the_configuration_file_says():
    config = json.loads((BENCH / "configs" /
                         "qwen3-next-80b-a3b.json").read_text())
    sz, cfg = _both(config)
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert f"{n:,}" in config["held_here"]["parameters"]
    theirs = jax.eval_shape(lambda: ref.make_weights(0, sz))
    assert _leaves(jax.tree_util.tree_map(lambda a: a.shape, shapes)) == \
        _leaves(jax.tree_util.tree_map(lambda a: a.shape, theirs))
    assert cfg.layer_kinds == (("none", 0, "gated_deltanet"),) * 3 + (
        ("rope", 0, "attention"),)
    assert cfg.head_dim == 256 and cfg.rotary_dims == 64
    assert cfg.experts_held == (0, 32) and cfg.n_experts == 512
    assert cfg.vocab_size * 8 == config["published"]["vocab_size"]


def test_the_linear_router_tells_its_choices():
    """Under the linear router the step tells the experts every token took,
    as under every other router: a top-10 of 512 ties within bf16's rounding
    as any top-k does."""
    sz, cfg = _both(tiny())
    params = jax.eval_shape(lambda: ref.make_weights(5, sz))
    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    told = jax.eval_shape(lambda p, i: tfm._lm_loss_stats(
        p, cfg, i, i)[1], params, ids)
    assert sorted(told) == ["choices", "load", "moved"]
    assert told["choices"].shape == (4, 3, 32)
    assert told["choices"].dtype == jnp.int32


GDN = dict(layer_mixers=("gated_deltanet", "attention"),
           layer_positions=("none", "rope"), layer_windows=(0, 0),
           gdn_key_heads=2, gdn_value_heads=4, gdn_key_size=4,
           gdn_value_size=4, n_kv_heads=1, head_size=8, mlp="swiglu",
           n_experts=8, expert_top_k=2, experts_held=(0, 4), expert_ff=6,
           shared_experts=1, shared_expert_gate=True, qk_norm=True,
           attn_output_gate=True, norm_zero_centred=True)


@pytest.mark.parametrize("fields,error", [
    (dict(gdn_value_heads=3), ValueError),
    (dict(gdn_key_size=0), ValueError),
    (dict(layer_positions=("rope", "rope")), ValueError),
    (dict(layer_windows=(4, 0)), ValueError),
    (dict(layer_mixers=("mamba", "attention")), ValueError),
    (dict(use_ring_attention=True), NotImplementedError),
    (dict(attention="cca"), NotImplementedError),
    (dict(n_layers=6, dense_layers=2), NotImplementedError),
    (dict(predict_ahead=1), NotImplementedError),
    (dict(router="sigmoid"), NotImplementedError),
    (dict(router="mlp", router_hidden=4, expert_top_k=1),
     NotImplementedError),
    (dict(shared_experts=0), NotImplementedError),
], ids=["value_heads", "no_key_size", "rope_in_gdn", "window_in_gdn",
        "unknown_mixer", "ring", "cca_beside", "dense_layers", "mtp",
        "sigmoid_gate", "mlp_gate", "gate_without_shared"])
def test_fields_no_code_computes_are_refused(fields, error):
    cfg = tfm.TransformerConfig(**{"vocab_size": 50, "d_model": 32,
                                   "n_heads": 2, "n_layers": 4, "d_ff": 40,
                                   "max_seq": 16, **GDN, **fields})
    with pytest.raises(error):
        tfm._check(cfg)


def test_the_reference_refuses_what_neither_side_implements():
    for key, value in (("decoder_sparse_step", 2), ("mlp_only_layers", [1]),
                       ("norm_topk_prob", False),
                       ("rope_scaling", {"factor": 2}),
                       ("use_sliding_window", True),
                       ("shared_expert_intermediate_size", 8),
                       ("num_hidden_layers", 6)):
        with pytest.raises(ValueError, match="qwen3_next"):
            ref.sizes_of(tiny(**{key: value}))


def test_serving_and_pipeline_refuse_the_recurrent_layer_by_name():
    from deeplearning4j_tpu.parallel import pipeline
    from deeplearning4j_tpu.serving import GenerationEngine
    cfg = tfm.TransformerConfig(vocab_size=50, d_model=32, n_heads=2,
                                n_layers=2, d_ff=16, max_seq=16,
                                **{k: v for k, v in GDN.items()
                                   if not k.startswith(("n_exp", "expert",
                                                        "shared"))})
    with pytest.raises(NotImplementedError, match="gated_deltanet"):
        GenerationEngine(cfg, None)     # refused before it reads a weight
    with pytest.raises(NotImplementedError, match="gated_deltanet"):
        pipeline._stage_loss_fn(cfg, 2)


def test_every_costly_operation_of_the_step_sits_under_a_scope():
    """As ``test_step_scopes.py`` holds the other LM families: every product,
    gather, scatter and kernel of the step's jaxpr is under a scope of the
    table; the DeltaNet's own parts nest in ``attn_core`` (gdn_conv,
    gdn_rule, gdn_norm), its products in ``attn_qkv`` and ``attn_wo``, the
    shared expert's gate in ``moe_shared``."""
    import optax
    from test_step_scopes import COSTLY, TABLE, _equations, _scopes_of
    sz, cfg = _both(tiny())
    params = jax.eval_shape(lambda: ref.make_weights(5, sz))
    ids = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    opt = optax.adamw(1e-3)
    step = tfm.make_train_step(cfg, opt)
    eqns = list(_equations(jax.make_jaxpr(step)(
        params, jax.eval_shape(opt.init, params), ids, ids).jaxpr))
    names = TABLE + tfm.KERNEL_SCOPES
    costly = [(p, s) for p, s in eqns if p in COSTLY]
    assert len(costly) >= 10
    for prim, stack in costly:
        assert _scopes_of(stack, names), (prim, stack)
    gdn = {c for _, s in eqns for c in _scopes_of(s, ["gdn_"])}
    assert gdn == {"gdn_conv", "gdn_rule", "gdn_norm"}
    for _, stack in eqns:
        if _scopes_of(stack, ["gdn_"]):
            assert _scopes_of(stack, ["attn_core"]), stack
        assert len(_scopes_of(stack, [n for n in names if n != "mtp"])) <= 1
    assert any("moe_shared" in _scopes_of(s, ["moe_"]) for _, s in eqns)
