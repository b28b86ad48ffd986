"""The GLM-4.7-Flash-style model (PR 34) at tiny widths on the CPU: the
program (``zoo.transformer`` with latent attention, a leading dense layer
before sigmoid-routed experts of which a share is held, a shared expert, a
prediction module behind the shared head) against the benchmark's plain
reference (``benchmark/reference/glm_lite.py``, which imports nothing of the
package), on seeded weights."""
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

from drivers import glm_train                                    # noqa: E402
from reference import glm_lite as ref                            # noqa: E402

from deeplearning4j_tpu.zoo import transformer as tfm            # noqa: E402


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def tiny(**over) -> dict:
    """A configuration file's keys at a tiny width: 8 experts published of
    which 3 (ids 2-4) are held, top-2, one shared; 3 heads of 6 + 4 (q, k)
    and 10 (v) from latents of 12 and 8 under a width of 32; one dense layer
    of width 40 before two expert layers of width 12; one prediction
    module."""
    config = dict(
        hidden_size=32, num_attention_heads=3, num_key_value_heads=3,
        q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=6,
        qk_rope_head_dim=4, v_head_dim=10, num_hidden_layers=3,
        first_k_dense_replace=1, intermediate_size=40,
        moe_intermediate_size=12, n_routed_experts=3, first_expert_held=2,
        num_experts_per_tok=2, published={"n_routed_experts": 8},
        n_shared_experts=1, routed_scaling_factor=1.8, norm_topk_prob=True,
        n_group=1, topk_group=1, topk_method="noaux_tc",
        num_nextn_predict_layers=1, mtp_loss_weight=0.3,
        partial_rotary_factor=1, rope_scaling=None, rope_theta=1e6,
        rms_norm_eps=1e-5, max_position_embeddings=64, vocab_size=50,
        tie_word_embeddings=False, compute_dtype="float32",
        param_dtype="float32",
        program={"fused_loss": True, "remat": True,
                 "remat_policy": "save_attn", "loss_chunk": 16})
    config.update(over)
    return config


def _weights(seed, sz, noise=0.1):
    """The reference's draw with every leaf moved off its initial value, so
    that the norm scales and beta are all live."""
    leaves, tree = jax.tree_util.tree_flatten(ref.make_weights(seed, sz))
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return tree.unflatten([a + noise * jax.random.normal(k, a.shape)
                           for a, k in zip(leaves, keys)])


def _batch(sz, seed=3, batch=2, seq=16):
    ids, tgt = ref.make_batches(seed, 1, batch, seq, sz["vocab"])
    return jnp.asarray(ids[0]), jnp.asarray(tgt[0])


def _leaves(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def _layer(blocks, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], blocks)


def _both(config):
    return ref.sizes_of(config), glm_train.program_config(config)


def _close(got, want, tol):
    got, want = _leaves(got), _leaves(want)
    assert sorted(got) == sorted(want)
    for name, w in want.items():
        top = float(jnp.max(jnp.abs(w)))
        if name.endswith("['router_beta']"):        # no gradient reaches it
            assert top == 0.0 == float(jnp.max(jnp.abs(got[name])))
            continue
        gap = float(jnp.max(jnp.abs(got[name] - w))) / top
        assert gap <= tol, (name, gap)


def _ref_grad(sz):
    """One compile a side: op by op the checkpointed scans cost a minute."""
    return jax.jit(jax.value_and_grad(
        lambda p, i, t, **kw: ref.loss(p, i, t, sz, **kw), has_aux=True))


def _prog_grad(cfg):
    return jax.jit(jax.value_and_grad(
        lambda p, i, t: tfm._lm_loss_stats(p, cfg, i, t), has_aux=True))


CASES = {
    "share_of_experts": {},
    "all_experts_held": dict(n_routed_experts=8, first_expert_held=0),
    "two_dense_layers_top_3": dict(first_k_dense_replace=2,
                                   num_hidden_layers=4,
                                   num_experts_per_tok=3),
    "no_dense_layer_two_shared": dict(first_k_dense_replace=0,
                                      n_shared_experts=2),
    "no_shared_expert": dict(n_shared_experts=0),
    "no_prediction_module": dict(num_nextn_predict_layers=0),
    "prediction_weight_one": dict(mtp_loss_weight=1.0),
    "unfused_loss_no_remat": dict(program={"fused_loss": False,
                                           "remat": False}),
    "remat_full": dict(program={"fused_loss": True, "remat": True,
                                "remat_policy": "full", "loss_chunk": 16}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_both_losses_and_every_gradient_leaf_match_the_reference(case):
    config = tiny(**CASES[case])
    sz, cfg = _both(config)
    params = _weights(3, sz)
    ids, tgt = _batch(sz, batch=2 if case == "share_of_experts" else 1)
    (want, parts), g_want = _ref_grad(sz)(params, ids, tgt)
    (got, told), g_got = _prog_grad(cfg)(params, ids, tgt)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    _close(g_got, g_want, 2e-5)
    routing = sz["layers"] - sz["dense"] + sz["predict"]
    assert told["choices"].shape == (routing, sz["top_k"], ids.size)
    load = np.asarray(told["load"])
    assert load.shape == (routing, 4)
    assert (load[:, 0] == ids.size * sz["top_k"]).all()
    assert (load[:, 2] == 0).all() and (load[:, 1] <= load[:, 0]).all()
    if sz["held"] == sz["experts"]:
        assert (load[:, 1] == load[:, 0]).all()
    if sz["predict"]:
        np.testing.assert_allclose(told["losses"], parts, rtol=1e-5)
        assert abs(float(got) - float(
            parts[0] + sz["predict_weight"] * parts[1])) <= 1e-5 * float(got)
    else:
        assert "losses" not in told and "mtp" not in params


def test_bf16_program_stays_near_the_float32_reference():
    """The program as the cell runs it (bf16 compute, float32 weights)
    against the float32 reference handed the PROGRAM's choices: rounding may
    flip a top-k, and then the two differentiate different functions."""
    config = tiny(compute_dtype="bfloat16")
    sz, cfg = _both(config)
    params = _weights(4, sz, noise=0.05)
    ids, tgt = _batch(sz, seed=4, batch=1)
    with jax.default_matmul_precision("default"):   # as the cell runs it
        (got, told), g_got = _prog_grad(cfg)(params, ids, tgt)
    took = jnp.asarray(told["choices"]).reshape(-1, sz["top_k"], *ids.shape)
    (want, parts), g_want = _ref_grad(sz)(params, ids, tgt, choices=took)
    assert abs(float(got) - float(want)) <= 2e-2 * abs(float(want))
    np.testing.assert_allclose(told["losses"], parts, rtol=2e-2)
    norm = lambda g: float(jnp.sqrt(sum(                        # noqa: E731
        jnp.sum(jnp.square(a.astype(jnp.float32)))
        for a in jax.tree_util.tree_leaves(g))))
    assert abs(norm(g_got) - norm(g_want)) <= 0.1 * norm(g_want)
    assert g_got["embed"].dtype == jnp.float32


def test_train_step_hands_back_load_choices_and_the_two_losses():
    import optax
    from deeplearning4j_tpu.obs import get_registry
    from deeplearning4j_tpu.obs.lm import record_losses
    from deeplearning4j_tpu.obs.moe import record_expert_load
    config = tiny()
    sz, cfg = _both(config)
    params = ref.make_weights(5, sz)
    ids, tgt = _batch(sz, seed=5)
    opt = optax.adamw(1e-3)
    out = jax.jit(tfm.make_train_step(cfg, opt))(params, opt.init(params),
                                                 ids, tgt)
    assert len(out) == 4 and sorted(out[3]) == ["choices", "load", "losses", "moved"]
    load, choices, losses = (out[3][k] for k in ("load", "choices", "losses"))
    # two expert layers and the prediction module's block, not the dense one
    assert load.shape == (3, 4) and choices.shape == (3, 2, ids.size)
    assert choices.dtype == jnp.int32
    assert 0 <= int(choices.min()) and int(choices.max()) < 8
    assert abs(float(out[2]) - float(losses[0] + 0.3 * losses[1])) < 1e-5
    # a token's two choices are two experts
    assert bool(jnp.all(choices[:, 0] != choices[:, 1]))
    took = np.asarray(choices).reshape(3, 2, *ids.shape)
    held = (took >= 2) & (took < 5)
    np.testing.assert_array_equal(held.sum(axis=(1, 2, 3)), load[:, 1])
    # handed the program's choices, the reference follows the same function
    # and finds its own top-k agreeing (float32 on both sides)
    p = ref.unstack(params)
    row_loss = jax.jit(lambda i, t, c: ref.row_loss(p, i, t, sz, choices=c))
    for row in range(ids.shape[0]):
        mine, (_, other) = row_loss(ids[row], tgt[row],
                                    jnp.asarray(took[:, :, row]))
        theirs, _ = row_loss(ids[row], tgt[row], None)
        assert float(other) == 0.0
        assert abs(float(mine) - float(theirs)) <= 1e-6 * float(theirs)
    # handed other choices, it follows THEM and counts what it lacks
    wrong = (took[:, :, 0] + 4) % 8
    forced, (_, other) = row_loss(ids[0], tgt[0], jnp.asarray(wrong))
    assert float(other) > 0.25 * wrong.size
    assert abs(float(forced) - float(theirs)) > 1e-6
    moved = _leaves(jax.tree_util.tree_map(jnp.subtract, out[0], params))
    for name, a in moved.items():
        still = name.endswith("['router_beta']")
        assert (float(jnp.max(jnp.abs(a))) == 0.0) == still, name
    # the counters and the two gauges, from what the step handed back
    read = record_expert_load(jax.device_get(out[3]))
    assert read["assignments"] == 3 * 2 * ids.size and read["dropped"] == 0
    assert record_losses(jax.device_get(losses)) == {
        "main": float(losses[0]), "mtp": float(losses[1])}
    reg = get_registry()
    assert reg.get("dl4j_lm_main_loss").value() == pytest.approx(
        float(losses[0]))
    assert reg.get("dl4j_lm_mtp_loss").value() == pytest.approx(
        float(losses[1]))


def _parts(cfg, blk, x):
    """The program's layer by parts: (attention's, x after it, the MLP's
    input, the routed experts', the shared expert's)."""
    h = tfm._rmsnorm(x, blk["ln1"], cfg.norm_eps)
    a = tfm._attention(cfg, *tfm._mla_qkv(cfg, h, blk, "rope"),
                       positions="rope") @ blk["wo"]
    x1 = x + a
    u = tfm._rmsnorm(x1, blk["ln2"], cfg.norm_eps)
    chosen, weight = tfm._route_sigmoid(
        cfg, tfm._router_logits(u, blk["router"]), blk["router_beta"])
    y, stats, _ = tfm._moe_share(cfg, u, chosen, weight, blk["we_in"],
                              blk["we_out"])
    return a, x1, u, y, tfm._dense_mlp(cfg, u, blk["ws_in"], blk["ws_out"]), \
        stats


def test_the_eight_shares_parts_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of the eight shares (experts
    2k and 2k + 1 of 16) sum to what the uncut layer gives, attention and
    the shared expert (held whole by all eight) counted once, in the program
    and in the reference; so does the dense layer, which no share cuts."""
    full = tiny(n_routed_experts=16, first_expert_held=0,
                published={"n_routed_experts": 16}, num_experts_per_tok=4)
    full_sz, full_cfg = _both(full)
    weights = _weights(11, full_sz)
    blk = _layer(weights["blocks"], 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 32), jnp.float32)
    a_full, x1, u, y_full, shared, stats_full = _parts(
        tfm._group_configs(full_cfg)[1], blk, x)
    want_x, _ = ref.layer_fn(x[0], blk, full_sz)    # the uncut reference
    y_sum, local = 0.0, 0.0
    for share in range(8):
        cut = tiny(n_routed_experts=2, first_expert_held=2 * share,
                   published={"n_routed_experts": 16}, num_experts_per_tok=4)
        sz, cfg = _both(cut)
        part = dict(blk, we_in=blk["we_in"][2 * share: 2 * share + 2],
                    we_out=blk["we_out"][2 * share: 2 * share + 2])
        a, _, _, y, sh, stats = _parts(tfm._group_configs(cfg)[1], part, x)
        np.testing.assert_allclose(a, a_full, rtol=1e-6, atol=1e-7)  # alike
        np.testing.assert_allclose(sh, shared, rtol=1e-6, atol=1e-7)
        s0 = ref.scores(u[0], blk)
        np.testing.assert_allclose(
            y[0], ref.experts_part(u[0], s0, ref.choose(s0, blk, sz), part,
                                   sz), rtol=1e-5, atol=1e-6)
        y_sum, local = y_sum + y, local + float(stats[1])
    np.testing.assert_allclose(y_sum, y_full, rtol=1e-5, atol=1e-6)
    got_x = x1 + y_sum + shared         # attention and the shared one ONCE
    np.testing.assert_allclose(got_x[0], want_x, rtol=1e-5, atol=1e-5)
    # every assignment is local to exactly one share
    assert local == 24 * 4 == float(stats_full[1])
    # the dense layer is whole on every chip: program and reference agree
    dense_cfg = tfm._group_configs(full_cfg)[0]
    dblk = _layer(weights["dense_blocks"])
    got = tfm._run_blocks(weights["dense_blocks"], dense_cfg, x)[0]
    np.testing.assert_allclose(got[0], ref.layer_fn(x[0], dblk, full_sz)[0],
                               rtol=1e-5, atol=1e-5)


def test_every_head_reads_one_rotary_key_and_only_the_rotated_part_moves():
    """k's last ``rope`` dimensions are the same for all heads; moving the
    sequence along (the same token at a later position) changes only those
    dimensions of q and k, and nothing of v."""
    sz, cfg = _both(tiny())
    blk = _layer(_weights(7, sz)["blocks"])
    dn, dr, H = sz["nope"], sz["rope"], sz["heads"]
    row = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 32), jnp.float32)
    h = jnp.tile(row, (1, 6, 1))        # one token at positions 0..5
    q, k, v = (a.reshape(1, 6, H, -1) for a in tfm._mla_qkv(cfg, h, blk,
                                                              "rope"))
    assert q.shape[-1] == k.shape[-1] == dn + dr and v.shape[-1] == sz["vd"]
    for head in range(1, H):
        np.testing.assert_array_equal(k[..., head, dn:], k[..., 0, dn:])
        assert float(jnp.max(jnp.abs(k[..., head, :dn] - k[..., 0, :dn]))) > 0
    for a in (q, k):
        np.testing.assert_allclose(a[:, 1:, :, :dn], a[:, :-1, :, :dn],
                                   rtol=1e-6, atol=1e-6)
        assert float(jnp.min(jnp.max(jnp.abs(
            a[:, 1:, :, dn:] - a[:, :1, :, dn:]), axis=-1))) > 1e-3
    np.testing.assert_allclose(v[:, 1:], v[:, :-1], rtol=1e-6, atol=1e-6)
    # without positions nothing moves at all
    q0, k0, _ = tfm._mla_qkv(cfg, h, blk, "none")
    np.testing.assert_allclose(q0[:, 1:], q0[:, :-1], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(k0[:, 1:], k0[:, :-1], rtol=1e-6, atol=1e-6)
    # the reference assembles the same heads
    rq, rk, rv = ref.qkv(h[0], blk, sz)
    np.testing.assert_allclose(q[0], rq, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(k[0], rk, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(v[0], rv, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("top_k,scale", [(2, 1.8), (4, 1.8), (3, 1.0)])
def test_the_weights_sum_to_the_scale_and_beta_moves_choices_alone(top_k,
                                                                   scale):
    sz, cfg = _both(tiny(num_experts_per_tok=top_k,
                         routed_scaling_factor=scale))
    logits = jax.random.normal(jax.random.PRNGKey(5), (40, 8), jnp.float32)
    zero = jnp.zeros((8,), jnp.float32)
    chosen, weight = tfm._route_sigmoid(cfg, logits, zero)
    assert chosen.shape == weight.shape == (top_k, 40)
    np.testing.assert_allclose(weight.sum(axis=0), scale, rtol=1e-6)
    # a bias large enough to put expert 7 into every token's choice
    beta = zero.at[7].set(5.0)
    moved, w_moved = tfm._route_sigmoid(cfg, logits, beta)
    assert bool(jnp.all(jnp.any(moved == 7, axis=0)))
    assert not bool(jnp.all(jnp.any(chosen == 7, axis=0)))
    np.testing.assert_allclose(w_moved.sum(axis=0), scale, rtol=1e-6)
    # the weight is the score WITHOUT the bias, over the chosen scores' sum
    score = jax.nn.sigmoid(logits)
    kept = jnp.take_along_axis(score, moved.T, axis=-1)
    np.testing.assert_allclose(
        w_moved.T, scale * kept / kept.sum(-1, keepdims=True), rtol=1e-6)
    # and no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(
        tfm._route_sigmoid(cfg, logits, b)[1] ** 2))(beta)
    assert float(jnp.max(jnp.abs(g))) == 0.0
    # the reference takes the same experts and weighs them alike
    blk = {"router_beta": beta}
    e = ref.choose(score, blk, sz)
    assert {tuple(sorted(r)) for r in np.asarray(e).tolist()} == \
        {tuple(sorted(r)) for r in np.asarray(moved.T).tolist()}
    np.testing.assert_allclose(
        np.sort(ref.weights_of(score, e, sz), axis=-1),
        np.sort(w_moved.T, axis=-1), rtol=1e-6)


def test_layer_zero_has_no_router_and_the_others_no_dense_mlp():
    sz, cfg = _both(tiny())
    p = tfm.init_params(jax.random.PRNGKey(0), cfg)
    experts = {"router", "router_beta", "we_in", "we_out", "ws_in", "ws_out"}
    assert not experts & set(p["dense_blocks"])
    assert {"w_in", "w_out"} <= set(p["dense_blocks"])
    for blocks in (p["blocks"], p["mtp"]["block"]):
        assert experts <= set(blocks) and not {"w_in", "w_out"} & set(blocks)
        assert "wqkv" not in blocks
    assert p["dense_blocks"]["w_in"].shape == (1, 32, 2 * 40)
    assert p["blocks"]["we_in"].shape == (2, 3, 32, 2 * 12)
    assert p["blocks"]["ws_in"].shape == (2, 32, 2 * 12)
    assert p["blocks"]["router"].shape == (2, 32, 8)    # every published one
    assert p["mtp"]["proj"].shape == (64, 32)


def test_the_prediction_module_reads_the_next_token_and_scores_the_one_after():
    sz, cfg = _both(tiny())
    params = _weights(8, sz)
    ids, tgt = _batch(sz, seed=8, batch=1, seq=12)
    # a last target that is nobody's input: not among ids, not an earlier one
    used = set(np.asarray(ids).ravel().tolist()) \
        | set(np.asarray(tgt[0, :-1]).tolist())
    last = min(set(range(sz["vocab"])) - used)
    tgt = tgt.at[0, -1].set(last)
    losses = jax.jit(lambda p, t: tfm._lm_loss_stats(
        p, cfg, ids, t)[1]["losses"])
    base = losses(params, tgt)
    want = jax.jit(lambda p: ref.row_loss(ref.unstack(p), ids[0], tgt[0],
                                          sz)[1][0])(params)
    np.testing.assert_allclose(base, want, rtol=1e-5)
    # with the embedding's half of the module's input switched off the
    # targets reach the predicted-token loss only where they are SCORED:
    # targets 1 .. T-1 (at positions 0 .. T-2), never target 0
    blind = dict(params, mtp=dict(params["mtp"],
                                  ln_e=jnp.zeros_like(params["mtp"]["ln_e"])))
    seen = losses(blind, tgt)
    bump = lambda i: tgt.at[0, i].set((tgt[0, i] + 1) % sz["vocab"])  # noqa: E731
    assert float(losses(blind, bump(0))[1]) == float(seen[1])
    assert float(losses(blind, bump(0))[0]) != float(seen[0])  # main: scored
    for i in (1, 5, 11):
        assert float(losses(blind, bump(i))[1]) != float(seen[1])
    # switched on, target 0 is READ at position 0: Emb(targets_0)
    assert float(losses(params, bump(0))[1]) != float(base[1])
    # the embedding and the head each get gradient from BOTH losses
    g_main, g_ahead = (jax.jit(jax.grad(lambda p, i=i: tfm._lm_loss_stats(
        p, cfg, ids, tgt)[1]["losses"][i]))(params) for i in (0, 1))
    for name in ("embed", "head"):
        assert float(jnp.max(jnp.abs(g_main[name]))) > 0
        assert float(jnp.max(jnp.abs(g_ahead[name]))) > 0
    # the module's own weights only from the predicted-token loss
    assert float(jnp.max(jnp.abs(g_main["mtp"]["proj"]))) == 0.0
    assert float(jnp.max(jnp.abs(g_ahead["mtp"]["proj"]))) > 0
    # a row of the table that the module alone reads (a target, no input)
    only = min(set(np.asarray(tgt[0, :-1]).tolist())
               - set(np.asarray(ids).ravel().tolist()))
    assert float(jnp.max(jnp.abs(g_main["embed"][only]))) == 0.0
    assert float(jnp.max(jnp.abs(g_ahead["embed"][only]))) > 0
    # position T-1 weighs 0: its input, Emb(targets_{T-1}), gets no gradient
    assert float(jnp.max(jnp.abs(g_ahead["embed"][last]))) == 0.0
    # weight 0 gives the main loss alone, with the module's loss still told
    off = glm_train.program_config(tiny(mtp_loss_weight=0.0))
    loss, told = jax.jit(lambda p: tfm._lm_loss_stats(p, off, ids, tgt))(
        params)
    assert float(loss) == float(told["losses"][0])
    np.testing.assert_allclose(told["losses"], base, rtol=1e-6)


def test_program_through_the_flash_kernel_at_head_size_256(monkeypatch):
    """The published head: 192 + 64 for q and k, 256 for v, through the
    Pallas kernels (interpret mode), under save_attn."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)   # as on one chip
    config = tiny(num_attention_heads=2, num_key_value_heads=2,
                  qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                  num_hidden_layers=2, num_nextn_predict_layers=0,
                  program={"fused_loss": True, "remat": True,
                           "remat_policy": "save_attn", "loss_chunk": 16,
                           "use_flash_attention": True})
    sz, cfg = _both(config)
    assert cfg.head_dim == 256
    assert tfm.attention_path(cfg, 16, jnp.float32) == "flash"
    params = _weights(9, sz)
    ids, tgt = _batch(sz, seed=9, batch=1, seq=16)
    (want, _), g_want = _ref_grad(sz)(params, ids, tgt)
    (got, _), g_got = _prog_grad(cfg)(params, ids, tgt)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    _close(g_got, g_want, 5e-5)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p: tfm.lm_loss(p, cfg, ids, tgt)))(params))
    assert jaxpr.count("name=attn_out") >= 1 and "name=attn_lse" in jaxpr


GLM = dict(attention="mla", q_rank=12, kv_rank=8, nope_head_size=6,
           rope_head_size=4, v_head_size=10, layer_positions=("rope",),
           layer_windows=(0,), mlp="swiglu", n_experts=8, expert_top_k=2,
           experts_held=(2, 3), router="sigmoid", router_scale=1.8,
           dense_layers=1, expert_ff=12, shared_experts=1, predict_ahead=1)


@pytest.mark.parametrize("fields,error", [
    (dict(q_rank=0), ValueError),
    (dict(rope_head_size=3, nope_head_size=7), ValueError),
    (dict(v_head_size=12), NotImplementedError),    # two head sizes
    (dict(n_kv_heads=1), NotImplementedError),
    (dict(rotary_share=0.5), NotImplementedError),
    (dict(use_ring_attention=True), NotImplementedError),
    (dict(router="softmax"), ValueError),
    (dict(experts_held=()), NotImplementedError),   # sigmoid, capacity layer
    (dict(router_input="pre_attention"), NotImplementedError),
    (dict(router="linear", experts_held=(), shared_experts=1, dense_layers=0,
          mlp="gelu"), NotImplementedError),        # shared, capacity layer
    (dict(dense_layers=3), ValueError),             # no expert layer left
    (dict(n_experts=0, experts_held=(), router="linear", shared_experts=0),
     ValueError),                                   # dense before dense
    (dict(predict_ahead=2), NotImplementedError),
    (dict(n_layers=4, dense_layers=2, layer_positions=("rope", "none"),
          layer_windows=(0, 0)), NotImplementedError),
], ids=["no_rank", "odd_rotary_part", "two_head_sizes", "grouped_kv",
        "partial_rotary", "ring", "router", "sigmoid_capacity",
        "pre_attention", "shared_capacity", "all_dense", "dense_then_dense",
        "two_modules", "two_kinds_of_layer"])
def test_fields_no_code_computes_are_refused(fields, error):
    cfg = tfm.TransformerConfig(**{"vocab_size": 50, "d_model": 32,
                                   "n_heads": 3, "n_layers": 3, "d_ff": 40,
                                   "max_seq": 16, **GLM, **fields})
    with pytest.raises(error):
        tfm.init_params(jax.random.PRNGKey(0), cfg)


def test_the_reference_refuses_what_neither_side_implements():
    for key, value in (("n_group", 2), ("topk_group", 2),
                       ("norm_topk_prob", False), ("topk_method", "greedy"),
                       ("partial_rotary_factor", 0.5),
                       ("rope_scaling", {"factor": 2})):
        with pytest.raises(ValueError, match="glm_lite"):
            ref.sizes_of(tiny(**{key: value}))


def test_apply_blocks_refuses_a_stack_of_two_groups():
    sz, cfg = _both(tiny())
    p = tfm.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, 8, 32), jnp.float32)
    with pytest.raises(NotImplementedError, match="dense_blocks"):
        tfm.apply_blocks(p["blocks"], cfg, x)
    logits, _ = tfm.forward(p, cfg, jnp.zeros((1, 8), jnp.int32))
    assert logits.shape == (1, 8, 50)   # forward() runs both groups


def test_init_params_draws_the_tree_the_reference_draws():
    sz, cfg = _both(tiny())
    mine = _leaves(tfm.init_params(jax.random.PRNGKey(0), cfg))
    theirs = _leaves(ref.make_weights(0, sz))
    assert {k: (v.shape, v.dtype) for k, v in mine.items()} == \
        {k: (v.shape, v.dtype) for k, v in theirs.items()}
    assert "['pos_embed']" not in mine and "['head']" in mine
    for tree in (mine, theirs):
        for name, a in tree.items():
            if "norm']" in name or "['ln" in name:
                assert float(jnp.min(a)) == float(jnp.max(a)) == 1.0, name
            if name.endswith("['router_beta']"):
                assert float(jnp.max(jnp.abs(a))) == 0.0
    specs = _leaves(tfm.param_pspecs(cfg))
    assert sorted(specs) == sorted(mine)
    # the reference's unstacked tree names its norms as the stacked one does
    norms = ref.stacked_norms(ref.unstack(ref.make_weights(0, sz)))
    assert sorted(norms) == sorted(theirs)
    for name, a in theirs.items():
        np.testing.assert_allclose(norms[name], jnp.sqrt(jnp.sum(a * a)),
                                   rtol=1e-5)


def test_the_published_share_counts_what_the_configuration_file_says():
    import json
    config = json.loads((BENCH / "configs" / "glm-4.7-flash.json").read_text())
    sz, cfg = _both(config)
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == 706_518_848
    assert f"{n:,}" in config["held_here"]["parameters"]
    theirs = jax.eval_shape(lambda: ref.make_weights(0, sz))
    assert _leaves(jax.tree_util.tree_map(lambda a: a.shape, shapes)) == \
        _leaves(jax.tree_util.tree_map(lambda a: a.shape, theirs))
    assert cfg.head_dim == 256 and cfg.remat_policy == "save_attn"
    assert (cfg.n_layers, cfg.dense_layers, cfg.predict_ahead) == (5, 1, 1)
    assert cfg.experts_held == (0, 8) and cfg.n_experts == 64
    assert cfg.vocab_size * 8 == config["published"]["vocab_size"]


def test_the_older_blocks_draw_what_they_drew():
    """PR 34's leaves take their keys from a split of an unused one: the
    GPT-2, the SmallThinker and the ZAYA1 trees are the parent's, value for
    value."""
    cfg = tfm.TransformerConfig(vocab_size=50, d_model=32, n_heads=4,
                                n_layers=2, d_ff=16, max_seq=16,
                                n_experts=4, experts_held=(0, 2),
                                mlp="reglu", layer_positions=("rope",),
                                layer_windows=(0,))
    p = tfm.init_params(jax.random.PRNGKey(0), cfg)
    k = jax.random.split(jax.random.PRNGKey(0), 12)
    np.testing.assert_array_equal(
        p["blocks"]["we_in"],
        jax.random.normal(k[5], (2, 2, 32, 32)) / np.sqrt(32))
    np.testing.assert_array_equal(
        p["blocks"]["router"], jax.random.normal(k[4], (2, 32, 4)) / np.sqrt(32))
    assert sorted(p["blocks"]) == ["ln1", "ln2", "router", "we_in", "we_out",
                                   "wo", "wqkv"]
    assert sorted(p) == ["blocks", "embed", "head", "ln_f"]


def test_serving_engine_refuses_the_block_by_name():
    from deeplearning4j_tpu.serving import GenerationEngine
    cfg = glm_train.program_config(tiny())
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="attention='mla'"):
        GenerationEngine(cfg, params)
    for fields in (dict(predict_ahead=1), dict(
            n_experts=4, experts_held=(0, 4), router="sigmoid")):
        plain = tfm.TransformerConfig(vocab_size=50, d_model=32, n_heads=4,
                                      n_layers=2, d_ff=16, max_seq=16,
                                      **fields)
        with pytest.raises(NotImplementedError, match="prediction module"):
            GenerationEngine(plain, tfm.init_params(jax.random.PRNGKey(0),
                                                    plain))


def test_pipeline_stages_refuse_two_groups_and_the_module():
    from deeplearning4j_tpu.parallel import pipeline
    cfg = glm_train.program_config(tiny())
    with pytest.raises(NotImplementedError, match="dense_layers"):
        pipeline._stage_loss_fn(cfg, 2)
    plain = tfm.TransformerConfig(vocab_size=50, d_model=32, n_heads=4,
                                  n_layers=2, d_ff=16, max_seq=16,
                                  predict_ahead=1)
    with pytest.raises(NotImplementedError, match="predict_ahead"):
        pipeline._stage_loss_fn(plain, 2)
