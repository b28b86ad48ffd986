"""The ZAYA1-style block (PR 32) at tiny widths on the CPU: the program
(``zoo.transformer`` with compressed convolutional attention, partial
rotary, the mlp router with its carried state and its skip, SwiGLU experts
of which a share is held, scaled residuals) against the benchmark's plain
reference (``benchmark/reference/zaya.py``, which imports nothing of the
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

from drivers import zaya_train                                   # noqa: E402
from reference import zaya as ref                                # noqa: E402

from deeplearning4j_tpu.zoo import transformer as tfm            # noqa: E402


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def tiny(**over) -> dict:
    """A configuration file's keys at a tiny width: 6 experts published of
    which 3 (ids 1-3) are held, top-1, and the skip; 4 query heads on 2 K/V
    heads of 8 in a latent of 32 under a width of 48; half of a head
    rotated; two taps and two taps."""
    config = dict(
        hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, num_hidden_layers=3, moe_intermediate_size=16,
        num_experts=3, first_expert_held=1, num_experts_per_tok=1,
        published={"num_experts": 6}, router_hidden_size=12,
        cca_time0=2, cca_time1=2,
        rope_parameters={"hybrid": {"partial_rotary_factor": 0.5,
                                    "rope_theta": 5e6}},
        rms_norm_eps=1e-5, max_position_embeddings=64, vocab_size=50,
        tie_word_embeddings=True, compute_dtype="float32",
        param_dtype="float32",
        program={"fused_loss": True, "remat": True,
                 "remat_policy": "save_attn", "loss_chunk": 16})
    config.update(over)
    return config


def _weights(seed, sz, noise=0.1):
    """The reference's draw with every leaf moved off its initial value, so
    that the biases, the scales, tau, gamma and beta are all live."""
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


def _layer(params, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], params["blocks"])


CASES = {
    "share_of_experts": {},
    "all_experts_held": dict(num_experts=6, first_expert_held=0),
    "three_taps_then_one": dict(cca_time0=3, cca_time1=1),
    "whole_head_rotated": dict(rope_parameters={"hybrid": {
        "partial_rotary_factor": 1.0, "rope_theta": 1e4}}),
    "one_kv_group_pair": dict(num_attention_heads=2, num_key_value_heads=2),
    "unfused_loss_no_remat": dict(program={"fused_loss": False,
                                           "remat": False}),
    "remat_full": dict(program={"fused_loss": True, "remat": True,
                                "remat_policy": "full", "loss_chunk": 16}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_every_gradient_leaf_match_the_reference(case):
    config = tiny(**CASES[case])
    sz, cfg = ref.sizes_of(config), zaya_train.program_config(config)
    params = _weights(3, sz)
    ids, tgt = _batch(sz)
    want, g_want = jax.value_and_grad(ref.loss)(params, ids, tgt, sz)
    (got, stats), g_got = jax.value_and_grad(
        tfm._lm_loss_stats, has_aux=True)(params, cfg, ids, tgt)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    g_want, g_got = _leaves(g_want), _leaves(g_got)
    assert sorted(g_got) == sorted(g_want)
    for name, w in g_want.items():
        if name == "['blocks']['router_beta']":     # no gradient reaches it
            assert float(jnp.max(jnp.abs(w))) == 0.0
            assert float(jnp.max(jnp.abs(g_got[name]))) == 0.0
            continue
        gap = float(jnp.max(jnp.abs(g_got[name] - w)) / jnp.max(jnp.abs(w)))
        assert gap <= 2e-5, (name, gap)
    assert stats["choices"].shape == (sz["layers"], 1, ids.size)
    stats = np.asarray(stats["load"])
    assert stats.shape == (sz["layers"], 5)
    assert (stats[:, 0] == ids.size).all() and (stats[:, 2] == 0).all()
    assert (stats[:, 1] + stats[:, 4] <= ids.size).all()
    if sz["held"] == sz["experts"]:
        assert (stats[:, 1] + stats[:, 4] == ids.size).all()


def test_bf16_program_stays_near_the_float32_reference():
    """The program as the cell runs it (bf16 compute, float32 weights)
    against the float32 reference handed the PROGRAM's choices: rounding may
    flip an argmax, and then the two differentiate different functions."""
    config = tiny(compute_dtype="bfloat16")
    sz, cfg = ref.sizes_of(config), zaya_train.program_config(config)
    params = _weights(4, sz, noise=0.05)
    ids, tgt = _batch(sz, seed=4)
    # jitted and at the default precision, as the cell runs it
    with jax.default_matmul_precision("default"):
        got, g_got = jax.jit(jax.value_and_grad(
            lambda p: tfm.lm_loss(p, cfg, ids, tgt)))(params)
    want, g_want = jax.value_and_grad(ref.loss)(params, ids, tgt, sz)
    assert abs(float(got) - float(want)) <= 2e-2 * abs(float(want))
    norm = lambda g: float(jnp.sqrt(sum(                        # noqa: E731
        jnp.sum(jnp.square(a.astype(jnp.float32)))
        for a in jax.tree_util.tree_leaves(g))))
    assert abs(norm(g_got) - norm(g_want)) <= 0.1 * norm(g_want)
    assert g_got["embed"].dtype == jnp.float32


def test_train_step_hands_back_rows_of_five_beside_the_loss():
    import optax
    config = tiny()
    sz, cfg = ref.sizes_of(config), zaya_train.program_config(config)
    params = ref.make_weights(5, sz)
    ids, tgt = _batch(sz, seed=5)
    opt = optax.adamw(1e-3)
    out = jax.jit(tfm.make_train_step(cfg, opt))(params, opt.init(params),
                                                 ids, tgt)
    # four outputs, as from every configuration that holds experts
    assert len(out) == 4 and sorted(out[3]) == ["choices", "load", "moved"]
    load, choices = out[3]["load"], out[3]["choices"]
    assert load.shape == (3, 5)
    # the expert every token took in every layer
    assert choices.shape == (3, 1, ids.size) and choices.dtype == jnp.int32
    assert int(choices.min()) >= 0 and int(choices.max()) <= 6
    took = np.asarray(choices).reshape(3, *ids.shape)
    for layer in range(3):
        assert np.sum(took[layer] == 6) == load[layer, 4]       # the skip
    # handed the program's choices, the reference follows the same function
    # and finds its own argmax agreeing (float32 on both sides)
    p = ref.unstack(params)
    for row in range(ids.shape[0]):
        mine, other = ref.row_loss(p, ids[row], tgt[row], sz,
                                   choices=jnp.asarray(took[:, row]))
        theirs, _ = ref.row_loss(p, ids[row], tgt[row], sz)
        assert float(other) == 0.0 and float(mine) == float(theirs)
    # handed other choices, it follows THEM and says how many it disagrees on
    wrong = (took[:, 0] + 1) % 7
    forced, other = ref.row_loss(p, ids[0], tgt[0], sz,
                                 choices=jnp.asarray(wrong))
    # (all of layer 0's; further down its own argmax follows the forced x)
    assert wrong.shape[1] <= float(other) <= wrong.size
    assert abs(float(forced) - float(ref.row_loss(p, ids[0], tgt[0], sz)[0])) \
        > 1e-6
    moved = _leaves(jax.tree_util.tree_map(jnp.subtract, out[0], params))
    assert float(jnp.max(jnp.abs(moved["['blocks']['router_beta']"]))) == 0.0
    assert float(jnp.max(jnp.abs(moved["['blocks']['cca_w1']"]))) > 0.0


def _halves(full_blk, held):
    """The two shares of one layer's weights: experts 0..held-1 and
    held..2 held-1; everything else whole."""
    return [dict(full_blk, we_in=full_blk["we_in"][s * held:(s + 1) * held],
                 we_out=full_blk["we_out"][s * held:(s + 1) * held])
            for s in range(2)]


def test_the_two_shares_parts_add_up_to_the_uncut_layer():
    """The guide's share test: the expert parts of the two shares (experts
    0-2 and 3-5 of 6, the skip adding nothing in either) sum to what the
    uncut layer gives, attention (held whole by both) counted once, in the
    program and in the reference."""
    full = tiny(num_experts=6, first_expert_held=0)
    full_sz, full_cfg = ref.sizes_of(full), zaya_train.program_config(full)
    blk = _layer(_weights(11, full_sz), 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 48), jnp.float32)
    r_prev = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 12), jnp.float32)

    def program_parts(cfg, blk):
        h = tfm._rmsnorm(x, blk["ln1"], cfg.norm_eps)
        a = tfm._attention(cfg, *tfm._cca_qkv(cfg, h, blk, "rope"),
                           positions="rope") @ blk["wo"]
        x1 = tfm._residual(cfg, x, a, blk, 0)
        u = tfm._rmsnorm(x1, blk["ln2"], cfg.norm_eps)
        _, chosen, weight = tfm._route_mlp(cfg, u, r_prev, blk)
        y, stats, _ = tfm._moe_share(cfg, u, chosen, weight, blk["we_in"],
                                  blk["we_out"])
        return a, x1, u, y, stats

    a_full, x1, u, y_full, stats_full = program_parts(full_cfg, blk)
    h0 = ref._rmsnorm(x[0], blk["ln1"], 1e-5)
    np.testing.assert_allclose(a_full[0], ref.attention_part(h0, blk, full_sz),
                               rtol=1e-5, atol=1e-6)
    _, p0 = ref.router(u[0], r_prev[0], blk, full_sz)
    e0 = ref.choose(p0, blk)
    np.testing.assert_allclose(
        y_full[0], ref.experts_part(u[0], p0, e0, blk, full_sz),
        rtol=1e-5, atol=1e-6)
    # the uncut reference layer, whole
    want_x, _, _ = ref.layer_fn(x[0], r_prev[0], blk, full_sz)
    s, b = blk["res_scale"], blk["res_bias"]
    y_sum, local, skipped = 0.0, 0.0, []
    for share, part in enumerate(_halves(blk, 3)):
        cut = tiny(num_experts=3, first_expert_held=3 * share)
        sz, cfg = ref.sizes_of(cut), zaya_train.program_config(cut)
        a, x1_s, u_s, y, stats = program_parts(cfg, part)
        np.testing.assert_allclose(a, a_full, rtol=1e-6, atol=1e-7)  # alike
        np.testing.assert_allclose(
            y[0], ref.experts_part(u[0], p0, e0, part, sz),
            rtol=1e-5, atol=1e-6)
        y_sum, local = y_sum + y, local + float(stats[1])
        skipped.append(float(stats[4]))
    np.testing.assert_allclose(y_sum, y_full, rtol=1e-5, atol=1e-6)
    got_x = (s[2] * x1 + b[2]) + (s[3] * y_sum + b[3])  # attention once
    np.testing.assert_allclose(got_x[0], want_x, rtol=1e-5, atol=1e-5)
    # a token is local to one share or took the skip: none is lost or twice
    assert skipped[0] == skipped[1] == float(stats_full[4])
    assert local + skipped[0] == 24 == float(stats_full[1]) + skipped[0]


@pytest.mark.parametrize("taps,reach", [((2, 2), 2), ((3, 1), 2), ((1, 1), 0)],
                         ids=["2_and_2", "3_and_1", "1_and_1"])
def test_the_mixing_is_causal_and_reads_as_far_back_as_its_taps(taps, reach):
    """Changing token t moves no q, k or v before t; through the mixing
    alone (the projections are per token) it moves q and k up to t + reach
    = t + (taps0 - 1) + (taps1 - 1) and no further; v, shifted by one, moves
    at t and t + 1."""
    config = tiny(cca_time0=taps[0], cca_time1=taps[1])
    sz, cfg = ref.sizes_of(config), zaya_train.program_config(config)
    blk = _layer(_weights(7, sz))
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 48), jnp.float32)
    t = 5
    h2 = h.at[0, t].add(1.0)
    for positions in ("none", "rope"):
        base = tfm._cca_qkv(cfg, h, blk, positions)
        moved = tfm._cca_qkv(cfg, h2, blk, positions)
        dq, dk, dv = (np.abs(np.asarray(m - b))[0].max(axis=-1)
                      for m, b in zip(moved, base))
        for d in (dq, dk):
            assert (d[:t] == 0).all() and (d[t: t + reach + 1] > 0).all()
            assert (d[t + reach + 1:] == 0).all()
        assert (dv[:t] == 0).all() and (dv[t: t + 2] > 0).all()
        assert (dv[t + 2:] == 0).all()


def test_value_head_one_is_the_token_befores_and_zero_at_position_zero():
    config = tiny()
    sz, cfg = ref.sizes_of(config), zaya_train.program_config(config)
    blk = _layer(_weights(8, sz))
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 10, 48), jnp.float32)
    _, _, v = tfm._cca_qkv(cfg, h, blk, "rope")
    v = v.reshape(10, 2, 8)
    want = ref.values(h[0], blk, sz)
    np.testing.assert_allclose(v, want, rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(v[0, 1]))) == 0.0
    assert float(jnp.min(jnp.abs(v[0, 0]))) > 0.0
    wv2 = blk["wqkv"][:, 32 + 16 + 8:]
    np.testing.assert_allclose(v[1:, 1], h[0, :-1] @ wv2, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("share,rotary", [(0.5, 8), (0.25, 4), (1.0, 16)])
def test_rotary_leaves_the_rest_of_a_head_alone(share, rotary):
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 3, 16), jnp.float32)
    got = tfm._rope(x, 5e6, rotary=rotary)
    np.testing.assert_array_equal(got[..., rotary:], x[..., rotary:])
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)   # position 0
    assert not bool(jnp.allclose(got[:, 1:, :, :rotary], x[:, 1:, :, :rotary]))
    np.testing.assert_allclose(got[0], ref.partial_rope(x[0], 5e6, rotary),
                               rtol=1e-6, atol=1e-6)
    cfg = tfm.TransformerConfig(d_model=48, n_heads=3, head_size=16,
                                rotary_share=share)
    assert cfg.rotary_dims == rotary
    # q and k of the layer: every head has norm sqrt(dh) times tau, and the
    # rotation keeps it
    config = tiny()
    sz, cfg = ref.sizes_of(config), zaya_train.program_config(config)
    blk = _layer(_weights(9, sz))
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 48), jnp.float32)
    q, k, _ = tfm._cca_qkv(cfg, h, blk, "rope")
    np.testing.assert_allclose(
        jnp.linalg.norm(q.reshape(9, 4, 8), axis=-1), np.sqrt(8.0), rtol=1e-5)
    np.testing.assert_allclose(
        jnp.linalg.norm(k.reshape(9, 2, 8), axis=-1),
        np.sqrt(8.0) * np.abs(blk["cca_tau"])[None].repeat(9, 0), rtol=1e-5)


def test_the_routers_state_reaches_the_next_layer_and_gamma_gets_a_gradient():
    config = tiny(num_experts=6, first_expert_held=0)
    sz, cfg = ref.sizes_of(config), zaya_train.program_config(config)
    params = _weights(6, sz)
    blk = _layer(params, 1)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 48), jnp.float32)
    zero = jnp.zeros((1, 10, 12), jnp.float32)
    state = jax.random.normal(jax.random.PRNGKey(1), (1, 10, 12), jnp.float32)
    r0, _, w0 = tfm._route_mlp(cfg, u, zero, blk)
    r1, _, w1 = tfm._route_mlp(cfg, u, state, blk)
    np.testing.assert_allclose(r1 - r0, blk["router_gamma"] * state,
                               rtol=1e-5, atol=1e-6)
    assert not bool(jnp.allclose(w0, w1))
    want_r, want_p = ref.router(u[0], state[0], blk, sz)
    np.testing.assert_allclose(r1[0], want_r, rtol=1e-5, atol=1e-6)
    # through the whole stack: layer 0's gamma multiplies zeros, the others'
    # get a gradient, and layer 0's router weights get one THROUGH layer 1
    ids, tgt = _batch(sz, seed=6)
    g = jax.grad(tfm.lm_loss)(params, cfg, ids, tgt)["blocks"]
    gamma = np.abs(np.asarray(g["router_gamma"])).max(axis=-1)
    assert gamma[0] == 0.0 and (gamma[1:] > 0).all()

    def last_layers_weight(down0):
        """Layer 2's weights p_e as a function of layer 0's down-projection,
        x held fixed: only the carried state connects them."""
        blocks = dict(params["blocks"])
        blocks["router_down"] = blocks["router_down"].at[0].set(down0)
        state = zero
        for i in range(3):
            b = jax.tree_util.tree_map(lambda a: a[i], blocks)
            state, _, weight = tfm._route_mlp(cfg, u, state, b)
        return jnp.sum(weight)

    through = jax.grad(last_layers_weight)(params["blocks"]["router_down"][0])
    assert float(jnp.max(jnp.abs(through))) > 0.0


def test_a_token_on_the_skip_gets_nothing_and_is_counted():
    from deeplearning4j_tpu.obs import get_registry
    from deeplearning4j_tpu.obs.moe import record_expert_load
    config = tiny(num_experts=6, first_expert_held=0)
    sz, cfg = ref.sizes_of(config), zaya_train.program_config(config)
    blk = _layer(ref.make_weights(7, sz))
    n = 20
    u = jax.random.normal(jax.random.PRNGKey(1), (1, n, 48), jnp.float32)
    # beta sends the even tokens' neighbours nowhere: a large bias on the
    # skip output moves every token there
    blk_skip = dict(blk, router_beta=blk["router_beta"].at[6].set(10.0))
    zero = jnp.zeros((1, n, 12), jnp.float32)
    _, chosen, weight = tfm._route_mlp(cfg, u, zero, blk_skip)
    assert (np.asarray(chosen) == 6).all()
    y, stats, _ = tfm._moe_share(cfg, u, chosen, weight, blk["we_in"],
                              blk["we_out"])
    assert float(jnp.max(jnp.abs(y))) == 0.0
    assert list(np.asarray(stats)) == [n, 0, 0, 0, n]
    # half of the tokens on the skip, by hand
    _, chosen, _ = tfm._route_mlp(cfg, u, zero, blk)
    chosen = jnp.where(chosen == 6, 0, chosen).at[0, ::2].set(6)
    _, p = ref.router(u[0], zero[0], blk, sz)
    weight = jnp.take_along_axis(p, chosen[0][:, None], axis=-1).T
    y, stats, _ = tfm._moe_share(cfg, u, chosen, weight, blk["we_in"],
                              blk["we_out"])
    norms = np.asarray(jnp.linalg.norm(y[0], axis=-1))
    assert (norms[::2] == 0).all() and (norms[1::2] > 0).all()
    np.testing.assert_allclose(
        y[0], ref.experts_part(u[0], p, chosen[0], blk, sz),
        rtol=1e-5, atol=1e-6)
    reg = get_registry()
    before = reg.get("dl4j_moe_skipped_total")
    before = before.value() if before else 0.0
    read = record_expert_load(np.asarray(stats)[None])
    assert read["skipped"] == n // 2 and read["assignments"] == n
    assert reg.get("dl4j_moe_skipped_total").value() == before + n // 2
    # the step's fourth output as it is, or its array alone
    four = record_expert_load({"load": np.array([[72, 27, 0, 1.4]],
                                                np.float32)})
    assert "skipped" not in four and four["local"] == 27
    assert reg.get("dl4j_moe_skipped_total").value() == before + n // 2


def test_program_through_the_flash_kernel_matches_the_reference(monkeypatch):
    """The same tiny model with the Pallas kernels in the path (interpret
    mode): grouped heads reach the kernel, and under save_attn what is kept
    is the kernel's output and log-sum-exp."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)   # as on one chip
    config = tiny(program={"fused_loss": True, "remat": True,
                           "remat_policy": "save_attn", "loss_chunk": 16,
                           "use_flash_attention": True})
    sz, cfg = ref.sizes_of(config), zaya_train.program_config(config)
    assert tfm.attention_path(cfg, 16, jnp.float32) == "flash"
    params = _weights(9, sz)
    ids, tgt = _batch(sz, seed=9, batch=1, seq=16)
    want, g_want = jax.value_and_grad(ref.loss)(params, ids, tgt, sz)
    got, g_got = jax.value_and_grad(tfm.lm_loss)(params, cfg, ids, tgt)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    for name, w in _leaves(g_want).items():
        if float(jnp.max(jnp.abs(w))) == 0.0:
            continue
        gap = float(jnp.max(jnp.abs(_leaves(g_got)[name] - w))
                    / jnp.max(jnp.abs(w)))
        assert gap <= 5e-5, (name, gap)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p: tfm.lm_loss(p, cfg, ids, tgt)))(params))
    assert jaxpr.count("name=attn_out") >= 1 and "name=attn_lse" in jaxpr


ZAYA = dict(attention="cca", mlp="swiglu", router="mlp", router_hidden=12,
            router_skip=True, scaled_residuals=True, n_experts=6,
            expert_top_k=1, experts_held=(0, 3), n_kv_heads=2, head_size=8,
            layer_positions=("rope",), layer_windows=(0,), rotary_share=0.5)


@pytest.mark.parametrize("fields,error", [
    (dict(attention="mla"), ValueError),
    (dict(router="hash"), ValueError),
    (dict(rotary_share=0.4), ValueError),           # 3 dimensions: 1.5 pairs
    (dict(rotary_share=0.0), ValueError),
    (dict(cca_taps=(2,)), ValueError),
    (dict(cca_taps=(0, 2)), ValueError),
    (dict(use_ring_attention=True), NotImplementedError),
    (dict(expert_top_k=2), NotImplementedError),
    (dict(router_hidden=0), NotImplementedError),
    (dict(experts_held=()), NotImplementedError),
    (dict(router="linear"), NotImplementedError),   # a skip without the mlp
    (dict(router_input="pre_attention"), NotImplementedError),
], ids=["attention", "router", "rotary_pairs", "rotary_none", "one_tap_count",
        "no_taps", "ring", "top_2", "no_router_width", "capacity_layer",
        "skip_of_linear", "pre_attention"])
def test_fields_no_code_computes_are_refused(fields, error):
    cfg = tfm.TransformerConfig(**{"vocab_size": 50, "d_model": 48,
                                   "n_heads": 4, "n_layers": 2, "d_ff": 16,
                                   "max_seq": 16, **ZAYA, **fields})
    with pytest.raises(error):
        tfm.init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("positions,refused", [((), False), (("none",), False),
                                               (("rope",), True)],
                         ids=["learned", "none", "rope"])
def test_an_odd_head_is_refused_only_where_a_layer_rotates(positions, refused):
    cfg = tfm.TransformerConfig(vocab_size=50, d_model=36, n_heads=4,
                                n_layers=2, d_ff=16, max_seq=16,
                                layer_positions=positions,
                                layer_windows=(0,) * len(positions))
    if refused:
        with pytest.raises(ValueError, match="pairs"):
            tfm._check(cfg)
    else:
        tfm._check(cfg)


def test_init_params_draws_the_tree_the_reference_draws():
    config = tiny()
    sz, cfg = ref.sizes_of(config), zaya_train.program_config(config)
    mine = _leaves(tfm.init_params(jax.random.PRNGKey(0), cfg))
    theirs = _leaves(ref.make_weights(0, sz))
    assert {k: (v.shape, v.dtype) for k, v in mine.items()} == \
        {k: (v.shape, v.dtype) for k, v in theirs.items()}
    assert "['pos_embed']" not in mine and "['head']" not in mine
    assert "['blocks']['router']" not in mine
    for name in ("res_scale", "cca_tau", "router_gamma", "ln1"):
        assert float(jnp.min(mine[f"['blocks']['{name}']"])) == 1.0
        assert float(jnp.max(theirs[f"['blocks']['{name}']"])) == 1.0
    for name in ("res_bias", "cca_b0", "cca_b1", "router_beta", "router_c1"):
        assert float(jnp.max(jnp.abs(mine[f"['blocks']['{name}']"]))) == 0.0
        assert float(jnp.max(jnp.abs(theirs[f"['blocks']['{name}']"]))) == 0.0
    specs = _leaves(tfm.param_pspecs(cfg))
    assert sorted(specs) == sorted(mine)


def test_the_older_blocks_draw_what_they_drew():
    """PR 32's leaves take their keys from a split of an unused one: the
    GPT-2 and the SmallThinker trees are the parent's, value for value."""
    cfg = tfm.TransformerConfig(vocab_size=50, d_model=32, n_heads=4,
                                n_layers=2, d_ff=16, max_seq=16)
    p = tfm.init_params(jax.random.PRNGKey(0), cfg)
    k = jax.random.split(jax.random.PRNGKey(0), 12)
    np.testing.assert_array_equal(
        p["blocks"]["w_in"], jax.random.normal(k[7], (2, 32, 16)) / np.sqrt(32))
    assert sorted(p["blocks"]) == ["ln1", "ln2", "w_in", "w_out", "wo", "wqkv"]


def test_serving_engine_refuses_the_block_by_name():
    from deeplearning4j_tpu.serving import GenerationEngine
    cfg = zaya_train.program_config(tiny())
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="attention='cca'"):
        GenerationEngine(cfg, params)
    dense = tfm.TransformerConfig(vocab_size=50, d_model=32, n_heads=4,
                                  n_layers=2, d_ff=16, max_seq=16,
                                  scaled_residuals=True)
    with pytest.raises(NotImplementedError, match="scaled residuals"):
        GenerationEngine(dense, tfm.init_params(jax.random.PRNGKey(0), dense))


def test_pipeline_stages_refuse_the_routers_state():
    """A stage hands on the residual stream alone; the state of the layer
    before would be lost at every stage boundary."""
    from deeplearning4j_tpu.parallel import pipeline
    cfg = zaya_train.program_config(tiny())
    with pytest.raises(NotImplementedError, match="router's state"):
        pipeline._stage_loss_fn(cfg, 2)
