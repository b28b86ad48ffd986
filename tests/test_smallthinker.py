"""The SmallThinker-style block (PR 28) at tiny widths on the CPU, float32:
the program (``zoo.transformer`` with grouped K/V heads, rotary or no
positions per layer, a window per layer, dropless routed ReGLU experts of
which a share is held, the router reading the block's input) against the
benchmark's plain reference (``benchmark/reference/smallthinker.py``, which
imports nothing of the package), on seeded weights."""
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

from drivers import moe_train                                    # noqa: E402
from reference import smallthinker as ref                        # noqa: E402

from deeplearning4j_tpu.kernels.flash_attention import (         # noqa: E402
    flash_attention, flash_attention_ntc, mha_reference)
from deeplearning4j_tpu.zoo import transformer as tfm            # noqa: E402


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def tiny(**over) -> dict:
    """A configuration file's keys at a tiny width: 8 experts published of
    which 3 (ids 2-4) are held, top-3; 4 query heads on 2 K/V heads; one
    period of a full layer without positions and three window-RoPE layers."""
    config = dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, num_hidden_layers=4, moe_ffn_hidden_size=16,
        moe_num_primary_experts=3, first_expert_held=2,
        moe_num_active_primary_experts=3,
        published={"moe_num_primary_experts": 8},
        rope_layout=[0, 1, 1, 1] * 2, sliding_window_layout=[0, 1, 1, 1] * 2,
        sliding_window_size=5, rope_theta=1.5e6, rms_norm_eps=1e-6,
        max_position_embeddings=64, vocab_size=50, tie_word_embeddings=False,
        compute_dtype="float32", param_dtype="float32",
        program={"fused_loss": True, "remat": True, "remat_policy": "full",
                 "loss_chunk": 16})
    config.update(over)
    return config


def _batch(sz, seed=3, batch=2, seq=12):
    ids, tgt = ref.make_batches(seed, 1, batch, seq, sz["vocab"])
    return jnp.asarray(ids[0]), jnp.asarray(tgt[0])


def _leaves(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


CASES = {
    "share_of_experts": {},
    "all_experts_held": dict(moe_num_primary_experts=8, first_expert_held=0),
    "one_kv_group": dict(num_attention_heads=4, num_key_value_heads=1),
    "unfused_loss_no_remat": dict(program={"fused_loss": False,
                                           "remat": False}),
    "two_periods": dict(num_hidden_layers=8),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_every_gradient_leaf_match_the_reference(case):
    config = tiny(**CASES[case])
    sz, cfg = ref.sizes_of(config), moe_train.program_config(config)
    params = ref.make_weights(3, sz)
    ids, tgt = _batch(sz)
    want, g_want = jax.value_and_grad(ref.loss)(params, ids, tgt, sz)
    (got, stats), g_got = jax.value_and_grad(
        tfm._lm_loss_stats, has_aux=True)(params, cfg, ids, tgt)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    g_want, g_got = _leaves(g_want), _leaves(g_got)
    assert sorted(g_got) == sorted(g_want)
    for name, w in g_want.items():
        gap = float(jnp.max(jnp.abs(g_got[name] - w)) / jnp.max(jnp.abs(w)))
        assert gap <= 1e-5, (name, gap)
    assert sorted(stats) == ["choices", "load", "moved"]
    assert stats["choices"].shape == (sz["layers"], sz["top_k"], ids.size)
    stats = np.asarray(stats["load"])
    assert stats.shape == (sz["layers"], 4)
    assert (stats[:, 0] == ids.size * sz["top_k"]).all()
    assert (stats[:, 2] == 0).all()
    if sz["held"] == sz["experts"]:
        assert (stats[:, 1] == stats[:, 0]).all()


def test_train_step_hands_back_the_expert_load_beside_the_loss():
    import optax
    config = tiny()
    sz, cfg = ref.sizes_of(config), moe_train.program_config(config)
    params = ref.make_weights(5, sz)
    ids, tgt = _batch(sz, seed=5)
    opt = optax.adamw(3e-4)
    out = jax.jit(tfm.make_train_step(cfg, opt))(params, opt.init(params),
                                                 ids, tgt)
    assert len(out) == 4 and sorted(out[3]) == ["choices", "load", "moved"]
    assert out[3]["load"].shape == (4, 4)
    assert out[3]["load"].dtype == jnp.float32
    dense = tfm.TransformerConfig(vocab_size=50, d_model=32, n_heads=4,
                                  n_layers=2, d_ff=64, max_seq=16,
                                  dtype=jnp.float32)
    p = tfm.init_params(jax.random.PRNGKey(0), dense)
    assert len(jax.jit(tfm.make_train_step(dense, opt))(
        p, opt.init(p), ids, tgt)) == 3       # as it always was


def _share_of(full_blk, full_sz, s, n_shares):
    """Share ``s`` of one layer's weights: its query heads with their K/V
    heads, its experts; the router whole."""
    dh = full_sz["head_dim"]
    hq, hk = full_sz["heads"] * dh, full_sz["kv_heads"] * dh
    q_w, k_w = hq // n_shares, hk // n_shares
    held = full_sz["held"] // n_shares
    wqkv = full_blk["wqkv"]
    cols = lambda off, w: wqkv[:, off + s * w: off + (s + 1) * w]  # noqa: E731
    blk = dict(full_blk)
    blk["wqkv"] = jnp.concatenate(
        [cols(0, q_w), cols(hq, k_w), cols(hq + hk, k_w)], axis=1)
    blk["wo"] = full_blk["wo"][s * q_w: (s + 1) * q_w]
    blk["we_in"] = full_blk["we_in"][s * held: (s + 1) * held]
    blk["we_out"] = full_blk["we_out"][s * held: (s + 1) * held]
    return blk


@pytest.mark.parametrize("layer", [0, 1], ids=["full_nope", "window_rope"])
def test_the_four_shares_parts_add_up_to_the_uncut_layer(layer):
    """The guide's share test: attention parts over the shares' heads and
    expert parts over the shares' experts sum to what the uncut layer
    gives, in the program and in the reference."""
    n_shares = 4
    full = tiny(num_attention_heads=8, num_key_value_heads=4,
                moe_num_primary_experts=8, first_expert_held=0)
    full_sz, full_cfg = ref.sizes_of(full), moe_train.program_config(full)
    w = ref.make_weights(11, full_sz)
    full_blk = jax.tree_util.tree_map(lambda a: a[layer], w["blocks"])
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (1, 12, 32), jnp.float32)
    kind = full_cfg.layer_kinds[layer]

    def program_parts(cfg, blk):
        h = tfm._rmsnorm(x, blk["ln1"])
        hq = cfg.n_heads * cfg.head_dim
        hk = cfg.kv_heads * cfg.head_dim
        q, k, v = jnp.split(h @ blk["wqkv"], (hq, hq + hk), axis=-1)
        a = tfm._attention(cfg, q, k, v, positions=kind[0],
                           window=kind[1]) @ blk["wo"]
        u = tfm._rmsnorm(x, blk["ln2"])    # the same u for every share
        y, stats, _ = tfm._moe_share(
            cfg, u, *tfm._route_top_k(cfg, tfm._router_logits(h, blk["router"])),
            blk["we_in"], blk["we_out"])
        return a, y, stats

    a_full, y_full, _ = program_parts(full_cfg, full_blk)
    h0 = ref._rmsnorm(x[0], full_blk["ln1"], 1e-6)
    u0 = ref._rmsnorm(x[0], full_blk["ln2"], 1e-6)
    r0 = h0 @ full_blk["router"]
    np.testing.assert_allclose(
        a_full[0], ref.attention_part(h0, full_blk, full_sz, layer),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        y_full[0], ref.experts_part(u0, r0, full_blk, full_sz),
        rtol=1e-5, atol=1e-6)
    a_sum, y_sum, local = 0.0, 0.0, 0.0
    for s in range(n_shares):
        cut = tiny(num_attention_heads=2, num_key_value_heads=1,
                   moe_num_primary_experts=2, first_expert_held=2 * s)
        sz, cfg = ref.sizes_of(cut), moe_train.program_config(cut)
        blk = _share_of(full_blk, full_sz, s, n_shares)
        a, y, stats = program_parts(cfg, blk)
        np.testing.assert_allclose(
            a[0], ref.attention_part(h0, blk, sz, layer), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            y[0], ref.experts_part(u0, r0, blk, sz), rtol=1e-5, atol=1e-6)
        a_sum, y_sum, local = a_sum + a, y_sum + y, local + float(stats[1])
    np.testing.assert_allclose(a_sum, a_full, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y_sum, y_full, rtol=1e-5, atol=1e-5)
    assert local == 12 * 3      # every assignment is local to one share


@pytest.mark.parametrize("target,held", [(3, (2, 3)), (0, (0, 1)), (7, (2, 3))],
                         ids=["held", "only_expert_held", "absent"])
def test_every_token_on_one_expert_loses_nothing(target, held):
    """The worst routing: every token's first choice is the same expert.
    Held: all N rows are computed (a capacity of 1.25 would keep
    1.25 * N * K / E of them) and ``dropped`` reads 0; absent: no row is
    local and the part is zero."""
    config = tiny(first_expert_held=held[0], moe_num_primary_experts=held[1])
    sz, cfg = ref.sizes_of(config), moe_train.program_config(config)
    blk = jax.tree_util.tree_map(lambda a: a[0],
                                 ref.make_weights(7, sz)["blocks"])
    n = 40
    u = jax.random.normal(jax.random.PRNGKey(1), (1, n, 32), jnp.float32)
    logits = jax.random.normal(jax.random.PRNGKey(4), (n, 8), jnp.float32)
    logits = logits.at[:, target].set(50.0)     # all but the whole weight
    y, stats, _ = tfm._moe_share(cfg, u, *tfm._route_top_k(cfg, logits),
                              blk["we_in"], blk["we_out"])
    want = ref.experts_part(u[0], logits, blk, sz)
    np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-6)
    stats = np.asarray(stats)
    assert stats[0] == n * 3 and stats[2] == 0
    on_target = held[0] <= target < held[0] + held[1]
    assert stats[1] >= n if on_target else stats[1] <= 2 * n
    if on_target:
        # each token's row for the target is there: its part is not small
        assert float(jnp.min(jnp.linalg.norm(y[0], axis=-1))) > 1e-3
        assert stats[3] >= 1.0
    if held[1] == 1 and not on_target:
        assert float(jnp.max(jnp.abs(y))) == 0.0


@pytest.mark.parametrize("layout,moves", [(0, False), (1, True)],
                         ids=["layout0_ignores_positions",
                              "layout1_reads_positions"])
def test_positions_reach_only_the_rope_layers(layout, moves):
    """Shift every position by 5 (the same tokens seen 5 places later): a
    layer without positions gives the same output; attention under RoPE
    depends on differences of positions only, so it too is unchanged by a
    common shift, but NOT by rotating q alone."""
    config = tiny()
    cfg = moe_train.program_config(config)
    b, t, h, dh = 1, 10, 4, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (b, t, h * dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, t, 2 * dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, t, 2 * dh), jnp.float32)
    positions = "rope" if layout else "none"
    base = tfm._attention(cfg, q, k, v, positions=positions, window=0)
    plain = tfm._attention(cfg, q, k, v, positions="none", window=0)
    assert bool(jnp.allclose(base, plain, atol=1e-6)) is (not moves)
    # reversing the order of the keys' POSITIONS changes a rope layer
    x = q.reshape(b, t, h, dh)
    assert bool(jnp.allclose(tfm._rope(x, 1.5e6), x, atol=1e-6)) is False
    np.testing.assert_allclose(tfm._rope(x, 1.5e6)[:, 0], x[:, 0], atol=1e-7)
    np.testing.assert_allclose(
        tfm._rope(x, 1.5e6)[0], ref.rope(x[0], 1.5e6), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("heads,kv,window,t,blocks", [
    (7, 1, 40, 96, (16, 32)),       # T not a multiple of the window
    (7, 1, None, 96, (32, 16)),     # grouped heads, full causal
    (6, 2, 24, 80, (16, 16)),       # two groups of three
    (2, 2, 20, 64, (16, 16)),       # a window without groups
    (7, 1, 100, 64, (16, 32)),      # a window wider than the sequence
], ids=["g7_w40_t96", "g7_full_t96", "g3_w24_t80", "g1_w20_t64",
        "g7_w100_t64"])
def test_flash_window_and_groups_against_the_oracle(heads, kv, window, t,
                                                    blocks):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, heads, t, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, kv, t, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, kv, t, 16), jnp.float32)
    co = jax.random.normal(ks[3], (2, heads, t, 16), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, None, True, *blocks, True, window)

    def oracle(q, k, v):
        return mha_reference(q, k, v, None, True, window)

    np.testing.assert_allclose(flash(q, k, v), oracle(q, k, v),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * co), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(oracle(*a) * co), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


def test_flash_ntc_takes_fewer_kv_heads_and_a_window():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 48, 7, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 48, 1, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 48, 1, 16), jnp.float32)
    flat = lambda a: a.reshape(1, 48, -1)                       # noqa: E731
    got = flash_attention_ntc(flat(q), flat(k), flat(v), 7, causal=True,
                              interpret=True, window=20).reshape(q.shape)
    tr = lambda a: a.transpose(0, 2, 1, 3)                      # noqa: E731
    want = tr(mha_reference(tr(q), tr(k), tr(v), None, True, 20))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(tr(q), tr(k), tr(v), window=20)
    with pytest.raises(ValueError, match="share"):
        flash_attention(tr(q)[:, :5], tr(k).repeat(2, 1), tr(v).repeat(2, 1))


def test_program_through_the_flash_kernel_matches_the_reference(monkeypatch):
    """The same tiny model with the Pallas kernels in the path (interpret
    mode): window, groups and rope reach the kernel as the layer's kind."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)   # as on one chip
    config = tiny(program={"fused_loss": True, "remat": True,
                           "remat_policy": "full", "loss_chunk": 16,
                           "use_flash_attention": True})
    sz, cfg = ref.sizes_of(config), moe_train.program_config(config)
    assert tfm.attention_path(cfg, 16, jnp.float32) == "flash"
    params = ref.make_weights(9, sz)
    ids, tgt = _batch(sz, seed=9, batch=1, seq=16)
    want, g_want = jax.value_and_grad(ref.loss)(params, ids, tgt, sz)
    got, g_got = jax.value_and_grad(tfm.lm_loss)(params, cfg, ids, tgt)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    for name, w in _leaves(g_want).items():
        gap = float(jnp.max(jnp.abs(_leaves(g_got)[name] - w))
                    / jnp.max(jnp.abs(w)))
        assert gap <= 2e-5, (name, gap)


@pytest.mark.parametrize("fields,error", [
    (dict(n_heads=4, n_kv_heads=3), ValueError),
    (dict(mlp="geglu"), ValueError),
    (dict(layer_positions=("rope", "none"), layer_windows=(0,)), ValueError),
    (dict(layer_positions=("rope",) * 3), ValueError),      # 3 does not divide 4
    (dict(n_experts=8, experts_held=(6, 3)), ValueError),
    (dict(n_experts=8, mlp="reglu"), NotImplementedError),
    (dict(n_experts=8, router_input="pre_attention"), NotImplementedError),
], ids=["kv_heads", "mlp", "period_lengths", "period_divides", "share",
        "capacity_reglu", "capacity_pre_attention"])
def test_fields_no_code_computes_are_refused(fields, error):
    cfg = tfm.TransformerConfig(**{"vocab_size": 50, "d_model": 32,
                                   "n_heads": 4, "n_layers": 4, "d_ff": 16,
                                   "max_seq": 16, **fields})
    with pytest.raises(error):
        tfm.init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("window", [0, 20], ids=["full", "window_20"])
def test_bf16_scores_path_takes_groups_and_a_window(window):
    """The XLA path bf16 activations take off the chip: grouped K/V heads
    written out, the band masked, against the float32 oracle."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (2, 48, 6, 16), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 48, 2, 16), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 48, 2, 16), jnp.bfloat16)
    got = tfm._xla_attention_bf16_scores(q, k, v, window=window)
    tr = lambda a: a.astype(jnp.float32).transpose(0, 2, 1, 3)  # noqa: E731
    want = tr(mha_reference(tr(q), tr(k), tr(v), None, True, window or None))
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=3e-2)


def test_dense_reglu_mlp_is_the_gated_formula():
    cfg = tfm.TransformerConfig(vocab_size=50, d_model=32, n_heads=4,
                                n_layers=2, d_ff=16, max_seq=16, mlp="reglu",
                                dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    w_in, w_out = params["blocks"]["w_in"][0], params["blocks"]["w_out"][0]
    assert w_in.shape == (32, 32) and w_out.shape == (16, 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 32), jnp.float32)
    want = (jax.nn.relu(x @ w_in[:, :16]) * (x @ w_in[:, 16:])) @ w_out
    np.testing.assert_allclose(tfm._dense_mlp(cfg, x, w_in, w_out), want,
                               rtol=1e-5, atol=1e-6)


def test_serving_engine_refuses_the_blocks_it_cannot_decode():
    from deeplearning4j_tpu.serving import GenerationEngine
    cfg = tfm.TransformerConfig(vocab_size=50, d_model=32, n_heads=4,
                                n_kv_heads=2, n_layers=2, d_ff=16, max_seq=16,
                                layer_positions=("rope",), dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="GPT-2-style"):
        GenerationEngine(cfg, params)


def test_init_params_draws_the_tree_the_reference_draws():
    config = tiny()
    sz, cfg = ref.sizes_of(config), moe_train.program_config(config)
    mine = _leaves(tfm.init_params(jax.random.PRNGKey(0), cfg))
    theirs = _leaves(ref.make_weights(0, sz))
    assert {k: v.shape for k, v in mine.items()} == \
        {k: v.shape for k, v in theirs.items()}
    assert "['pos_embed']" not in mine and "['head']" in mine


def test_expert_load_reaches_the_registry():
    from deeplearning4j_tpu.obs import get_registry
    from deeplearning4j_tpu.obs.moe import record_expert_load
    reg = get_registry()
    before = {n: (reg.get(n).value() if reg.get(n) else 0.0)
              for n in ("dl4j_moe_assignments_total",
                        "dl4j_moe_local_assignments_total",
                        "dl4j_moe_dropped_total")}
    read = record_expert_load(np.array([[72, 27, 0, 1.4], [72, 30, 0, 1.9]],
                                       np.float32))
    assert read["assignments"] == 144 and read["local"] == 57
    assert read["dropped"] == 0 and abs(read["max_over_mean"] - 1.9) < 1e-6
    assert reg.get("dl4j_moe_assignments_total").value() \
        == before["dl4j_moe_assignments_total"] + 144
    assert reg.get("dl4j_moe_local_assignments_total").value() \
        == before["dl4j_moe_local_assignments_total"] + 57
    assert reg.get("dl4j_moe_dropped_total").value() \
        == before["dl4j_moe_dropped_total"]
    assert abs(reg.get("dl4j_moe_load_max_over_mean").value() - 1.9) < 1e-6
