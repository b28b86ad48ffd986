"""The dropless expert layer's way back (``zoo.transformer._rows_back``):
expert order to tokens, weighted and summed, with a backward rule of its own
that forms the rows' gradient IN EXPERT ORDER and only for the ``n_local``
rows routed here (``_scaled_rows``: a pass of rows at a time,
``ceil(n_local / pass)`` passes; the rows after them stay unset).

Here, on the CPU, for the three expert cells' routed layers at toy widths:
the rule's gradients are the numbers JAX's own transposition of the forward
gives, the rows in use to the last bit; that holds with no assignment local,
every assignment local and one row past a pass's edge; whatever lies in the
rows after them reaches nothing (they are overwritten with NaN and ``y``, the
numbers told and every gradient stay finite and the same); nothing is
dropped; through ``_moe_share`` every gradient of the layer equals the plain
formulation's; the counter of the rows moved.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.zoo import transformer as tfm

N = 48          # tokens
PASS = 16       # rows a pass of the mover (``_ROWS_A_PASS``) in these tests

#: the three cells' routed layers at toy widths: K choices of E outputs
#: (the zaya router's last output is its skip), `held` experts from `first`
ROUTERS = {
    "smallthinker_top6_of_64_holds_16": dict(
        k=6, outputs=64, first=16, held=16, d=32, dtype=jnp.float32,
        mlp="reglu", skip=False),
    "glm_top4_of_64_holds_8_bf16": dict(
        k=4, outputs=64, first=8, held=8, d=48, dtype=jnp.bfloat16,
        mlp="swiglu", skip=False),
    "zaya_top1_of_16_and_skip_holds_8": dict(
        k=1, outputs=17, first=0, held=8, d=32, dtype=jnp.float32,
        mlp="swiglu", skip=True),
    "zaya_top1_bf16_wide": dict(
        k=1, outputs=17, first=8, held=8, d=128, dtype=jnp.bfloat16,
        mlp="swiglu", skip=True),
}
ROUTINGS = ("as_routed", "none_local", "all_local", "one_past_a_pass")
CASES = [(c, r) for c in ROUTERS for r in ROUTINGS]
IDS = [f"{c}-{r}" for c, r in CASES]


def _layer(case, routing):
    """(cfg, x, chosen, weight, we_in, we_out) of one routed layer."""
    c = ROUTERS[case]
    experts = c["outputs"] - c["skip"]
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=c["d"], n_heads=2, n_layers=1, d_ff=24,
        max_seq=N, n_experts=experts, expert_top_k=c["k"], mlp=c["mlp"],
        experts_held=(c["first"], c["held"]), router_skip=c["skip"],
        router="mlp" if c["skip"] else "linear", dtype=c["dtype"])
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 5)
    x = jax.random.normal(keys[0], (1, N, c["d"]), c["dtype"])
    _, chosen = jax.lax.top_k(
        jax.random.normal(keys[1], (N, c["outputs"])), c["k"])
    chosen = chosen.T.astype(jnp.int32)                         # (K, N)
    weight = jax.random.uniform(keys[2], chosen.shape, jnp.float32, 0.1, 1.0)
    we_in = (jax.random.normal(keys[3], (c["held"], c["d"], 48))
             / np.sqrt(c["d"])).astype(c["dtype"])
    we_out = (jax.random.normal(keys[4], (c["held"], 24, c["d"]))
              / np.sqrt(24)).astype(c["dtype"])
    first, held = cfg.experts_held
    absent = (first + held) % experts
    home = first + jnp.arange(chosen.size).reshape(chosen.shape) % held
    if routing == "none_local":
        chosen = jnp.full_like(chosen, absent)
    elif routing == "all_local":
        chosen = home
    elif routing == "one_past_a_pass":
        chosen = jnp.where(jnp.arange(chosen.size).reshape(chosen.shape)
                           <= PASS, home, absent)
    return cfg, x, chosen, weight, we_in, we_out


def _sorted(cfg, chosen):
    """``_moe_share``'s own sort: (local, order, inv, sizes)."""
    first, held = cfg.experts_held
    local = (chosen >= first) & (chosen < first + held)
    slot = jnp.where(local, chosen - first, held).reshape(-1)
    order = jnp.argsort(slot, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.size, dtype=jnp.int32))
    sizes = jnp.sum(slot[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    return local, order, inv, sizes


def _plain_back(rows, weight, inv, local):
    """The way back as plain array code, for JAX to transpose."""
    parts = jnp.where(local[:, :, None],
                      rows[inv].reshape(*local.shape, -1), 0)
    return jnp.sum(parts.astype(jnp.float32) * weight[:, :, None], axis=0
                   ).astype(rows.dtype)


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.fixture(autouse=True)
def short_passes(monkeypatch):
    monkeypatch.setattr(tfm, "_ROWS_A_PASS", PASS)


@pytest.mark.parametrize("case,routing", CASES, ids=IDS)
def test_the_way_backs_own_gradient_is_the_transposed_forwards(case, routing):
    cfg, x, chosen, weight, _, _ = _layer(case, routing)
    local, order, inv, sizes = _sorted(cfg, chosen)
    n_local = int(jnp.sum(sizes))
    assert n_local == {"none_local": 0, "all_local": chosen.size,
                       "one_past_a_pass": PASS + 1}.get(routing, n_local)
    rows = jax.random.normal(jax.random.PRNGKey(7),
                             (chosen.size, x.shape[-1]), x.dtype)
    g = jax.random.normal(jax.random.PRNGKey(8), (N, x.shape[-1]), x.dtype)
    y, pull = jax.jit(lambda r, w: jax.vjp(
        lambda r, w: tfm._rows_back(r, w, order, inv, local, n_local),
        r, w))(rows, weight)
    y_plain, pull_plain = jax.jit(lambda r, w: jax.vjp(
        lambda r, w: _plain_back(r, w, inv, local), r, w))(rows, weight)
    # the same expression twice, but not one compilation: a fused
    # multiply-add may round a float32 sum over K differently
    np.testing.assert_allclose(_f32(y), _f32(y_plain), rtol=1e-6, atol=1e-6)
    (d_rows, d_weight), (p_rows, p_weight) = pull(g), pull_plain(g)
    # in expert order the rows in use come first: theirs is the gradient the
    # grouped products read, and it is the transposed forward's to the bit
    np.testing.assert_array_equal(_f32(d_rows[:n_local]),
                                  _f32(p_rows[:n_local]))
    np.testing.assert_allclose(_f32(d_weight), _f32(p_weight),
                               rtol=1e-6, atol=1e-6)
    if routing == "none_local":
        assert not _f32(y).any() and not _f32(d_weight).any()


def _plain_share(cfg, x, chosen, weight, we_in, we_out):
    """``_moe_share``'s value with no backward rule of its own anywhere."""
    local, order, inv, sizes = _sorted(cfg, chosen)
    tokens = x.reshape(N, -1)
    rows = tokens[order % N]
    hidden = tfm._mlp_act(cfg, jax.lax.ragged_dot(rows, we_in, sizes))
    out = jax.lax.ragged_dot(hidden, we_out, sizes)
    # rows past the held experts' are unset by the grouped products
    out = jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None],
                    out, 0)
    return _plain_back(out, weight, inv, local).reshape(x.shape)


@pytest.mark.parametrize("case,routing", CASES, ids=IDS)
def test_every_gradient_of_the_layer_is_the_plain_formulations(case, routing):
    cfg, x, chosen, weight, we_in, we_out = _layer(case, routing)
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape, jnp.float32)

    def loss(share):
        def f(x, weight, we_in, we_out):
            y = share(cfg, x, chosen, weight, we_in, we_out)
            y = y[0] if isinstance(y, tuple) else y
            return jnp.sum(y.astype(jnp.float32) * probe)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3)))(
            x, weight, we_in, we_out)

    (got, g_got), (want, g_want) = loss(tfm._moe_share), loss(_plain_share)
    tol = 1e-5 if x.dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(float(got), float(want), rtol=tol, atol=tol)
    for a, b in zip(g_got, g_want):
        a, b = _f32(a), _f32(b)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, rtol=tol,
                                   atol=tol * max(1.0, np.abs(b).max()))


def _poisoning(real):
    """A mover that moves what ``real`` moves and leaves NaN in every row
    after the ``count`` it was asked for."""
    def move(src, order, scale, count, into):
        out = real(src, order, scale, count, into)
        return jnp.where((jnp.arange(out.shape[0]) < count)[:, None], out,
                         jnp.nan)
    return move


@pytest.mark.parametrize("case,routing", CASES, ids=IDS)
def test_nothing_past_the_rows_in_use_reaches_the_layer(case, routing,
                                                        monkeypatch):
    cfg, x, chosen, weight, we_in, we_out = _layer(case, routing)

    def run(x, weight, we_in, we_out):
        y, stats, moved = tfm._moe_share(cfg, x, chosen, weight, we_in,
                                         we_out)
        return jnp.sum(y.astype(jnp.float32) ** 2), (y, stats, moved)

    def grads():
        return jax.jit(jax.value_and_grad(run, argnums=(0, 1, 2, 3),
                                          has_aux=True))(
            x, weight, we_in, we_out)

    (_, (y, stats, moved)), g = grads()
    monkeypatch.setattr(tfm, "_scaled_rows", _poisoning(tfm._scaled_rows))
    (_, (y_p, stats_p, moved_p)), g_p = grads()
    for a, b in zip(jax.tree_util.tree_leaves((y, stats, moved, g)),
                    jax.tree_util.tree_leaves((y_p, stats_p, moved_p, g_p))):
        assert np.isfinite(_f32(a)).all() and np.isfinite(_f32(b)).all()
        np.testing.assert_array_equal(_f32(a), _f32(b))
    n_local = float(jnp.sum(_sorted(cfg, chosen)[3]))
    assert float(stats[0]) == chosen.size and float(stats[1]) == n_local
    assert float(stats[2]) == 0                     # nothing dropped
    # whole passes, and never more than a pass over the rows in use
    assert float(moved) == min(-(-n_local // PASS) * PASS, chosen.size)
    if routing == "none_local":
        assert not _f32(y).any() and float(moved) == 0


def test_a_buffer_that_is_no_multiple_of_the_pass_is_filled_to_its_end():
    """50 rows in passes of 16: the fourth steps back to row 34."""
    src = jnp.arange(20 * 8, dtype=jnp.float32).reshape(20, 8)
    order = jax.random.permutation(jax.random.PRNGKey(0), 50)
    scale = jnp.linspace(0.5, 2.0, 50)
    want = src[order % 20] * scale[order][:, None]
    into = jnp.full((50, 8), -1.0)
    for count in (0, 1, 16, 17, 49, 50):
        out = jax.jit(tfm._scaled_rows)(src, order, scale, count, into)
        np.testing.assert_array_equal(out[:count], want[:count])
        crossed = int(tfm._rows_crossed(count, 50))
        np.testing.assert_array_equal(out[crossed:], into[crossed:])
        assert float(tfm._rows_crossed(count, 50)) \
            == min(-(-count // PASS) * PASS, 50)


def test_rows_moved_are_counted_beside_the_assignments():
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.obs.moe import record_expert_load
    reg = obs.get_registry()
    load = np.array([[96., 30., 0., 1.5], [96., 20., 0., 1.2]], np.float32)
    got = reg.get("dl4j_moe_rows_moved_total")
    before = got.value() if got is not None else 0.0
    assert "moved" not in record_expert_load(load)      # a step that tells none
    read = record_expert_load({"load": load,
                               "moved": np.array([32., 32.], np.float32)})
    assert read["moved"] == 64.0 and read["assignments"] == 192.0
    assert reg.get("dl4j_moe_rows_moved_total").value() == before + 64.0
