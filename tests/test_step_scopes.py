"""The step's account by scope (ISSUE 37): ``zoo.transformer.STEP_SCOPES``
is the table of the training step's top-level ``jax.named_scope``s. Held here,
for a tiny configuration of each LM family and for the ``ComputationGraph``
step: every operation that costs device time sits under a scope; no
operation sits under two; the compiled step carries the names; the
benchmark's metric files read exactly the table; and a scope changes no
number."""
import contextlib
import json
import re
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeplearning4j_tpu.nn._fit_common import build_train_step
from deeplearning4j_tpu.zoo import transformer as tfm

ROOT = Path(__file__).resolve().parents[1]
METRICS = ROOT / "benchmark" / "layer_metrics"
TABLE = tuple(name for name, _ in tfm.STEP_SCOPES)
#: the primitives that are a step's device time: every one must have a name
COSTLY = {"dot_general", "gather", "scatter-add", "scatter_add", "ragged_dot",
          "ragged_dot_general", "pallas_call", "conv_general_dilated"}

_TINY = dict(vocab_size=50, max_seq=64, dtype=jnp.float32, fused_loss=True,
             loss_chunk=16, use_flash_attention=True)
#: the benchmark's four LM families at a tiny width, the Pallas kernels in
#: the path (interpret mode) as on the chip
FAMILIES = {
    "dense": tfm.TransformerConfig(
        **_TINY, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        remat_policy="save_attn", tie_embeddings=True),
    "held_experts_window": tfm.TransformerConfig(
        **_TINY, d_model=32, n_heads=4, n_layers=4, d_ff=16, n_experts=8,
        expert_top_k=3, n_kv_heads=2, head_size=8,
        layer_positions=("none", "rope", "rope", "rope"),
        layer_windows=(0, 5, 5, 5), rope_theta=1.5e6, embed_scale=False,
        mlp="reglu", experts_held=(2, 3), router_input="pre_attention"),
    "cca_mlp_router": tfm.TransformerConfig(
        **_TINY, d_model=48, n_heads=4, n_layers=3, d_ff=16, n_experts=6,
        expert_top_k=1, remat_policy="save_attn", tie_embeddings=True,
        n_kv_heads=2, head_size=8, layer_positions=("rope",),
        layer_windows=(0,), rope_theta=5e6, embed_scale=False, mlp="swiglu",
        experts_held=(1, 3), norm_eps=1e-5, attention="cca",
        rotary_share=0.5, router="mlp", router_hidden=12, router_skip=True,
        scaled_residuals=True),
    "mla_dense_group_mtp": tfm.TransformerConfig(
        **_TINY, d_model=32, n_heads=3, n_layers=3, d_ff=40, n_experts=8,
        remat_policy="save_attn", layer_positions=("rope",),
        layer_windows=(0,), rope_theta=1e6, embed_scale=False, mlp="swiglu",
        experts_held=(2, 3), norm_eps=1e-5, attention="mla",
        router="sigmoid", q_rank=12, kv_rank=8, nope_head_size=6,
        rope_head_size=4, v_head_size=10, dense_layers=1, expert_ff=12,
        shared_experts=1, router_scale=1.8, predict_ahead=1),
}
MOE = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
#: the scopes a family's compiled step must carry (the head reshapes of
#: attn_qkv are no operation once compiled, so only plain heads show it)
EXPECTED = {
    "dense": ("embed", "attn_qkv", "attn_wo", "attn_core", "mlp",
              "resid_norm", "optimizer", "lm_head", "flash_fwd",
              "flash_bwd_dq", "flash_bwd_dkv"),
    "held_experts_window": (
        "embed", "attn_qkv", "attn_wo", "attn_core", "resid_norm",
        "optimizer", "lm_head", "flash_fwd_win", "flash_bwd_dkv_win", *MOE),
    "cca_mlp_router": (
        "embed", "attn_wo", "attn_core", "resid_norm", "optimizer",
        "lm_head", "cca_proj", "cca_mix", "flash_fwd", *MOE),
    "mla_dense_group_mtp": (
        "embed", "attn_wo", "attn_core", "mlp", "resid_norm", "optimizer",
        "lm_head", "mla_q", "mla_kv", "mla_rope", "moe_shared", "mtp",
        "flash_fwd", *MOE),
    "cg": ("optimizer", "stem.ConvolutionLayer", "stem_bn.BatchNormalization",
           "b0_add.ElementWiseVertex", "gap.GlobalPoolingLayer",
           "out.OutputLayer.loss"),
}
NODE = re.compile(r"[^/()]*\.[A-Z][A-Za-z0-9]+(?:\.loss)?$")


def _one_chip():
    """``flash_engages`` asks for one device, as on the benchmark's chip."""
    return mock.patch.object(jax, "device_count", lambda: 1)


def _lm_step(cfg):
    """(step, its arguments) of an LM family; AdamW as the benchmark's."""
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    return tfm.make_train_step(cfg, opt), (params, opt.init(params), ids, tgt)


def _cg_step():
    """(step, its arguments) of the one fit() step, on a small residual CNN
    with training batch norm."""
    from test_remat_cg import _residual_cnn
    net = _residual_cnn()
    net._build_optimizer()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 8, 8, 3)), jnp.float32)
    y = jnp.asarray(np.eye(5, dtype=np.float32)[rng.integers(0, 5, 4)])
    return build_train_step(net, "cg_train_step")[1], (
        net.params, net.states, net._opt_state, x, y, jax.random.PRNGKey(1),
        None, None)


def _step(family):
    return _cg_step() if family == "cg" else _lm_step(FAMILIES[family])


def _sub_jaxprs(eqn):
    if eqn.primitive.name == "pallas_call":     # a kernel is one operation
        return
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def _equations(jaxpr, outer=""):
    """(primitive, whole name stack) of every equation, through scan, remat,
    custom_vjp, cond and pjit bodies: a body's stacks are relative to its
    equation's, as lowering joins them."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, stack
        for inner in _sub_jaxprs(eqn):
            yield from _equations(inner, stack)


def _scopes_of(stack, names):
    """The components of a name stack, inside any jvp(...) / transpose(...)
    wrapper, that START with one of ``names``: how the benchmark's readers
    match."""
    return {c for c in re.findall(r"[^/()]+", stack)
            if c.startswith(tuple(names))}


_CACHE = {}


def _traced(family):
    if family not in _CACHE:
        with _one_chip():
            step, args = _step(family)
            if family != "cg":
                assert tfm.attention_path(FAMILIES[family], 16,
                                          jnp.float32) == "flash"
            _CACHE[family] = list(_equations(
                jax.make_jaxpr(step)(*args).jaxpr))
    return _CACHE[family]


ALL = [*FAMILIES, "cg"]


@pytest.mark.parametrize("family", ALL)
def test_every_costly_operation_sits_under_a_scope(family):
    costly = [(p, s) for p, s in _traced(family) if p in COSTLY]
    assert len(costly) >= 10
    for prim, stack in costly:
        if family == "cg":
            named = "optimizer" in _scopes_of(stack, ["optimizer"]) or any(
                NODE.match(c) for c in re.findall(r"[^/()]+", stack))
        else:
            named = _scopes_of(stack, TABLE + tfm.KERNEL_SCOPES)
        assert named, (prim, stack)


@pytest.mark.parametrize("family", ALL)
def test_no_operation_sits_under_two_scopes(family):
    """The top-level scopes are disjoint, so their device times add up;
    ``mtp`` alone wraps others (a second cut, as the benchmark documents)."""
    names = [n for n in TABLE + tfm.KERNEL_SCOPES if n != "mtp"]
    seen = set()
    for prim, stack in _traced(family):
        under = _scopes_of(stack, names)
        assert len(under) <= 1, (prim, stack)
        seen |= under
    if family != "cg":      # and each is opened somewhere
        assert {s for s in EXPECTED[family] if s != "mtp"} <= seen


@pytest.mark.parametrize("family", ALL)
def test_the_compiled_step_carries_the_names(family):
    with _one_chip():
        step, args = _step(family)
        text = jax.jit(step).lower(*args).compile().as_text()
    stacks = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in EXPECTED[family]:
        assert any(scope in _scopes_of(s, [scope]) or
                   (family == "cg" and scope in s) for s in stacks), scope
    if family != "cg":
        absent = set(TABLE) - set(EXPECTED[family]) - {"attn_qkv"}
        assert not any(_scopes_of(s, absent) for s in stacks), absent


def _reads(metric):
    spec = json.loads((METRICS / f"{metric}.json").read_text())
    args = spec["args"]
    if spec["reducer"] == "scope_time_share":
        return list(args["scopes"]), args.get("ops", []), False
    assert spec["reducer"] == "scope_regex_share"
    return args["pattern"].split("|"), args.get("ops", []), \
        args.get("rest", False)


def test_the_lm_metric_files_read_exactly_the_table():
    """Each scope of the table is read by ONE of the new shares, and the
    unscoped share leaves out all of them and every older scope: a scope
    added to the program without its metric, or the other way round, fails
    here."""
    shares = ["optimizer_time_share_pct.lm", "embed_time_share_pct.lm",
              "proj_time_share_pct.lm", "attn_core_time_share_pct.lm",
              "mlp_time_share_pct.lm", "norm_time_share_pct.lm"]
    read = [name for m in shares for name in _reads(m)[0]]
    assert sorted(read) == sorted(TABLE)
    names, ops, rest = _reads("unscoped_time_share_pct.lm")
    assert rest and sorted(names) == sorted(TABLE + tfm.KERNEL_SCOPES)
    assert set(ops) == {"ragged-dot", "flash_"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in bench["per_layer"]}
    for m in shares + ["unscoped_time_share_pct.lm"]:
        assert listed[m]["moves"] == "lm_train_tokens_per_s"
        assert listed[m]["workloads"]


def test_the_fit_metric_files_read_the_step_and_the_nodes():
    assert _reads("optimizer_time_share_pct.fit")[0] == ["optimizer"]

    def finds(metric):
        args = json.loads((METRICS / f"{metric}.json").read_text())["args"]
        under = re.compile("(?:^|[/(])(?:%s)" % args["pattern"])
        return lambda scope: bool(
            under.search(f"jit(step)/transpose(jvp({scope}))/mul:"))

    named = finds("unscoped_time_share_pct.fit")
    assert all(named(scope) for scope in EXPECTED["cg"])
    assert not named("reg_score") and not named("...d,df->...f")
    conv, bn = finds("conv_time_share_pct.fit"), finds("bn_time_share_pct.fit")
    assert conv("stem.ConvolutionLayer") and bn("stem_bn.BatchNormalization")
    assert not conv("stem_bn.BatchNormalization") and not conv("optimizer")
    assert not bn("stem.ConvolutionLayer") and not bn("out.OutputLayer.loss")


def test_no_name_starts_another_or_a_component_of_jaxs():
    """A name is matched as the START of a component: none may claim
    another's events, JAX's own components, or a primitive's."""
    names = TABLE + tfm.KERNEL_SCOPES
    for a in names:
        for b in names:
            assert a == b or not b.startswith(a), (a, b)
    jaxs = {"jit", "pjit", "jvp", "transpose", "vmap", "while", "body",
            "cond", "branch", "checkpoint", "rematted_computation",
            "closed_call", "remat", "custom_vjp_call", "custom_jvp_call",
            "shard_map", "scan"}
    jaxs |= {p for f in ALL for p, _ in _traced(f)}
    for f in ALL:       # and the components the traced steps really hold
        for _, stack in _traced(f):
            jaxs |= {c for c in re.findall(r"[^/()]+", stack)
                     if not c.startswith(names) and not NODE.match(c)}
    for name in TABLE:
        assert not any(c.startswith(name) for c in jaxs), name


def _three_steps(family):
    with _one_chip():
        step, args = _step(family)
        run, outs = jax.jit(step), []
        if family == "cg":
            params, states, opt, x, y, rng, *masks = args
            for _ in range(3):
                params, states, opt, loss, _, rng = run(
                    params, states, opt, x, y, rng, *masks)
                outs.append(loss)
            return outs, (params, states)
        params, opt, ids, tgt = args
        for _ in range(3):
            params, opt, loss, *told = run(params, opt, ids, tgt)
            outs.append((loss, told))
        return outs, params


@pytest.mark.parametrize("family", ALL)
def test_a_scope_changes_no_number(family, monkeypatch):
    """Three steps with every ``jax.named_scope`` of the package a null
    context give the losses, what the step tells and the parameters of the
    scoped step, to the last bit: a scope is metadata."""
    want = _three_steps(family)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    with _one_chip():
        step, args = _step(family)
        bare = list(_equations(jax.make_jaxpr(step)(*args).jaxpr))
    assert not any(_scopes_of(s, TABLE) for _, s in bare)   # really off
    got = _three_steps(family)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
