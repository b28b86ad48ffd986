"""Pallas kernel tests — run in interpreter mode on the CPU mesh, checked
against plain-XLA oracles (SURVEY.md §7 R2 item, pulled into R1)."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels.flash_attention import (flash_attention,
                                                        mha_reference)

RNG = np.random.default_rng(7)


def _qkv(b=2, h=3, t=64, d=16, dtype=np.float32):
    return tuple(jnp.asarray(RNG.standard_normal((b, h, t, d)).astype(dtype))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference_forward(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, None, causal, 32, 16)
    ref = mha_reference(q, k, v, None, causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference_grads(causal):
    q, k, v = _qkv(t=32, d=8)
    w = jnp.cos(jnp.arange(8))

    def f(impl):
        def loss(q_, k_, v_):
            o = (flash_attention(q_, k_, v_, None, causal, 16, 16) if impl
                 else mha_reference(q_, k_, v_, None, causal))
            return jnp.sum(o * w)
        return loss

    g = jax.grad(f(True), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f(False), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_flash_kernels_carry_stable_names():
    """``name=`` on the three pallas_calls, inside a named_scope of the same
    name: the lowered program (with debug info) and the jaxpr hold each, so
    a trace reduction can tell forward, dq and dkv apart."""
    q = jnp.zeros((1, 2, 128, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2))
    lowered = jax.jit(grad).lower(q, q, q).as_text(debug_info=True)
    jaxpr = str(jax.make_jaxpr(grad)(q, q, q))
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in lowered, name
        assert name in jaxpr, name


def _leaves_equal(a, b):
    return all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


@pytest.mark.parametrize("kind,hkv,window", [
    ("plain", 4, None), ("plain", 4, 20), ("plain", 2, 20), ("lse", 4, None),
], ids=["causal", "causal_window", "grouped_kv_window", "with_lse"])
def test_save_attn_runs_the_flash_forward_once_a_block(kind, hkv, window):
    """The kernel names its output and log-sum-exp; the policy that
    ``_remat_wrap("save_attn")`` builds keeps both, so a scanned block's
    gradient holds forward, dq and dkv once each. ``"full"`` saves nothing
    and runs the forward kernel again. Either way the gradient is the one
    that no ``jax.checkpoint`` gives, to the bit."""
    from deeplearning4j_tpu.kernels.flash_attention import flash_attention_lse
    from deeplearning4j_tpu.zoo.transformer import _remat_wrap

    b, h, t, d, width = 1, 4, 32, 8, 24

    def attn(q, k, v):
        if kind == "lse":
            o, lse = flash_attention_lse(q, k, v, None, True, 16, 16, True)
            return o * jnp.tanh(lse)[..., None]     # the lse has a cotangent
        return flash_attention(q, k, v, None, True, 16, 16, True, window)

    def block(x, w):
        xn = jnp.tanh(x)
        heads = lambda y, n: y.reshape(b, t, n, d).transpose(0, 2, 1, 3)  # noqa: E731,E501
        o = attn(heads(xn @ w["q"], h), heads(xn @ w["k"], hkv),
                 heads(xn @ w["v"], hkv))
        return x + o.transpose(0, 2, 1, 3).reshape(b, t, h * d) @ w["o"], None

    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    shapes = {"q": (width, h * d), "k": (width, hkv * d),
              "v": (width, hkv * d), "o": (h * d, width)}
    ws = {n: 0.2 * jax.random.normal(k_, (2, *shp))      # two scanned blocks
          for k_, (n, shp) in zip(ks, shapes.items())}
    x = jax.random.normal(ks[4], (b, t, width))

    def grad_of(policy):
        fn = block if policy is None else _remat_wrap(block, policy)
        g = jax.grad(lambda ws_, x_: jnp.sum(jnp.sin(
            jax.lax.scan(fn, x_, ws_)[0])), argnums=(0, 1))
        return str(jax.make_jaxpr(g)(ws, x)).count("pallas_call["), g(ws, x)

    n_plain, g_plain = grad_of(None)
    n_attn, g_attn = grad_of("save_attn")
    n_full, g_full = grad_of("full")
    assert (n_plain, n_attn, n_full) == (3, 3, 4)
    assert _leaves_equal(g_attn, g_plain)
    assert _leaves_equal(g_full, g_plain)


def _vocab_products_and_loops(text, vocab):
    """(products with the vocabulary dimension in their result or in an
    operand, loops that hold such a product) in a compiled module's text; a
    loop's body counts with every computation it calls."""
    comps = {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S)}
    wide = {name for name, dims in re.findall(
        r"%([\w.\-]+) = \w+\[([\d,]*)\]", text)
        if str(vocab) in dims.split(",")}

    def products(body):
        return sum(
            name in wide or any(op in wide for op in
                                re.findall(r"%([\w.\-]+)", operands))
            for name, operands in re.findall(
                r"%([\w.\-]+) = [^\n]*? dot\(([^)]*)\)", body))

    def holds(name, seen):
        if name in seen or name not in comps:
            return False
        seen.add(name)
        return products(comps[name]) > 0 or any(
            holds(callee, seen) for callee in re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                comps[name]))

    loops = sum(holds(body, set()) for body in re.findall(
        r" while\([^\n]*body=%?([\w.\-]+)", text))
    return products(text), loops


def test_chunked_head_multiplies_by_the_vocabulary_three_times_a_chunk():
    """A tiny tied LM step under ``value_and_grad``, compiled: the chunked
    head's loop holds the logits' product, ``dx`` and the table's gradient,
    and there is no second head loop that forms the logits again (with a
    ``jax.checkpoint`` around each chunk it was 4 products in 2 loops). The
    loss alone, which nothing differentiates, multiplies once."""
    from deeplearning4j_tpu.zoo import transformer as tfm
    vocab = 509     # no other dimension of the step
    cfg = tfm.TransformerConfig(vocab_size=vocab, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=16,
                                dtype=jnp.float32, tie_embeddings=True,
                                fused_loss=True, loss_chunk=16)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    ids = jnp.zeros((4, 16), jnp.int32)
    loss = lambda p: tfm.lm_loss(p, cfg, ids, ids)         # noqa: E731

    def compiled(fn):
        return jax.jit(fn).lower(params).compile().as_text()

    assert _vocab_products_and_loops(
        compiled(jax.value_and_grad(loss)), vocab) == (3, 1)
    assert _vocab_products_and_loops(compiled(loss), vocab) == (1, 1)


@pytest.mark.parametrize("head_size,kv_heads,out_shape", [
    (128, 2, "2,32,512"), (64, 4, "2,32,256"), (8, 2, "8,32,8"),
], ids=["ntc", "ntc_pairs", "transposed"])
def test_save_attn_through_the_transformer_saves_one_output_and_one_lse(
        monkeypatch, capsys, head_size, kv_heads, out_shape):
    """``zoo.transformer`` with the kernels in the path (interpret mode), a
    stack of two kinds of layer, ``remat_policy="save_attn"``: the loss and
    every gradient equal the ones without rematerialization, and what the
    backward pass is handed per layer is ONE copy of the attention output,
    the kernel's own ((B, T, H·Dh) where the kernels read the projections'
    layout, (B·H, T, Dh) where they transpose), and the kernel's (B·H, 1, T)
    lse."""
    from deeplearning4j_tpu.zoo import transformer as tfm
    monkeypatch.setattr(jax, "device_count", lambda: 1)   # as on one chip

    def cfg_of(**over):
        kw = dict(vocab_size=50, d_model=48, n_heads=4, n_kv_heads=kv_heads,
                  head_size=head_size, n_layers=4, d_ff=40, max_seq=32,
                  dtype=jnp.float32, layer_positions=("none", "rope"),
                  layer_windows=(0, 20), use_flash_attention=True,
                  fused_loss=False, remat=True, remat_policy="save_attn")
        return tfm.TransformerConfig(**{**kw, **over})

    cfg = cfg_of()
    assert tfm.attention_path(cfg, 32, jnp.float32) == "flash"
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 50)
    tgt = jnp.roll(ids, -1, 1)
    loss = lambda p, c: tfm.lm_loss(p, c, ids, tgt)       # noqa: E731
    got, g_got = jax.value_and_grad(loss)(params, cfg)
    want, g_want = jax.value_and_grad(loss)(params, cfg_of(remat=False))
    assert float(got) == float(want)
    assert _leaves_equal(g_got, g_want)

    def saved(c):
        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(lambda p: loss(p, c), params)
        shapes = [line.split()[0] for line in
                  capsys.readouterr().out.splitlines() if " scan " in line]
        # stacked over the 2 periods; one line per layer of the period:
        # the kernel's output, the output as (B, T, H, Dh), and the lse
        return tuple(shapes.count(f"f32[2,{shape}]") for shape in
                     (out_shape, f"2,32,4,{head_size}", "8,1,32"))

    assert saved(cfg) == (2, 0, 2)
    assert saved(cfg_of(remat_policy="full")) == (0, 0, 0)
    # without the kernel the XLA path's output keeps its name, and no lse
    assert saved(cfg_of(use_flash_attention=False,
                        attn_scores_bf16=False)) == (0, 2, 0)


def _layout_count(layout):
    from deeplearning4j_tpu.obs import get_registry
    return get_registry().counter(
        "dl4j_flash_layout_total", labelnames=("layout",)).value(layout=layout)


@pytest.mark.parametrize("h,hkv,d,window,layout", [
    (4, 4, 64, None, "ntc_pairs"),          # gpt2: two heads a tile
    (4, 4, 32, 24, "ntc_pairs"),            # four heads a tile
    (7, 1, 128, None, "ntc"),               # the moe cell's group of 7
    (7, 1, 128, 24, "ntc"),
    (8, 2, 128, None, "ntc"),               # zaya's group of 4
    (8, 2, 128, 24, "ntc"),
    (5, 5, 256, None, "ntc"),               # glm's head of 256
    (3, 3, 64, None, "transposed"),         # odd heads cannot pair
    (4, 2, 64, 24, "transposed"),           # nor grouped ones
], ids=["d64_pairs", "d32_fours_window", "d128_group7", "d128_group7_window",
        "d128_group4", "d128_group4_window", "d256", "d64_odd_heads",
        "d64_grouped"])
def test_flash_ntc_reads_the_projections_layout(monkeypatch, h, hkv, d,
                                                 window, layout):
    """``flash_attention_ntc`` on (B, T, H·D) operands, several blocks of
    queries and keys (interpret mode): the output and all three gradients
    are ``mha_reference``'s, and the call counts the layout it took."""
    fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")
    monkeypatch.setattr(fa, "_tuned_blocks", lambda *a, **k: (16, 32))
    b, t = 2, 64
    q, k, v, w = (jnp.asarray(RNG.standard_normal((b, t, n * d)), jnp.float32)
                  for n in (h, hkv, hkv, h))

    def heads(x):
        return x.reshape(b, t, -1, d).transpose(0, 2, 1, 3)

    def ref(q_, k_, v_):
        o = mha_reference(heads(q_), heads(k_), heads(v_), None, True, window)
        return o.transpose(0, 2, 1, 3).reshape(b, t, h * d)

    def ntc(q_, k_, v_):
        return fa.flash_attention_ntc(q_, k_, v_, h, causal=True,
                                      interpret=True, window=window)

    before = _layout_count(layout)
    np.testing.assert_allclose(ntc(q, k, v), ref(q, k, v), atol=2e-5)
    assert _layout_count(layout) == before + 1
    got = jax.grad(lambda *a: jnp.sum(ntc(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(ref(*a) * w), (0, 1, 2))(q, k, v)
    for name, x, y in zip("qkv", got, want):
        np.testing.assert_allclose(x, y, atol=2e-5, err_msg=name)


def _eqns_outside_kernels(jaxpr):
    """Every equation of a jaxpr and its sub-jaxprs, the pallas_calls' own
    bodies left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_outside_kernels(sub)


@pytest.mark.parametrize("h,hkv,d,layout", [
    (4, 4, 64, "ntc_pairs"), (8, 2, 128, "ntc"),
], ids=["d64_pairs", "d128_group4"])
def test_flash_ntc_runs_no_layout_work_round_the_kernels(h, hkv, d, layout):
    """Forward and backward through ``flash_attention_ntc`` traced: three
    pallas_calls, and outside them no transpose and no broadcast to a
    minor dimension of 8 (the old 8-lane copies of lse and delta)."""
    b, t = 2, 256
    q = jnp.zeros((b, t, h * d), jnp.bfloat16)
    kv = jnp.zeros((b, t, hkv * d), jnp.bfloat16)
    from deeplearning4j_tpu.kernels.flash_attention import flash_attention_ntc

    def loss(q_, k_, v_):
        return flash_attention_ntc(q_, k_, v_, h, causal=True, interpret=True
                                   ).astype(jnp.float32).sum()

    before = _layout_count(layout)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv)
    assert _layout_count(layout) == before + 1
    eqns = list(_eqns_outside_kernels(jaxpr.jaxpr))
    assert [e.primitive.name for e in eqns].count("pallas_call") == 3
    assert not [e for e in eqns if e.primitive.name == "transpose"]
    assert not [e for e in eqns if e.primitive.name == "broadcast_in_dim"
                and e.params["shape"][-1] == 8]


def test_flash_odd_seq_falls_back_to_smaller_blocks():
    # t=48 not divisible by 32 → block sizes shrink to 16
    q, k, v = _qkv(t=48)
    out = flash_attention(q, k, v, None, True, 32, 32)
    ref = mha_reference(q, k, v, None, True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_flash_bf16_inputs():
    q, k, v = _qkv(t=32, d=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, None, False, 16, 16)
    ref = mha_reference(q, k, v, None, False)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < 2e-2


def test_fused_lstm_matches_reference_forward():
    from deeplearning4j_tpu.kernels.fused_lstm import (fused_lstm_seq,
                                                       lstm_seq_reference)
    b, t, h = 2, 12, 16
    xproj = jnp.asarray(RNG.standard_normal((b, t, 4 * h)).astype(np.float32))
    rw = jnp.asarray(RNG.standard_normal((h, 4 * h)).astype(np.float32) * 0.3)
    peep = jnp.asarray(RNG.standard_normal((3, h)).astype(np.float32) * 0.1)
    z = jnp.zeros((b, h))
    out = fused_lstm_seq(xproj, rw, peep, z, z, True)   # interpret mode
    ref = lstm_seq_reference(xproj, rw, peep, z, z)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_fused_lstm_grads_match_reference():
    from deeplearning4j_tpu.kernels.fused_lstm import (fused_lstm_seq,
                                                       lstm_seq_reference)
    b, t, h = 2, 8, 8
    xproj = jnp.asarray(RNG.standard_normal((b, t, 4 * h)).astype(np.float32))
    rw = jnp.asarray(RNG.standard_normal((h, 4 * h)).astype(np.float32) * 0.3)
    peep = jnp.asarray(RNG.standard_normal((3, h)).astype(np.float32) * 0.1)
    z = jnp.zeros((b, h))
    w = jnp.cos(jnp.arange(h))

    g = jax.grad(lambda *a: jnp.sum(fused_lstm_seq(*a, True) * w),
                 argnums=(0, 1, 2))(xproj, rw, peep, z, z)
    gr = jax.grad(lambda *a: jnp.sum(lstm_seq_reference(*a) * w),
                  argnums=(0, 1, 2))(xproj, rw, peep, z, z)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)


def test_lstm_layer_fused_path_matches_scan():
    """LSTM/GravesLSTM with fused=True (interpret) == the lax.scan path,
    forward AND parameter gradients, through the layer API."""
    from deeplearning4j_tpu.nn.layers.base import Ctx
    from deeplearning4j_tpu.nn.layers.recurrent import LSTM, GravesLSTM
    for cls in (LSTM, GravesLSTM):
        scan_l = cls(n_in=5, n_out=6, fused=False)
        fused_l = cls(n_in=5, n_out=6, fused=True)
        params, state, _ = scan_l.init(jax.random.PRNGKey(3), (7, 5))
        x = jnp.asarray(RNG.standard_normal((3, 7, 5)).astype(np.float32))
        y_scan, _ = scan_l.apply(params, state, x, Ctx())
        y_fused, _ = fused_l.apply(params, state, x, Ctx())
        np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_scan),
                                   atol=1e-5, err_msg=cls.__name__)

        def loss(l, p):
            y, _ = l.apply(p, state, x, Ctx())
            return jnp.sum(jnp.square(y))

        g_scan = jax.grad(lambda p: loss(scan_l, p))(params)
        g_fused = jax.grad(lambda p: loss(fused_l, p))(params)
        for key in params:
            np.testing.assert_allclose(np.asarray(g_fused[key]),
                                       np.asarray(g_scan[key]), atol=1e-4,
                                       err_msg=f"{cls.__name__}.{key}")
        # masked input must route to the scan path (fused can't freeze state)
        mask = jnp.ones((3, 7)).at[0, 5:].set(0.0)
        ym, _ = fused_l.apply(params, state, x, Ctx(mask=mask))
        ym_ref, _ = scan_l.apply(params, state, x, Ctx(mask=mask))
        np.testing.assert_allclose(np.asarray(ym), np.asarray(ym_ref),
                                   atol=1e-5)


def test_fused_bn_act_matches_reference():
    from deeplearning4j_tpu.kernels.fused_ops import (bn_act_reference,
                                                      fused_bn_act)
    n, c = 384, 24
    x = jnp.asarray(RNG.standard_normal((n, c)).astype(np.float32))
    scale = jnp.asarray(RNG.uniform(0.5, 2.0, c).astype(np.float32))
    shift = jnp.asarray(RNG.standard_normal(c).astype(np.float32))
    for act in ("identity", "relu", "tanh", "swish"):
        out = fused_bn_act(x, scale, shift, act, True)   # interpret mode
        ref = bn_act_reference(x, scale, shift, act)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, err_msg=act)
    # gradients flow via the recompute backward
    g = jax.grad(lambda x_: jnp.sum(
        jnp.square(fused_bn_act(x_, scale, shift, "relu", True))))(x)
    gr = jax.grad(lambda x_: jnp.sum(
        jnp.square(bn_act_reference(x_, scale, shift, "relu"))))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-5)


def test_batchnorm_fused_inference_matches_plain():
    """BN(activation=...) inference: fused pallas path == plain path; the
    activation field itself matches an explicit ActivationLayer after."""
    from deeplearning4j_tpu.nn.layers.base import Ctx
    from deeplearning4j_tpu.nn.layers.norm import BatchNormalization
    x = jnp.asarray(RNG.standard_normal((6, 5, 5, 8)).astype(np.float32))
    plain = BatchNormalization(activation="relu", fused=False)
    fused = BatchNormalization(activation="relu", fused=True)
    params, state, _ = plain.init(jax.random.PRNGKey(0), (5, 5, 8))
    # train a step so running stats are non-trivial
    _, state = plain.apply(params, state, x, Ctx(train=True))
    y_plain, _ = plain.apply(params, state, x, Ctx(train=False))
    y_fused, _ = fused.apply(params, state, x, Ctx(train=False))
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_plain),
                               atol=1e-5)
    assert float(jnp.min(y_fused)) >= 0.0    # relu actually applied


def test_autotune_picks_and_caches(tmp_path, monkeypatch):
    from deeplearning4j_tpu.kernels import autotune as at
    monkeypatch.setattr(at, "_CACHE_PATH", tmp_path / "autotune.json")
    at._memory_cache.clear()
    calls = []

    def make_run(cand):
        if cand == (9, 9):
            return None                     # invalid for the shape
        def run():
            calls.append(cand)
            time_cost = 0.02 if cand == (1, 1) else 0.0
            import time as _t
            _t.sleep(time_cost)
            return jnp.zeros(1)
        return run

    choice = at.autotune("k1", [(1, 1), (2, 2), (9, 9)], make_run)
    assert choice == (2, 2)                 # the fast one wins
    # cached: no further timing calls
    n = len(calls)
    assert at.autotune("k1", [(1, 1), (2, 2)], make_run) == (2, 2)
    assert len(calls) == n
    # disk cache survives a fresh in-process cache
    at._memory_cache.clear()
    assert at.autotune("k1", [(1, 1), (2, 2)], make_run) == (2, 2)
    assert len(calls) == n
    # disabled → first candidate, untimed
    assert at.autotune("k2", [(3, 3), (4, 4)], make_run,
                       enabled=False) == (3, 3)
    assert len(calls) == n


def test_autotune_keeps_error_text_and_raises_when_all_fail(tmp_path,
                                                            monkeypatch):
    """A candidate the compiler refuses keeps its error text in the
    record; with no candidate timed nothing is persisted and the call
    raises (never candidates[0] dressed up as a measurement)."""
    from deeplearning4j_tpu.kernels import autotune as at
    monkeypatch.setattr(at, "_CACHE_PATH", tmp_path / "autotune.json")
    at._memory_cache.clear()

    def make_run(cand):
        def run():
            if cand == (1, 1):
                raise ValueError("vmem exceeded for (1, 1)")
            return jnp.zeros(1)
        return run

    assert at.autotune("kerr", [(1, 1), (2, 2)], make_run) == (2, 2)
    meas = dict((tuple(c), t) for c, t in
                at.measurement_meta("kerr")["measurements"])
    assert "ValueError: vmem exceeded" in meas[(1, 1)]
    assert isinstance(meas[(2, 2)], float)

    with pytest.raises(RuntimeError, match="vmem exceeded"):
        at.autotune("kallfail", [(1, 1)], make_run)
    assert at.lookup("kallfail") is None
    assert "kallfail" not in at._memory_cache


def test_autotune_times_real_kernels_while_a_jit_is_tracing(tmp_path,
                                                            monkeypatch):
    """The flash block choice is made inside the model's traced forward
    (under scan and remat): candidates must still run on concrete arrays
    there. In the outer trace every array is a tracer and nothing can be
    fetched; under ensure_compile_time_eval a pallas kernel cannot even be
    traced (``program_id`` has no evaluation rule) — the first chip run of
    this kernel's tuner failed exactly so, for all seven candidates."""
    from deeplearning4j_tpu.kernels import autotune as at
    monkeypatch.setattr(at, "_CACHE_PATH", tmp_path / "autotune.json")
    at._memory_cache.clear()

    def make_run(cand):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 128, 32))
        grad = jax.jit(jax.grad(lambda q_, k_, v_: jnp.sum(
            flash_attention(q_, k_, v_, None, True, *cand, True))))
        return lambda: grad(q, q, q)

    def body(carry, _):
        bq, _bk = at.autotune("ktrace", [(64, 64), (128, 64)], make_run)
        return carry * bq, None

    @jax.jit
    def traced(y):
        return jax.lax.scan(jax.checkpoint(body), y, None, length=2)[0]

    assert float(traced(jnp.ones(()))) in (64.0 ** 2, 128.0 ** 2)
    timed = at.measurement_meta("ktrace")["measurements"]
    assert [c for c, _ in timed] == [[64, 64], [128, 64]]
    assert all(isinstance(t, float) for _, t in timed), timed


def test_tuned_blocks_defaults_off_tpu():
    # off-TPU fallback: the measured v5e sweet spot (512, 1024), clamped
    # to divisors of T (diag_t4096 phase-F sweep, 2026-08-01)
    from deeplearning4j_tpu.kernels.flash_attention import _tuned_blocks
    assert _tuned_blocks(2, 4, 256, 64, jnp.float32, True, None) == (256, 256)
    assert _tuned_blocks(4, 8, 4096, 64, jnp.bfloat16, True, None) == (512, 1024)


def test_self_attention_layer_pallas_impl_matches_xla():
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.base import Ctx
    x = jnp.asarray(RNG.standard_normal((2, 16, 32)).astype(np.float32))
    base = SelfAttentionLayer(n_in=32, n_out=32, n_heads=4)
    params, state, _ = base.init(jax.random.PRNGKey(0), (16, 32))
    y_xla, _ = base.apply(params, state, x, Ctx())
    pall = SelfAttentionLayer(n_in=32, n_out=32, n_heads=4, impl="pallas_interpret")
    y_pal, _ = pall.apply(params, state, x, Ctx())
    assert float(jnp.max(jnp.abs(y_xla - y_pal))) < 1e-4


def test_fused_bn_act_train_matches_autodiff_reference():
    """Training BN kernel: values AND all four gradients must match plain
    autodiff through batch-stats BN (the full d mean/d x, d var/d x paths,
    which the custom VJP implements analytically)."""
    from deeplearning4j_tpu.kernels.fused_ops import fused_bn_act_train
    n, c = 512, 16
    x = jnp.asarray(RNG.standard_normal((n, c)).astype(np.float32)) * 2 + 1.5
    gamma = jnp.asarray(RNG.uniform(0.5, 2.0, c).astype(np.float32))
    beta = jnp.asarray(RNG.standard_normal(c).astype(np.float32))
    center = jnp.asarray(RNG.standard_normal(c).astype(np.float32)) * 0.1
    eps = 1e-5

    def ref(x_, g_, b_, act):
        from deeplearning4j_tpu.kernels.fused_ops import _ACTS
        mean = jnp.mean(x_, axis=0)
        var = jnp.var(x_, axis=0)
        xhat = (x_ - mean) * jax.lax.rsqrt(var + eps)
        return _ACTS[act](xhat * g_ + b_)

    for act in ("identity", "relu", "tanh", "sigmoid"):
        y, mean, var = fused_bn_act_train(x, gamma, beta, center, eps, act,
                                          True)  # interpret mode
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, gamma, beta, act)),
                                   atol=2e-4, err_msg=act)
        np.testing.assert_allclose(np.asarray(mean), np.asarray(jnp.mean(x, 0)),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(var), np.asarray(jnp.var(x, 0)),
                                   rtol=1e-4, atol=1e-4)

        def loss_k(x_, g_, b_):
            y_, _, _ = fused_bn_act_train(x_, g_, b_, center, eps, act, True)
            return jnp.sum(jnp.square(y_) * 0.5 + y_ * 0.25)

        def loss_r(x_, g_, b_):
            y_ = ref(x_, g_, b_, act)
            return jnp.sum(jnp.square(y_) * 0.5 + y_ * 0.25)

        gk = jax.grad(loss_k, argnums=(0, 1, 2))(x, gamma, beta)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, gamma, beta)
        for a, b, tag in zip(gk, gr, ("dx", "dgamma", "dbeta")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, err_msg=f"{act}:{tag}")


def test_batchnorm_fused_training_matches_plain():
    """BN layer train path: fused pallas kernel == plain jnp path (outputs,
    running-stat updates, and gradients through a downstream loss)."""
    from deeplearning4j_tpu.nn.layers.base import Ctx
    from deeplearning4j_tpu.nn.layers.norm import BatchNormalization
    x = jnp.asarray(RNG.standard_normal((8, 4, 4, 12)).astype(np.float32))
    plain = BatchNormalization(activation="relu", fused=False)
    fused = BatchNormalization(activation="relu", fused=True)
    params, state, _ = plain.init(jax.random.PRNGKey(0), (4, 4, 12))
    # second step from warm stats exercises the shifted-center path
    _, state = plain.apply(params, state, x, Ctx(train=True))
    y_p, st_p = plain.apply(params, state, x, Ctx(train=True))
    y_f, st_f = fused.apply(params, state, x, Ctx(train=True))
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_p), atol=1e-4)
    for k in ("mean", "var"):
        np.testing.assert_allclose(np.asarray(st_f[k]), np.asarray(st_p[k]),
                                   rtol=1e-4, atol=1e-5)

    def loss(p, layer):
        y, _ = layer.apply(p, state, x, Ctx(train=True))
        return jnp.sum(jnp.square(y))

    gp = jax.grad(loss)(params, plain)
    gf = jax.grad(loss)(params, fused)
    np.testing.assert_allclose(np.asarray(gf["gamma"]), np.asarray(gp["gamma"]),
                               atol=5e-4)
    np.testing.assert_allclose(np.asarray(gf["beta"]), np.asarray(gp["beta"]),
                               atol=5e-4)


def test_fused_bn_act_bf16_grad_through_frozen_bn():
    """r4 regression: bf16 input to the inference fused BN+act must accept
    the bf16 cotangent (the recompute-based VJP previously emitted f32 and
    rejected it — scripts/diag_resnet.py phase D failure)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.kernels.fused_ops import fused_bn_act

    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 128)),
                    jnp.bfloat16)
    scale = jnp.asarray(np.random.default_rng(1).random(128), jnp.float32)
    shift = jnp.asarray(np.random.default_rng(2).random(128), jnp.float32)

    def f(x):
        y = fused_bn_act(x, scale, shift, "relu", True)
        # consume in bf16 like the next conv does
        return jnp.sum(y * y)

    g = jax.grad(f)(x)
    assert g.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


def test_bn_auto_training_path_stays_xla():
    """r4 policy: fused='auto' must NOT engage the pallas kernel on the
    training path (on-chip regression, see norm.py _can_fuse_train)."""
    from deeplearning4j_tpu.nn.layers.norm import BatchNormalization

    bn = BatchNormalization(activation="relu")
    assert bn.fused == "auto" and not bn._can_fuse_train()
    assert BatchNormalization(activation="relu",
                              fused=True)._can_fuse_train()


def test_causal_clamp_index_maps_match_liveness():
    """The causal DMA-clamp index maps must agree exactly with the kernels'
    pl.when liveness: a (q-block i, k-block j) step is live iff
    j*bk <= i*bq + bq - 1; dead steps must re-reference the LAST live block
    (fwd/dq kv map) or the FIRST live block (dkv q map) so Pallas skips the
    fetch."""
    from deeplearning4j_tpu.kernels.flash_attention import _causal_kv_map

    for bq, bk in ((128, 128), (256, 128), (128, 256), (64, 512)):
        t = 1024
        nq, nk = t // bq, t // bk
        kv_map = _causal_kv_map(bq, bk, True)
        for i in range(nq):
            last_live = (i * bq + bq - 1) // bk
            for j in range(nk):
                live = j * bk <= i * bq + bq - 1
                _, jj, _ = kv_map(0, i, j)
                jj = int(jj)
                if live:
                    assert jj == j, (bq, bk, i, j)
                else:
                    assert jj == last_live, (bq, bk, i, j, jj)
                # dead steps always clamp to a LIVE block index
                assert jj * bk <= i * bq + bq - 1
    # non-causal: identity
    ident = _causal_kv_map(128, 128, False)
    assert tuple(int(x) for x in ident(3, 2, 5)) == (3, 5, 0)
