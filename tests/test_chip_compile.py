"""The main path's kernels, compiled for a DESCRIBED v5e (no chip attached).

The TPU compiler is installed here and compiles for a topology that is only
described; interpret-mode tests cannot see what it refuses (layouts Mosaic
cannot express, tiling, VMEM). This is the only file that describes the
chip: the topology is touched inside a module-scoped, non-autouse fixture
— never at import, in a ``skipif`` or in ``parametrize`` — so every xdist
worker collects the same tests and only the worker that runs this file
loads the TPU library. Nothing here runs on a device or times anything; a
compile that passes is not a chip run.

``jax.default_backend()`` still says "cpu" here, so the cases that guard a
``== "tpu"`` branch steer it from the test (monkeypatch), not through an
option of the program.
"""

from __future__ import annotations

import functools
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from chip_smoke import LM_WIDTH as LM  # noqa: E402 — what the chip will run

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp

SLOTS, PAGE_LEN = 8, 16
N_PAGES = SLOTS * (LM["max_seq"] // PAGE_LEN)
# BN nodes that carry a relu in zoo.resnet.ResNet50: stem, then the a/b
# convs of each stage — (spatial side, channels)
RESNET_BN_ACT = ((112, 64), (56, 64), (28, 128), (14, 256), (7, 512))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next one warns): keep it
    off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture()
def as_tpu(monkeypatch):
    """Take the branches the program takes on the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _placed(one_chip, tree):
    return jax.tree_util.tree_map(
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_described_chip_is_a_v5e(topo):
    from deeplearning4j_tpu.obs import floors
    assert len(topo.devices) == 4
    kind = topo.devices[0].device_kind
    assert floors.device_peaks(kind)["flops"]["bf16"] == 197e12


@pytest.mark.parametrize("shape,blocks", [
    ((32, 8, 1024, 64), (512, 1024)),       # train_lm: T=1024 batch 32
    ((4, 8, 4096, 64), (1024, 1024)),       # transformer_long: T=4096
], ids=["b32_t1024_512x1024", "b4_t4096_1024x1024"])
def test_flash_fwd_bwd_compiles(one_chip, no_persistent_cache, shape,
                                blocks):
    from deeplearning4j_tpu.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True, *blocks, False)
                       .astype(jnp.float32))

    x = _sds(one_chip, shape, jnp.bfloat16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert text.count("tpu_custom_call") >= 3       # fwd, dq, dkv
    # each kernel's HLO instruction carries the kernel's own name (a trace
    # names device events by their HLO line), not the jaxpr scope's
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert re.search(rf"%{name}(\.\d+)? = [^\n]*tpu_custom_call", text), name


@pytest.mark.parametrize("b,t,h,hkv,d,window", [
    (16, 1024, 16, 16, 64, None),           # gpt2m-train-b16: heads paired
    (2, 8192, 7, 1, 128, 4096),             # smallthinker's window layers
    (1, 8192, 20, 20, 256, None),           # glm47flash-train-b1-t8192
    (1, 8192, 16, 2, 256, None),            # qwen3next-train-b1-t8192
], ids=["gpt2_pairs", "moe_window", "glm_d256", "qwen3next_d256_grouped"])
def test_flash_ntc_compiles_with_no_layout_work(one_chip, no_persistent_cache,
                                                b, t, h, hkv, d, window):
    """The kernels on the cells' (B, T, H·D) projections, at the race's
    first blocks: three custom calls and no transpose or copy round them."""
    from deeplearning4j_tpu.kernels.flash_attention import _flash

    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v, None, True, 512, 1024, False, window,
                              (h, hkv)).astype(jnp.float32))

    q = _sds(one_chip, (b, t, h * d), jnp.bfloat16)
    kv = _sds(one_chip, (b, t, hkv * d), jnp.bfloat16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("tpu_custom_call") == 3
    assert " transpose(" not in text and " copy(" not in text


@pytest.mark.parametrize("window,suffix", [(4096, "_win"), (None, "")],
                         ids=["window_4096", "full"])
def test_flash_grouped_heads_compile_at_the_moe_cell_shape(
        one_chip, no_persistent_cache, window, suffix):
    """smallthinker-train-b2-t8192's attention: 7 query heads of 128 on one
    K/V head at T 8192, with and without the 4096 window (PR 28); the
    window kernels carry names of their own."""
    from deeplearning4j_tpu.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True, 1024, 1024, False,
                                       window).astype(jnp.float32))

    q = _sds(one_chip, (2, 7, 8192, 128), jnp.bfloat16)
    kv = _sds(one_chip, (2, 1, 8192, 128), jnp.bfloat16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert re.search(rf"%{name}{suffix}(\.\d+)? = [^\n]*tpu_custom_call",
                         text), name
    # dK and dV come out of the kernel summed over the group: one K/V head
    assert re.search(r"%flash_bwd_dkv\w* = \(bf16\[2,8192,128\]", text) or \
        "bf16[2,8192,128]" in text


@pytest.mark.parametrize("blocks", [(512, 1024), (1024, 1024), (1024, 512)],
                         ids=["512x1024", "1024x1024", "1024x512"])
def test_flash_compiles_at_the_zaya_cell_shape(one_chip, no_persistent_cache,
                                               blocks):
    """zaya1-train-b1-t32768's attention in the latent: 8 query heads of 128
    on 2 K/V heads over 32,768 positions, full causal (PR 32), at the larger
    block pairs the race may pick there."""
    from deeplearning4j_tpu.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True, *blocks, False)
                       .astype(jnp.float32))

    q = _sds(one_chip, (1, 8, 32768, 128), jnp.bfloat16)
    kv = _sds(one_chip, (1, 2, 32768, 128), jnp.bfloat16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert re.search(rf"%{name}(\.\d+)? = [^\n]*tpu_custom_call", text), name


def _glm_flash_grad(blocks):
    from deeplearning4j_tpu.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True, *blocks, False)
                       .astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("blocks", [(512, 1024), (1024, 512), (512, 512)],
                         ids=["512x1024", "1024x512", "512x512"])
def test_flash_compiles_at_the_glm_cell_shape(one_chip, no_persistent_cache,
                                              blocks):
    """glm47flash-train-b1-t8192's assembled heads: 20 heads, a K/V head
    each, of 256 (192 + 64 for q and k, 256 for v) over 8,192 positions,
    full causal (PR 34): the kernels' first head of 256."""
    x = _sds(one_chip, (1, 20, 8192, 256), jnp.bfloat16)
    text = _compile(_glm_flash_grad(blocks), x, x, x)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert re.search(rf"%{name}(\.\d+)? = [^\n]*tpu_custom_call", text), name


def test_a_block_pair_past_vmem_is_refused_at_head_size_256(
        one_chip, no_persistent_cache):
    """2048 x 1024 does not fit VMEM at a head of 256: the compiler raises
    an ordinary exception, which the block race
    (``kernels/autotune.py::_race``) records for the candidate and goes on
    from; the race does not die on it. (Until PR 38 the race's own largest
    pair, 1024 x 1024, was refused here: the dkv kernel held transposed
    copies of its score blocks; since it works on scores as (keys,
    queries) that pair fits, and is raced.)"""
    x = _sds(one_chip, (1, 20, 8192, 256), jnp.bfloat16)
    _compile(_glm_flash_grad((1024, 1024)), x, x, x)
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(_glm_flash_grad((2048, 1024)), x, x, x)


def test_chunked_head_compiles_at_the_zaya_cell_shape(one_chip,
                                                      no_persistent_cache):
    """zaya1-train-b1-t32768's head under ``value_and_grad``: 32,768 rows of
    2,048 against the half table's 131,136 columns, bf16, chunks of 1,024
    (PR 33). ONE loop with three products, and temporaries that hold a chunk's
    logits, nothing (N, V)."""
    from deeplearning4j_tpu.zoo.transformer import _chunked_ce

    x = _sds(one_chip, (32768, 2048), jnp.bfloat16)
    head = _sds(one_chip, (2048, 131136), jnp.bfloat16)
    targets = _sds(one_chip, (32768,), jnp.int32)
    compiled = jax.jit(jax.value_and_grad(
        lambda x, h, t: _chunked_ce(x, h, t, 1024), argnums=(0, 1))).lower(
            x, head, targets).compile()
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == 1
    assert len(re.findall(r" convolution\(", text)) == 3
    # this tree reads 806,178,816 (the checkpointed form it replaced
    # 806,340,096): a chunk's f32 logits, 537 MB, and their bf16 copy; dx
    # and dhead are outputs. Every row's logits would be 17.2 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.85e9


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_paged_decode_kernel_compiles(one_chip, no_persistent_cache, dtype):
    """Mosaic refused the first form of this kernel (batched dots whose
    batch axis sat at a different position in each operand)."""
    from deeplearning4j_tpu.kernels.paged_attention import paged_attention
    h, dh = LM["n_heads"], LM["d_model"] // LM["n_heads"]
    pool = _sds(one_chip, (N_PAGES, PAGE_LEN, h, dh), dtype)
    text = _compile(
        functools.partial(paged_attention, interpret=False),
        _sds(one_chip, (SLOTS, h, dh), dtype), pool, pool,
        _sds(one_chip, (SLOTS, LM["max_seq"] // PAGE_LEN), jnp.int32),
        _sds(one_chip, (SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


def test_kernel_program_is_the_same_from_any_call_site(one_chip, tmp_path,
                                                       monkeypatch):
    """The compile cache's key holds the kernel's serialized module, call
    stacks included: with the cache helper on, lowering the same kernel
    from another frame, after other kernels, gives the same program (the
    LM train step missed the cache on every early chip run)."""
    from deeplearning4j_tpu.kernels.paged_attention import paged_attention
    from deeplearning4j_tpu.utils.compile_cache import enable_compile_cache
    h, dh = LM["n_heads"], LM["d_model"] // LM["n_heads"]

    def lower(page_len=PAGE_LEN):
        pool = _sds(one_chip, (N_PAGES, page_len, h, dh), jnp.bfloat16)
        return jax.jit(functools.partial(paged_attention, interpret=False)
                       ).lower(
            _sds(one_chip, (SLOTS, h, dh), jnp.bfloat16), pool, pool,
            _sds(one_chip, (SLOTS, LM["max_seq"] // page_len), jnp.int32),
            _sds(one_chip, (SLOTS,), jnp.int32)).as_text()

    def from_another_frame():
        lower(page_len=128)              # history: another kernel first
        return (lambda: lower())()

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_traceback_in_locations_limit
    try:
        enable_compile_cache()           # sets no directory: the env has one
        here, there = lower(), from_another_frame()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
    assert "tpu_custom_call" in here and here == there


#: the expert cells' routed layers: tokens N, choices K, width d
WAY_BACK_SHAPES = {"smallthinker": (16384, 6, 2560), "glm": (8192, 4, 2048),
                   "zaya": (32768, 1, 2048), "qwen3next": (8192, 10, 2048)}


@pytest.mark.parametrize("cell", sorted(WAY_BACK_SHAPES))
def test_expert_layers_way_back_compiles_at_the_cells_shapes(
        one_chip, no_persistent_cache, cell):
    """``zoo.transformer._rows_back`` in bf16 with its own backward rule:
    the rows' gradient is filled by ``_scaled_rows``' passes from the (N, d)
    gradient of the sum, and no float32 (K, N, d) is ever held (JAX's own
    transposition of the forward holds two, 1 GB each in the smallthinker
    cell, and gathers all K·N rows it rounds them to)."""
    from deeplearning4j_tpu.zoo import transformer as tfm
    n, k, d = WAY_BACK_SHAPES[cell]
    a = n * k

    def plain(rows, weight, order, inv, local, n_local):
        parts = jnp.where(local[:, :, None], rows[inv].reshape(k, n, d), 0)
        return jnp.sum(parts.astype(jnp.float32) * weight[:, :, None],
                       axis=0).astype(rows.dtype)

    def both_gradients(back):
        def f(rows, weight, order, inv, local, n_local, g):
            _, pull = jax.vjp(
                lambda r, w: back(r, w, order, inv, local, n_local),
                rows, weight)
            return pull(g)
        return jax.jit(f).lower(
            _sds(one_chip, (a, d), jnp.bfloat16),
            _sds(one_chip, (k, n), jnp.float32),
            _sds(one_chip, (a,), jnp.int32), _sds(one_chip, (a,), jnp.int32),
            _sds(one_chip, (k, n), jnp.bool_), _sds(one_chip, (), jnp.int32),
            _sds(one_chip, (n, d), jnp.bfloat16)).compile()

    ours, theirs = both_gradients(tfm._rows_back), both_gradients(plain)
    text = ours.as_text()
    # the rows' gradient: ONE loop whose trip count is no constant
    assert len(re.findall(r" while\(", text)) == 1
    assert "known_trip_count" not in text
    ours_temp = ours.memory_analysis().temp_size_in_bytes
    theirs_temp = theirs.memory_analysis().temp_size_in_bytes
    assert ours_temp <= 2.2 * a * d * 2      # the gathered rows, twice
    if k > 1:
        assert ours_temp < theirs_temp


def test_row_mover_is_lowered_once_however_many_layers_call_it():
    """The regression that refused PR 35 (set-up that grew with the mover's
    call sites), held without a chip: a two-layer expert stack under
    ``remat_policy="full"``, with one more expert block behind it as the
    prediction module has, calls ``_scaled_rows`` from two backward passes,
    and the step's module holds its loop ONCE."""
    from deeplearning4j_tpu.zoo import transformer as tfm
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=16,
        max_seq=16, n_experts=8, expert_top_k=2, experts_held=(0, 4),
        mlp="reglu", dtype=jnp.float32, remat=True, remat_policy="full",
        fused_loss=True)
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)

    def loss(p, i):
        x = tfm.embed(p, cfg, i)
        x, _, _, _ = tfm._run_blocks(p["blocks"], cfg, x)
        x, _, _, _ = tfm._run_blocks(p["blocks"], cfg, x)   # a second stack
        return jnp.sum(x.astype(jnp.float32))

    text = jax.jit(jax.grad(loss)).lower(params, ids).as_text()
    bodies = re.findall(r"func\.func private @(scaled_rows[^(]*)\(", text)
    calls = re.findall(r"call @(scaled_rows[^(]*)\(", text)
    assert len(bodies) == 1 and len(calls) == 2 and set(calls) == set(bodies)


#: qwen3next-train-b1-t8192's gated DeltaNet: B, T, key heads, value heads,
#: head size
GDN_SHAPE = (1, 8192, 16, 32, 128)


def _gdn_operands(one_chip, b, t, hk, hv, d):
    return (_sds(one_chip, (b, t, 2 * hk * d + hv * d), jnp.bfloat16),
            _sds(one_chip, (b, t, hv), jnp.float32),
            _sds(one_chip, (b, t, hv), jnp.float32))


def test_gated_delta_kernels_compile_at_the_qwen3next_cell_shape(
        one_chip, no_persistent_cache):
    """``kernels.gated_delta`` forward and backward at the cell's shapes:
    two custom calls, every one under ``attn_core/gdn_rule`` (the scope
    ``gdn_roofline_pct.qwen3next`` and ``gdn_time_share_pct.qwen3next``
    read), and nothing of a chunk's size in HBM: the temporaries are the
    chunk-start states and little else."""
    from deeplearning4j_tpu.kernels import gated_delta
    from deeplearning4j_tpu.zoo import transformer as tfm
    b, t, hk, hv, d = GDN_SHAPE

    def loss(x, g, beta, w):
        return jnp.sum(gated_delta.gated_delta_rule(
            x, g, beta, hk, d, d, scopes=tfm._GDN_RULE_SCOPES,
            interpret=False) * w)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_gdn_operands(one_chip, *GDN_SHAPE),
        _sds(one_chip, (b, t, hv * d), jnp.float32)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    assert sorted(re.match(r"\s*%(\w+?)(\.\d+)? =", ln).group(1)
                  for ln in calls) == ["gdn_bwd", "gdn_fwd"]
    for ln in calls:
        assert re.search(r'op_name="[^"]*attn_core/gdn_rule/', ln), ln
    states = t // 64 * hv * d * d * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * states


def test_gated_delta_kernels_are_lowered_once_for_every_layer(one_chip):
    """The regression that refused the rule's first kernel (set-up that grew
    with its call sites), held without a chip: three layers under
    ``jax.checkpoint`` call the forward six times (each layer's forward and
    its recomputation) and the backward three times, and the module holds
    each kernel's body ONCE."""
    from deeplearning4j_tpu.kernels import gated_delta
    b, t, hk, hv, d = 1, 256, 2, 4, 128

    def loss(x, g, beta):
        rule = jax.checkpoint(lambda x: gated_delta.gated_delta_rule(
            x, g, beta, hk, d, d, interpret=False))
        return sum(jnp.sum(jnp.square(rule(x * (i + 1)))) for i in range(3))

    text = jax.jit(jax.grad(loss)).lower(
        *_gdn_operands(one_chip, b, t, hk, hv, d)).as_text()
    for name, n_calls in (("gdn_fwd", 6), ("gdn_bwd", 3)):
        bodies = re.findall(rf"func\.func private @({name}[^(]*)\(", text)
        calls = re.findall(rf"call @({name}[^(]*)\(", text)
        assert len(bodies) == 1 and len(calls) == n_calls, (name, bodies)
        assert text.count(f'name = "{name}"') <= 1   # one Mosaic module


@pytest.mark.parametrize("batch", [1, 128], ids=["b1", "b128"])
@pytest.mark.parametrize("side,channels", RESNET_BN_ACT,
                         ids=[f"{s}x{s}x{c}" for s, c in RESNET_BN_ACT])
def test_fused_bn_act_compiles_at_resnet50_shapes(one_chip,
                                                  no_persistent_cache,
                                                  side, channels, batch):
    from deeplearning4j_tpu.kernels.fused_ops import fused_bn_act
    vec = _sds(one_chip, (channels,), jnp.float32)
    text = _compile(
        lambda x, s, b: fused_bn_act(x, s, b, "relu", False),
        _sds(one_chip, (batch * side * side, channels), jnp.bfloat16),
        vec, vec)
    assert "tpu_custom_call" in text


def test_bf16_scores_attention_compiles_with_its_tpu_preset(
        one_chip, no_persistent_cache, as_tpu):
    """DotAlgorithmPreset.BF16_BF16_F32 has no CPU form: this is the only
    place it meets the installed jax before a chip does."""
    from deeplearning4j_tpu.zoo import transformer as tfm
    x = _sds(one_chip, (32, 1024, 8, 64), jnp.bfloat16)
    lowered = jax.jit(tfm._xla_attention_bf16_scores).lower(x, x, x)
    assert "accumulation_type = f32" in lowered.as_text()   # the preset
    assert lowered.compile().memory_analysis() is not None


def _serving(one_chip, quantized=False):
    from deeplearning4j_tpu.serving import GenerationEngine, kvcache
    from deeplearning4j_tpu.zoo import transformer as tfm
    cfg = tfm.TransformerConfig(**LM, dtype=jnp.bfloat16, remat=False)
    params = _placed(one_chip, jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)))
    cache = _placed(one_chip, jax.eval_shape(
        lambda: kvcache.init_paged_cache(cfg, SLOTS, N_PAGES, PAGE_LEN,
                                         cfg.max_seq, quantized=quantized)))
    return GenerationEngine(cfg, params), params, cache


@pytest.mark.parametrize("arm", ["gather", "kernel", "gather_int8_pool"])
def test_paged_decode_step_compiles_at_lm_width(one_chip,
                                                no_persistent_cache, as_tpu,
                                                arm):
    """The whole decode programs the serving races compile on the chip:
    both arms of the paged-kernel race, and the int8 pool of the quant_kv
    race."""
    eng, params, cache = _serving(one_chip, quantized=arm.endswith("pool"))
    text = _compile(
        functools.partial(eng._decode_paged_raw, use_kernel=arm == "kernel"),
        params, cache, _sds(one_chip, (SLOTS,), jnp.int32))
    assert ("tpu_custom_call" in text) is (arm == "kernel")


def test_dense_decode_with_int8_weights_compiles_at_lm_width(
        one_chip, no_persistent_cache, as_tpu):
    """The candidate arm of the quant_weights race (serving/quant.py)."""
    from deeplearning4j_tpu.serving import kvcache, quant
    eng, params, _ = _serving(one_chip)
    qparams = _placed(one_chip, jax.eval_shape(quant.quantized_params,
                                               params))
    cache = _placed(one_chip, jax.eval_shape(
        lambda: kvcache.init_cache(eng.cfg, 2, 256)))
    _compile(eng._decode_raw, qparams, cache,
             _sds(one_chip, (2,), jnp.int32))


def test_prefill_chunk_compiles_at_lm_width(one_chip, no_persistent_cache,
                                            as_tpu):
    eng, params, cache = _serving(one_chip)
    scalar = _sds(one_chip, (), jnp.int32)
    _compile(eng._prefill_chunk_raw, params, cache,
             _sds(one_chip, (1, eng.chunk_len), jnp.int32),
             scalar, scalar, scalar)
