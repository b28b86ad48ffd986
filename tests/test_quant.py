"""Quantization plane (ISSUE 19): int8 KV pages + int8 decode weights
behind the fidelity gate.

Oracles, same discipline as tests/test_paged_kv.py: the quantized pool
is an optimization, never a different model — greedy output through an
int8 paged cache must match ``engine.generate()`` token for token on
the tiny config, scales must ride every page operation (copy_page, CoW
prefix sharing) beside their rows, and byte accounting must tell the
truth about the shrink. The promotion lifecycle (race → sha-stamped
cost record → ``dl4j_autotune_promotions_total``) is pinned end to
end, including the ``--max-kl`` acceptance bound at 1e-3.

Fast tier-1 suite — tiny f32 configs on CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import autotune as at
from deeplearning4j_tpu.kernels.paged_attention import PROMOTION_MAX_KL
from deeplearning4j_tpu.obs import get_registry
from deeplearning4j_tpu.serving import (ContinuousBatchingScheduler,
                                        GenerationEngine, PageTable,
                                        init_paged_cache, is_quantized,
                                        token_nbytes)
from deeplearning4j_tpu.serving import kvcache, quant
from deeplearning4j_tpu.zoo import transformer as tfm


def tiny_cfg(**kw):
    base = dict(vocab_size=61, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                max_seq=32, dtype=jnp.float32, remat=False,
                attn_scores_bf16=False)
    base.update(kw)
    return tfm.TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    return GenerationEngine(cfg, params, prefill_chunk=8)


@pytest.fixture(autouse=True)
def _isolated_store(tmp_path, monkeypatch):
    """Every test gets its own autotune store — promotion races must
    never read a verdict another test measured."""
    monkeypatch.setattr(at, "_CACHE_PATH", tmp_path / "autotune.json")
    at._memory_cache.clear()
    yield
    at._memory_cache.clear()


def _toks(shape, vocab=61, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, shape).astype(
        np.int32)


# ----------------------------------------------------- primitives

def test_quantize_rows_roundtrip_bound():
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.standard_normal((3, 5, 4, 8)), jnp.float32)
    q, s = quant.quantize_rows(rows)
    assert q.dtype == jnp.int8 and q.shape == rows.shape
    assert s.dtype == jnp.float32 and s.shape == rows.shape[:-1]
    back = quant.dequantize_rows(q, s)
    # symmetric rounding: error per element <= half the row's LSB
    bound = np.asarray(s)[..., None] * 0.5 + 1e-7
    assert np.all(np.abs(np.asarray(back) - np.asarray(rows)) <= bound)
    # zero rows survive (the 1e-8 amax clamp, no div-by-zero NaNs)
    qz, sz = quant.quantize_rows(jnp.zeros((2, 4, 8)))
    assert np.all(np.asarray(qz) == 0) and np.all(np.isfinite(sz))


def test_quantize_block_weights_layout_and_sharing(model):
    cfg, params = model
    qb = quant.quantize_block_weights(params["blocks"])
    for name in ("wqkv", "wo", "w_in", "w_out"):
        w = np.asarray(params["blocks"][name], np.float32)
        assert qb[name].dtype == jnp.int8 and qb[name].shape == w.shape
        s = np.asarray(qb[name + "_scale"])
        assert s.shape == (w.shape[0], 1, w.shape[2])
        back = np.asarray(qb[name], np.float32) * s
        assert np.max(np.abs(back - w)) <= s.max() * 0.5 + 1e-7
    # norms stay full precision; non-matvec entries untouched
    assert qb["ln1"] is params["blocks"]["ln1"]
    qp = quant.quantized_params(params)
    # embeddings/head are SHARED arrays, not copies
    assert qp["embed"] is params["embed"]
    assert qp["ln_f"] is params["ln_f"]


# ------------------------------------------------- pool geometry

def test_quantized_pool_shapes_and_byte_accounting(model):
    cfg, _ = model
    cache = init_paged_cache(cfg, n_slots=2, n_pages=8, page_len=4,
                             quantized=True)
    assert is_quantized(cache) and kvcache.is_paged(cache)
    assert cache["k"].dtype == jnp.int8
    assert cache["k_scale"].shape == cache["k"].shape[:-1]
    assert cache["k_scale"].dtype == jnp.float32
    # int8 rows + f32 per-head scales vs the f32 baseline rows
    expect = (2 * cfg.n_layers * cfg.d_model * 1
              + 2 * cfg.n_layers * cfg.n_heads * 4)
    assert token_nbytes(cache) == expect
    base = init_paged_cache(cfg, n_slots=2, n_pages=8, page_len=4)
    assert not is_quantized(base)
    assert token_nbytes(cache) < token_nbytes(base)


# ------------------------------------------------ decode oracles

def _paged_greedy(eng, prompt, n, quantized):
    """Greedy decode of one request over a private paged pool."""
    per_slot = -(-eng.max_len // 4)
    cache = eng.init_paged_cache(1, per_slot, 4, quantized=quantized)
    assert is_quantized(cache) == quantized
    pt = PageTable.for_cache(cache)
    assert pt.map(0, len(prompt) + n - 1)
    cache = pt.sync(cache)
    logits = None
    for s in range(0, len(prompt), eng.chunk_len):
        logits, cache = eng.prefill_chunk(
            cache, prompt[s:s + eng.chunk_len], 0, s)
    out = [int(np.argmax(np.asarray(logits, np.float32)))]
    while len(out) < n:
        logits, cache = eng.decode_step(
            cache, np.asarray([out[-1]], np.int32))
        out.append(int(np.argmax(np.asarray(logits, np.float32)[0])))
    return out


def test_quantized_paged_decode_matches_generate(engine):
    """The acceptance oracle: greedy output through an int8 paged pool
    == engine.generate() token for token (the quantization error stays
    inside the argmax margin on the tiny config)."""
    prompt = _toks((12,))
    want = [int(t) for t in engine.generate(prompt, 16)]
    assert _paged_greedy(engine, prompt, 16, quantized=False) == want
    assert _paged_greedy(engine, prompt, 16, quantized=True) == want


def test_quantized_weight_decode_argmax_matches(model, engine):
    """int8 weights + bf16-style dequant-on-the-fly: logits close, the
    greedy choice identical on the tiny config."""
    cfg, params = model
    qp = quant.quantized_params(params)
    cache_a = engine.init_cache(1)
    cache_b = engine.init_cache(1)
    prompt = _toks((1, 10), seed=3)
    _, cache_a = engine.prefill(cache_a, prompt)
    _, cache_b = engine.prefill(cache_b, prompt)
    toks = _toks((1,), seed=4)
    ref, _ = engine._decode(params, cache_a, toks)
    got, _ = engine._decode(qp, cache_b, toks)
    ref = np.asarray(ref, np.float32)
    got = np.asarray(got, np.float32)
    assert np.max(np.abs(ref - got)) < 0.1
    assert np.argmax(ref, -1).tolist() == np.argmax(got, -1).tolist()


def test_copy_page_carries_scales(model, engine):
    """CoW device page copy: the scale arrays ride the rows as one
    unit — a split page must dequantize identically to its source."""
    cfg, _ = model
    cache = engine.init_paged_cache(2, 6, 4, quantized=True)
    rng = np.random.default_rng(5)
    rows = jnp.asarray(rng.standard_normal(
        (cfg.n_layers, 4, cfg.n_heads, cfg.head_dim)), jnp.float32)
    q, s = quant.quantize_rows(rows)
    cache["k"] = cache["k"].at[:, 1].set(q)
    cache["k_scale"] = cache["k_scale"].at[:, 1].set(s)
    cache["v"] = cache["v"].at[:, 1].set(q)
    cache["v_scale"] = cache["v_scale"].at[:, 1].set(s)
    cache = engine.copy_page(cache, 1, 4)
    for name in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(np.asarray(cache[name][:, 4]),
                                      np.asarray(cache[name][:, 1]))


# -------------------------------------------- scheduler integration

def _teacher_forced_logits(engine, ctx, quantized):
    """(len(ctx), V): the next-token logits after every prefix of ``ctx``
    through a paged pool of the given storage — ``verify_chunk`` is the
    chunked-prefill body with every row's logits, rows written to (and
    read back from) the pool as it goes."""
    cache = engine.init_paged_cache(1, 16, 4, quantized=quantized)
    table = PageTable.for_cache(cache)
    assert table.map(0, len(ctx))
    cache = table.sync(cache)
    rows = []
    for start in range(0, len(ctx), engine.chunk_len):
        chunk = ctx[start:start + engine.chunk_len]
        logits, cache = engine.verify_chunk(cache, chunk, slot=0,
                                            start=start)
        rows.append(np.asarray(logits, np.float32)[:len(chunk)])
    return np.concatenate(rows)


def test_scheduler_quant_kv_greedy_equivalence(engine):
    """The serve-loop oracle: a scheduler over an int8 pool (prefix
    sharing on — scales must survive shared pages and CoW splits)
    against the float pool. Judged on logits, the rule ROADMAP Speed 1
    states for the kernel gate: per-position KL within the repo's own
    promotion bound, and the same greedy tokens up to the first NEAR-TIE
    — a position whose top-2 logit gap the int8 perturbation can close
    (random weights produce them; an argmax flip there is not a defect,
    and past it the two continuations legitimately differ)."""
    from deeplearning4j_tpu.obs.fidelity import compare_logits
    prompts = [_toks((14,), seed=7), _toks((9,), seed=8)]
    # shared prefix: the second pair of requests exercises prefix-hit
    # admission over quantized pages
    prompts.append(np.concatenate([prompts[0][:8], _toks((4,), seed=9)]))
    outs = {}
    for mode in ("off", "on"):
        sched = ContinuousBatchingScheduler(
            engine, n_slots=2, page_len=4, n_pages=16,
            prefix_cache=True, quant_kv=mode)
        assert is_quantized(sched.cache) == (mode == "on")
        futs = [sched.submit(p, max_new_tokens=6) for p in prompts]
        sched.run_until_idle()
        outs[mode] = [f.result(timeout=600).tokens.tolist() for f in futs]
        assert sched.check_pages()
        assert sched.kv_report()["kv_dtype"] == (
            "int8" if mode == "on" else "float32")
    compared = 0
    for prompt, off, on in zip(prompts, outs["off"], outs["on"]):
        # both pools teacher-forced along the float pool's own tokens
        ctx = np.concatenate([prompt, np.asarray(off[:-1], np.int32)])
        ref = _teacher_forced_logits(engine, ctx, False)[len(prompt) - 1:]
        cand = _teacher_forced_logits(engine, ctx, True)[len(prompt) - 1:]
        assert ref.argmax(-1).tolist() == off      # the oracle is the path
        assert compare_logits(ref, cand)["kl_max"] <= PROMOTION_MAX_KL
        top2 = np.sort(ref, axis=-1)[:, -2:]
        gap = top2[:, 1] - top2[:, 0]
        # every logit moved by at most this much, so only a gap within
        # twice it can flip
        reach = 2.0 * np.abs(ref - cand).max(axis=-1) * 1.01 + 1e-5
        near_tie = np.nonzero(gap <= reach)[0]
        upto = int(near_tie[0]) if near_tie.size else len(off)
        assert on[:upto] == off[:upto], (on, off, upto)
        compared += upto
    assert compared >= 12          # the rule still pins most of 18 tokens


def test_scheduler_quant_kv_requires_paged_pool(engine):
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatchingScheduler(engine, n_slots=2, quant_kv="on")


# ------------------------------------------------ promotion races

def test_race_kv_verdict_record_counter(engine):
    reg = get_registry()
    reg.reset()
    res = quant.race_kv(engine, 2, 10, 4)
    # the --max-kl acceptance bound: int8 KV holds 1e-3 on this config
    assert res["fidelity"]["kl_max"] <= PROMOTION_MAX_KL == 1e-3
    assert res["verdict"] in ("promoted", "fallback_slower")
    assert res["bf16_s"] > 0 and res["int8_s"] > 0
    bpt = res["bytes_per_token"]
    assert bpt["int8"] < bpt["bf16"]
    key = quant.kv_bucket_key(engine.cfg, 2, 10, 4)
    rec = at.lookup(key, sha=quant.quant_sha())
    assert rec is not None and rec["choice"][0] in ("int8", "bf16")
    assert reg.get("dl4j_autotune_promotions_total").value(
        kernel="quant_kv", verdict=res["verdict"]) == 1


def test_race_weights_verdict_record_counter(engine):
    reg = get_registry()
    reg.reset()
    res = quant.race_weights(engine)
    assert res["fidelity"]["kl_max"] <= PROMOTION_MAX_KL
    assert res["verdict"] in ("promoted", "fallback_slower")
    rec = at.lookup(quant.w_bucket_key(engine.cfg),
                    sha=quant.quant_sha())
    assert rec is not None
    assert reg.get("dl4j_autotune_promotions_total").value(
        kernel="quant_w", verdict=res["verdict"]) == 1


def test_decide_mode_ladder(engine, monkeypatch):
    reg = get_registry()
    reg.reset()
    # pinned modes resolve with no race
    assert quant.decide_kv(engine, 2, 10, 4, mode="off") == "bf16"
    assert quant.decide_kv(engine, 2, 10, 4, mode="int8") == "int8"
    assert quant.decide_weights(engine, mode="bf16") == "bf16"
    assert quant.decide_weights(engine, mode="on") == "int8"
    # auto off-TPU: conservative bf16, still no race
    assert quant.decide_kv(engine, 2, 10, 4, mode="auto") == "bf16"
    assert at.lookup(quant.kv_bucket_key(engine.cfg, 2, 10, 4)) is None
    # env knob wins when nothing is pinned
    monkeypatch.setattr(engine, "quant_kv_mode", None)
    monkeypatch.setenv("DL4J_QUANT_KV", "int8")
    assert quant.decide_kv(engine, 2, 10, 4) == "int8"
    # race mode runs the race once, then the cached verdict serves
    choice = quant.decide_kv(engine, 2, 10, 4, mode="race")
    races = sum(reg.get("dl4j_autotune_promotions_total").value(
        kernel="quant_kv", verdict=v)
        for v in ("promoted", "fallback_slower", "fallback_fidelity"))
    assert races == 1
    assert quant.decide_kv(engine, 2, 10, 4, mode="race") == choice
    races2 = sum(reg.get("dl4j_autotune_promotions_total").value(
        kernel="quant_kv", verdict=v)
        for v in ("promoted", "fallback_slower", "fallback_fidelity"))
    assert races2 == 1                     # memoized — no re-race
    # every resolution was censused
    assert reg.get("dl4j_quant_pool_total").value(
        kernel="quant_kv", mode="bf16") >= 2


def test_engine_pinned_quant_kv_mode(model):
    """Engine-constructor pinning flows through init_paged_cache's
    quantized=None resolution."""
    cfg, params = model
    eng = GenerationEngine(cfg, params, prefill_chunk=8, quant_kv="on")
    cache = eng.init_paged_cache(1, 4, 4)
    assert is_quantized(cache)
    eng_off = GenerationEngine(cfg, params, prefill_chunk=8,
                               quant_kv="off")
    assert not is_quantized(eng_off.init_paged_cache(1, 4, 4))
