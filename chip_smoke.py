#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one chip: drives the three paths users enter by, each once, at
the repo's own full width (the ``bench.py`` configurations), through the
normal entry points, and checks what comes out.

- ``train_lm``      ``zoo.transformer`` LM (vocab 32000, d 512, 8 heads,
                    8 layers, ff 2048, bf16, T=1024, batch 32, fused loss,
                    remat save_attn) through ``tfm.make_train_step``.
- ``fit_resnet50``  ``ResNet50(1000, bf16)`` through
                    ``ComputationGraph.fit(DataSetIterator)`` at batch 128,
                    224x224, then ``output()`` (fused BN-act inference path).
- ``serve_lm``      ``GenerationEngine`` + ``ContinuousBatchingScheduler``
                    (paged pool, page_len 16, prefix cache) at the LM width,
                    ``max_seq`` 1024, 8 slots, 8 mixed requests.

``--chips 4`` runs ONLY the sharded path and what it is compared with: the
``train_lm`` configuration under ``make_mesh(dp=2, tp=2)`` against the same
steps on a one-device mesh, and ``ParallelWrapper(dp=4).fit`` on a conv net
against single-device ``fit``.

Each phase prints one JSON line of observations (device, versions, compile
seconds, steady milliseconds, peak bytes, dispatch facts). Any phase that
raises, or whose check fails, ends the run non-zero. Without a TPU the
script exits non-zero at once. The last line of a successful run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Weights and data are random, made from ``--seed``. Needs no network, no git
and no file outside the checkout (the autotune store lives in the home
directory; the records read and written are printed).
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import statistics
import sys
import time

LM_WIDTH = dict(vocab_size=32000, d_model=512, n_heads=8, n_layers=8,
                d_ff=2048, max_seq=1024)
#: first-token / decode logits of the bf16 engine against the float32 dense
#: forward: the repo's own promotion bound on KL, and an absolute logit
#: error a few bf16 steps (2^-8 relative) wide at these logit magnitudes
LOGIT_MAX_KL = 1e-3
LOGIT_MAX_ABS = 0.25
#: sharded arm vs one-device arm, per-step loss (bf16 reduction order)
SHARDED_LOSS_TOL = 0.01


class SmokeFailure(AssertionError):
    """A phase ran but its result is wrong."""


def _check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def _emit(rec):
    print(json.dumps(rec, default=str), flush=True)


def _ms(seconds):
    return round(seconds * 1e3, 3)


def device_facts():
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _versions():
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def _mem_stat(key, device=None):
    """One of the backend's memory counters for a device (default: the
    first); None where the backend reports no memory stats (CPU).
    ``peak_bytes_in_use`` is one high-water mark per PROCESS, so a later
    phase reports a running maximum."""
    import jax
    stats = (device or jax.devices()[0]).memory_stats()
    return None if not stats else stats.get(key)


def _compile_spans(since_epoch):
    """{entry point: seconds} of the CompileSentinel spans recorded since
    ``since_epoch`` — first call at a new signature: trace + compile."""
    from deeplearning4j_tpu.obs import get_tracer
    out = {}
    for sp in get_tracer().spans():
        if sp.name.startswith("compile.") and sp.start_ts >= since_epoch:
            key = sp.name[len("compile."):]
            out[key] = round(out.get(key, 0.0) + sp.time_s, 3)
    return out


def _lm_batch(cfg, batch, seed):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    shape = (batch, cfg.max_seq)
    return (jnp.asarray(rng.integers(0, cfg.vocab_size, shape), jnp.int32),
            jnp.asarray(rng.integers(0, cfg.vocab_size, shape), jnp.int32))


def lm_train_config():
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo import transformer as tfm
    return tfm.TransformerConfig(**LM_WIDTH, dtype=jnp.bfloat16,
                                 fused_loss=True, remat=True,
                                 remat_policy="save_attn",
                                 attn_scores_bf16=True)


def lm_serve_config():
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo import transformer as tfm
    return tfm.TransformerConfig(**LM_WIDTH, dtype=jnp.bfloat16, remat=False)


# ------------------------------------------------------------- train_lm --

def _run_lm_steps(cfg, params, ids, tgt, steps):
    """Compile ``tfm.make_train_step`` ahead of time for these (placed)
    arguments and take ``steps`` steps on the one batch. Returns the
    observations and the final params."""
    import jax
    import optax

    from deeplearning4j_tpu.zoo import transformer as tfm

    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)
    step = jax.jit(tfm.make_train_step(cfg, opt), donate_argnums=(0, 1))
    t0 = time.perf_counter()
    traced = step.trace(params, opt_state, ids, tgt)    # flash blocks are
    lowered = traced.lower()                            # raced in here
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    losses, step_s = [], []
    for _ in range(steps):
        t = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, ids, tgt)
        jax.block_until_ready(loss)
        step_s.append(time.perf_counter() - t)
        losses.append(float(loss))
    obs = {
        "trace_s": round(t1 - t0, 3), "compile_s": round(t2 - t1, 3),
        "losses": [round(v, 5) for v in losses],
        "step_ms": [_ms(s) for s in step_s],
        "steady_step_ms": _ms(statistics.median(step_s[1:] or step_s)),
        "attention_path": tfm.attention_path(cfg, cfg.max_seq, cfg.dtype),
        "flash_in_program": "tpu_custom_call" in compiled.as_text(),
        # Pallas calls a layer in the step as traced (the scan's bodies hold
        # one period of layers): forward, dq and dkv are 3; 4 means the
        # backward pass runs the forward kernel again (remat "full")
        "flash_calls_per_layer": str(traced.jaxpr).count("pallas_call[")
        / len(cfg.layer_kinds),
    }
    return obs, losses, params


def phase_train_lm(cfg, batch, steps=4, seed=0, require_flash=False):
    import jax
    import numpy as np

    from deeplearning4j_tpu.kernels.flash_attention import (_tuned_blocks,
                                                            ntc_layout)
    from deeplearning4j_tpu.zoo import transformer as tfm

    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    ids, tgt = _lm_batch(cfg, batch, seed)
    obs, losses, _ = _run_lm_steps(cfg, params, ids, tgt, steps)
    _check(all(np.isfinite(losses)), f"train_lm: non-finite loss {losses}")
    _check(losses[-1] < losses[0],
           f"train_lm: loss did not fall on a repeated batch: {losses}")
    blocks = None
    if obs["attention_path"] == "flash":
        blocks = list(_tuned_blocks(
            batch, cfg.n_heads, cfg.max_seq, cfg.head_dim, cfg.dtype, True,
            None, group=cfg.n_heads // cfg.kv_heads,
            layout=ntc_layout(cfg.head_dim, cfg.n_heads, cfg.kv_heads)))
    if require_flash:
        _check(obs["attention_path"] == "flash" and obs["flash_in_program"],
               "train_lm: the flash kernel is not in the compiled program "
               f"(path {obs['attention_path']}, tpu_custom_call "
               f"{obs['flash_in_program']})")
        if cfg.remat and cfg.remat_policy == "save_attn":
            _check(obs["flash_calls_per_layer"] == 3,
                   "train_lm: save_attn keeps the kernel's residuals, so a "
                   "layer runs forward, dq and dkv once each; the step holds "
                   f"{obs['flash_calls_per_layer']} Pallas calls a layer")
    return {"phase": "train_lm", "batch": batch, "seq": cfg.max_seq,
            "d_model": cfg.d_model, "n_layers": cfg.n_layers,
            "vocab": cfg.vocab_size, "flash_blocks": blocks, **obs,
            "peak_bytes_in_use": _mem_stat("peak_bytes_in_use")}


# --------------------------------------------------------- fit_resnet50 --

def _timed_scores():
    """A TrainingListener that keeps (host time, loss) per iteration; the
    loss it is handed is already a host float, so each entry ends after
    the step that produced it."""
    from deeplearning4j_tpu.nn.listeners import TrainingListener

    class TimedScores(TrainingListener):
        def __init__(self):
            self.times, self.scores = [], []

        def iteration_done(self, model, iteration, epoch, score):
            self.times.append(time.perf_counter())
            self.scores.append(float(score))

    return TimedScores()


def _image_data(n, shape, num_classes, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    x = rng.random((n, *shape), np.float32)
    y = np.eye(num_classes, dtype=np.float32)[rng.integers(0, num_classes, n)]
    return x, y


def phase_fit_resnet50(model, batch, iters=4, seed=0, require_fused=False):
    """``model`` is the zoo model to fit (ResNet50 at 224x224 on the chip;
    the CPU test hands in a shallow one)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.kernels.fused_ops import (bn_act_reference,
                                                      fused_bn_act)

    t_phase = time.time()
    net = model.init()
    # ONE batch, repeated: on fresh random labels Adam's first steps raise
    # the loss on any backend; on a repeated batch it must fall
    x, y = _image_data(batch, model.input_shape, model.num_classes, seed)
    x, y = np.tile(x, (iters, 1, 1, 1)), np.tile(y, (iters, 1))
    before = jax.tree_util.tree_map(jnp.copy, net.params)
    rec = _timed_scores()
    net.set_listeners(rec)
    t0 = time.perf_counter()
    last = net.fit(ArrayDataSetIterator(x, y, batch))
    fit_s = time.perf_counter() - t0
    _check(len(rec.scores) == iters and np.isfinite(rec.scores).all(),
           f"fit_resnet50: expected {iters} finite losses, got {rec.scores}")
    _check(last == rec.scores[-1], "fit_resnet50: fit() return != last loss")
    _check(rec.scores[-1] < rec.scores[0],
           "fit_resnet50: loss did not fall on a repeated batch: "
           f"{rec.scores}")
    moved = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(net.params),
        jax.tree_util.tree_leaves(before)))
    _check(moved > 0.0, "fit_resnet50: fit() left the parameters unchanged")
    step_s = np.diff([t0, *rec.times])

    # inference: output() takes the fused BN-act path where fused="auto"
    # turns it on (a TPU only)
    xb = x[:batch]
    t1 = time.perf_counter()
    out = jax.block_until_ready(net.output(xb))
    out_first_s = time.perf_counter() - t1
    t1 = time.perf_counter()
    out = jax.block_until_ready(net.output(xb))
    out_s = time.perf_counter() - t1
    out = np.asarray(out, np.float32)
    _check(out.shape == (batch, model.num_classes),
           f"fit_resnet50: output shape {out.shape}")
    _check(np.isfinite(out).all(), "fit_resnet50: non-finite output")
    _check(np.allclose(out.sum(-1), 1.0, atol=2e-2),
           "fit_resnet50: softmax rows do not sum to 1")
    fused_in_program = "tpu_custom_call" in net._infer_fn.lower(
        net.params, net.states, {"in": jnp.asarray(xb)}).as_text()
    if require_fused:
        _check(fused_in_program, "fit_resnet50: output() does not contain "
                                 "the fused BN-act kernel")
    # the kernel against its jnp reference at the stem's shape
    rng = np.random.default_rng(seed)
    c = 64
    rows = batch * (model.input_shape[0] // 2) * (model.input_shape[1] // 2)
    x2d = jnp.asarray(rng.standard_normal((rows, c)), jnp.bfloat16)
    scale = jnp.asarray(rng.random(c) + 0.5, jnp.float32)
    shift = jnp.asarray(rng.standard_normal(c), jnp.float32)
    got = jax.jit(lambda a, s, b: fused_bn_act(a, s, b, "relu"))(
        x2d, scale, shift)
    want = bn_act_reference(x2d, scale, shift, "relu").astype(x2d.dtype)
    kernel_err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                       - want.astype(jnp.float32))))
    _check(kernel_err <= 0.0625, "fit_resnet50: fused_bn_act differs from "
                                 f"bn_act_reference by {kernel_err}")
    return {"phase": "fit_resnet50", "model": type(model).__name__,
            "batch": batch, "input_shape": list(model.input_shape),
            "num_classes": model.num_classes, "iters": iters,
            "losses": [round(v, 5) for v in rec.scores],
            "fit_s": round(fit_s, 3),
            "compile_s": _compile_spans(t_phase),
            "iter_ms": [_ms(s) for s in step_s],
            "steady_iter_ms": _ms(statistics.median(step_s[1:])),
            "output_first_call_s": round(out_first_s, 3),
            "output_ms": _ms(out_s),
            "fused_bn_act_in_output_program": fused_in_program,
            "fused_bn_act_max_err_vs_reference": kernel_err,
            "peak_bytes_in_use": _mem_stat("peak_bytes_in_use")}


# ------------------------------------------------------------- serve_lm --

def _race_records():
    """The dispatch races' cost records (kernels/autotune store): verdict
    and both arms' seconds, per raced surface."""
    from deeplearning4j_tpu.kernels import autotune
    out = {}
    for kind in ("paged_decode", "quant_kv", "quant_w"):
        for key, rec in autotune.records(kind=kind).items():
            meta = rec["meta"] or {}
            out[key] = {"choice": rec["choice"],
                        **{k: meta[k] for k in (
                            "verdict", "gather_s", "kernel_s", "bf16_s",
                            "int8_s", "speedup") if k in meta},
                        "fidelity_kl_max":
                            (meta.get("fidelity") or {}).get("kl_max")}
    return out


def _engine_logit_check(eng, params, cfg, cache, prompt):
    """Chunk-prefill ``prompt`` into slot 0 of ``cache`` and decode one
    token, through the engine's own entry points; compare the first-token
    and the decode logits with the float32 dense forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.obs.fidelity import compare_logits
    from deeplearning4j_tpu.serving import kvcache
    from deeplearning4j_tpu.zoo import transformer as tfm

    ref_cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                                  attn_scores_bf16=False,
                                  use_flash_attention=False)

    def dense_last(ids):
        with jax.default_matmul_precision("highest"):
            logits = tfm.forward(params, ref_cfg, jnp.asarray(ids)[None])[0]
        return np.asarray(logits[0, -1], np.float32)

    table = kvcache.PageTable.for_cache(cache)
    _check(table.map(0, len(prompt) + 1), "serve_lm: probe pages unmapped")
    cache = table.sync(cache)
    logits = None
    for start in range(0, len(prompt), eng.chunk_len):
        logits, cache = eng.prefill_chunk(
            cache, prompt[start:start + eng.chunk_len], slot=0, start=start)
    ref0 = dense_last(prompt)
    first = compare_logits(ref0[None], np.asarray(logits, np.float32)[None])
    tok = int(ref0.argmax())
    toks = np.zeros((kvcache.cache_slots(cache),), np.int32)
    toks[0] = tok
    path = eng.decode_path(cache)
    logits1, cache = eng.decode_step(cache, toks)
    ref1 = dense_last(np.concatenate([prompt, [tok]]))
    dec = compare_logits(ref1[None],
                         np.asarray(logits1, np.float32)[:1])
    del cache
    keep = ("max_abs_err", "kl_max", "greedy_match_frac")
    return path, {k: first[k] for k in keep}, {k: dec[k] for k in keep}


def phase_serve_lm(cfg, n_slots=8, page_len=16,
                   prompt_lens=(40, 96, 130, 300, 520, 64, 200),
                   shared=(128, 72), new_tokens=32, seed=0):
    """``prompt_lens``: the independent requests; ``shared`` = (prefix,
    tail): two more requests share a ``prefix``-token prompt head, the
    second submitted once the first has finished, so it admits against
    cached pages."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.serving import (ContinuousBatchingScheduler,
                                            GenerationEngine, kvcache)
    from deeplearning4j_tpu.zoo import transformer as tfm

    t_phase = time.time()
    rng = np.random.default_rng(seed)
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    eng = GenerationEngine(cfg, params)
    n_pages = n_slots * (-(-eng.max_len // page_len))
    t0 = time.perf_counter()
    sched = ContinuousBatchingScheduler(eng, n_slots=n_slots,
                                        page_len=page_len, n_pages=n_pages,
                                        prefix_cache=True)
    construct_s = time.perf_counter() - t0   # holds the quant_kv race

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)

    def wave():
        head = prompt(shared[0])
        reqs = [prompt(n) for n in prompt_lens]
        reqs.append(np.concatenate([head, prompt(shared[1])]))
        follower = np.concatenate([head, prompt(shared[1] + 7)])
        return reqs, follower

    def serve(reqs, follower, threaded):
        """``threaded``: the scheduler's own thread steps (``start()``);
        otherwise this thread does."""
        t = time.perf_counter()
        futs = [sched.submit(p, max_new_tokens=new_tokens) for p in reqs]
        if not threaded:
            sched.run_until_idle()
        futs[-1].result(timeout=900)         # the prefix's first holder
        futs.append(sched.submit(follower, max_new_tokens=new_tokens))
        if not threaded:
            sched.run_until_idle()
        results = [f.result(timeout=900) for f in futs]
        return results, time.perf_counter() - t

    # warm-up wave: every chunk bucket, the decode sweep, the races
    t0 = time.perf_counter()
    serve(*wave(), threaded=False)
    warm_s = time.perf_counter() - t0
    # logits, on a second pool of the scheduler's exact geometry (shares
    # its compiled programs)
    quantized = kvcache.is_quantized(sched.cache)
    probe = eng.init_paged_cache(n_slots, n_pages, page_len,
                                 quantized=quantized)
    # two chunks where the context allows (200 tokens at chunk_len 128)
    decode_path, first_fid, decode_fid = _engine_logit_check(
        eng, params, cfg, probe,
        prompt(min(eng.max_len - 2, max(eng.chunk_len + 8, 200))))
    del probe
    for name, fid in (("first-token", first_fid), ("decode", decode_fid)):
        _check(fid["kl_max"] <= LOGIT_MAX_KL
               and fid["max_abs_err"] <= LOGIT_MAX_ABS,
               f"serve_lm: {name} logits differ from the float32 dense "
               f"forward: {fid} (bounds kl {LOGIT_MAX_KL}, abs "
               f"{LOGIT_MAX_ABS})")

    eng.mark_warm()
    compiles_warm = {k: v["compiles"] for k, v in eng.compile_report().items()}
    sched.reset_kv_window()
    hits_before = sched.kv_report()["prefix"]["prefix_hits"]
    reqs, follower = wave()
    sched.start()
    try:
        results, wave_s = serve(reqs, follower, threaded=True)
    finally:
        sched.stop()
    report = eng.compile_report()
    _check(all(len(r.tokens) == new_tokens for r in results),
           "serve_lm: a request resolved with the wrong number of tokens: "
           f"{[len(r.tokens) for r in results]}")
    _check(all(0 <= int(t) < cfg.vocab_size for r in results
               for t in r.tokens), "serve_lm: token outside the vocabulary")
    retraces = sum(v["retraces_after_warm"] for v in report.values())
    _check(retraces == 0 and compiles_warm == {
        k: v["compiles"] for k, v in report.items()},
        f"serve_lm: compiles after mark_warm(): {report}")
    kv = sched.kv_report()
    _check(kv["prefix"]["prefix_hits"] > hits_before,
           f"serve_lm: the shared prefix was never hit: {kv['prefix']}")
    traces = {t.request_id: t for t in sched.flight_recorder.requests()}
    chunks = [traces[r.request_id].first("prefill")[2].get("chunks")
              for r in results]
    _check(max(c or 1 for c in chunks) >= 2,
           f"serve_lm: no request was admitted in chunks: {chunks}")
    _check(sched.check_pages(), "serve_lm: page-table invariant broken")
    lat = sorted(r.latency_s for r in results)
    ttft = sorted(r.ttft_s for r in results)
    return {"phase": "serve_lm", "n_slots": n_slots, "page_len": page_len,
            "n_pages": n_pages, "max_seq": eng.max_len,
            "d_model": cfg.d_model, "n_layers": cfg.n_layers,
            "requests": len(results), "new_tokens": new_tokens,
            "prompt_lens": [len(p) for p in reqs] + [len(follower)],
            "prefill_chunks": chunks,
            "scheduler_construct_s": round(construct_s, 3),
            "warm_wave_s": round(warm_s, 3),
            "compile_s": _compile_spans(t_phase),
            "wave_s": round(wave_s, 3),
            "request_ms_median": _ms(statistics.median(lat)),
            "request_ms_max": _ms(lat[-1]),
            "ttft_ms_median": _ms(statistics.median(ttft)),
            "decode_path": decode_path, "kv_dtype": kv["kv_dtype"],
            "races": _race_records(),
            "first_token_logits_vs_f32_dense": first_fid,
            "decode_logits_vs_f32_dense": decode_fid,
            "logit_bounds": {"kl_max": LOGIT_MAX_KL,
                             "max_abs_err": LOGIT_MAX_ABS},
            "compiles_after_warm": retraces,
            "prefix": kv["prefix"],
            "peak_bytes_in_use": _mem_stat("peak_bytes_in_use")}


# ---------------------------------------------------- sharded (--chips 4) --

def _bytes_in_use(devices):
    return [_mem_stat("bytes_in_use", d) for d in devices]


def phase_sharded_train_lm(cfg, batch, devices, steps=3, seed=0):
    """The train_lm configuration under dp x tp=2 over ``devices`` against
    the same steps on a one-device mesh of the same host."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.zoo import transformer as tfm

    n = len(devices)
    arms = {"one_device": make_mesh(devices[:1], dp=1, tp=1),
            f"dp{n // 2}_tp2": make_mesh(devices, dp=n // 2, tp=2)}
    ids, tgt = _lm_batch(cfg, batch, seed)
    out, losses = {}, {}
    for name, mesh in arms.items():
        params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
        params = jax.tree_util.tree_map(jax.device_put, params,
                                        tfm.shardings_for(mesh, cfg))
        dsh = NamedSharding(mesh, P("dp", None))
        obs, losses[name], params = _run_lm_steps(
            cfg, params, jax.device_put(ids, dsh), jax.device_put(tgt, dsh),
            steps)
        wqkv = params["blocks"]["wqkv"]
        obs["wqkv_devices"] = len(wqkv.sharding.device_set)
        obs["wqkv_shard_shape"] = list(wqkv.addressable_shards[0].data.shape)
        obs["bytes_in_use"] = _bytes_in_use(devices)
        obs["peak_bytes_in_use"] = [_mem_stat("peak_bytes_in_use", d)
                                    for d in devices]
        out[name] = obs
        del params, wqkv
        gc.collect()
    sharded = next(k for k in arms if k != "one_device")
    diffs = [abs(a - b) for a, b in zip(losses["one_device"],
                                        losses[sharded])]
    _check(max(diffs) <= SHARDED_LOSS_TOL,
           f"sharded train_lm: losses differ by {diffs} "
           f"(tolerance {SHARDED_LOSS_TOL}): {losses}")
    _check(out[sharded]["wqkv_devices"] == n,
           f"sharded train_lm: wqkv lives on {out[sharded]['wqkv_devices']} "
           f"devices, not {n}")
    used = out[sharded]["bytes_in_use"]
    if None not in used:
        _check(all(b > 0 for b in used),
               f"sharded train_lm: a device holds no data: {used}")
    return {"phase": "sharded_train_lm", "batch": batch, "seq": cfg.max_seq,
            "d_model": cfg.d_model, "n_layers": cfg.n_layers,
            "devices": n, "loss_abs_diff": [round(d, 6) for d in diffs],
            "loss_tolerance": SHARDED_LOSS_TOL, "arms": out}


def phase_sharded_fit_conv(batch, devices, iters=4, seed=0):
    """``ParallelWrapper(net, mesh=make_mesh(dp=n)).fit`` on the LeNet conv
    net (bench.py's lenet configuration) against single-device ``fit``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh
    from deeplearning4j_tpu.zoo import LeNet

    n = len(devices)
    model = LeNet(num_classes=10, compute_dtype=jnp.bfloat16)
    x, y = _image_data(iters * batch, model.input_shape, 10, seed)

    def timed_fit(net, fit):
        rec = _timed_scores()
        net.set_listeners(rec)
        t0 = time.perf_counter()
        fit(ArrayDataSetIterator(x, y, batch))
        return rec, np.diff([t0, *rec.times])

    net1 = model.init()
    rec1, s1 = timed_fit(net1, net1.fit)
    netn = model.init()
    wrapper = ParallelWrapper(netn, mesh=make_mesh(devices, dp=n))
    recn, sn = timed_fit(netn, wrapper.fit)
    _check(len(rec1.scores) == len(recn.scores) == iters
           and np.isfinite(rec1.scores + recn.scores).all(),
           f"sharded fit: losses {rec1.scores} vs {recn.scores}")
    diffs = [abs(a - b) for a, b in zip(rec1.scores, recn.scores)]
    _check(max(diffs) <= SHARDED_LOSS_TOL,
           f"sharded fit: dp{n} losses differ from single-device fit by "
           f"{diffs} (tolerance {SHARDED_LOSS_TOL})")
    leaf = jax.tree_util.tree_leaves(netn.params)[0]
    _check(len(leaf.sharding.device_set) == n,
           f"sharded fit: params live on {len(leaf.sharding.device_set)} "
           f"devices, not {n}")
    drift = wrapper.audit_drift()
    _check(len(drift["replicas"]) == n and drift["bit_identical"],
           f"sharded fit: replicas drifted: {drift}")
    return {"phase": "sharded_fit_conv", "model": "LeNet", "batch": batch,
            "iters": iters, "devices": n,
            "losses_single": [round(v, 5) for v in rec1.scores],
            f"losses_dp{n}": [round(v, 5) for v in recn.scores],
            "loss_abs_diff": [round(d, 6) for d in diffs],
            "loss_tolerance": SHARDED_LOSS_TOL,
            "single_iter_ms": [_ms(s) for s in s1],
            f"dp{n}_iter_ms": [_ms(s) for s in sn],
            "param_devices": len(leaf.sharding.device_set),
            "replica_drift": drift,
            "bytes_in_use": _bytes_in_use(devices)}


# ------------------------------------------------------------------ main --

def run_one_chip(seed):
    import jax.numpy as jnp

    from deeplearning4j_tpu.zoo.resnet import ResNet50
    _emit(phase_train_lm(lm_train_config(), batch=32, steps=4, seed=seed,
                         require_flash=True))
    gc.collect()
    _emit(phase_fit_resnet50(
        ResNet50(num_classes=1000, compute_dtype=jnp.bfloat16), batch=128,
        iters=4, seed=seed, require_fused=True))
    gc.collect()
    _emit(phase_serve_lm(lm_serve_config(), seed=seed))


def run_sharded(seed):
    import jax
    devices = jax.devices()
    _emit(phase_sharded_train_lm(lm_train_config(), batch=32,
                                 devices=devices, steps=3, seed=seed))
    gc.collect()
    _emit(phase_sharded_fit_conv(batch=512, devices=devices, iters=4,
                                 seed=seed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the three one-chip phases (default); 4: only "
                         "the sharded path and what it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no accelerator: jax found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(jax.devices()) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly that many "
              f"devices in this process, jax found {len(jax.devices())}",
              file=sys.stderr)
        return 2

    from deeplearning4j_tpu.kernels import autotune
    from deeplearning4j_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    store_before = autotune.records()
    _emit({"phase": "start", "device": device_facts(), **_versions(),
           "chips": args.chips, "seed": args.seed,
           "compile_cache_dir": cache_dir,
           "autotune_store": str(autotune._CACHE_PATH),
           "autotune_records_read": sorted(store_before)})
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(args.seed)
    else:
        run_sharded(args.seed)
    store_after = autotune.records()
    _emit({"phase": "end", "seconds": round(time.perf_counter() - t0, 1),
           "autotune_records_written": {
               k: v for k, v in store_after.items()
               if store_before.get(k) != v}})
    print(json.dumps({"ok": True, "device": device_facts()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
