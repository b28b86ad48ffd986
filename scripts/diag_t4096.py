"""T=4096 perf-cliff diagnosis on the real chip (VERDICT r4 → r5 item 2).

The mystery: `t4096 b4 remat-full` runs 5.17 TFLOP/step in ~462 ms
(MFU 0.057) while `t1024 b16` runs MORE flops (6.27 TFLOP) in ~86 ms
(MFU 0.37) — same tokens/step, and the number is identical across the
xla / bf16-scores / flash attention paths, so the attention *kernel*
is not the differentiator. This script decomposes the step:

  A. full train step at t1024 b16 and t4096 b4 (benched baselines)
  B. same steps with attention REPLACED BY IDENTITY — everything-but-
     attention (embeddings, ffn, norms, loss head, optimizer, remat
     recompute of all of those). If B(t4096) ≈ B(t1024), the cliff is
     inside attention despite "all paths equal"; if B alone shows the
     cliff, attention was never the problem.
  C. forward-only loss (no grad/optimizer) — backward-specific cost.
  D. remat policy variants at t4096 (full / dots / dots_no_batch / off)
     — is it the *recompute* of the T² scores in backward (remat-full
     recomputes attention once per grad pass) rather than attention
     itself?
  E. XLA's own opinion: compiled cost_analysis (flops, bytes accessed)
     and memory_analysis (peak HBM) for both configs — if bytes/step
     explains 462 ms at 819 GB/s, it's traffic; if not, serialization.

Writes scripts/diag_t4096_out.json incrementally.
"""

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import bench  # noqa: E402

OUT = pathlib.Path(__file__).with_name("diag_t4096_out.json")
RESULTS = []


def emit(tag, **kw):
    rec = bench._stamp({"tag": tag, **kw})
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)
    OUT.write_text(json.dumps(RESULTS, indent=2))


def cfg_for(seq, **kw):
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo import transformer as tfm
    d = dict(vocab_size=32000, d_model=512, n_heads=8, n_layers=8,
             d_ff=2048, max_seq=seq, dtype=jnp.bfloat16, fused_loss=True,
             remat=True, remat_policy="full", attn_scores_bf16=True)
    d.update(kw)
    return tfm.TransformerConfig(**d)


def step_time(tag, cfg, batch, steps=9):
    run_chain, flops = bench.build_transformer(batch, cfg)
    timing = bench.measure_marginal(run_chain, n1=3, n2=steps)
    rec = bench._record(tag, "tokens/sec/chip", batch * cfg.max_seq,
                        timing, flops, batch=batch, seq=cfg.max_seq)
    emit(rec.pop("metric"), **rec)
    return rec


def no_attention(tag, cfg, batch):
    """Full train step with _attention monkeypatched to identity."""
    from deeplearning4j_tpu.zoo import transformer as tfm
    real = tfm._attention

    def identity_attn(cfg_, q, k, v, mask_bias=None):
        return q

    tfm._attention = identity_attn
    try:
        step_time(tag, cfg, batch)
    finally:
        tfm._attention = real


def forward_only(tag, cfg, batch):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.utils.tracing import total_flops
    from deeplearning4j_tpu.zoo import transformer as tfm

    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq)))
    tgt = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq)))

    def fwd(params, bump):
        return tfm.lm_loss(params, cfg, ids, tgt) + bump

    jf = jax.jit(fwd)
    flops = total_flops(fwd, params, 0.0)

    def step_once(bump):
        loss = jf(params, bump)
        return (loss * 0.0,), loss

    run_chain = bench.chain_runner(step_once, [jnp.float32(0.0)])
    timing = bench.measure_marginal(run_chain, n1=3, n2=9)
    rec = bench._record(tag, "tokens/sec/chip", batch * cfg.max_seq,
                        timing, flops, batch=batch, seq=cfg.max_seq)
    emit(rec.pop("metric"), **rec)


def xla_opinion(tag, cfg, batch):
    """Compiled cost_analysis + memory_analysis for the full train step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from deeplearning4j_tpu.zoo import transformer as tfm

    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)
    raw_step = tfm.make_train_step(cfg, opt)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq)))
    tgt = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq)))
    out = {}
    try:
        compiled = jax.jit(raw_step, donate_argnums=(0, 1)).lower(
            params, opt_state, ids, tgt).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        for k in ("flops", "bytes accessed", "optimal_seconds",
                  "bytes accessed output", "bytes accessed operand 0 {}"):
            if ca and k in ca:
                out[k.replace(" ", "_")] = float(ca[k])
        if ca:
            ba = float(ca.get("bytes accessed", 0.0))
            out["hbm_floor_ms_at_819GBs"] = round(ba / 819e9 * 1e3, 2)
        try:
            ma = compiled.memory_analysis()
            for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                         "output_size_in_bytes", "generated_code_size_in_bytes"):
                v = getattr(ma, attr, None)
                if v is not None:
                    out[attr] = int(v)
        except Exception as e:  # noqa: BLE001
            out["memory_analysis_error"] = str(e)[:200]
    except Exception as e:  # noqa: BLE001
        out["error"] = f"{type(e).__name__}: {e}"[:300]
    emit(tag, **out)


def block_sweep(tag_prefix, t, b, h=8, d=64):
    """Phase F: attention-only fwd+bwd time vs flash block size — the
    direct test of the grid-overhead theory (steps = (B·H)(T/bq)(T/bk);
    if per-step overhead dominates, time ~ 1/(bq·bk) until VMEM/MXU
    effects take over)."""
    import time

    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.kernels.flash_attention import flash_attention

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, h, t, d), jnp.bfloat16)
    for bq, bk in ((128, 128), (256, 256), (512, 512), (1024, 512),
                   (512, 1024), (1024, 1024), (2048, 1024), (1024, 2048),
                   (2048, 2048), (512, 4096), (1024, 4096), (4096, 1024)):
        if t % bq or t % bk:
            continue
        try:
            def loss(q_, k_, v_, _bq=bq, _bk=bk):
                return jnp.sum(flash_attention(
                    q_, k_, v_, None, True, _bq, _bk, False
                ).astype(jnp.float32))

            jfn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            out = jfn(q, q, q)
            float(jax.tree_util.tree_leaves(out)[0].reshape(-1)[0])
            n1, n2 = 2, 8
            t0 = time.perf_counter()
            for _ in range(n1):
                out = jfn(q, q, q)
            float(jax.tree_util.tree_leaves(out)[0].reshape(-1)[0])
            t1 = time.perf_counter()
            for _ in range(n2):
                out = jfn(q, q, q)
            float(jax.tree_util.tree_leaves(out)[0].reshape(-1)[0])
            t2 = time.perf_counter()
            dt = ((t2 - t1) - (t1 - t0)) / (n2 - n1)
            steps = (b * h) * (t // bq) * (t // bk)
            emit(f"{tag_prefix} flash bq{bq} bk{bk}",
                 ms=round(dt * 1e3, 3), grid_steps=steps,
                 us_per_step=round(dt * 1e6 / steps, 3))
        except Exception as e:  # noqa: BLE001 — VMEM overflow etc.
            emit(f"{tag_prefix} flash bq{bq} bk{bk}",
                 error=f"{type(e).__name__}: {e}"[:200])


def main():
    phases = sys.argv[1:] or ["A", "B", "C", "D", "E", "F"]
    if "A" in phases:
        step_time("A full t1024 b16 remat-full bf16s", cfg_for(1024), 16)
        step_time("A full t4096 b4 remat-full (auto->flash on TPU)",
                  cfg_for(4096), 4)
    if "B" in phases:
        no_attention("B no-attn t1024 b16", cfg_for(1024), 16)
        no_attention("B no-attn t4096 b4", cfg_for(4096), 4)
    if "C" in phases:
        forward_only("C fwd-only t1024 b16", cfg_for(1024), 16)
        forward_only("C fwd-only t4096 b4", cfg_for(4096), 4)
    if "D" in phases:
        step_time("D t4096 b4 remat-dots", cfg_for(4096, remat_policy="dots"), 4)
        step_time("D t4096 b4 remat-dots-nobatch",
                  cfg_for(4096, remat_policy="dots_no_batch"), 4)
        try:
            step_time("D t4096 b4 remat-off", cfg_for(4096, remat=False), 4)
        except Exception as e:  # noqa: BLE001
            emit("D t4096 b4 remat-off", error=f"{type(e).__name__}: {e}"[:300])
        step_time("D t4096 b4 flash-forced",
                  cfg_for(4096, use_flash_attention=True), 4)
        # THE comparison the r4 sweep never actually ran: flash OFF at
        # T=4096 (the "auto" default silently engaged flash in every r4
        # "xla"-tagged t4096 run — see sweep_transformer.py phase4 note).
        # The compiler may reject these; record that too.
        for tag, kw in (("xla-true", dict(use_flash_attention=False,
                                          attn_scores_bf16=False)),
                        ("bf16s-true", dict(use_flash_attention=False,
                                            attn_scores_bf16=True))):
            try:
                step_time(f"D t4096 b4 remat-full {tag}",
                          cfg_for(4096, **kw), 4)
            except Exception as e:  # noqa: BLE001
                emit(f"D t4096 b4 remat-full {tag}",
                     error=f"{type(e).__name__}: {e}"[:300])
        try:
            step_time("D t4096 b8 remat-full", cfg_for(4096), 8)
        except Exception as e:  # noqa: BLE001
            emit("D t4096 b8 remat-full", error=f"{type(e).__name__}: {e}"[:300])
    if "E" in phases:
        xla_opinion("E cost t1024 b16", cfg_for(1024), 16)
        xla_opinion("E cost t4096 b4", cfg_for(4096), 4)
    if "F" in phases:
        block_sweep("F t4096 b4", 4096, 4)
        block_sweep("F t1024 b16", 1024, 16)


if __name__ == "__main__":
    bench.require_tpu()    # one process per chip: this one takes it
    main()
