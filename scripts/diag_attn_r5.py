"""Full-model attention-path A/B at the fixed flash block sizes (r5).

The diag_t4096 phase-F sweep showed the flash kernel's 128×128 default
blocks were the whole t4096 story (34 ms -> 6.1 ms fwd+bwd at 1024×1024,
vs 26.6 ms for the best XLA arm), and the flash5 autotuner now times the
grad path so big blocks actually get picked. This script decides the
production dispatch with full-model numbers:

  - t1024 b16: does flash now beat the bf16-scores XLA path (the 0.379
    benched config) at SHORT T too? (attention-only says 2.1 vs ~6 ms)
  - t4096 b4: does flash beat bf16s-true (MFU 0.2432, the phase-D
    winner)? And does remat_policy="save_attn" (skip re-running the T²
    op in backward) compose with either?
  - t8192 b2: the long-context point nothing has measured end-to-end.
  - charnn f32: the fused-LSTM kernel's remaining unmeasured dtype
    (bf16 measured scan-wins 3.05M vs 2.42M tok/s, diag_charnn_out).

Writes scripts/diag_attn_r5_out.json incrementally.
"""

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import bench  # noqa: E402

OUT = pathlib.Path(__file__).with_name("diag_attn_r5_out.json")
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
             remat=True, remat_policy="full", attn_scores_bf16=True,
             use_flash_attention=False)
    d.update(kw)
    return tfm.TransformerConfig(**d)


def step_time(tag, cfg, batch, steps=9):
    try:
        run_chain, flops = bench.build_transformer(batch, cfg)
        timing = bench.measure_marginal(run_chain, n1=3, n2=steps)
        rec = bench._record(tag, "tokens/sec/chip", batch * cfg.max_seq,
                            timing, flops, batch=batch, seq=cfg.max_seq)
        emit(rec.pop("metric"), **rec)
    except Exception as e:  # noqa: BLE001
        emit(tag, error=f"{type(e).__name__}: {e}"[:300])


def charnn_bf16_isolated(fused):
    """bf16 re-run, one arm per process (diag_charnn ran both shared)."""
    import jax.numpy as jnp
    _charnn_arm(f"charnn b256 bf16 {'fused-lstm-kernel' if fused else 'xla-scan'} isolated",
                fused, jnp.bfloat16)


def charnn_f32(tag, fused):
    _charnn_arm(tag, fused, None)


def _charnn_arm(tag, fused, compute_dtype):
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.zoo import TextGenerationLSTM

    batch, seq, vocab = 256, 60, 77
    net = TextGenerationLSTM(num_classes=vocab, input_shape=(seq, vocab),
                             compute_dtype=compute_dtype).init()
    for lyr in net.conf.layers:
        if hasattr(lyr, "fused"):
            lyr.fused = fused
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, (batch, seq))])
    y = jnp.asarray(np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, (batch, seq))])
    run_chain, flops = bench._mln_chain(net, x, y)
    timing = bench.measure_marginal(run_chain, n1=3, n2=15)
    rec = bench._record(tag, "tokens/sec/chip", batch * seq, timing, flops,
                        batch=batch, seq=seq)
    emit(rec.pop("metric"), **rec)


def main():
    phases = sys.argv[1:] or ["S", "L", "XL", "R"]
    if "S" in phases:  # t1024 b16
        step_time("t1024 b16 bf16s remat-full (benched cfg)",
                  cfg_for(1024), 16)
        step_time("t1024 b16 flash5 remat-full",
                  cfg_for(1024, use_flash_attention=True), 16)
        step_time("t1024 b16 flash5 save-attn",
                  cfg_for(1024, use_flash_attention=True,
                          remat_policy="save_attn"), 16)
        step_time("t1024 b16 bf16s save-attn",
                  cfg_for(1024, remat_policy="save_attn"), 16)
        step_time("t1024 b32 flash5 remat-full",
                  cfg_for(1024, use_flash_attention=True), 32)
    if "L" in phases:  # t4096 b4
        step_time("t4096 b4 bf16s remat-full (phase-D winner)",
                  cfg_for(4096), 4)
        step_time("t4096 b4 flash5 remat-full",
                  cfg_for(4096, use_flash_attention=True), 4)
        step_time("t4096 b4 flash5 save-attn",
                  cfg_for(4096, use_flash_attention=True,
                          remat_policy="save_attn"), 4)
        step_time("t4096 b4 flash5 remat-off",
                  cfg_for(4096, use_flash_attention=True, remat=False), 4)
        step_time("t4096 b4 bf16s save-attn",
                  cfg_for(4096, remat_policy="save_attn"), 4)
        step_time("t4096 b8 flash5 remat-full",
                  cfg_for(4096, use_flash_attention=True), 8)
    if "XL" in phases:  # t8192 b2
        step_time("t8192 b2 flash5 remat-full",
                  cfg_for(8192, use_flash_attention=True), 2)
        step_time("t8192 b2 flash5 save-attn",
                  cfg_for(8192, use_flash_attention=True,
                          remat_policy="save_attn"), 2)
        step_time("t8192 b2 bf16s remat-full", cfg_for(8192), 2)
        step_time("t8192 b4 flash5 best-policy",
                  cfg_for(8192, use_flash_attention=True), 4)
    # charnn arms as SEPARATE phases: the r4 lesson (charnn 2.9M shared
    # vs 4.7M isolated) says same-process A/B arms bias close races — run
    # each arm in its own interpreter: `python diag_attn_r5.py Rf`, `Rs`.
    # kernel arms pass fused=True, NOT "auto": since the demotion "auto"
    # resolves to the lax.scan path, so an "auto" arm would silently
    # measure scan vs scan while labeled kernel vs scan (ADVICE r5 #1)
    if "Rf" in phases or "R" in phases:
        charnn_f32("charnn b256 f32 fused-lstm-kernel", True)
    if "Rs" in phases or "R" in phases:
        charnn_f32("charnn b256 f32 xla-scan", False)
    if "Bf" in phases:
        charnn_bf16_isolated(True)
    if "Bs" in phases:
        charnn_bf16_isolated(False)


if __name__ == "__main__":
    bench.require_tpu()    # one process per chip: this one takes it
    main()
