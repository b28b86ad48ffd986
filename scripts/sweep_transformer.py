"""Transformer-LM perf sweep on the real chip (VERDICT r2 item 2 runbook).

Usage: python scripts/sweep_transformer.py [phase]
  phase 1 — fused-loss on/off + remat policies at T=1024 (find best base)
  phase 2 — batch sweep on the best base config
  phase 3 — flash-vs-XLA attention crossover table over T
Each record is MFU-audited via the bench harness. Writes
scripts/sweep_transformer_out.json (appending per phase).
"""

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax.numpy as jnp  # noqa: E402

import bench  # noqa: E402
from deeplearning4j_tpu.zoo import transformer as tfm  # noqa: E402

OUT = pathlib.Path(__file__).with_name("sweep_transformer_out.json")


def run(tag, cfg, batch, steps=11):
    run_chain, flops = bench.build_transformer(batch, cfg)
    timing = bench.measure_marginal(run_chain, n1=3, n2=steps)
    rec = bench._record(tag, "tokens/sec/chip", batch * cfg.max_seq, timing,
                        flops, batch=batch, seq=cfg.max_seq)
    print(tag, "->", rec["value"], "tok/s  mfu", rec["mfu"],
          "step", rec["step_time_ms"], flush=True)
    results = json.loads(OUT.read_text()) if OUT.exists() else []
    results.append(rec)
    OUT.write_text(json.dumps(results, indent=2))
    return rec


def base_cfg(**kw):
    d = dict(vocab_size=32000, d_model=512, n_heads=8, n_layers=8,
             d_ff=2048, max_seq=1024, dtype=jnp.bfloat16, remat=False,
             fused_loss=False)
    d.update(kw)
    return tfm.TransformerConfig(**d)


def phase1():
    run("t1024 b16 naive-loss remat-off", base_cfg(), 16)
    run("t1024 b16 fused-loss remat-off", base_cfg(fused_loss=True), 16)
    run("t1024 b16 fused-loss chunk2048",
        base_cfg(fused_loss=True, loss_chunk=2048), 16)
    run("t1024 b16 fused-loss bf16-scores",
        base_cfg(fused_loss=True, attn_scores_bf16=True), 16)
    run("t1024 b16 fused-loss flash-forced",
        base_cfg(fused_loss=True, use_flash_attention=True), 16)
    run("t1024 b16 fused-loss remat-dots",
        base_cfg(fused_loss=True, remat=True, remat_policy="dots"), 16)
    run("t1024 b16 fused-loss remat-full",
        base_cfg(fused_loss=True, remat=True, remat_policy="full"), 16)


def phase2():
    for b in (8, 24, 32):
        run(f"t1024 b{b} fused-loss", base_cfg(fused_loss=True), b)


def phase3():
    for t in (1024, 2048, 4096):
        toks = 16 * 1024
        b = max(1, toks // t)
        for attn, tag in ((False, "xla"), (True, "flash")):
            try:
                run(f"t{t} b{b} fused {tag}-attn",
                    base_cfg(max_seq=t, fused_loss=True,
                             use_flash_attention=attn,
                             remat=(t >= 4096), remat_policy="dots"), b)
            except Exception as e:  # noqa: BLE001 — record and continue
                print(f"t{t} {tag}: FAILED {type(e).__name__}: {e}",
                      flush=True)


def phase4():
    """r4: combine the two phase-1/3 winners (remat-full 0.3046, bf16-scores
    0.2527) and settle the T=4096 long-context config with an XLA-vs-flash
    comparison under the same remat policy."""
    best = dict(fused_loss=True, remat=True, remat_policy="full",
                attn_scores_bf16=True)
    run("t1024 b16 remat-full+bf16-scores", base_cfg(**best), 16)
    run("t1024 b16 remat-full+bf16-scores chunk2048",
        base_cfg(**best, loss_chunk=2048), 16)
    for b in (32, 64):
        try:
            run(f"t1024 b{b} remat-full+bf16-scores", base_cfg(**best), b)
        except Exception as e:  # noqa: BLE001
            print(f"b{b}: FAILED {type(e).__name__}: {e}", flush=True)
    # r5 NOTE: the r4 version of this comparison left use_flash_attention
    # at its "auto" default (flash_min_seq=2048), so at T=4096 ALL THREE
    # tags ran the flash kernel — the 0.0575≈0.0568 "tie" the r4 verdict
    # flagged was the same program measured twice. Force the path OFF for
    # the xla/bf16-scores tags so the comparison is real.
    for tag, kw in (("xla", {"use_flash_attention": False,
                             "attn_scores_bf16": False}),
                    ("bf16-scores", {"use_flash_attention": False,
                                     "attn_scores_bf16": True}),
                    ("flash", {"use_flash_attention": True})):
        try:
            run(f"t4096 b4 remat-full {tag}",
                base_cfg(max_seq=4096, fused_loss=True, remat=True,
                         remat_policy="full", **kw), 4)
        except Exception as e:  # noqa: BLE001
            print(f"t4096 {tag}: FAILED {type(e).__name__}: {e}",
                  flush=True)


if __name__ == "__main__":
    phase = sys.argv[1] if len(sys.argv) > 1 else "1"
    bench.require_tpu()    # one process per chip: this one takes it
    {"1": phase1, "2": phase2, "3": phase3, "4": phase4}[phase]()
