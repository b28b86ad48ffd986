"""BERT fine-tune composition sweep on the real chip (VERDICT r4 → r5 item 5).

The transformer-LM sweep's two HBM cuts (remat-full, bf16 score
materialization) applied to the BERT-base T=128 fine-tune step, which
last measured MFU 0.40 WITHOUT either. At T=128 the score tensor is
small (B32·H12·128² bf16 ≈ 12 MB/layer) so bf16-scores should matter
less than at T=1024 — the sweep says which levers pay here, and whether
remat frees enough HBM for a larger batch to win.

Writes scripts/diag_bert_out.json; if a composition beats the 0.40
record, flip bench.bench_bert's config to the winner and re-capture.
"""

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import bench  # noqa: E402

OUT = pathlib.Path(__file__).with_name("diag_bert_out.json")
RESULTS = []


def emit(tag, **kw):
    rec = bench._stamp({"tag": tag, **kw})
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)
    OUT.write_text(json.dumps(RESULTS, indent=2))


def run(tag, batch, **cfg_kw):
    from deeplearning4j_tpu.zoo import transformer as tfm
    cfg = tfm.BertConfig(max_seq=128, **cfg_kw)
    try:
        run_chain, flops = bench.build_bert(batch, cfg)
        timing = bench.measure_marginal(run_chain, n1=3, n2=11)
        rec = bench._record(tag, "seq/sec/chip", batch, timing, flops,
                            batch=batch, seq=cfg.max_seq)
        emit(rec.pop("metric"), **rec)
    except Exception as e:  # noqa: BLE001
        emit(tag, error=f"{type(e).__name__}: {e}"[:300])


def main():
    run("bert b32 base (r4 record config)", 32)
    run("bert b32 bf16-scores", 32, attn_scores_bf16=True)
    run("bert b32 remat-full", 32, remat=True)
    run("bert b32 remat-full+bf16s", 32, remat=True, attn_scores_bf16=True)
    run("bert b32 remat-dots+bf16s", 32, remat=True, remat_policy="dots",
        attn_scores_bf16=True)
    run("bert b64 base", 64)
    run("bert b64 remat-full+bf16s", 64, remat=True, attn_scores_bf16=True)
    run("bert b128 remat-full+bf16s", 128, remat=True, attn_scores_bf16=True)


if __name__ == "__main__":
    bench.require_tpu()    # one process per chip: this one takes it
    main()
