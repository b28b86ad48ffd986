"""Post-adjudication batch sweeps on the real chip (r5 session 2).

The scan-path charnn and the b128 BERT winner were adopted at the batch
sizes tuned for their PREDECESSOR configs — sweep one step further:
  - charnn bf16 scan at b512 / b1024 (b256 was tuned for the fused kernel)
  - BERT remat-full+bf16s at b256 (b128 was the sweep edge, 0.61 and rising)
  - T=8192 b2 flash save-attn at the benched-config settings (candidate
    extra-long-context README row; r5b measured 106.9k tokens/s)

Writes scripts/diag_sweep_r5c_out.json. One arm per process when the
result would decide a config (the shared-process bias lesson): this
script takes the arm name as argv.
"""

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import bench  # noqa: E402

OUT = pathlib.Path(__file__).with_name("diag_sweep_r5c_out.json")


def emit(tag, **kw):
    rec = bench._stamp({"tag": tag, **kw})
    try:
        results = json.loads(OUT.read_text())
    except Exception:  # noqa: BLE001
        results = []
    results.append(rec)
    print(json.dumps(rec), flush=True)
    OUT.write_text(json.dumps(results, indent=2))


def charnn(batch):
    rec = bench.bench_charnn(batch, 25)
    emit(rec.pop("metric") + f" b{batch}", **rec)


def bert(batch):
    rec = bench.bench_bert(batch, 13)
    emit(rec.pop("metric") + f" b{batch}", **rec)


def t8192(batch):
    # one source of truth: measure the EXACT benched config
    rec = bench.bench_transformer_xlong(batch, 9)
    emit(rec.pop("metric") + f" b{batch}", **rec)


ARMS = {
    "charnn512": lambda: charnn(512),
    "charnn1024": lambda: charnn(1024),
    "bert256": lambda: bert(256),
    "t8192b2": lambda: t8192(2),
}

if __name__ == "__main__":
    bench.require_tpu()    # one process per chip: this one takes it
    for arm in sys.argv[1:]:
        ARMS[arm]()
