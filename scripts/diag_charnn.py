"""char-RNN fused-LSTM-kernel A/B on the real chip (r4).

Same lesson-check as the BN training kernel: does the pallas whole-sequence
LSTM kernel actually beat the lax.scan XLA path on-chip at the benched
config? Writes scripts/diag_charnn_out.json.
"""

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import bench  # noqa: E402

OUT = pathlib.Path(__file__).with_name("diag_charnn_out.json")
RESULTS = []


def emit(tag, **kw):
    rec = {"tag": tag, **kw}
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)
    OUT.write_text(json.dumps(RESULTS, indent=2))


def run(tag, fused):
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.zoo import TextGenerationLSTM

    batch, seq, vocab = 256, 60, 77
    net = TextGenerationLSTM(num_classes=vocab, input_shape=(seq, vocab),
                             compute_dtype=jnp.bfloat16).init()
    # flip the kernel policy on the built layer instances (dataclass
    # defaults are baked into __init__, so mutate post-construction)
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


if __name__ == "__main__":
    bench.require_tpu()    # one process per chip: this one takes it
    run("charnn b256 bf16 fused-lstm-kernel", "auto")
    run("charnn b256 bf16 xla-scan", False)
