"""Flash-attention kernel diagnostic: attention-ONLY fwd+bwd timing vs the
XLA paths, per sequence length, on the real chip.

The transformer sweep showed flash ~tying XLA at T=4096 (MFU 0.11) — this
isolates the attention op to find where the kernel loses. Reports achieved
TFLOP/s counting LIVE flops only (causal ≈ half the rectangle), so an
efficient causal kernel should show ~flat achieved TFLOP/s across T while
the materializing XLA path degrades.

Usage: python scripts/diag_flash.py [fwd bwd ...]   (default: bwd = train path)
Writes scripts/diag_flash_out.json.
"""

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import bench  # noqa: E402

OUT = pathlib.Path(__file__).with_name("diag_flash_out.json")
RESULTS = []


def emit(tag, **kw):
    rec = bench._stamp({"tag": tag, **kw})
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)
    OUT.write_text(json.dumps(RESULTS, indent=2))


def attention_flops(b, h, t, d, causal, train, impl):
    """MXU flops for fwd(+bwd). Matmul counts differ per implementation:
    flash recomputes s in BOTH backward passes (fwd 2 + dq pass s/dp/dq 3 +
    dkv pass s/dv/dp/dk 4 = 9); the XLA paths keep p from the forward
    (fwd 2 + bwd dv/dp/ds->dq/ds->dk 4 = 6, with softmax vjp on the VPU).
    Reported achieved_tflops is thus per-impl WORK done, not a common
    denominator — compare impls on `ms`, not on achieved_tflops."""
    per_matmul = 2.0 * b * h * t * t * d
    if causal:
        per_matmul *= 0.5
    n_matmuls = (9 if impl == "flash" else 6) if train else 2
    return per_matmul * n_matmuls


def _timeit(fn, *args):
    import jax
    out = jax.block_until_ready(fn(*args))
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(leaf.reshape(-1)[0])  # host fetch: the result is on the host
    n1, n2 = 2, 8
    t0 = time.perf_counter()
    for _ in range(n1):
        out = fn(*args)
    float(jax.tree_util.tree_leaves(out)[0].reshape(-1)[0])
    t1 = time.perf_counter()
    for _ in range(n2):
        out = fn(*args)
    float(jax.tree_util.tree_leaves(out)[0].reshape(-1)[0])
    t2 = time.perf_counter()
    return ((t2 - t1) - (t1 - t0)) / (n2 - n1)


def run(train=True):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.kernels.flash_attention import (
        flash_attention_ntc, mha_reference)

    h, d = 8, 64      # matches the benched TransformerConfig (d_model 512)
    causal = True
    for t, b in ((1024, 16), (2048, 8), (4096, 4), (8192, 2)):
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, t, h * d), jnp.bfloat16)
        qh = q.reshape(b, t, h, d).transpose(0, 2, 1, 3)

        def xla_fn(q, k, v):
            return mha_reference(q, k, v, None, causal)

        def xla_bf16_fn(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                           preferred_element_type=jnp.float32) * (d ** -0.5)
            mask = jnp.tril(jnp.ones((t, t), bool))
            s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(jnp.bfloat16)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v)

        def flash_fn(q, k, v):
            return flash_attention_ntc(q, k, v, h, causal=causal)

        for name, fn, arg in (("xla", xla_fn, qh),
                              ("xla-bf16p", xla_bf16_fn, qh),
                              ("flash", flash_fn, q)):
            try:
                if train:
                    def loss(q_, k_, v_, _fn=fn):
                        return jnp.sum(_fn(q_, k_, v_).astype(jnp.float32))
                    jfn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                else:
                    jfn = jax.jit(fn)
                dt = _timeit(jfn, arg, arg, arg)
                fl = attention_flops(b, h, t, d, causal, train,
                                     "flash" if name == "flash" else "xla")
                emit(f"{name} t{t} b{b} {'bwd' if train else 'fwd'}",
                     ms=round(dt * 1e3, 3),
                     achieved_tflops=round(fl / dt / 1e12, 2),
                     live_flops=fl)
            except Exception as e:  # noqa: BLE001
                emit(f"{name} t{t} {'bwd' if train else 'fwd'}",
                     error=f"{type(e).__name__}: {e}"[:300])


if __name__ == "__main__":
    which = sys.argv[1:] or ["bwd"]
    bench.require_tpu()    # one process per chip: this one takes it
    for w in which:
        run(train=(w == "bwd"))
