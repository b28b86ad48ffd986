"""Plain reference for the decoder-only LM configurations (family "lm").

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, no
kernel, no chunked loss, no bf16: the equations of the repo's block written
out (see the configuration's ``departures`` for where they leave GPT-2).
It imports nothing of ``deeplearning4j_tpu`` and takes nothing the program
made: weights and batches are drawn here from the seed, and the driver hands
the SAME draws to the program.

    x0   = E[ids] * sqrt(d) + P[:T]
    h    = rmsnorm(x, g1);  q,k,v = split(h Wqkv);  a = causal_softmax(q k^T / sqrt(dh)) v
    x    = x + a Wo;        x = x + gelu_tanh(rmsnorm(x, g2) Win) Wout
    loss = mean_rows( logsumexp(z) - z[target] ),  z = rmsnorm(x_L, gf) E^T   (tied head)
    AdamW: m,v moments, bias-corrected, p -= lr * (m^/(sqrt(v^)+eps) + wd * p)

A training step is computed in blocks of rows (the loss is a mean over rows,
so the gradient is the mean of the blocks' gradients) with each block of the
model recomputed in the backward pass, so that the float32 step fits beside
nothing else on one chip.

``product`` is the control's hook: a wrapper round every matrix product
(``lowprec.EXACT`` for the reference; ``lowprec.FP8`` for the control, which
rounds both operands and the gradient flowing back to scaled float8, the
nearest precision under the bf16 the configuration states).
``rows`` plants the half-batch fault: only those rows of each batch are used
and the mean is taken over them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import delta_norms, leaf_norms, seed_key
from reference.lowprec import EXACT

RMS_EPS = 1e-6


def sizes_of(config: dict) -> dict:
    d = int(config["n_embd"])
    return {"vocab": int(config["vocab_size"]), "d": d,
            "heads": int(config["n_head"]), "layers": int(config["n_layer"]),
            "ff": int(config.get("n_inner") or 4 * d),
            "positions": int(config["n_positions"])}


def make_weights(seed: int, sz: dict):
    """All weights in one jitted call on the default device, float32 (the
    type the program keeps them in), from the seed alone."""
    d, f, L, V, T = sz["d"], sz["ff"], sz["layers"], sz["vocab"], sz["positions"]

    def draw(key):
        k = jax.random.split(key, 7)

        def norm(key, shape, fan_in):
            return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

        return {
            "embed": norm(k[0], (V, d), d),
            "pos_embed": 0.02 * jax.random.normal(k[1], (T, d), jnp.float32),
            "blocks": {
                "ln1": jnp.ones((L, d), jnp.float32),
                "wqkv": norm(k[2], (L, d, 3 * d), d),
                "wo": norm(k[3], (L, d, d), d),
                "ln2": jnp.ones((L, d), jnp.float32),
                "w_in": norm(k[4], (L, d, f), d),
                "w_out": norm(k[5], (L, f, d), f),
            },
            "ln_f": jnp.ones((d,), jnp.float32),
        }

    return jax.jit(draw)(seed_key(seed))


def make_batches(seed: int, n: int, batch: int, seq: int, vocab: int):
    """(ids, targets), each (n, batch, seq) int32 on the host: uniform ids,
    every row different, the same sizes for every seed."""
    rng = np.random.default_rng([int(seed), 0x6C6D])
    ids = rng.integers(0, vocab, (n, batch, seq), dtype=np.int32)
    tgt = rng.integers(0, vocab, (n, batch, seq), dtype=np.int32)
    return ids, tgt


def _rmsnorm(x, g):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + RMS_EPS) * g


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _block(x, blk, heads, product):
    b, t, d = x.shape
    dh = d // heads
    mm = product(jnp.matmul)
    h = _rmsnorm(x, blk["ln1"])
    qkv = mm(h, blk["wqkv"])
    q, k, v = (z.reshape(b, t, heads, dh) for z in jnp.split(qkv, 3, axis=-1))
    s = product(lambda q, k: jnp.einsum("bqhd,bkhd->bhqk", q, k))(q, k)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s / math.sqrt(dh), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = product(lambda p, v: jnp.einsum("bhqk,bkhd->bqhd", p, v))(p, v)
    x = x + mm(a.reshape(b, t, d), blk["wo"])
    m = _gelu_tanh(mm(_rmsnorm(x, blk["ln2"]), blk["w_in"]))
    return x + mm(m, blk["w_out"])


def loss(params, ids, tgt, sz, product=EXACT):
    """Mean next-token NLL of (B, T) ids against (B, T) targets."""
    d = sz["d"]
    x = params["embed"][ids] * math.sqrt(d) + params["pos_embed"][: ids.shape[1]]
    blk_fn = jax.checkpoint(lambda x, blk: _block(x, blk, sz["heads"], product))
    x, _ = jax.lax.scan(lambda x, blk: (blk_fn(x, blk), None), x,
                        params["blocks"])
    z = product(lambda h, e: h @ e.T)(_rmsnorm(x, params["ln_f"]),
                                      params["embed"])
    lse = jax.scipy.special.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def train_steps(seed: int, config: dict, ids, tgt, n_steps: int,
                rows_per_block: int = 4, product=EXACT, rows=None):
    """Follow ``n_steps`` AdamW steps from the seed's weights on batches
    ``ids[i], tgt[i]``. Returns the readings the comparison uses:
    ``losses`` (one per step), ``grad_norms`` (per leaf, of the first step's
    gradient) and ``delta_norms`` (per leaf, of the parameters' change after
    the last step)."""
    sz = sizes_of(config)
    hp = config["optimizer"]
    lr, b1, b2 = hp["learning_rate"], hp["b1"], hp["b2"]
    eps, wd = hp["eps"], hp["weight_decay"]
    if rows is not None:
        ids, tgt = ids[:, rows], tgt[:, rows]
    batch = ids.shape[1]
    rpb = math.gcd(rows_per_block, batch)
    n_blocks = batch // rpb

    def step(params, m, v, count, ids_b, tgt_b):
        def one(carry, xs):
            acc, total = carry
            l, g = jax.value_and_grad(loss)(params, xs[0], xs[1], sz, product)
            return (jax.tree_util.tree_map(jnp.add, acc, g), total + l), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (g, total), _ = jax.lax.scan(
            one, (zeros, jnp.zeros((), jnp.float32)),
            (ids_b.reshape(n_blocks, rpb, -1), tgt_b.reshape(n_blocks, rpb, -1)))
        g = jax.tree_util.tree_map(lambda a: a / n_blocks, g)
        count = count + 1
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count

        def upd(p, m, v):
            return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)

        return (jax.tree_util.tree_map(upd, params, m, v), m, v, count,
                total / n_blocks, leaf_norms(g))

    with jax.default_matmul_precision("highest"):
        step = jax.jit(step, donate_argnums=(0, 1, 2))
        params = make_weights(seed, sz)
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        v = jax.tree_util.tree_map(jnp.zeros_like, params)
        count = jnp.zeros((), jnp.float32)
        losses, grad_norms = [], None
        for i in range(n_steps):
            params, m, v, count, l, gn = step(params, m, v, count,
                                              jnp.asarray(ids[i]),
                                              jnp.asarray(tgt[i]))
            losses.append(float(l))
            if i == 0:
                grad_norms = {k: float(x) for k, x in gn.items()}
        del m, v
        delta = jax.jit(delta_norms)(params, make_weights(seed, sz))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": {k: float(x) for k, x in delta.items()}}
