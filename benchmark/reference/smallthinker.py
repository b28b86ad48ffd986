"""Plain reference for SmallThinker-style sparse-expert decoders (family
"smallthinker"), given ONE CHIP'S SHARE of a deployment in which several
chips share each layer: some of the query heads with their K/V heads, some
of the routed experts, some rows of the embedding and of the head.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
kernel, no sort, no grouped product, no bf16. It imports nothing of
``deeplearning4j_tpu`` and takes nothing the program made: weights and
batches are drawn here from the seed and the driver hands the SAME draws to
the program. One layer (``x`` is the residual stream):

    h     = rmsnorm(x, g1)
    r     = h Wr                         (all published experts' logits: the router reads what attention reads)
    q,k,v = split(h Wqkv)                (heads held here, their K/V heads; no bias)
    layout 1: q,k = rope(q,k; theta), split-half pairs over the whole head;  mask = causal and i - j < window
    layout 0: no positions at all;                                         mask = causal
    a     = softmax(q k^T / sqrt(dh) + mask) v        (query head i uses K/V head i // group)
    x     = x + a Wo
    u     = rmsnorm(x, g2)
    (s,e) = top_k(r);  w = softmax(s)                 (over the kept logits)
    y     = sum_{j: e_j held here} w_j (relu(u Wg[e_j]) * (u Wu[e_j])) Wd[e_j]     (ReGLU; Wg|Wu stored side by side)
    x     = x + y
    loss  = mean_rows(logsumexp(z) - z[target]),  z = rmsnorm(x_L, gf) H   (untied head H (d, V), embedding E not scaled)
    AdamW: m,v moments, bias-corrected, p -= lr * (m^/(sqrt(v^)+eps) + wd * p)

What the absent heads and experts would have added is left out, and that
partial result goes on to the next layer, exactly as in the program.

A training step is computed one row of the batch at a time (the loss is the
mean over rows, so the gradient is the mean of the rows' gradients), every
layer recomputed in the backward pass, attention in blocks of queries, the
loss in blocks of positions, each layer's weights an array of their own, and
Adam's moments kept on the host between steps, so that the float32 step of
0.6 B parameters (9.5 GB with gradient and moments) fits on one chip.

``product`` is the control's hook (``lowprec.FP8`` rounds both operands of
every product and the gradient flowing back to scaled float8); ``rows``
plants the half-batch fault.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import seed_key
from reference.lowprec import EXACT

Q_BLOCK = 1024       # queries per attention block
LOSS_BLOCK = 2048    # positions per block of the loss


def sizes_of(config: dict) -> dict:
    """The share this chip holds, from a configuration file whose reduced
    keys give the counts HELD (the published ones are under ``published``)."""
    layers = int(config["num_hidden_layers"])
    pub = config.get("published", {})
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]), "layers": layers,
        "ff": int(config["moe_ffn_hidden_size"]),
        #: the router's width: every published expert has a logit
        "experts": int(pub.get("moe_num_primary_experts",
                               config["moe_num_primary_experts"])),
        "held": int(config["moe_num_primary_experts"]),
        "first": int(config.get("first_expert_held", 0)),
        "top_k": int(config["moe_num_active_primary_experts"]),
        "rope": tuple(int(v) for v in config["rope_layout"][:layers]),
        "windowed": tuple(int(v) for v in
                          config["sliding_window_layout"][:layers]),
        "window": int(config["sliding_window_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "positions": int(config["max_position_embeddings"]),
    }


def make_weights(seed: int, sz: dict):
    """All weights in one jitted call on the default device, float32, from
    the seed alone: normal / sqrt(fan_in) for the matrices, ones for the
    norm scales, and unit-variance entries for the embedding, which this
    model does not scale: the size the benchmark's other LM's blocks see (it
    multiplies a 1 / sqrt(d) draw by sqrt(d)). Drawn at 1 / sqrt(d), a
    token's embedding is no larger than the mean of a few thousand value
    vectors that full attention adds to every token alike; the router's
    input then has a component common to all tokens and routing skews with
    depth (the largest held expert at 1.2, 2, 3.5 and 5 times the mean in
    layers 0 to 3), by another amount on every seed."""
    d, f, L, V = sz["d"], sz["ff"], sz["layers"], sz["vocab"]
    hq, hk = sz["heads"] * sz["head_dim"], sz["kv_heads"] * sz["head_dim"]

    def draw(key):
        k = jax.random.split(key, 7)

        def norm(key, shape, fan_in):
            return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

        return {
            "embed": jax.random.normal(k[0], (V, d), jnp.float32),
            "head": norm(k[1], (d, V), d),
            "blocks": {
                "ln1": jnp.ones((L, d), jnp.float32),
                "wqkv": norm(k[2], (L, d, hq + 2 * hk), d),
                "wo": norm(k[3], (L, hq, d), hq),
                "ln2": jnp.ones((L, d), jnp.float32),
                "router": norm(k[4], (L, d, sz["experts"]), d),
                "we_in": norm(k[5], (L, sz["held"], d, 2 * f), d),
                "we_out": norm(k[6], (L, sz["held"], f, d), f),
            },
            "ln_f": jnp.ones((d,), jnp.float32),
        }

    return jax.jit(draw)(seed_key(seed))


def make_batches(seed: int, n: int, batch: int, seq: int, vocab: int):
    """(ids, targets), each (n, batch, seq) int32 on the host: uniform over
    the rows of the vocabulary held here, the same sizes for every seed."""
    rng = np.random.default_rng([int(seed), 0x6D6F65])
    ids = rng.integers(0, vocab, (n, batch, seq), dtype=np.int32)
    tgt = rng.integers(0, vocab, (n, batch, seq), dtype=np.int32)
    return ids, tgt


def _rmsnorm(x, g, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * g


def rope(x, theta):
    """Rotary positions 0..T-1 on (T, heads, dh), split-half pairs
    (dimension i with i + dh/2) over the whole head."""
    t, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh // 2, dtype=jnp.float32) / (dh // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, product):
    """(T, H, dh) queries on (T, Hkv, dh) keys and values, causal, and
    within ``window`` keys where it is not None; blocks of queries."""
    t, h, dh = q.shape
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    qb = math.gcd(Q_BLOCK, t)
    scores = product(lambda q, k: jnp.einsum("qhd,khd->hqk", q, k))
    mix = product(lambda p, v: jnp.einsum("hqk,khd->qhd", p, v))
    j = jnp.arange(t)[None, :]

    def block(args):
        qs, i0 = args
        i = i0 + jnp.arange(qb)[:, None]
        seen = j <= i
        if window is not None:
            seen = seen & (i - j < window)
        s = jnp.where(seen[None], scores(qs, k) / math.sqrt(dh), -jnp.inf)
        return mix(jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(jax.checkpoint(block),
                      (q.reshape(t // qb, qb, h, dh), jnp.arange(0, t, qb)))
    return out.reshape(t, h, dh)


def attention_part(h, blk, sz, layer: int, product=EXACT):
    """What the heads held here add to the residual stream: a Wo."""
    mm = product(jnp.matmul)
    t = h.shape[0]
    hq, hk = sz["heads"] * sz["head_dim"], sz["kv_heads"] * sz["head_dim"]
    qkv = mm(h, blk["wqkv"])
    q = qkv[:, :hq].reshape(t, sz["heads"], sz["head_dim"])
    k = qkv[:, hq: hq + hk].reshape(t, sz["kv_heads"], sz["head_dim"])
    v = qkv[:, hq + hk:].reshape(t, sz["kv_heads"], sz["head_dim"])
    if sz["rope"][layer]:
        q, k = rope(q, sz["theta"]), rope(k, sz["theta"])
    window = sz["window"] if sz["windowed"][layer] else None
    a = attention(q, k, v, window, product)
    return mm(a.reshape(t, hq), blk["wo"])


def experts_part(u, r, blk, sz, product=EXACT):
    """What the experts held here add: for every token the ReGLU experts
    among its top-k that live here, weighted by the softmax over the kept
    logits. A plain loop over the experts held, each over every token with
    a weight that is zero where it was not chosen."""
    mm = product(jnp.matmul)
    f = sz["ff"]
    s, e = jax.lax.top_k(r, sz["top_k"])
    w = jax.nn.softmax(s, axis=-1)

    def one(y, xs):
        w_in, w_out, eid = xs
        weight = jnp.sum(jnp.where(e == eid, w, 0.0), axis=-1)
        gu = mm(u, w_in)
        act = jax.nn.relu(gu[:, :f]) * gu[:, f:]
        return y + weight[:, None] * mm(act, w_out), None

    ids = sz["first"] + jnp.arange(sz["held"])
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(u),
                        (blk["we_in"], blk["we_out"], ids))
    return y


def layer_fn(x, blk, sz, layer: int, product=EXACT):
    mm = product(jnp.matmul)
    h = _rmsnorm(x, blk["ln1"], sz["eps"])
    r = mm(h, blk["router"])
    x = x + attention_part(h, blk, sz, layer, product)
    u = _rmsnorm(x, blk["ln2"], sz["eps"])
    return x + experts_part(u, r, blk, sz, product)


def unstack(params):
    """The weights with each layer's on its own (``layers``: a list of
    dicts) in place of the stacked ``blocks``: a gradient by one layer's
    slice of a stacked array is a whole stacked array of zeros around it."""
    layers = params["blocks"]["ln1"].shape[0]
    out = {k: v for k, v in params.items() if k != "blocks"}
    out["layers"] = [{k: a[i] for k, a in params["blocks"].items()}
                     for i in range(layers)]
    return out


def stacked_norms(tree) -> dict:
    """``leaf_norms`` of an unstacked tree under the STACKED tree's leaf
    names: a block leaf's norm runs over all its layers."""
    sq = lambda a: jnp.sum(jnp.square(a))       # noqa: E731
    out = {f"['{k}']": jnp.sqrt(sq(v)) for k, v in tree.items()
           if k != "layers"}
    for k in tree["layers"][0]:
        out[f"['blocks']['{k}']"] = jnp.sqrt(
            sum(sq(layer[k]) for layer in tree["layers"]))
    return out


def row_loss(p, ids, tgt, sz, product=EXACT):
    """Mean next-token NLL of one row, (T,) ids against (T,) targets, on
    unstacked weights."""
    x = p["embed"][ids]
    for i, blk in enumerate(p["layers"]):
        x = jax.checkpoint(
            lambda x, blk, i=i: layer_fn(x, blk, sz, i, product))(x, blk)
    x = _rmsnorm(x, p["ln_f"], sz["eps"])
    t = x.shape[0]
    lb = math.gcd(LOSS_BLOCK, t)
    mm = product(jnp.matmul)

    def block(total, xs):
        xb, tb = xs
        z = mm(xb, p["head"])
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        picked = jnp.take_along_axis(z, tb[:, None], axis=-1)[:, 0]
        return total + jnp.sum(lse - picked), None

    total, _ = jax.lax.scan(jax.checkpoint(block), jnp.zeros((), jnp.float32),
                            (x.reshape(t // lb, lb, -1), tgt.reshape(-1, lb)))
    return total / t


def loss(params, ids, tgt, sz, product=EXACT):
    """Mean next-token NLL of (B, T) ids against (B, T) targets on the
    stacked weights the program holds (tests; ``train_steps`` goes row by
    row on unstacked ones)."""
    p = unstack(params)
    rows = [row_loss(p, ids[r], tgt[r], sz, product)
            for r in range(ids.shape[0])]
    return sum(rows) / len(rows)


def train_steps(seed: int, config: dict, ids, tgt, n_steps: int,
                product=EXACT, rows=None):
    """Follow ``n_steps`` AdamW steps from the seed's weights on batches
    ``ids[i], tgt[i]``. Returns the readings the comparison uses:
    ``losses`` (one per step), ``grad_norms`` (per leaf, of the first step's
    gradient) and ``delta_norms`` (per leaf, of the parameters' change after
    the last step).

    What is on the device at once: the weights, one row's gradient and the
    sum of the rows before it (three copies, 7.1 GB at the timed size) with
    one row's activations; Adam's two moments live on the host between
    steps and come over for the update, when the rows' gradients are one."""
    sz = sizes_of(config)
    hp = config["optimizer"]
    lr, b1, b2 = hp["learning_rate"], hp["b1"], hp["b2"]
    eps, wd = hp["eps"], hp["weight_decay"]
    if rows is not None:
        ids, tgt = ids[:, rows], tgt[:, rows]
    batch = ids.shape[1]
    tmap = jax.tree_util.tree_map

    def adam(p, g, m, v, count):
        m = tmap(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count

        def upd(p, m, v):
            return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)

        return tmap(upd, p, m, v), m, v

    with jax.default_matmul_precision("highest"):
        start = jax.jit(lambda: unstack(make_weights(seed, sz)))
        grad_row = jax.jit(jax.value_and_grad(
            lambda p, i, t: row_loss(p, i, t, sz, product)))
        add = jax.jit(lambda a, b: tmap(jnp.add, a, b), donate_argnums=(0, 1))
        mean = jax.jit(lambda g: tmap(lambda a: a / batch, g),
                       donate_argnums=0)
        adam = jax.jit(adam, donate_argnums=(0, 2, 3))
        norms = jax.jit(stacked_norms)
        p = start()
        m, v = (tmap(lambda a: np.zeros(a.shape, np.float32), p)
                for _ in range(2))
        losses, grad_norms = [], None
        for i in range(n_steps):
            g, total = None, 0.0
            for r in range(batch):
                l, g_row = grad_row(p, jnp.asarray(ids[i, r]),
                                    jnp.asarray(tgt[i, r]))
                g = g_row if g is None else add(g, g_row)
                total += float(l)
            g = mean(g)
            losses.append(total / batch)
            if i == 0:
                grad_norms = {k: float(x) for k, x in norms(g).items()}
            p, m, v = adam(p, g, m, v, jnp.float32(i + 1))
            del g
            m, v = jax.device_get((m, v))
        del m, v
        delta = jax.jit(lambda p, p0: stacked_norms(
            tmap(jnp.subtract, p, p0)))(p, start())
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": {k: float(x) for k, x in delta.items()}}
