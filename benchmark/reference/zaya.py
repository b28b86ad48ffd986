"""Plain reference for ZAYA1-style decoders (family "zaya"), given ONE
CHIP'S SHARE of a deployment in which two chips share each layer: some of
the routed experts and some rows of the tied embedding table; attention, the
router, norms and residual scales are held whole by every chip.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
kernel, no sort, no grouped product, no bf16. It imports nothing of
``deeplearning4j_tpu`` and takes nothing the program made: weights and
batches are drawn here from the seed and the driver hands the SAME draws to
the program. One layer (``x`` (T, D) is the residual stream, ``r_prev`` (T,
R) the router's state of the layer before, zeros for the first; H query
heads, J K/V heads of d channels, G = H / J; C = (H + J) d):

    h    = rmsnorm(x, g1)
    q~   = h Wq;  k~ = h Wk                      (D -> H d, D -> J d, no bias)
    v    = [h Wv1 | shift(h) Wv2]                (each D -> J d / 2; shift(h)_t = h_{t-1}, shift(h)_0 = 0:
                                                  the second half of the value channels is the token before's)
    mq_i = (q~_i + k~_{i // G}) / 2;   mk_j = (mean_{i in group j} q~_i + k~_j) / 2
    c    = [q~ | k~]                             (C channels, H + J heads)
    y_t  = sum_a w0[a] * c_{t-(K0-1-a)} + b0     (depthwise causal convolution, K0 taps, zeros on the left)
    z_t  = sum_a y_{t-(K1-1-a)} W1[a] + b1       (causal, K1 taps, each head's d channels to its own d; y_{<0} = 0)
    q    = z[:H d] + mq;  k = z[H d:] + mk
    q_i  = sqrt(d) q_i / |q_i|;  k_j = sqrt(d) tau_j k_j / |k_j|
    q,k  = rope on the first `rotary` dimensions of every head, split-half pairs, theta
    a    = softmax(q k^T / sqrt(d) + causal) v   (query head i on K/V head i // G)
    x    = (s1 x + b1) + (s2 (a Wo) + b2)
    u    = rmsnorm(x, g2)
    r    = u Wd + bd + gamma * r_prev            (D -> R; r is the next layer's r_prev)
    p    = softmax(W3 gelu(W2 gelu(W1 rmsnorm(r) + c1) + c2))    (R -> R -> R -> E + 1; exact gelu)
    e    = argmax(p + beta)                      (beta: a buffer no gradient reaches)
    m    = p_e (silu(u Wg[e]) * (u Wu[e])) Wdn[e]  if e is held here;  0 if e is absent or e = E, the skip
    x    = (s3 x + b3) + (s4 m + b4)
    loss = mean_t(logsumexp(z) - z[target]),  z = rmsnorm(x_L, gf) Emb^T   (tied table, embedding not scaled)
    AdamW: m,v moments, bias-corrected, p -= lr * (m^/(sqrt(v^)+eps) + wd * p)

What the absent experts would have added is left out, and that partial
result goes on to the next layer, exactly as in the program.

A training step is computed one row of the batch at a time, every layer
recomputed in the backward pass, attention in blocks of queries, the experts
one after another over every token, the loss in blocks of positions, each
layer's weights an array of their own, and Adam's moments kept on the host
between steps, so that the float32 step of 0.7 B parameters at 32,768
positions fits on one chip.

``product`` is the control's hook (``lowprec.FP8`` rounds both operands of
every product, the two convolutions among them, and the gradient flowing
back to scaled float8). ``rows`` plants the half-batch fault: a slice of the
batch's rows; where it keeps no row (a batch of one), the first half of
every row's positions is kept instead. ``choices`` hands ``row_loss`` the
experts to take (layers, T) in place of its own argmax.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import seed_key
from reference.lowprec import EXACT
from reference.smallthinker import (_rmsnorm, make_batches,  # noqa: F401
                                    stacked_norms, unstack)

Q_BLOCK = 512        # queries per attention block
LOSS_BLOCK = 2048    # positions per block of the loss


def sizes_of(config: dict) -> dict:
    """The share this chip holds, from a configuration file whose reduced
    keys give the counts HELD (the published ones are under ``published``)."""
    pub = config.get("published", {})
    dh = int(config["head_dim"])
    rope = config["rope_parameters"]["hybrid"]
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]), "head_dim": dh,
        "layers": int(config["num_hidden_layers"]),
        "ff": int(config["moe_intermediate_size"]),
        #: the router has an output for every published expert and the skip
        "experts": int(pub.get("num_experts", config["num_experts"])),
        "held": int(config["num_experts"]),
        "first": int(config.get("first_expert_held", 0)),
        "top_k": int(config["num_experts_per_tok"]),
        "router_hidden": int(config["router_hidden_size"]),
        "taps": (int(config["cca_time0"]), int(config["cca_time1"])),
        "rotary": int(round(dh * float(rope["partial_rotary_factor"]))),
        "theta": float(rope["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "positions": int(config["max_position_embeddings"]),
        #: the model's own depth: the output projection's draw follows it
        "depth": int(pub.get("num_hidden_layers", config["num_hidden_layers"])),
    }


def make_weights(seed: int, sz: dict):
    """All weights in one jitted call on the default device, float32, from
    the seed alone: normal / sqrt(fan_in) for the matrices, the convolutions'
    taps and the tied table, ones for the norm scales, the residual scales,
    the key temperature and the router's ``gamma``, zeros for every bias and
    for ``beta``. Two draws differ, so that an UNTRAINED router spreads its
    tokens as a trained one does (the configuration's ``assumed.init`` has
    the readings): attention's output projection is drawn at 0.02 / sqrt(2
    x depth), GPT-2's and Megatron's scaled draw of projections that write
    into the residual stream (attention at its first step returns nearly the
    mean of the values, the same vector for every token); and every column of the
    router MLP's second and third matrix sums to zero over its inputs (a
    GELU's output has a positive mean, which a random matrix turns into a
    preference for some experts that every token shares)."""
    d, f, L, V = sz["d"], sz["ff"], sz["layers"], sz["vocab"]
    dh, R, E = sz["head_dim"], sz["router_hidden"], sz["experts"]
    hq, hk = sz["heads"] * dh, sz["kv_heads"] * dh
    C, k0, k1 = hq + hk, *sz["taps"]
    f32 = jnp.float32

    def draw(key):
        k = jax.random.split(key, 11)

        def norm(key, shape, fan_in):
            return jax.random.normal(key, shape, f32) / math.sqrt(fan_in)

        def zero_sum(key, shape, fan_in):
            w = norm(key, shape, fan_in)
            return w - jnp.mean(w, axis=1, keepdims=True)

        return {
            "embed": norm(k[0], (V, d), d),
            "blocks": {
                "ln1": jnp.ones((L, d), f32),
                "wqkv": norm(k[1], (L, d, hq + 2 * hk), d),
                "cca_w0": norm(k[2], (L, k0, C), k0),
                "cca_b0": jnp.zeros((L, C), f32),
                "cca_w1": norm(k[3], (L, k1, C // dh, dh, dh), k1 * dh),
                "cca_b1": jnp.zeros((L, C), f32),
                "cca_tau": jnp.ones((L, sz["kv_heads"]), f32),
                "wo": jax.random.normal(k[4], (L, hq, d), f32)
                * (0.02 / math.sqrt(2 * sz["depth"])),
                "res_scale": jnp.ones((L, 4, d), f32),
                "res_bias": jnp.zeros((L, 4, d), f32),
                "ln2": jnp.ones((L, d), f32),
                "router_down": norm(k[5], (L, d, R), d),
                "router_down_b": jnp.zeros((L, R), f32),
                "router_gamma": jnp.ones((L, R), f32),
                "router_w1": norm(k[6], (L, R, R), R),
                "router_c1": jnp.zeros((L, R), f32),
                "router_w2": zero_sum(k[7], (L, R, R), R),
                "router_c2": jnp.zeros((L, R), f32),
                "router_w3": zero_sum(k[8], (L, R, E + 1), R),
                "router_beta": jnp.zeros((L, E + 1), f32),
                "we_in": norm(k[9], (L, sz["held"], d, 2 * f), d),
                "we_out": norm(k[10], (L, sz["held"], f, d), f),
            },
            "ln_f": jnp.ones((d,), f32),
        }

    return jax.jit(draw)(seed_key(seed))


def shift(x, n: int = 1):
    """x_{t-n} along the first axis, zeros on the left."""
    if n == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:n]), x[:-n]], axis=0)


def partial_rope(x, theta, rotary: int):
    """Rotary positions 0..T-1 on the first ``rotary`` dimensions of every
    head of (T, heads, dh), split-half pairs within them (dimension i with
    i + rotary / 2); the other dimensions pass through."""
    t, half = x.shape[0], rotary // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary:]], -1)


def depthwise_conv(c, w):
    """(T, C) by (K, C) taps, causal: tap a reads position t - (K - 1 - a)."""
    k = w.shape[0]
    return sum(shift(c, k - 1 - a) * w[a] for a in range(k))


def grouped_conv(y, w):
    """(T, heads, dh) by (K, heads, dh, dh) taps, causal: every head's dh
    channels to its own dh."""
    k = w.shape[0]
    return sum(jnp.einsum("thd,hde->the", shift(y, k - 1 - a), w[a])
               for a in range(k))


def mix(qt, kt, blk, sz, product=EXACT):
    """Steps 3 to 5 up to the rotation: (T, H d) and (T, J d) projections ->
    q (T, H, d) and k (T, J, d), each head of norm sqrt(d) (k times tau)."""
    t, dh = qt.shape[0], sz["head_dim"]
    h, j = sz["heads"], sz["kv_heads"]
    g = h // j
    q3, k3 = qt.reshape(t, h, dh), kt.reshape(t, j, dh)
    mq = (q3 + jnp.repeat(k3, g, axis=1)) / 2
    mk = (q3.reshape(t, j, g, dh).mean(axis=2) + k3) / 2
    c = jnp.concatenate([qt, kt], axis=-1)
    y = product(depthwise_conv)(c, blk["cca_w0"]) + blk["cca_b0"]
    z = product(grouped_conv)(y.reshape(t, h + j, dh), blk["cca_w1"]) \
        + blk["cca_b1"].reshape(h + j, dh)
    q, k = z[:, :h] + mq, z[:, h:] + mk

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-12)

    return (math.sqrt(dh) * unit(q),
            math.sqrt(dh) * blk["cca_tau"][None, :, None] * unit(k))


def values(h, blk, sz, product=EXACT):
    """(T, J, d): the first half of the value channels from the token, the
    second half from the token before."""
    mm = product(jnp.matmul)
    hq, hk = sz["heads"] * sz["head_dim"], sz["kv_heads"] * sz["head_dim"]
    wv = blk["wqkv"][:, hq + hk:]
    v = jnp.concatenate([mm(h, wv[:, : hk // 2]),
                         mm(shift(h), wv[:, hk // 2:])], axis=-1)
    return v.reshape(h.shape[0], sz["kv_heads"], sz["head_dim"])


def attention(q, k, v, product=EXACT):
    """(T, H, dh) queries on (T, J, dh) keys and values, causal; blocks of
    queries."""
    t, h, dh = q.shape
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    qb = math.gcd(Q_BLOCK, t)
    scores = product(lambda q, k: jnp.einsum("qhd,khd->hqk", q, k))
    weigh = product(lambda p, v: jnp.einsum("hqk,khd->qhd", p, v))
    j = jnp.arange(t)[None, :]

    def block(args):
        qs, i0 = args
        seen = j <= i0 + jnp.arange(qb)[:, None]
        s = jnp.where(seen[None], scores(qs, k) / math.sqrt(dh), -jnp.inf)
        return weigh(jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(jax.checkpoint(block),
                      (q.reshape(t // qb, qb, h, dh), jnp.arange(0, t, qb)))
    return out.reshape(t, h, dh)


def attention_part(h, blk, sz, product=EXACT):
    """What compressed convolutional attention adds: a Wo, (T, D)."""
    mm = product(jnp.matmul)
    hq, hk = sz["heads"] * sz["head_dim"], sz["kv_heads"] * sz["head_dim"]
    qt, kt = mm(h, blk["wqkv"][:, :hq]), mm(h, blk["wqkv"][:, hq: hq + hk])
    q, k = mix(qt, kt, blk, sz, product)
    q = partial_rope(q, sz["theta"], sz["rotary"])
    k = partial_rope(k, sz["theta"], sz["rotary"])
    a = attention(q, k, values(h, blk, sz, product), product)
    return mm(a.reshape(h.shape[0], hq), blk["wo"])


def router(u, r_prev, blk, sz, product=EXACT):
    """(r, p): the router's state (T, R) after the layer before's was added,
    and the probabilities (T, E + 1) over every expert and the skip."""
    mm = product(jnp.matmul)
    r = mm(u, blk["router_down"]) + blk["router_down_b"] \
        + blk["router_gamma"] * r_prev
    z = _rmsnorm(r, 1.0, sz["eps"])
    z = jax.nn.gelu(mm(z, blk["router_w1"]) + blk["router_c1"],
                    approximate=False)
    z = jax.nn.gelu(mm(z, blk["router_w2"]) + blk["router_c2"],
                    approximate=False)
    return r, jax.nn.softmax(mm(z, blk["router_w3"]), axis=-1)


def choose(p, blk):
    """The expert every token takes, E for the skip."""
    return jnp.argmax(p + jax.lax.stop_gradient(blk["router_beta"]), axis=-1)


def experts_part(u, p, e, blk, sz, product=EXACT):
    """What the experts held here add: for every token whose choice ``e``
    lives here, that SwiGLU expert weighted by its probability over all E +
    1 outputs. A plain loop over the experts held, each over every token
    with a weight that is zero where it was not chosen."""
    mm = product(jnp.matmul)
    f = sz["ff"]
    p_e = jnp.take_along_axis(p, e[:, None], axis=-1)[:, 0]

    def one(y, xs):
        w_in, w_out, eid = xs
        weight = jnp.where(e == eid, p_e, 0.0)
        gu = mm(u, w_in)
        act = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        return y + weight[:, None] * mm(act, w_out), None

    ids = sz["first"] + jnp.arange(sz["held"])
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(u),
                        (blk["we_in"], blk["we_out"], ids))
    return y


def layer_fn(x, r_prev, blk, sz, product=EXACT, choice=None):
    """(x, r, tokens whose own argmax is not ``choice``) after one layer;
    ``choice`` (T,) overrides the argmax."""
    s, b = blk["res_scale"], blk["res_bias"]
    h = _rmsnorm(x, blk["ln1"], sz["eps"])
    a = attention_part(h, blk, sz, product)
    x = (s[0] * x + b[0]) + (s[1] * a + b[1])
    u = _rmsnorm(x, blk["ln2"], sz["eps"])
    r, p = router(u, r_prev, blk, sz, product)
    own = choose(p, blk)
    e = own if choice is None else choice
    m = experts_part(u, p, e, blk, sz, product)
    return ((s[2] * x + b[2]) + (s[3] * m + b[3]), r,
            jnp.sum(own != e).astype(jnp.float32))


def row_loss(p, ids, tgt, sz, product=EXACT, choices=None, count=None):
    """(mean next-token NLL of one row, (T,) ids against (T,) targets, on
    unstacked weights; choices (layers, T) handed in that the row's own
    argmax would have made otherwise, summed over layers). ``count``
    positions enter the mean (all of them)."""
    x = p["embed"][ids]
    r = jnp.zeros((x.shape[0], sz["router_hidden"]), jnp.float32)
    other = jnp.zeros((), jnp.float32)
    for i, blk in enumerate(p["layers"]):
        choice = None if choices is None else choices[i]
        x, r, n = jax.checkpoint(
            lambda x, r, blk, choice: layer_fn(x, r, blk, sz, product, choice)
        )(x, r, blk, choice)
        other = other + n
    x = _rmsnorm(x, p["ln_f"], sz["eps"])
    t = x.shape[0]
    lb = math.gcd(LOSS_BLOCK, t)
    mm = product(jnp.matmul)
    keep = (jnp.arange(t) < (t if count is None else count)).astype(x.dtype)

    def block(total, xs):
        xb, tb, kb = xs
        z = mm(xb, p["embed"].T)
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        picked = jnp.take_along_axis(z, tb[:, None], axis=-1)[:, 0]
        return total + jnp.sum((lse - picked) * kb), None

    total, _ = jax.lax.scan(
        jax.checkpoint(block), jnp.zeros((), jnp.float32),
        (x.reshape(t // lb, lb, -1), tgt.reshape(-1, lb), keep.reshape(-1, lb)))
    return total / jnp.sum(keep), other


def loss(params, ids, tgt, sz, product=EXACT, choices=None):
    """Mean next-token NLL of (B, T) ids against (B, T) targets on the
    stacked weights the program holds (tests; ``train_steps`` goes row by
    row on unstacked ones). ``choices`` is (layers, B, T) or None."""
    p = unstack(params)
    rows = [row_loss(p, ids[r], tgt[r], sz, product,
                     None if choices is None else choices[:, r])[0]
            for r in range(ids.shape[0])]
    return sum(rows) / len(rows)


def train_steps(seed: int, config: dict, ids, tgt, n_steps: int,
                product=EXACT, rows=None, choices=None):
    """Follow ``n_steps`` AdamW steps from the seed's weights on batches
    ``ids[i], tgt[i]``. Returns the readings the comparison uses:
    ``losses`` (one per step), ``grad_norms`` (per leaf, of the first step's
    gradient) and ``delta_norms`` (per leaf, of the parameters' change after
    the last step). ``choices`` (n_steps, layers, B, T) or None: the experts
    every token takes, in place of the reference's own argmax; then
    ``choice_mismatch`` is the share of the choices, over every step
    followed, that its own argmax would have made otherwise (0.0 without
    ``choices``).

    What is on the device at once: the weights, one row's gradient and the
    sum of the rows before it with one row's activations; Adam's two moments
    live on the host between steps and come over for the update."""
    sz = sizes_of(config)
    hp = config["optimizer"]
    lr, b1, b2 = hp["learning_rate"], hp["b1"], hp["b2"]
    eps, wd = hp["eps"], hp["weight_decay"]
    count = None
    if rows is not None:
        if len(range(ids.shape[1])[rows]):
            ids, tgt = ids[:, rows], tgt[:, rows]
            if choices is not None:
                choices = [c[:, rows] for c in choices]
        else:       # a batch of one: half of the row's positions
            count = ids.shape[2] // 2
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
            lambda p, i, t, c: row_loss(p, i, t, sz, product, c, count),
            has_aux=True))
        add = jax.jit(lambda a, b: tmap(jnp.add, a, b), donate_argnums=(0, 1))
        mean = jax.jit(lambda g: tmap(lambda a: a / batch, g),
                       donate_argnums=0)
        adam = jax.jit(adam, donate_argnums=(0, 2, 3))
        norms = jax.jit(stacked_norms)
        p = start()
        m, v = (tmap(lambda a: np.zeros(a.shape, np.float32), p)
                for _ in range(2))
        losses, grad_norms, mismatch = [], None, 0.0
        for i in range(n_steps):
            g, total = None, 0.0
            for r in range(batch):
                c = None if choices is None else jnp.asarray(choices[i][:, r])
                (l, other), g_row = grad_row(p, jnp.asarray(ids[i, r]),
                                             jnp.asarray(tgt[i, r]), c)
                g = g_row if g is None else add(g, g_row)
                total += float(l)
                mismatch += float(other) / (
                    n_steps * batch * sz["layers"] * ids.shape[2])
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
            "delta_norms": {k: float(x) for k, x in delta.items()},
            "choice_mismatch": mismatch}
