"""Plain reference for Qwen3-Next-style decoders (family "qwen3_next",
``model_type: qwen3_next``), given ONE CHIP'S SHARE of a deployment in which
sixteen chips share each layer by expert parallelism: some of the routed
experts and some rows of the embedding and of the head; the gated DeltaNet
and attention layers, the shared expert with its gate, the router and the
norms are held whole by every chip.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
kernel, no sort, no grouped product, no chunked algebra, no bf16. It imports
nothing of ``deeplearning4j_tpu`` and takes nothing the program made: weights
and batches are drawn here from the seed and ``drivers/qwen3next_train.py``
hands the SAME draws to the program. Every RMS norm but the gated one is
zero-centred:
``norm(x, w) = x / sqrt(mean(x^2) + eps) * (1 + w)``. One layer (``x`` (T, D)
is the residual stream; no bias anywhere):

    h    = norm(x, g1)
    layer i with (i + 1) % full_attention_interval != 0, gated DeltaNet
    (Hk key heads of dk, Hv value heads of dv; value head j reads key head
    j // (Hv / Hk)):
      [q | k | v | z] = h Wqkvz;  [b | a] = h Wba
      [q | k | v] = silu(conv(q | k | v))     (depthwise, causal, K taps: tap a reads t - (K - 1 - a), zeros before 0)
      q^ = q / sqrt(|q|^2 + 1e-6) / sqrt(dk);  k^ = k / sqrt(|k|^2 + 1e-6)   (per head)
      beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias);  alpha = exp(g)
      per value head, S (dk, dv) from zeros, TOKEN BY TOKEN:
        S <- alpha_t S;  d_t = beta_t (v_t - S^T k^_t);  S <- S + k^_t d_t^T;  o_t = S^T q^_t
      o    = o / sqrt(mean(o^2) + eps) * wg * silu(z)          (per head over dv; wg drawn at ones)
      m    = o Wout
    the other layers, gated attention (H heads of Dh on Hkv K/V heads):
      [q | k | v | gate] = h Wqkv;  q = norm(q, gq), k = norm(k, gk) per head
      q, k = rope on the first `rotary` dimensions of every head, split-half pairs, theta
      m    = (softmax(q k^T / sqrt(Dh) + causal) v  *  sigmoid(gate)) Wo   (query head i on K/V head i // G)
    x    = x + m
    u    = norm(x, g2)
    p    = softmax(u Wr)                      (D -> E, every published expert)
    e    = top_k(p);  w_j = p_{e_j} / sum_j p_{e_j}
    y    = sum_{j: e_j held here} w_j (silu(u Wg[e_j]) * (u Wu[e_j])) Wd[e_j]
           + sigmoid(u ws) (silu(u Wsg) * (u Wsu)) Wsd                      (the shared expert, gated per token)
    x    = x + y
    loss = mean_t(logsumexp(z_t) - z_t[target_t]),  z = norm(x_L, gf) Wh    (untied head, embedding not scaled)
    AdamW: m,v moments, bias-corrected, p -= lr * (m^/(sqrt(v^)+eps) + wd * p)

What the absent experts would have added is left out, and that partial
result goes on to the next layer, exactly as in the program.

Departures from the published model, each one the program's too: the norms
are zero-centred scales w decayed toward 0 by AdamW (as published); the
columns of Wqkvz, Wba and Wqkv are in the blocked order written above where
the published code groups them by key head (q and the gate by head): on
weights drawn from a seed a fixed permutation of columns, the same model; no
multi-token prediction module (``described_as`` names one, config.json has
no key for it) and no auxiliary balancing loss (config.json names none).

A training step is computed one row of the batch at a time, every layer
recomputed in the backward pass, attention in blocks of queries, the
recurrence as a scan over tokens checkpointed every ``CHUNK`` of them, the
experts one after another over every token, the loss in blocks of positions,
each layer's weights an array of their own, and Adam's moments kept on the
host between steps, so that the float32 step of 0.6 B parameters at 8,192
positions fits on one chip.

``product`` is the control's hook (``lowprec.FP8`` rounds both operands of
every product, the recurrence's among them, and the gradient flowing back to
scaled float8). ``rows`` plants the half-batch fault (a batch of one: the
first half of the row's positions). ``choices`` hands ``row_loss`` the
experts to take (layers, K, T) in place of its own top-k. ``fault`` plants
one of this model's own: ``"no_decay"`` (alpha = 1), ``"no_delta"`` (S <-
alpha S + beta k v^T), ``"no_attn_gate"`` (attention's output ungated),
``"no_shared_gate"`` (the shared expert with weight 1).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import seed_key
from reference.glm_lite import mean_nll
from reference.lowprec import EXACT
from reference.smallthinker import attention, make_batches  # noqa: F401
from reference.zaya import depthwise_conv, partial_rope

CHUNK = 64           # tokens of the recurrence between two checkpoints
FAULTS = ("no_decay", "no_delta", "no_attn_gate", "no_shared_gate")


def sizes_of(config: dict) -> dict:
    """The share this chip holds, from a configuration file whose reduced
    keys give the counts HELD (the published ones are under ``published``)."""
    pub = config.get("published", {})
    fe = int(config["moe_intermediate_size"])
    fs = int(config["shared_expert_intermediate_size"])
    interval = int(config["full_attention_interval"])
    layers = int(config["num_hidden_layers"])
    if int(config.get("decoder_sparse_step", 1)) != 1 \
            or config.get("mlp_only_layers") \
            or not config.get("norm_topk_prob", True) \
            or config.get("rope_scaling") is not None \
            or config.get("use_sliding_window", False) \
            or fs % fe or layers % interval:
        raise ValueError("qwen3_next: dense layers among the sparse ones, "
                         "unnormed weights, a rope scaling, a window, a "
                         "shared expert of another width than a whole number "
                         "of experts' or a stack of no whole period is in "
                         "neither the reference nor the program")
    dh = int(config["head_dim"])
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]), "head_dim": dh,
        "rotary": int(round(dh * float(config["partial_rotary_factor"]))),
        "layers": layers, "interval": interval,
        "ff": int(config["intermediate_size"]), "expert_ff": fe,
        #: the router has an output for every published expert
        "experts": int(pub.get("num_experts", config["num_experts"])),
        "held": int(config["num_experts"]),
        "first": int(config.get("first_expert_held", 0)),
        "top_k": int(config["num_experts_per_tok"]),
        "shared": fs // fe,
        "key_heads": int(config["linear_num_key_heads"]),
        "value_heads": int(config["linear_num_value_heads"]),
        "key_size": int(config["linear_key_head_dim"]),
        "value_size": int(config["linear_value_head_dim"]),
        "taps": int(config["linear_conv_kernel_dim"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "positions": int(config["max_position_embeddings"]),
    }


def is_attention(sz, i: int) -> bool:
    """Layer i is full attention: every ``interval``-th, the others gated
    DeltaNet (the published ``layer_types``)."""
    return (i + 1) % sz["interval"] == 0


def make_weights(seed: int, sz: dict):
    """All weights in one jitted call on the default device, float32, from
    the seed alone, in the tree the program holds: ``blocks`` with the
    leaves of every layer stacked over all layers, attention's over the
    attention layers and the gated DeltaNet's (``gdn_*``) over those layers,
    each in stack order. Normal / sqrt(fan_in) for every matrix (the
    convolution's fan-in is its taps), zeros for the zero-centred norm
    scales, ones for the gated norm's and for dt_bias, A_log = log U(0, 16)
    (the published initialization), and unit-variance entries for the
    embedding, which this model does not scale (as
    ``smallthinker.make_weights``: a token's own row then outweighs what
    early mixing adds to every token alike, and the untrained router spreads
    its tokens)."""
    d, V, L = sz["d"], sz["vocab"], sz["layers"]
    La = L // sz["interval"]
    Lg = L - La
    hq, hk = sz["heads"] * sz["head_dim"], sz["kv_heads"] * sz["head_dim"]
    nk = sz["key_heads"] * sz["key_size"]
    Hv, nv = sz["value_heads"], sz["value_heads"] * sz["value_size"]
    f, fs = sz["expert_ff"], sz["shared"] * sz["expert_ff"]
    f32 = jnp.float32

    def norm(key, shape, fan_in):
        return jax.random.normal(key, shape, f32) / math.sqrt(fan_in)

    def draw(key):
        k = jax.random.split(key, 16)
        blocks = {
            "ln1": jnp.zeros((L, d), f32), "ln2": jnp.zeros((L, d), f32),
            "wqkv": norm(k[2], (La, d, 2 * hq + 2 * hk), d),
            "wo": norm(k[3], (La, hq, d), hq),
            "q_norm": jnp.zeros((La, sz["head_dim"]), f32),
            "k_norm": jnp.zeros((La, sz["head_dim"]), f32),
            "gdn_wqkvz": norm(k[4], (Lg, d, 2 * nk + 2 * nv), d),
            "gdn_wba": norm(k[5], (Lg, d, 2 * Hv), d),
            "gdn_conv": norm(k[6], (Lg, sz["taps"], 2 * nk + nv), sz["taps"]),
            "gdn_a_log": jnp.log(jax.random.uniform(k[7], (Lg, Hv), f32,
                                                    0.0, 16.0)),
            "gdn_dt_bias": jnp.ones((Lg, Hv), f32),
            "gdn_norm": jnp.ones((Lg, sz["value_size"]), f32),
            "gdn_wo": norm(k[8], (Lg, nv, d), nv),
            "router": norm(k[9], (L, d, sz["experts"]), d),
            "we_in": norm(k[10], (L, sz["held"], d, 2 * f), d),
            "we_out": norm(k[11], (L, sz["held"], f, d), f),
            "ws_in": norm(k[12], (L, d, 2 * fs), d),
            "ws_out": norm(k[13], (L, fs, d), fs),
            "ws_gate": norm(k[14], (L, d, 1), d),
        }
        return {"embed": jax.random.normal(k[0], (V, d), f32),
                "head": norm(k[1], (d, V), d), "blocks": blocks,
                "ln_f": jnp.zeros((d,), f32)}

    return jax.jit(draw)(seed_key(seed))


def znorm(x, w, eps):
    """The zero-centred RMS norm over the last axis."""
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + w)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def recurrence(q, k, v, g, beta, product=EXACT, fault=None):
    """The gated delta rule token by token: q, k (T, Hk, dk) normed and
    scaled, v (T, Hv, dv), g and beta (T, Hv) -> o (T, Hv, dv). A scan over
    tokens, checkpointed every ``CHUNK`` of them (a sequence that is no
    multiple of it is padded at its end, where nothing reads the padding)."""
    t, hv, dv = v.shape
    rep = hv // q.shape[1]
    q, k = jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1)
    pad = (-t) % CHUNK
    read = product(lambda s, x: jnp.einsum("hkv,hk->hv", s, x))     # S^T x
    write = product(lambda x, y: jnp.einsum("hk,hv->hkv", x, y))

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        if fault != "no_decay":
            s = jnp.exp(g_t)[:, None, None] * s
        d = b_t[:, None] * (v_t if fault == "no_delta" else v_t - read(s, k_t))
        s = s + write(k_t, d)
        return s, read(s, q_t)

    def chunk(s, xs):
        return jax.lax.scan(token, s, xs)

    xs = tuple(jnp.concatenate([a, jnp.zeros((pad, *a.shape[1:]), a.dtype)])
               .reshape(-1, CHUNK, *a.shape[1:]) for a in (q, k, v, g, beta))
    s0 = jnp.zeros((hv, q.shape[-1], dv), jnp.float32)
    _, o = jax.lax.scan(jax.checkpoint(chunk), s0, xs)
    return o.reshape(-1, hv, dv)[:t]


def gdn_part(h, blk, sz, product=EXACT, fault=None):
    """What a gated DeltaNet layer adds: o Wout, (T, D)."""
    mm = product(jnp.matmul)
    t = h.shape[0]
    Hk, Hv = sz["key_heads"], sz["value_heads"]
    dk, dv = sz["key_size"], sz["value_size"]
    nk, nv = Hk * dk, Hv * dv
    qkvz, ba = mm(h, blk["gdn_wqkvz"]), mm(h, blk["gdn_wba"])
    c = jax.nn.silu(depthwise_conv(qkvz[:, :2 * nk + nv], blk["gdn_conv"]))
    q = _unit(c[:, :nk].reshape(t, Hk, dk)) / math.sqrt(dk)
    k = _unit(c[:, nk:2 * nk].reshape(t, Hk, dk))
    v = c[:, 2 * nk:].reshape(t, Hv, dv)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(blk["gdn_a_log"]) * jax.nn.softplus(ba[:, Hv:]
                                                      + blk["gdn_dt_bias"])
    o = recurrence(q, k, v, g, beta, product, fault)
    ms = jnp.mean(jnp.square(o), -1, keepdims=True)
    o = o * jax.lax.rsqrt(ms + sz["eps"]) * blk["gdn_norm"] \
        * jax.nn.silu(qkvz[:, 2 * nk + nv:].reshape(t, Hv, dv))
    return mm(o.reshape(t, nv), blk["gdn_wo"])


def attention_part(h, blk, sz, product=EXACT, fault=None):
    """What a gated attention layer adds: (a * sigmoid(gate)) Wo, (T, D)."""
    mm = product(jnp.matmul)
    t, H, J, dh = h.shape[0], sz["heads"], sz["kv_heads"], sz["head_dim"]
    hq, hk = H * dh, J * dh
    z = mm(h, blk["wqkv"])
    q = znorm(z[:, :hq].reshape(t, H, dh), blk["q_norm"], sz["eps"])
    k = znorm(z[:, hq:hq + hk].reshape(t, J, dh), blk["k_norm"], sz["eps"])
    v = z[:, hq + hk:hq + 2 * hk].reshape(t, J, dh)
    q = partial_rope(q, sz["theta"], sz["rotary"])
    k = partial_rope(k, sz["theta"], sz["rotary"])
    a = attention(q, k, v, None, product).reshape(t, hq)
    if fault != "no_attn_gate":
        a = a * jax.nn.sigmoid(z[:, hq + 2 * hk:])
    return mm(a, blk["wo"])


def swiglu(u, w_in, w_out, product=EXACT):
    mm = product(jnp.matmul)
    gu = mm(u, w_in)
    f = w_out.shape[0]
    return mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_out)


def experts_part(u, p, e, blk, sz, product=EXACT):
    """What the routed experts held here add: for every token those of its
    K choices ``e`` (T, K) that live here, each weighted by its probability
    over the chosen ones'. A plain loop over the experts held, each over
    every token with a weight that is zero where it was not chosen."""
    kept = jnp.take_along_axis(p, e, axis=-1)
    w = kept / jnp.sum(kept, -1, keepdims=True)

    def one(y, xs):
        w_in, w_out, eid = xs
        weight = jnp.sum(jnp.where(e == eid, w, 0.0), axis=-1)
        return y + weight[:, None] * swiglu(u, w_in, w_out, product), None

    ids = sz["first"] + jnp.arange(sz["held"])
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(u),
                        (blk["we_in"], blk["we_out"], ids))
    return y


def layer_fn(x, blk, sz, product=EXACT, choice=None, fault=None):
    """(x, assignments of ``choice`` that the layer's own top-k lacks) after
    one layer, gated DeltaNet where ``blk`` holds ``gdn_wqkvz``, else gated
    attention; ``choice`` (K, T) overrides its top-k."""
    mm = product(jnp.matmul)
    h = znorm(x, blk["ln1"], sz["eps"])
    mix = gdn_part if "gdn_wqkvz" in blk else attention_part
    x = x + mix(h, blk, sz, product, fault)
    u = znorm(x, blk["ln2"], sz["eps"])
    p = jax.nn.softmax(mm(u, blk["router"]), axis=-1)
    own = jax.lax.top_k(p, sz["top_k"])[1]
    e = own if choice is None else choice.T
    shared = swiglu(u, blk["ws_in"], blk["ws_out"], product)
    if fault != "no_shared_gate":
        shared = jax.nn.sigmoid(mm(u, blk["ws_gate"])) * shared
    y = experts_part(u, p, e, blk, sz, product) + shared
    other = jnp.sum(~jnp.any(e[:, :, None] == own[:, None, :], axis=-1))
    return x + y, other.astype(jnp.float32)


def row_loss(p, ids, tgt, sz, product=EXACT, choices=None, count=None,
             fault=None):
    """(loss of one row, (T,) ids against (T,) targets, on unstacked
    weights; assignments handed in that the row's own top-k lacks, summed
    over the layers). ``choices`` is (layers, K, T); the mean runs over the
    first ``count`` positions (all of them)."""
    t = ids.shape[0]
    layer = jax.checkpoint(
        lambda x, blk, choice: layer_fn(x, blk, sz, product, choice, fault))
    x = p["embed"][ids]
    other = jnp.zeros((), jnp.float32)
    for i, blk in enumerate(p["layers"]):
        x, miss = layer(x, blk, None if choices is None else choices[i])
        other = other + miss
    loss = mean_nll(znorm(x, p["ln_f"], sz["eps"]), tgt, p["head"],
                    jnp.arange(t) < (t if count is None else count), product)
    return loss, other


def unstack(params, sz):
    """The weights with each layer's on its own (``layers``: a list of
    dicts in stack order) in place of the stacked ``blocks``: the g-th gated
    DeltaNet layer takes row g of the ``gdn_*`` leaves, the a-th attention
    layer row a of attention's, every layer its own row of the others."""
    blocks = params["blocks"]
    gdn = [k for k in blocks if k.startswith("gdn_")]
    attn = ["wqkv", "wo", "q_norm", "k_norm"]
    every = [k for k in blocks if k not in gdn and k not in attn]
    layers, seen = [], {True: 0, False: 0}
    for i in range(sz["layers"]):
        full = is_attention(sz, i)
        j = seen[full]
        seen[full] += 1
        layers.append({**{k: blocks[k][i] for k in every},
                       **{k: blocks[k][j] for k in (attn if full else gdn)}})
    out = {k: v for k, v in params.items() if k != "blocks"}
    out["layers"] = layers
    return out


def stacked_norms(tree) -> dict:
    """``leaf_norms`` of an unstacked tree under the STACKED tree's leaf
    names: a block leaf's norm runs over all the layers that have it."""
    sq = lambda a: jnp.sum(jnp.square(a))       # noqa: E731
    out = {f"['{k}']": jnp.sqrt(sq(v)) for k, v in tree.items()
           if k != "layers"}
    names = {k for layer in tree["layers"] for k in layer}
    for k in sorted(names):
        out[f"['blocks']['{k}']"] = jnp.sqrt(
            sum(sq(layer[k]) for layer in tree["layers"] if k in layer))
    return out


def loss(params, ids, tgt, sz, product=EXACT, choices=None, fault=None):
    """Mean next-token NLL of (B, T) ids against (B, T) targets on the
    stacked weights the program holds (tests; ``train_steps`` goes row by
    row on unstacked ones). ``choices`` is (layers, K, B, T) or None."""
    p = unstack(params, sz)
    rows = [row_loss(p, ids[r], tgt[r], sz, product,
                     None if choices is None else choices[:, :, r],
                     fault=fault)[0]
            for r in range(ids.shape[0])]
    return sum(rows) / len(rows)


def train_steps(seed: int, config: dict, ids, tgt, n_steps: int,
                product=EXACT, rows=None, choices=None, fault=None):
    """Follow ``n_steps`` AdamW steps from the seed's weights on batches
    ``ids[i], tgt[i]``. Returns the readings the comparison uses:
    ``losses`` (one per step), ``grad_norms`` (per leaf, of the first step's
    gradient) and ``delta_norms`` (per leaf, of the parameters' change after
    the last step). ``choices`` (n_steps, layers, K, B, T) or None: the
    experts every token takes, in place of the reference's own top-k; then
    ``choice_mismatch`` is the share of those assignments, over every step
    followed, that its own top-k lacks (0.0 without ``choices``).

    What is on the device at once: the weights, one row's gradient and the
    sum of the rows before it with one row's activations; Adam's two moments
    live on the host between steps and come over for the update."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    sz = sizes_of(config)
    hp = config["optimizer"]
    lr, b1, b2 = hp["learning_rate"], hp["b1"], hp["b2"]
    eps, wd = hp["eps"], hp["weight_decay"]
    count = None
    if rows is not None:
        if len(range(ids.shape[1])[rows]):
            ids, tgt = ids[:, rows], tgt[:, rows]
            if choices is not None:
                choices = [c[:, :, rows] for c in choices]
        else:       # a batch of one: half of the row's positions
            count = ids.shape[2] // 2
    batch = ids.shape[1]
    handed = n_steps * batch * sz["top_k"] * sz["layers"] * ids.shape[2]
    tmap = jax.tree_util.tree_map

    def adam(p, g, m, v, count):
        m = tmap(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count

        def upd(p, m, v):
            return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)

        return tmap(upd, p, m, v), m, v

    with jax.default_matmul_precision("highest"):
        start = jax.jit(lambda: unstack(make_weights(seed, sz), sz))
        grad_row = jax.jit(jax.value_and_grad(
            lambda p, i, t, c: row_loss(p, i, t, sz, product, c, count,
                                        fault), has_aux=True))
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
                c = None if choices is None \
                    else jnp.asarray(choices[i][:, :, r])
                (l, other), g_row = grad_row(
                    p, jnp.asarray(ids[i, r]), jnp.asarray(tgt[i, r]), c)
                g = g_row if g is None else add(g, g_row)
                total += float(l)
                mismatch += float(other) / handed
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
