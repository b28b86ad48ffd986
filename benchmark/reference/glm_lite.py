"""Plain reference for GLM-4.7-Flash-style decoders (family "glm_lite",
``model_type: glm4_moe_lite``: the DeepSeek-V3 layer at other numbers), given
ONE CHIP'S SHARE of a deployment in which eight chips share each layer by
expert parallelism: some of the routed experts and some rows of the embedding
and of the head; attention, the shared expert, the router, the dense layers
and the norms are held whole by every chip.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
kernel, no sort, no grouped product, no bf16. It imports nothing of
``deeplearning4j_tpu`` and takes nothing the program made: weights and
batches are drawn here from the seed and the driver hands the SAME draws to
the program. One layer (``x`` (T, D) is the residual stream; H heads, each
``nope`` dimensions without positions and ``rope`` rotated ones for q and k,
``vd`` for v; eps in every RMS norm; no bias anywhere):

    h    = rmsnorm(x, g1)
    cq   = rmsnorm(h Wqa, gq);  q = cq Wqb                (D -> q_rank -> H (nope + rope))
    q_i  = [q_nope_i | rope(q_rope_i)]                    (per head; split-half pairs over all `rope` dimensions, theta)
    [ckv | kr] = h Wkva;  ckv <- rmsnorm(ckv, gkv)        (D -> kv_rank + rope)
    k_rope = rope(kr)                                     (ONE head of `rope` dimensions that every head reads)
    [k_nope | v] = ckv Wkvb                               (kv_rank -> H (nope + vd))
    k_i  = [k_nope_i | k_rope]
    a    = concat_i softmax(q_i k_i^T / sqrt(nope + rope) + causal) v_i
    x    = x + a Wo                                       (H vd -> D)
    u    = rmsnorm(x, g2)
    the first `dense` layers:   m = (silu(u Wg) * (u Wu)) Wd                  (width ff; Wg | Wu side by side)
    the layers after them:
      s  = sigmoid(u Wr)                                  (D -> E, every published expert)
      e  = top_k(s + beta)                                (beta: a buffer no gradient reaches; K choices)
      w_j = scale * s_{e_j} / (sum_j s_{e_j} + 1e-20)     (the chosen scores WITHOUT beta, normed, scaled)
      m  = sum_{j: e_j held here} w_j expert_{e_j}(u) + shared(u)             (each (silu(u Wg) * (u Wu)) Wd of width expert_ff)
    x    = x + m
    L0   = mean_t(logsumexp(z_t) - z_t[target_t]),  z = rmsnorm(x_L, gf) Wh   (untied head, embedding not scaled)
    the prediction module (one; DeepSeek-V3 §2.2), for positions i = 0 .. T-2:
      p_i = [rmsnorm(x_L,i, gh) | rmsnorm(Emb(target_i), ge)] Wp               (2 D -> D; x_L BEFORE gf; the NEXT token's embedding)
      p   = one more layer as the layers after the dense ones, causal over the row, its own weights
      L1  = mean_i(logsumexp(y_i) - y_i[target_{i+1}]),  y = rmsnorm(p, gm) Wh  (the SAME head; T-1 positions)
    loss = L0 + predict_weight * L1
    AdamW: m,v moments, bias-corrected, p -= lr * (m^/(sqrt(v^)+eps) + wd * p)

What the absent experts would have added is left out, and that partial
result goes on to the next layer, exactly as in the program. The group limit
of the published router (``n_group`` 1, ``topk_group`` 1) is a no-op and is
not implemented: ``sizes_of`` refuses other values.

A training step is computed one row of the batch at a time, every layer
recomputed in the backward pass, attention in blocks of queries, the experts
one after another over every token, each loss in blocks of positions, each
layer's weights an array of their own, and Adam's moments kept on the host
between steps, so that the float32 step of 0.7 B parameters at 8,192
positions fits on one chip.

``product`` is the control's hook (``lowprec.FP8`` rounds both operands of
every product and the gradient flowing back to scaled float8). ``rows``
plants the half-batch fault: a slice of the batch's rows; where it keeps no
row (a batch of one), the first half of every row's positions is kept
instead. ``choices`` hands ``row_loss`` the experts to take (routing layers,
K, T) in place of its own top-k; ``predict_weight`` 0 plants this model's own
fault, the prediction loss left out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import seed_key
from reference.lowprec import EXACT
from reference.smallthinker import _rmsnorm, make_batches, rope  # noqa: F401

Q_BLOCK = 512        # queries per attention block
LOSS_BLOCK = 2048    # positions per block of a loss


def sizes_of(config: dict) -> dict:
    """The share this chip holds, from a configuration file whose reduced
    keys give the counts HELD (the published ones are under ``published``)."""
    pub = config.get("published", {})
    if int(config.get("n_group", 1)) != 1 \
            or int(config.get("topk_group", 1)) != 1 \
            or not config.get("norm_topk_prob", True) \
            or config.get("topk_method", "noaux_tc") != "noaux_tc" \
            or float(config.get("partial_rotary_factor", 1)) != 1 \
            or config.get("rope_scaling") is not None:
        raise ValueError("glm_lite: a group limit, unnormed weights, another "
                         "top-k method, a partial rotation or a rope scaling "
                         "is in neither the reference nor the program")
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "vd": int(config["v_head_dim"]),
        "layers": int(config["num_hidden_layers"]),
        "dense": int(config["first_k_dense_replace"]),
        "ff": int(config["intermediate_size"]),
        "expert_ff": int(config["moe_intermediate_size"]),
        #: the router has an output for every published expert
        "experts": int(pub.get("n_routed_experts", config["n_routed_experts"])),
        "held": int(config["n_routed_experts"]),
        "first": int(config.get("first_expert_held", 0)),
        "top_k": int(config["num_experts_per_tok"]),
        "shared": int(config["n_shared_experts"]),
        "scale": float(config["routed_scaling_factor"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "positions": int(config["max_position_embeddings"]),
        "predict": int(config["num_nextn_predict_layers"]),
        "predict_weight": float(config.get("mtp_loss_weight", 0.3)),
    }


def make_weights(seed: int, sz: dict):
    """All weights in one jitted call on the default device, float32, from
    the seed alone, in the tree the program holds: ``dense_blocks`` (the
    leading layers, stacked), ``blocks`` (the expert layers, stacked),
    ``mtp`` (the prediction module with its one ``block``, stacked by one).
    Normal / sqrt(fan_in) for every matrix, ones for the norm scales, zeros
    for ``router_beta``, and unit-variance entries for the embedding, which
    this model does not scale (as ``smallthinker.make_weights``: a token's
    own row then outweighs the mean of the values that early attention adds
    to every token alike, and the untrained router spreads its tokens)."""
    d, V, H = sz["d"], sz["vocab"], sz["heads"]
    dn, dr, dv = sz["nope"], sz["rope"], sz["vd"]
    f32 = jnp.float32

    def norm(key, shape, fan_in):
        return jax.random.normal(key, shape, f32) / math.sqrt(fan_in)

    def group(key, L, experts: bool):
        k = jax.random.split(key, 11)
        blocks = {
            "ln1": jnp.ones((L, d), f32),
            "wq_a": norm(k[0], (L, d, sz["q_rank"]), d),
            "q_norm": jnp.ones((L, sz["q_rank"]), f32),
            "wq_b": norm(k[1], (L, sz["q_rank"], H * (dn + dr)), sz["q_rank"]),
            "wkv_a": norm(k[2], (L, d, sz["kv_rank"] + dr), d),
            "kv_norm": jnp.ones((L, sz["kv_rank"]), f32),
            "wkv_b": norm(k[3], (L, sz["kv_rank"], H * (dn + dv)),
                          sz["kv_rank"]),
            "wo": norm(k[4], (L, H * dv, d), H * dv),
            "ln2": jnp.ones((L, d), f32),
        }
        if not experts:
            f = sz["ff"]
            blocks.update(w_in=norm(k[5], (L, d, 2 * f), d),
                          w_out=norm(k[6], (L, f, d), f))
            return blocks
        f, fs = sz["expert_ff"], sz["shared"] * sz["expert_ff"]
        blocks.update(
            router=norm(k[5], (L, d, sz["experts"]), d),
            router_beta=jnp.zeros((L, sz["experts"]), f32),
            we_in=norm(k[6], (L, sz["held"], d, 2 * f), d),
            we_out=norm(k[7], (L, sz["held"], f, d), f))
        if fs:
            blocks.update(ws_in=norm(k[8], (L, d, 2 * fs), d),
                          ws_out=norm(k[9], (L, fs, d), fs))
        return blocks

    def draw(key):
        k = jax.random.split(key, 6)
        out = {
            "embed": jax.random.normal(k[0], (V, d), f32),
            "head": norm(k[1], (d, V), d),
            "blocks": group(k[2], sz["layers"] - sz["dense"], True),
            "ln_f": jnp.ones((d,), f32),
        }
        if sz["dense"]:
            out["dense_blocks"] = group(k[3], sz["dense"], False)
        if sz["predict"]:
            out["mtp"] = {"ln_h": jnp.ones((d,), f32),
                          "ln_e": jnp.ones((d,), f32),
                          "proj": norm(k[4], (2 * d, d), 2 * d),
                          "block": group(k[5], 1, True),
                          "ln_f": jnp.ones((d,), f32)}
        return out

    return jax.jit(draw)(seed_key(seed))


def attention(q, k, v, product=EXACT):
    """(T, H, dq) queries on (T, H, dq) keys and (T, H, dv) values, causal;
    blocks of queries, the last one padded where T is no multiple."""
    t, h, dq = q.shape
    qb = min(Q_BLOCK, t)
    pad = (-t) % qb
    scores = product(lambda q, k: jnp.einsum("qhd,khd->hqk", q, k))
    weigh = product(lambda p, v: jnp.einsum("hqk,khd->qhd", p, v))
    j = jnp.arange(t)[None, :]

    def block(args):
        qs, i0 = args
        seen = j <= i0 + jnp.arange(qb)[:, None]
        s = jnp.where(seen[None], scores(qs, k) / math.sqrt(dq), -jnp.inf)
        return weigh(jax.nn.softmax(s, axis=-1), v)

    qp = jnp.concatenate([q, jnp.zeros((pad, h, dq), q.dtype)])
    out = jax.lax.map(jax.checkpoint(block),
                      (qp.reshape(-1, qb, h, dq), jnp.arange(0, t + pad, qb)))
    return out.reshape(t + pad, h, -1)[:t]


def qkv(h, blk, sz, product=EXACT):
    """The assembled heads: q and k (T, H, nope + rope), v (T, H, vd)."""
    mm = product(jnp.matmul)
    t, H, dn = h.shape[0], sz["heads"], sz["nope"]
    cq = _rmsnorm(mm(h, blk["wq_a"]), blk["q_norm"], sz["eps"])
    q = mm(cq, blk["wq_b"]).reshape(t, H, dn + sz["rope"])
    down = mm(h, blk["wkv_a"])
    ckv = _rmsnorm(down[:, :sz["kv_rank"]], blk["kv_norm"], sz["eps"])
    k_rope = rope(down[:, None, sz["kv_rank"]:], sz["theta"])    # (T, 1, rope)
    kv = mm(ckv, blk["wkv_b"]).reshape(t, H, dn + sz["vd"])
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], sz["theta"])], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_rope, H, axis=1)], -1)
    return q, k, kv[..., dn:]


def attention_part(h, blk, sz, product=EXACT):
    """What latent attention adds: a Wo, (T, D)."""
    q, k, v = qkv(h, blk, sz, product)
    a = attention(q, k, v, product)
    return product(jnp.matmul)(a.reshape(h.shape[0], -1), blk["wo"])


def swiglu(u, w_in, w_out, product=EXACT):
    mm = product(jnp.matmul)
    gu = mm(u, w_in)
    f = w_out.shape[0]
    return mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_out)


def scores(u, blk, product=EXACT):
    """The router's sigmoid scores (T, E) over every published expert."""
    return jax.nn.sigmoid(product(jnp.matmul)(u, blk["router"]))


def choose(s, blk, sz):
    """The K experts every token takes, (T, K): top-k of score plus beta."""
    return jax.lax.top_k(s + jax.lax.stop_gradient(blk["router_beta"]),
                         sz["top_k"])[1]


def weights_of(s, e, sz):
    """(T, K): the chosen scores over their sum, times the scale."""
    kept = jnp.take_along_axis(s, e, axis=-1)
    return sz["scale"] * kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20)


def experts_part(u, s, e, blk, sz, product=EXACT):
    """What the routed experts held here add: for every token those of its
    K choices ``e`` that live here, each weighted. A plain loop over the
    experts held, each over every token with a weight that is zero where it
    was not chosen."""
    w = weights_of(s, e, sz)

    def one(y, xs):
        w_in, w_out, eid = xs
        weight = jnp.sum(jnp.where(e == eid, w, 0.0), axis=-1)
        return y + weight[:, None] * swiglu(u, w_in, w_out, product), None

    ids = sz["first"] + jnp.arange(sz["held"])
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(u),
                        (blk["we_in"], blk["we_out"], ids))
    return y


def layer_fn(x, blk, sz, product=EXACT, choice=None):
    """(x, assignments of ``choice`` that the layer's own top-k lacks) after
    one layer: a dense one where ``blk`` holds ``w_in``, else an expert
    layer; ``choice`` (K, T) overrides its top-k."""
    h = _rmsnorm(x, blk["ln1"], sz["eps"])
    x = x + attention_part(h, blk, sz, product)
    u = _rmsnorm(x, blk["ln2"], sz["eps"])
    if "w_in" in blk:
        return x + swiglu(u, blk["w_in"], blk["w_out"], product), \
            jnp.zeros((), jnp.float32)
    s = scores(u, blk, product)
    own = choose(s, blk, sz)
    e = own if choice is None else choice.T
    m = experts_part(u, s, e, blk, sz, product)
    if "ws_in" in blk:      # every token's, whichever chip it is on
        m = m + swiglu(u, blk["ws_in"], blk["ws_out"], product)
    other = jnp.sum(~jnp.any(e[:, :, None] == own[:, None, :], axis=-1))
    return x + m, other.astype(jnp.float32)


def mean_nll(x, tgt, head, keep, product=EXACT):
    """Mean over the positions ``keep`` marks of logsumexp(x head) - its
    target's logit; blocks of positions, the last one padded."""
    t = x.shape[0]
    lb = min(LOSS_BLOCK, t)
    pad = (-t) % lb
    mm = product(jnp.matmul)
    x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
    tgt = jnp.concatenate([tgt, jnp.zeros((pad,), tgt.dtype)])
    keep = jnp.concatenate([keep.astype(x.dtype), jnp.zeros((pad,), x.dtype)])

    def block(total, xs):
        xb, tb, kb = xs
        z = mm(xb, head)
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        picked = jnp.take_along_axis(z, tb[:, None], axis=-1)[:, 0]
        return total + jnp.sum((lse - picked) * kb), None

    total, _ = jax.lax.scan(
        jax.checkpoint(block), jnp.zeros((), jnp.float32),
        (x.reshape(-1, lb, x.shape[1]), tgt.reshape(-1, lb),
         keep.reshape(-1, lb)))
    return total / jnp.sum(keep)


def row_loss(p, ids, tgt, sz, product=EXACT, choices=None, count=None,
             predict_weight=None):
    """(loss of one row, (T,) ids against (T,) targets, on unstacked
    weights; [its main part, its predicted-token part]; assignments handed
    in that the row's own top-k lacks, summed over the routing layers).
    ``choices`` is (routing layers, K, T), the prediction module's block
    last; ``count`` positions enter the main mean (all of them), one fewer
    the predicted-token mean."""
    t = ids.shape[0]
    n = t if count is None else count
    weight = sz["predict_weight"] if predict_weight is None else predict_weight
    layer = jax.checkpoint(
        lambda x, blk, choice: layer_fn(x, blk, sz, product, choice))
    x = p["embed"][ids]
    other = jnp.zeros((), jnp.float32)
    for blk in p.get("dense_layers", ()):
        x, _ = layer(x, blk, None)
    for i, blk in enumerate(p["layers"]):
        x, miss = layer(x, blk, None if choices is None else choices[i])
        other = other + miss
    main = mean_nll(_rmsnorm(x, p["ln_f"], sz["eps"]), tgt, p["head"],
                    jnp.arange(t) < n, product)
    if "mtp" not in p:
        return main, (jnp.stack([main, jnp.zeros_like(main)]), other)
    m = p["mtp"]
    joined = jnp.concatenate(
        [_rmsnorm(x[:-1], m["ln_h"], sz["eps"]),
         _rmsnorm(p["embed"][tgt[:-1]], m["ln_e"], sz["eps"])], axis=-1)
    y = product(jnp.matmul)(joined, m["proj"])
    y, miss = layer(y, m["block"],
                    None if choices is None else choices[-1][:, :-1])
    ahead = mean_nll(_rmsnorm(y, m["ln_f"], sz["eps"]), tgt[1:], p["head"],
                     jnp.arange(t - 1) < n - 1, product)
    return main + weight * ahead, (jnp.stack([main, ahead]), other + miss)


def unstack(params):
    """The weights with each layer's on its own (``dense_layers`` and
    ``layers``: lists of dicts; ``mtp.block``: one dict) in place of the
    stacked groups: a gradient by one layer's slice of a stacked array is a
    whole stacked array of zeros around it."""
    def split(blocks):
        n = blocks["ln1"].shape[0]
        return [{k: a[i] for k, a in blocks.items()} for i in range(n)]

    out = {k: v for k, v in params.items()
           if k not in ("blocks", "dense_blocks", "mtp")}
    out["layers"] = split(params["blocks"])
    if "dense_blocks" in params:
        out["dense_layers"] = split(params["dense_blocks"])
    if "mtp" in params:
        out["mtp"] = dict(params["mtp"],
                          block=split(params["mtp"]["block"])[0])
    return out


def stacked_norms(tree) -> dict:
    """``leaf_norms`` of an unstacked tree under the STACKED tree's leaf
    names: a block leaf's norm runs over all the layers of its group."""
    sq = lambda a: jnp.sum(jnp.square(a))       # noqa: E731
    out = {f"['{k}']": jnp.sqrt(sq(v)) for k, v in tree.items()
           if k not in ("layers", "dense_layers", "mtp")}
    for name, group in (("blocks", "layers"), ("dense_blocks", "dense_layers")):
        for k in (tree.get(group) or [{}])[0]:
            out[f"['{name}']['{k}']"] = jnp.sqrt(
                sum(sq(layer[k]) for layer in tree[group]))
    for k, v in tree.get("mtp", {}).items():
        if k == "block":
            out.update({f"['mtp']['block']['{n}']": jnp.sqrt(sq(a))
                        for n, a in v.items()})
        else:
            out[f"['mtp']['{k}']"] = jnp.sqrt(sq(v))
    return out


def loss(params, ids, tgt, sz, product=EXACT, choices=None,
         predict_weight=None):
    """(loss, [main, predicted-token]) of (B, T) ids against (B, T) targets
    on the stacked weights the program holds, the mean over the rows (tests;
    ``train_steps`` goes row by row on unstacked ones). ``choices`` is
    (routing layers, K, B, T) or None."""
    p = unstack(params)
    rows = [row_loss(p, ids[r], tgt[r], sz, product,
                     None if choices is None else choices[:, :, r],
                     predict_weight=predict_weight)
            for r in range(ids.shape[0])]
    return (sum(r[0] for r in rows) / len(rows),
            sum(r[1][0] for r in rows) / len(rows))


def train_steps(seed: int, config: dict, ids, tgt, n_steps: int,
                product=EXACT, rows=None, choices=None, predict_weight=None):
    """Follow ``n_steps`` AdamW steps from the seed's weights on batches
    ``ids[i], tgt[i]``. Returns the readings the comparison uses:
    ``losses`` (one per step), ``mtp_losses`` (its predicted-token part, one
    per step), ``grad_norms`` (per leaf, of the first step's gradient) and
    ``delta_norms`` (per leaf, of the parameters' change after the last
    step). ``choices`` (n_steps, routing layers, K, B, T) or None: the
    experts every token takes, in place of the reference's own top-k; then
    ``choice_mismatch`` is the share of those assignments, over every step
    followed, that its own top-k lacks (0.0 without ``choices``).

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
                choices = [c[:, :, rows] for c in choices]
        else:       # a batch of one: half of the row's positions
            count = ids.shape[2] // 2
    batch = ids.shape[1]
    routing = sz["layers"] - sz["dense"] + sz["predict"]
    handed = n_steps * batch * sz["top_k"] * (
        routing * ids.shape[2] - sz["predict"])
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
            lambda p, i, t, c: row_loss(p, i, t, sz, product, c, count,
                                        predict_weight), has_aux=True))
        add = jax.jit(lambda a, b: tmap(jnp.add, a, b), donate_argnums=(0, 1))
        mean = jax.jit(lambda g: tmap(lambda a: a / batch, g),
                       donate_argnums=0)
        adam = jax.jit(adam, donate_argnums=(0, 2, 3))
        norms = jax.jit(stacked_norms)
        p = start()
        m, v = (tmap(lambda a: np.zeros(a.shape, np.float32), p)
                for _ in range(2))
        losses, ahead, grad_norms, mismatch = [], [], None, 0.0
        for i in range(n_steps):
            g, total, parts = None, 0.0, np.zeros(2)
            for r in range(batch):
                c = None if choices is None \
                    else jnp.asarray(choices[i][:, :, r])
                (l, (two, other)), g_row = grad_row(
                    p, jnp.asarray(ids[i, r]), jnp.asarray(tgt[i, r]), c)
                g = g_row if g is None else add(g, g_row)
                total += float(l)
                parts += np.asarray(two, np.float64)
                mismatch += float(other) / handed
            g = mean(g)
            losses.append(total / batch)
            ahead.append(float(parts[1]) / batch)
            if i == 0:
                grad_norms = {k: float(x) for k, x in norms(g).items()}
            p, m, v = adam(p, g, m, v, jnp.float32(i + 1))
            del g
            m, v = jax.device_get((m, v))
        del m, v
        delta = jax.jit(lambda p, p0: stacked_norms(
            tmap(jnp.subtract, p, p0)))(p, start())
    return {"losses": losses, "mtp_losses": ahead, "grad_norms": grad_norms,
            "delta_norms": {k: float(x) for k, x in delta.items()},
            "choice_mismatch": mismatch}
