"""Operations and bytes of the Qwen3-Next-style configurations (family
"qwen3_next": a chip's share of a decoder with three gated DeltaNet layers
to a gated attention layer, softmax-routed experts and a gated shared one),
computed from shapes, as ``flops.py``, ``moe_flops.py``, ``zaya_flops.py`` and
``glm_flops.py`` compute the other families'. A multiply-add is 2
operations; training is 3 x the forward pass; recomputed operations are not
counted. The configuration file's reduced keys give the counts HELD here
(experts, vocabulary rows, layers); ``published`` the model's own.
"""

from __future__ import annotations

#: positions a chunk of the delta rule takes (zoo.transformer.GDN_CHUNK)
CHUNK = 64


def _layers(config: dict):
    """(gated DeltaNet layers, attention layers) held here."""
    layers = int(config["num_hidden_layers"])
    attention = layers // int(config["full_attention_interval"])
    return layers - attention, attention


def held_assignments_per_token(config: dict) -> float:
    """Expected assignments a token sends to the routed experts held here:
    top-k times the share of the published experts that live here (10 x 32 /
    512 = 0.625), which is what even routing gives."""
    return (int(config["num_experts_per_tok"]) * int(config["num_experts"])
            / int(config["published"]["num_experts"]))


def rule_flops_per_token(config: dict, chunk: int = CHUNK) -> float:
    """Forward operations of the chunked delta rule, one token of one
    DeltaNet layer: per chunk of C positions and value head, the products
    k beta k^T and q k^T (2 C^2 dk each), the triangular solve for u and w
    (C^2 (dv + dk): half of a full product's 2 C^2 (dv + dk)), the intra-
    chunk attention on the corrected values (2 C^2 dv) and three products
    with the (dk, dv) state (w S, q S, the state's update: 2 C dk dv each);
    over C. qwen3-next-80b-a3b's (32 value heads of 128, C 64):
    32 x (4 x 64 x 128 + 64 x 256 + 2 x 64 x 128 + 6 x 128 x 128) = 5.24 M."""
    hv = int(config["linear_num_value_heads"])
    dk, dv = int(config["linear_key_head_dim"]), int(config["linear_value_head_dim"])
    c = chunk
    return hv * (4.0 * c * dk + c * (dv + dk) + 2.0 * c * dv + 6.0 * dk * dv)


def forward_parts_per_token(config: dict, seq: int) -> dict:
    """Forward operations of one token at context ``seq``, by part, summed
    over the layers held. qwen3-next-80b-a3b's share at 8,192 (3 DeltaNet
    layers, 1 attention layer):

      DeltaNet projections 3 x 2 x (2048 x 12288 + 2048 x 64 + 4096 x 2048) = 202.11 M
      convolution          3 x 2 x 4 x 8192                               =   0.20 M
      delta rule           3 x 5.24 M (rule_flops_per_token)              =  15.73 M
      attention proj.      1 x 2 x (2048 x 9216 + 4096 x 2048)            =  54.53 M
      scores               1 x 2 products x 2 x 16 x 256 x 4,096          =  67.11 M
      router               4 x 2 x 2048 x 512                             =   8.39 M
      shared expert        4 x 2 x (3 x 2048 x 512 + 2048)                =  25.18 M
      routed experts       4 x 0.625 x 2 x 3 x 2048 x 512                 =  15.73 M
      head                 2 x 2048 x 18,992                              =  77.79 M
                                                                    total   466.76 M

    The DeltaNet layers (projections, convolution, rule, their router and
    experts) are 255.01 M of it, 54.6 %. The embedding lookup, norms, rotations,
    softmax, top-k, the sort, the gathers and the gates' elementwise work
    are not matmul work and are left out."""
    d = int(config["hidden_size"])
    gdn, attn = _layers(config)
    hk, dk = int(config["linear_num_key_heads"]), int(config["linear_key_head_dim"])
    hv, dv = int(config["linear_num_value_heads"]), int(config["linear_value_head_dim"])
    nk, nv = hk * dk, hv * dv
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    dh = int(config["head_dim"])
    fe, fs = int(config["moe_intermediate_size"]), int(config["shared_expert_intermediate_size"])
    layers = gdn + attn
    return {
        "gdn_projections": gdn * 2.0 * (d * (2 * nk + 2 * nv) + d * 2 * hv
                                        + nv * d),
        "gdn_conv": gdn * 2.0 * int(config["linear_conv_kernel_dim"])
        * (2 * nk + nv),
        "gdn_rule": gdn * rule_flops_per_token(config),
        "attention_projections": attn * 2.0 * (d * (2 * heads + 2 * kv) * dh
                                               + heads * dh * d),
        "scores": attn * 2.0 * 2.0 * heads * dh * seq / 2.0,
        "router": layers * 2.0 * d * int(config["published"]["num_experts"]),
        "shared_expert": layers * 2.0 * (3 * d * fs + d),
        "routed_experts": layers * held_assignments_per_token(config)
        * 2.0 * 3 * d * fe,
        "head": 2.0 * d * int(config["vocab_size"]),
    }


def train_flops_per_token(config: dict, seq: int) -> float:
    """One training token at context ``seq``: 3 x the forward parts above.
    qwen3-next-80b-a3b's share at 8,192: 3 x 466.76 M = 1.4003 GFLOP, 11.47
    TFLOP a step of 8,192 tokens."""
    return 3.0 * sum(forward_parts_per_token(config, seq).values())


def gdn_flops_bytes(batch: int, seq: int, key_heads: int, value_heads: int,
                    key_dim: int, value_dim: int, layers: int, interval: int,
                    itemsize: int = 2):
    """(operations, bytes) ONE STEP's chunked delta rule needs over the
    DeltaNet layers held (``layers`` minus every ``interval``-th), forward
    and backward, fixed by the shapes whatever computes the rule, so that a
    later kernel is judged against the same work.

    Operations: 3 x the forward products of ``rule_flops_per_token`` a token
    and layer. Bytes: q and k (key heads), v and o (value heads), g and beta
    (value heads, one number each) and the gradients of all six, in
    ``itemsize`` bytes; the chunk-start states, (dk, dv) float32 per value
    head and chunk, written once and read once."""
    gdn = layers - layers // interval
    per_token = rule_flops_per_token(
        {"linear_num_value_heads": value_heads, "linear_key_head_dim": key_dim,
         "linear_value_head_dim": value_dim})
    tokens = batch * seq
    flops = gdn * 3.0 * tokens * per_token
    operands = 2 * key_heads * key_dim + 2 * value_heads * value_dim \
        + 2 * value_heads
    chunks = -(-seq // CHUNK)
    states = batch * chunks * value_heads * key_dim * value_dim * 4
    nbytes = gdn * (2.0 * tokens * operands * itemsize + 2.0 * states)
    return flops, nbytes


def flash_flops_bytes(batch: int, heads: int, kv_heads: int, seq: int,
                      head_dim: int, layers: int, interval: int,
                      itemsize: int = 2):
    """(operations, bytes) ONE STEP's causal attention needs over the
    attention layers held (every ``interval``-th of ``layers``): one forward
    and one backward call a layer, counted as ``zaya_flops.flash_flops_bytes``
    counts them. Per layer: 6 products of 2 x batch x heads x seq x seq / 2 x
    head_dim (S = QK^T and O = PV forward; dV, dP, dQ, dK backward), the
    scores a flash backward recomputes not counted; bytes: 6 tensors of the
    query heads (Q, O; Q, O, dO, dQ) and 6 of the K/V heads (K, V; K, V, dK,
    dV). qwen3-next-80b-a3b's share at 8,192 (1 layer of 16 heads of 256 on
    2 K/V heads): 6 x 2 x 16 x 8192 x 256 x 4096 = 1.6493 TFLOP and 6 x 18 x
    8192 x 256 x 2 = 452.98 MB."""
    attention = layers // interval
    flops = attention * 6 * 2.0 * batch * heads * seq * head_dim * seq / 2.0
    nbytes = attention * 6.0 * batch * (heads + kv_heads) * seq * head_dim \
        * itemsize
    return flops, nbytes
