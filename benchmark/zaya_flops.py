"""Operations and bytes of the ZAYA1-style configurations (family "zaya": a
chip's share of a decoder with compressed convolutional attention and a
top-1 expert layer behind an mlp router with a skip), computed from shapes,
as ``flops.py`` and ``moe_flops.py`` compute the other families'. A
multiply-add is 2 operations; training is 3 x the forward pass; recomputed
operations are not counted. The configuration file's reduced keys give the
counts HELD here (experts, vocabulary rows, layers); ``published`` the
model's own.
"""

from __future__ import annotations


def held_share(config: dict) -> float:
    """Expected share of the tokens whose one choice is an expert held here:
    the experts held over the router's outputs, the published experts and
    the skip (8 / 17), which is what an untrained router gives and what the
    cell's ``expert_load`` counters read within a few per cent."""
    published = int(config["published"]["num_experts"])
    return (int(config["num_experts_per_tok"]) * int(config["num_experts"])
            / (published + 1.0))


def forward_parts_per_token(config: dict, seq: int) -> dict:
    """Forward operations of one token at context ``seq``, by part, summed
    over the layers held. zaya1-8b's share at 32,768 (H 8, J 2, d 128):

      projections  4 x 2 x (2048 x (1024 + 256 + 256) + 1024 x 2048)    =   41.94 M
      convolutions 4 x 2 x (2 x 1280 + 2 x 10 x 128 x 128)              =    2.64 M
      scores       4 x 2 products x 2 x 8 x 128 x 16,384                =  268.44 M
      router       4 x 2 x (2048 x 256 + 2 x 256 x 256 + 256 x 17)      =    5.28 M
      experts      4 x 8/17 x 2 x 3 x 2048 x 2048                       =   47.37 M
      head         2 x 2048 x 131,136                                   =  537.13 M
                                                                  total    902.80 M

    (ISSUE 32 counts the projections without the values, 37.7 M.) The
    embedding lookup, norms, the mean of q and k, rotations, softmax, the
    argmax, the sort and the gathers are not matmul work and are left out."""
    d, dh = int(config["hidden_size"]), int(config["head_dim"])
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    layers, f = int(config["num_hidden_layers"]), int(config["moe_intermediate_size"])
    r = int(config["router_hidden_size"])
    outputs = int(config["published"]["num_experts"]) + 1
    k0, k1 = int(config["cca_time0"]), int(config["cca_time1"])
    channels = (heads + kv) * dh
    return {
        "projections": layers * 2.0 * (d * (heads + 2 * kv) * dh + heads * dh * d),
        "convolutions": layers * 2.0 * (k0 * channels + k1 * channels * dh),
        "scores": layers * 2 * 2.0 * heads * dh * seq / 2.0,
        "router": layers * 2.0 * (d * r + 2 * r * r + r * outputs),
        "experts": layers * held_share(config) * 2.0 * 3 * d * f,
        "head": 2.0 * d * int(config["vocab_size"]),
    }


def train_flops_per_token(config: dict, seq: int) -> float:
    """One training token at context ``seq``: 3 x the forward parts above.
    zaya1-8b's share at 32,768: 3 x 902.80 M = 2.7084 GFLOP, 88.75 TFLOP a
    step of 32,768 tokens."""
    return 3.0 * sum(forward_parts_per_token(config, seq).values())


def flash_flops_bytes(batch: int, heads: int, kv_heads: int, seq: int,
                      head_dim: int, layers: int, itemsize: int = 2):
    """(operations, bytes) ONE STEP's causal attention needs over the layers
    held: one forward and one backward call per layer.

    Per layer: forward S = QK^T and O = PV, backward dV, dP, dQ, dK: 6
    products of 2 x batch x heads x seq x seq / 2 x head_dim, the scores a
    flash backward recomputes not counted. Bytes: Q, O in the forward, Q, O,
    dO, dQ in the backward (6 tensors of the query heads), K, V and K, V,
    dK, dV (6 of the K/V heads, which 4 query heads share here)."""
    flops = layers * 6 * 2.0 * batch * heads * seq * head_dim * seq / 2.0
    nbytes = layers * 6.0 * batch * (heads + kv_heads) * seq * head_dim * itemsize
    return flops, nbytes


def experts_flops_bytes(batch: int, seq: int, d: int, f: int, held: int,
                        top_k: int, layers: int, local_share: float,
                        itemsize: int = 2):
    """(operations, bytes) ONE STEP's grouped expert products need over the
    layers held, at the load the run's counters read: ``local_share`` of the
    batch x seq x top_k assignments went to the ``held`` experts here (8 /
    17 from an untrained router). As ``moe_flops.experts_flops_bytes``: two
    products a layer, rows x d x 2f (gate | up) and rows x f x d (down),
    each with its forward, its gradient by the rows and its gradient by the
    weights."""
    rows = batch * seq * top_k * float(local_share)
    flops = nbytes = 0.0
    for k, n in ((d, 2 * f), (f, d)):
        flops += 3 * 2.0 * rows * k * n
        nbytes += 3.0 * (rows * k + held * k * n + rows * n) * itemsize
    return layers * flops, layers * nbytes
