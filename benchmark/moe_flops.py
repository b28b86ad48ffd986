"""Operations and bytes of the SmallThinker-style configurations (family
"smallthinker": a chip's share of a sparse-expert decoder), computed from
shapes, as ``flops.py`` computes the other families'. A multiply-add is 2
operations; training is 3 x the forward pass; recomputed operations are not
counted. The configuration file's reduced keys give the counts HELD here
(heads, experts, vocabulary rows, layers); ``published`` the model's own.
"""

from __future__ import annotations


def _held_layout(config: dict, key: str):
    return [int(v) for v in config[key][: int(config["num_hidden_layers"])]]


def keys_seen(seq: int, window: int | None) -> float:
    """Keys a query sees on average over a sequence of ``seq``: seq / 2 under
    a causal mask (as ``flops.py`` counts it); under a window w as well,
    i + 1 keys for the first w queries and w for the rest, w - w^2 / (2 seq)
    (4096 at 8,192 full, 3,072 under a window of 4,096)."""
    if window is None or window >= seq:
        return seq / 2.0
    return window - window * window / (2.0 * seq)


def held_assignments_per_token(config: dict) -> float:
    """Expected assignments a token sends to the experts held here: top-k
    times the share of the published experts that live here (6 x 16 / 64 =
    1.5), which is what uniform routing gives and what the cell's
    ``expert_load`` counters read within a few per cent."""
    published = int(config["published"]["moe_num_primary_experts"])
    return (int(config["moe_num_active_primary_experts"])
            * int(config["moe_num_primary_experts"]) / published)


def forward_parts_per_token(config: dict, seq: int) -> dict:
    """Forward operations of one token at context ``seq``, by part, summed
    over the layers held. smallthinker-21b-a3b's share at 8,192:

      projections  4 x 2 x 2560 x (7 + 1 + 1 + 7) x 128       =  41.94 M
      router       4 x 2 x 2560 x 64                          =   1.31 M
      experts      4 x 1.5 x 2 x 3 x 2560 x 768               =  70.78 M
      scores       2 products x 2 x 7 x 128 x (4096 + 3 x 3072) =  47.71 M
      head         2 x 2560 x 37,984                          = 194.48 M
                                                        total   356.22 M

    The embedding lookup, norms, rotations, softmax, top-k, the sort and
    the gathers are not matmul work and are left out."""
    d, dh = int(config["hidden_size"]), int(config["head_dim"])
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    layers = int(config["num_hidden_layers"])
    f = int(config["moe_ffn_hidden_size"])
    router_width = int(config["published"]["moe_num_primary_experts"])
    windowed = _held_layout(config, "sliding_window_layout")
    window = int(config["sliding_window_size"])
    keys = sum(keys_seen(seq, window if w else None) for w in windowed)
    return {
        "projections": layers * 2.0 * d * (2 * heads + 2 * kv) * dh,
        "router": layers * 2.0 * d * router_width,
        "experts": layers * held_assignments_per_token(config) * 2.0 * 3 * d * f,
        "scores": 2 * 2.0 * heads * dh * keys,
        "head": 2.0 * d * int(config["vocab_size"]),
    }


def train_flops_per_token(config: dict, seq: int) -> float:
    """One training token at context ``seq``: 3 x the forward parts above.
    smallthinker-21b-a3b's share at 8,192: 3 x 356.22 M = 1.0687 GFLOP,
    17.51 TFLOP a step of 16,384 tokens."""
    return 3.0 * sum(forward_parts_per_token(config, seq).values())


def flash_band_flops_bytes(batch: int, heads: int, kv_heads: int, seq: int,
                           head_dim: int, window: int, windowed, layers: int,
                           itemsize: int = 2):
    """(operations, bytes) ONE STEP's attention needs over the layers held:
    one forward and one backward call per layer, the first ``layers``
    entries of ``windowed`` saying which layers have the window.

    Per layer: forward S = QK^T and O = PV, backward dV, dP, dQ, dK: 6
    products of 2 x batch x heads x seq x keys_seen x head_dim (the band a
    window leaves, or the causal half), the scores a flash backward
    recomputes not counted. Bytes: Q, O in the forward, Q, O, dO, dQ in the
    backward (6 tensors of the query heads), K, V and K, V, dK, dV (6 of
    the K/V heads, which 7 query heads share here)."""
    kinds = [int(w) for w in windowed[: int(layers)]]
    keys = sum(keys_seen(seq, window if w else None) for w in kinds)
    flops = 6 * 2.0 * batch * heads * seq * head_dim * keys
    nbytes = len(kinds) * 6.0 * batch * (heads + kv_heads) * seq * head_dim * itemsize
    return flops, nbytes


def experts_flops_bytes(batch: int, seq: int, d: int, f: int, held: int,
                        top_k: int, layers: int, local_share: float,
                        itemsize: int = 2):
    """(operations, bytes) ONE STEP's grouped expert products need over the
    layers held, at the load the run's counters read: ``local_share`` of
    the batch x seq x top_k assignments went to the ``held`` experts here
    (0.25 under even routing over 64 experts of which 16 are held). The
    same work whether ``lax.ragged_dot`` or a hand-written kernel runs it.

    Two products a layer, rows x d x 2f (gate | up) and rows x f x d
    (down); each costs its forward, its gradient by the rows and its
    gradient by the weights: 3 x 2 x rows x k x n operations, and 3 x (rows
    x k + k x n x held + rows x n) elements moved (each of the three reads
    two of the product's tensors and writes the third)."""
    rows = batch * seq * top_k * float(local_share)
    flops = nbytes = 0.0
    for k, n in ((d, 2 * f), (f, d)):
        flops += 3 * 2.0 * rows * k * n
        nbytes += 3.0 * (rows * k + held * k * n + rows * n) * itemsize
    return layers * flops, layers * nbytes
