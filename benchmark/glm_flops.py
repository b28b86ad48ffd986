"""Operations and bytes of the GLM-4.7-Flash-style configurations (family
"glm_lite": a chip's share of a decoder with latent attention, a leading
dense layer before sigmoid-routed experts with a shared one, and a
prediction module behind the shared head), computed from shapes, as
``flops.py``, ``moe_flops.py`` and ``zaya_flops.py`` compute the other
families'. A multiply-add is 2 operations; training is 3 x the forward pass;
recomputed operations are not counted. The configuration file's reduced keys
give the counts HELD here (experts, vocabulary rows, layers); ``published``
the model's own.
"""

from __future__ import annotations


def held_assignments_per_token(config: dict) -> float:
    """Expected assignments a token sends to the routed experts held here:
    top-k times the share of the published experts that live here (4 x 8 /
    64 = 0.5), which is what even routing gives and what the cell's
    ``expert_load`` counters read within a few per cent."""
    published = int(config["published"]["n_routed_experts"])
    return (int(config["num_experts_per_tok"])
            * int(config["n_routed_experts"]) / published)


def _blocks(config: dict):
    """(dense layers, expert layers, prediction modules) held here."""
    dense = int(config["first_k_dense_replace"])
    return (dense, int(config["num_hidden_layers"]) - dense,
            int(config["num_nextn_predict_layers"]))


def forward_parts_per_token(config: dict, seq: int) -> dict:
    """Forward operations of one token at context ``seq``, by part, summed
    over the blocks held (the prediction module's block is one more expert
    block). glm-4.7-flash's share at 8,192 (H 20; 192 + 64 for q and k, 256
    for v; latents 768 and 512):

      latent projections  6 x 2 x (2048 x 768 + 768 x 5120 + 2048 x 576
                                   + 512 x 8960 + 5120 x 2048)            =  261.10 M
      scores              6 x 2 products x 2 x 20 x 256 x 4,096           =  503.32 M
      dense MLP           1 x 2 x 3 x 2048 x 10240                        =  125.83 M
      router              5 x 2 x 2048 x 64                               =    1.31 M
      shared expert       5 x 2 x 3 x 2048 x 1536                         =   94.37 M
      routed experts      5 x 0.5 x 2 x 3 x 2048 x 1536                   =   47.19 M
      joining projection  1 x 2 x 4096 x 2048                             =   16.78 M
      head, twice         2 x 2 x 2048 x 19,360                           =  158.60 M
                                                                    total   1,208.49 M

    (ISSUE 34 rounds the router to 0.3 M a layer: 1,208.6 M.) Attention in
    the latent, projections and scores, is 63 % of it. The prediction
    module is counted at every position of the row; the last one's weighs 0
    in the loss and is computed all the same. The embedding lookups, norms,
    rotations, softmax, sigmoid, top-k, the sort and the gathers are not
    matmul work and are left out."""
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv = int(config["v_head_dim"])
    rq, rkv = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    f, fe = int(config["intermediate_size"]), int(config["moe_intermediate_size"])
    dense, routed, modules = _blocks(config)
    blocks, expert_blocks = dense + routed + modules, routed + modules
    return {
        "latent_projections": blocks * 2.0 * (
            d * rq + rq * heads * (dn + dr) + d * (rkv + dr)
            + rkv * heads * (dn + dv) + heads * dv * d),
        "scores": blocks * 2.0 * heads * ((dn + dr) + dv) * seq / 2.0,
        "dense_mlp": dense * 2.0 * 3 * d * f,
        "router": expert_blocks * 2.0 * d
        * int(config["published"]["n_routed_experts"]),
        "shared_expert": expert_blocks * int(config["n_shared_experts"])
        * 2.0 * 3 * d * fe,
        "routed_experts": expert_blocks * held_assignments_per_token(config)
        * 2.0 * 3 * d * fe,
        "joining_projection": modules * 2.0 * 2 * d * d,
        "head": (1 + modules) * 2.0 * d * int(config["vocab_size"]),
    }


def train_flops_per_token(config: dict, seq: int) -> float:
    """One training token at context ``seq``: 3 x the forward parts above.
    glm-4.7-flash's share at 8,192: 3 x 1,208.49 M = 3.6255 GFLOP, 29.70
    TFLOP a step of 8,192 tokens (a token of the row counts once, whatever
    the prediction module predicts beside it)."""
    return 3.0 * sum(forward_parts_per_token(config, seq).values())


def flash_flops_bytes(batch: int, heads: int, seq: int, nope: int, rope: int,
                      v_dim: int, layers: int, modules: int,
                      itemsize: int = 2):
    """(operations, bytes) ONE STEP's causal attention needs over the blocks
    held, the prediction module's among them: one forward and one backward
    call per block.

    Per block: forward S = QK^T and O = PV, backward dV, dP, dQ, dK: three
    products over the query-key width nope + rope and three over the value
    width, each 2 x batch x heads x seq x seq / 2 x width, the scores a
    flash backward recomputes not counted. Bytes: Q, K and Q, K, dQ, dK (6
    tensors of nope + rope a head), O, V and O, V, dO, dV (6 of v_dim)."""
    blocks = layers + modules
    flops = blocks * 3 * 2.0 * batch * heads * seq * (
        (nope + rope) + v_dim) * seq / 2.0
    nbytes = blocks * 6.0 * batch * heads * seq * (
        (nope + rope) + v_dim) * itemsize
    return flops, nbytes


def experts_flops_bytes(batch: int, seq: int, d: int, f: int, held: int,
                        top_k: int, layers: int, dense: int, modules: int,
                        local_share: float, itemsize: int = 2):
    """(operations, bytes) ONE STEP's grouped products over the ROUTED
    experts need over the blocks that route (the expert layers and the
    prediction module's block), at the load the run's counters read:
    ``local_share`` of the batch x seq x top_k assignments went to the
    ``held`` experts here (8 / 64 = 0.125 under even routing). The shared
    expert is a plain product under another scope and is not in it. As
    ``moe_flops.experts_flops_bytes``: two products a block, rows x d x 2f
    (gate | up) and rows x f x d (down), each with its forward, its gradient
    by the rows and its gradient by the weights."""
    rows = batch * seq * top_k * float(local_share)
    flops = nbytes = 0.0
    for k, n in ((d, 2 * f), (f, d)):
        flops += 3 * 2.0 * rows * k * n
        nbytes += 3.0 * (rows * k + held * k * n + rows * n) * itemsize
    blocks = layers - dense + modules
    return blocks * flops, blocks * nbytes
