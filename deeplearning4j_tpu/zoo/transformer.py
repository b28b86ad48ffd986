"""Transformer-LM — flagship model for distributed training (tp/sp/ep/dp).

Reference counterpart: DL4J's transformer story is BERT via SameDiff TF
import (attention assembled from SameDiff ops, run per-op on cuDNN). The
TPU-native redesign is a pure-functional GPT-style LM engineered for SPMD:

- params for all L blocks are STACKED (leading L axis) and the blocks run
  under ``lax.scan`` — one compile of one block instead of L inlined copies
  (compile time O(1) in depth; XLA still pipelines the unrolled loop).
- Megatron-style tensor parallel: qkv/mlp-in weights column-sharded over
  'tp', out-proj/mlp-out row-sharded; XLA inserts the two psums per block.
- Sequence parallel: activations sharded over 'sp' on the time axis; the
  attention inner either all-gathers k/v (XLA default) or runs the ring
  kernel (`parallel/ring_attention.py`) when `use_ring_attention`.
- Expert parallel: optional MoE MLP (top-k router, capacity factor,
  einsum dispatch) with experts sharded over 'ep'.
- bf16 activations/f32 params & optimizer; `jax.checkpoint` on each block
  (remat) so long sequences fit HBM.
- The chunked head (`_chunked_ce`) has NO checkpoint: its `custom_vjp`
  forms `dx` and the table's gradient beside each chunk's loss and keeps
  those two (and the per-row loss), never a chunk's logits — three
  vocabulary-wide products a chunk, none of them a recomputation.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.extend import core as jex_core
from jax.interpreters import mlir
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import gated_delta

#: The training step's TOP-LEVEL ``jax.named_scope``s, name -> what it holds.
#: Each is opened only where no other of them is open (at the call sites in
#: ``block``, ``_lm_loss_stats``, ``embed`` and ``step``), so a device event
#: belongs to at most one and their device times add up; ``mtp`` alone is a
#: second cut, around a whole module. A name is read as the START of a path
#: component of an op's name stack, so none is the start of another, of a
#: component JAX makes (``jit(...)``, ``jvp``, ``while``, ``checkpoint``, a
#: primitive's name) or of an entry of ``KERNEL_SCOPES``. The benchmark's
#: ``*_time_share_pct.lm`` metrics read them (``tests/test_step_scopes.py``
#: holds this table and those files together).
STEP_SCOPES = (
    ("embed", "the table's gather, its scaling and the learned positions; "
              "backward: the scatter-add into the table's gradient"),
    ("attn_qkv", "the plain wqkv product and its split (of latent attention, "
                 "the rotary parts' split alone), q and k's norms; in "
                 "_attention the head reshapes and, for plain heads, the "
                 "rotary positions; a gated DeltaNet layer's two products"),
    ("attn_wo", "attention's output gate and projection, every attention "
                "kind, and a gated DeltaNet layer's output projection"),
    ("attn_core", "attention between q, k, v and its output where no Pallas "
                  "kernel runs (XLA's or the ring's), and the layout changes "
                  "around the kernel where one does; a gated DeltaNet layer's "
                  "convolution, beta and decays (gdn_conv), q and k's norms "
                  "and the delta rule (gdn_rule) and gated norm (gdn_norm)"),
    ("mlp", "the dense MLP of a layer without experts"),
    ("resid_norm", "the norms ln1, ln2, ln_f, a prediction module's three, "
                   "and the residual merges"),
    ("optimizer", "optimizer.update and apply_updates"),
)
#: The older scopes, each a prefix: a part of the model or a kernel that
#: names itself (``flash_fwd``, ``moe_dispatch``, ``cca_mix``, ``mla_kv``).
KERNEL_SCOPES = ("flash_", "moe_", "cca_", "mla_", "lm_head", "mtp")


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 8
    d_ff: int = 2048
    max_seq: int = 1024
    n_experts: int = 0          # 0 → dense MLP
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16   # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    # Fused (chunked) LM cross-entropy: never materializes the full
    # (B, T, V) f32 logits — per row-chunk the head matmul, logsumexp,
    # target gather and the chunk's gradient are one scan step
    # (`_chunked_ce`). Cuts the dominant HBM traffic of a 32k-vocab loss
    # (logits f32 write+read is ~4 GB/step at B16/T1024) at no extra head
    # matmul: the backward only scales what the forward formed.
    # True | False | "auto" (fuse when B*T*V is large enough to matter).
    fused_loss: Any = "auto"
    loss_chunk: int = 1024      # rows (B*T) per chunk in the fused loss
    # Rematerialization policy for the per-block checkpoint (remat=True):
    # "full"  — save only block inputs, recompute everything (min HBM)
    # "dots"  — save matmul outputs, recompute elementwise (XLA
    #           checkpoint_policies.dots_saveable: trades HBM for the
    #           cheap recompute only)
    # "dots_no_batch" — dots_with_no_batch_dims_saveable (saves the
    #           small contraction results, not the big batched ones)
    # "save_attn" — keep what attention produced (checkpoint_name
    #           "attn_out"; on the flash path also the kernel's
    #           log-sum-exp, "attn_lse"; of the gated delta rule its
    #           chunk-start states, "gdn_states"), recompute the rest:
    #           remat-full's HBM saving with attention run once a layer
    remat_policy: str = "full"
    use_ring_attention: bool = False
    # True = always pallas flash kernel (TPU single-chip); False = XLA fused
    # attention; "auto" = flash from `flash_min_seq` up. Measured on v5e
    # (2026-08-01, d_model 512/h8, grad-tuned flash5 blocks — the earlier
    # "XLA wins at short T" result was an artifact of fwd-only autotuning
    # picking 128×128 blocks): full-model train step, flash vs best XLA
    # path, tokens/s — t1024 b16: 221k vs 187k; t4096 b4: 160k vs 87k;
    # t8192 b2: 107k vs 44k (scripts/diag_attn_r5_out.json). Below 1024
    # the XLA bf16-scores path is unmeasured-against and stays default.
    use_flash_attention: Any = "auto"
    flash_min_seq: int = 1024
    # Default-on (r4): materialize attention scores in bf16 instead of f32
    # on the XLA path (matmuls still accumulate f32 in-register; softmax
    # still reduces in f32). Halves the dominant (B,H,T,T) HBM traffic at
    # T<=flash_min_seq for a ~1e-2-relative perturbation of the
    # probabilities — measured +18% MFU at T=1024 on v5e composed with
    # remat-full (scripts/sweep_transformer_out.json). Set False for
    # exact-f32 scores. Ignored when the flash kernel engages (which
    # keeps scores in VMEM and is exact).
    attn_scores_bf16: bool = True
    tie_embeddings: bool = False
    # ---- what the block IS (PR 28). Each field describes the architecture;
    # none picks an implementation. The defaults are the GPT-2-style block
    # the fields above describe: one K/V head per query head, heads of
    # d_model // n_heads, a learned position table added at the embedding,
    # full causal attention, an ungated GELU MLP, every expert held.
    n_kv_heads: int = 0         # 0 → n_heads; else query head i reads K/V
    #                             head i // (n_heads // n_kv_heads)
    head_size: int = 0          # 0 → d_model // n_heads
    # Kinds of layer, one entry per layer of a PERIOD that repeats down the
    # stack (n_layers % period == 0; both tuples the same length).
    # layer_positions: "rope" (rotary on q and k, split-half pairs over the
    # whole head) | "none" (no positions at all); () = the learned table.
    # layer_windows: keys a query sees (0 <= i - j < w); 0 = full causal.
    layer_positions: tuple = ()
    layer_windows: tuple = ()
    rope_theta: float = 10000.0
    embed_scale: bool = True    # token embedding times sqrt(d_model)
    mlp: str = "gelu"           # "gelu": gelu(x W_in) W_out | "reglu":
    #                             (relu(x Wg) * (x Wu)) W_out, Wg|Wu side by
    #                             side in one (d, 2·d_ff) matrix | "swiglu":
    #                             the same with silu for relu
    # The share of the n_experts published experts this program holds:
    # (first id, count). The router keeps n_experts outputs and top-k over
    # all of them; the layer computes the held experts' part of the result
    # for exactly the tokens routed to them — no capacity, nothing dropped —
    # and leaves out what the absent experts would add (expert parallelism
    # without its exchange). () = every expert held, with capacity_factor.
    experts_held: tuple = ()
    # what the router reads: the block's normed input ("pre_attention", what
    # attention reads) or the normed input of the MLP ("post_attention")
    router_input: str = "post_attention"
    # ---- PR 32. As above: what the block is, never how it is computed.
    norm_eps: float = 1e-6      # of every RMS norm
    # "mha": q, k, v = split(h Wqkv), heads as n_heads / n_kv_heads say.
    # "cca": compressed convolutional attention in a latent of n_heads ·
    # head_size channels (arXiv:2510.04476): q and k are mixed along the
    # sequence by a depthwise and then a per-head causal convolution of
    # cca_taps = (taps, taps), a mean of q and k is added to both, the second
    # half of the value channels is the token before's, every head of q and
    # k is scaled to norm sqrt(head size) (k times a learned temperature a
    # K/V head), and only then come rotary and the causal softmax.
    # "mla": latent attention, with the fields of PR 34 below.
    attention: str = "mha"
    cca_taps: tuple = (2, 2)
    rotary_share: float = 1.0   # the share of a head "rope" rotates, from
    #                             dimension 0; the rest passes through
    # "linear": logits = x W, top-k, weights = softmax over the kept logits.
    # "mlp" (arXiv:2511.17127): a down-projection to router_hidden, the state
    # of the layer before added (times a learned vector; carried down the
    # stack beside x), an RMS norm and a three-matrix GELU MLP; the choice is
    # the largest of probability plus a bias no gradient reaches, the weight
    # that choice's probability over ALL outputs. router_skip: one more
    # output, after the experts', whose tokens get nothing from the layer.
    # "sigmoid": see router_scale below.
    router: str = "linear"
    router_hidden: int = 0
    router_skip: bool = False
    # x + f(x) -> (s x + b) + (s' f(x) + b'), learned vectors of d_model
    scaled_residuals: bool = False
    # ---- PR 34. As above: what the model is, never how it is computed.
    # attention="mla": multi-head latent attention (arXiv:2405.04434 §2.1).
    # Queries go down to a latent of q_rank, through an RMS norm, and up to
    # n_heads heads of nope_head_size + rope_head_size; keys and values come
    # from ONE latent of kv_rank (an RMS norm, then up to n_heads x
    # (nope_head_size + v_head_size)) beside ONE rotary key of rope_head_size
    # that every head reads; a head's query and key are the part without
    # positions joined to the rotated part (the LAST rope_head_size
    # dimensions), its value v_head_size wide.
    q_rank: int = 0
    kv_rank: int = 0
    nope_head_size: int = 0
    rope_head_size: int = 0
    v_head_size: int = 0
    # the first dense_layers layers of the stack have a dense MLP of width
    # d_ff, the layers after them the routed experts (their own group of
    # stacked blocks, params["dense_blocks"] before params["blocks"])
    dense_layers: int = 0
    expert_ff: int = 0          # the experts' own width; 0 → d_ff
    # experts every token takes with weight 1 beside the routed ones, as one
    # MLP of shared_experts · (expert_ff or d_ff), held whole by every share
    shared_experts: int = 0
    # router="sigmoid" (arXiv:2412.19437 §2.1.2): scores = sigmoid(x W) in
    # float32; the CHOICE is top-k of score plus a bias no gradient reaches
    # (router_beta); the WEIGHT is the chosen scores, without the bias, over
    # their sum, times router_scale
    router_scale: float = 1.0
    # multi-token prediction (arXiv:2412.19437 §2.2): predict_ahead modules
    # after the stack (0 or 1), each [rmsnorm(hidden) | rmsnorm(embedding of
    # the NEXT token)] through a 2·d_model -> d_model projection and one more
    # block (an expert block where the stack has them), its own final norm,
    # the SAME embedding and the SAME head, scored on the token after next;
    # loss = main + predict_weight · that
    predict_ahead: int = 0
    predict_weight: float = 0.3
    # ---- Recurrent layers and gates. As above: what the model is, never how
    # it is computed.
    # layer_mixers: what mixes the tokens in each layer of the period, beside
    # layer_positions and layer_windows: "attention" (the attention kind
    # above) | "gated_deltanet" (arXiv:2412.06464): [q | k | v | z] = h W and
    # [b | a] = h W', q, k and v through a causal depthwise convolution of
    # gdn_conv_taps and silu, q and k L2-normed per head (q over sqrt of the
    # key size), beta = sigmoid(b), decay exp(g) with g = -exp(A_log) ·
    # softplus(a + dt_bias); a (key size x value size) state per value head
    # (key head j // (value heads / key heads)) carried along the sequence by
    # the delta rule S <- exp(g) S; S <- S + k beta (v - S^T k)^T; o = S^T q;
    # then an RMS norm per head times silu(z), and an output projection.
    # Its layers have no positions and see every earlier token. () =
    # attention in every layer. A leaf only one mixer's layers have is
    # stacked over those layers alone (gdn_* over the gated DeltaNet layers).
    layer_mixers: tuple = ()
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_size: int = 0
    gdn_value_size: int = 0
    gdn_conv_taps: int = 4
    # attention's output times sigmoid of a gate that the q projection gives
    # beside q (arXiv:2505.06708), per channel of the heads
    attn_output_gate: bool = False
    qk_norm: bool = False       # an RMS norm over every head of q and of k
    # every RMS norm of the stack (ln1, ln2, ln_f, q and k's) stores its
    # scale as w and multiplies by 1 + w, w drawn at zero
    norm_zero_centred: bool = False
    # the shared experts' output times sigmoid(h w), w a (d_model, 1) vector
    shared_expert_gate: bool = False

    @property
    def head_dim(self):
        if self.attention == "mla":     # of q and k; _check holds v to it
            return self.nope_head_size + self.rope_head_size
        return self.head_size or self.d_model // self.n_heads

    @property
    def kv_heads(self):
        return self.n_kv_heads or self.n_heads

    @property
    def rotary_dims(self):
        """Dimensions of a head that "rope" rotates."""
        return int(round(self.head_dim * self.rotary_share))

    @property
    def layer_kinds(self):
        """((positions, window, mixer), ...) for the layers of one period."""
        n = max(len(self.layer_positions), len(self.layer_windows),
                len(self.layer_mixers), 1)
        pos = self.layer_positions or ("learned",) * n
        win = self.layer_windows or (0,) * n
        mix = self.layer_mixers or ("attention",) * n
        if len(pos) != len(win) or len(mix) != n or self.n_layers % n \
                or set(pos) - {"rope", "none", "learned"} \
                or ("learned" in pos and set(pos) != {"learned"}) \
                or set(mix) - {"attention", "gated_deltanet"}:
            raise ValueError(
                f"layer_positions {self.layer_positions}, layer_windows "
                f"{self.layer_windows} and layer_mixers {self.layer_mixers} "
                f"must describe one period that divides "
                f"n_layers={self.n_layers}")
        return tuple(zip(pos, (int(w) for w in win), mix))

    def layers_of(self, mixer: str) -> int:
        """How many layers of the stack ``mixer`` mixes."""
        kinds = self.layer_kinds
        return self.n_layers // len(kinds) * sum(k[2] == mixer for k in kinds)


def _check(cfg: TransformerConfig):
    """Combinations of fields that no code computes."""
    if cfg.n_heads % cfg.kv_heads:
        raise ValueError(f"{cfg.n_heads} query heads cannot share "
                         f"{cfg.kv_heads} K/V heads")
    if cfg.mlp not in ("gelu", "reglu", "swiglu"):
        raise ValueError(f"Unknown mlp {cfg.mlp!r}; 'gelu', 'reglu' or "
                         "'swiglu'")
    if cfg.attention not in ("mha", "cca", "mla"):
        raise ValueError(f"Unknown attention {cfg.attention!r}")
    if cfg.router not in ("linear", "mlp", "sigmoid"):
        raise ValueError(f"Unknown router {cfg.router!r}")
    if cfg.attention == "mla":
        if min(cfg.q_rank, cfg.kv_rank, cfg.nope_head_size,
               cfg.rope_head_size, cfg.v_head_size) < 1 \
                or cfg.rope_head_size % 2:
            raise ValueError(
                "mla wants q_rank, kv_rank, nope_head_size, v_head_size and "
                f"an even rope_head_size (got {cfg.q_rank}, {cfg.kv_rank}, "
                f"{cfg.nope_head_size}, {cfg.v_head_size}, "
                f"{cfg.rope_head_size})")
        if cfg.v_head_size != cfg.head_dim or cfg.kv_heads != cfg.n_heads \
                or cfg.head_size not in (0, cfg.head_dim) \
                or cfg.rotary_share != 1.0 or cfg.use_ring_attention:
            raise NotImplementedError(
                "mla hands the attention paths assembled heads of ONE size "
                "(v_head_size = nope_head_size + rope_head_size), a K/V head "
                "per query head, rotates all of its rotated part "
                "(rotary_share=1) and has no ring step")
    if "rope" in cfg.layer_positions and (
            cfg.rotary_dims < 2 or cfg.rotary_dims % 2
            or cfg.rotary_dims > cfg.head_dim):
        raise ValueError(f"rotary_share {cfg.rotary_share} of a head of "
                         f"{cfg.head_dim} is not a whole number of pairs")
    if cfg.attention == "cca":
        if (cfg.kv_heads * cfg.head_dim) % 2 or len(cfg.cca_taps) != 2 \
                or min(cfg.cca_taps) < 1:
            raise ValueError(
                f"cca wants two tap counts of at least 1 (cca_taps "
                f"{cfg.cca_taps}) and an even number of value channels")
        if cfg.use_ring_attention:
            raise NotImplementedError(
                "cca's convolutions read the tokens before: no ring step "
                "hands them across the shards of the sequence")
    if cfg.router == "mlp":
        if not cfg.experts_held or cfg.expert_top_k != 1 \
                or cfg.router_hidden < 1 \
                or cfg.router_input != "post_attention":
            raise NotImplementedError(
                "the mlp router takes one expert a token (expert_top_k=1) "
                "of the dropless layer (experts_held), reads the MLP's "
                "input and needs router_hidden")
    elif cfg.router_skip:
        raise NotImplementedError("only the mlp router has a skip output")
    if cfg.router == "sigmoid" and (
            not cfg.experts_held or cfg.router_input != "post_attention"):
        raise NotImplementedError(
            "the sigmoid router feeds the dropless layer (experts_held) and "
            "reads the MLP's input")
    if cfg.shared_experts and not cfg.experts_held:
        raise NotImplementedError(
            "shared experts stand beside the dropless layer (experts_held)")
    if cfg.dense_layers:
        if not 0 < cfg.dense_layers < cfg.n_layers or not cfg.experts_held:
            raise ValueError(
                f"dense_layers={cfg.dense_layers} are the leading layers of "
                f"a stack of n_layers={cfg.n_layers} whose other layers hold "
                "routed experts (experts_held)")
        if cfg.router == "mlp" or len(cfg.layer_kinds) != 1:
            raise NotImplementedError(
                "the leading dense layers are a group of their own: the mlp "
                "router's state and a period of several kinds of layer do "
                "not cross from one group to the next")
    if cfg.predict_ahead not in (0, 1):
        raise NotImplementedError(
            f"predict_ahead={cfg.predict_ahead}: one prediction module, one "
            "token further ahead, is what the loss computes")
    if cfg.predict_ahead and (cfg.router == "mlp" or cfg.use_ring_attention
                              or len(cfg.layer_kinds) != 1):
        raise NotImplementedError(
            "the prediction module is one more block of the stack's one "
            "kind, over whole rows (no ring step), and starts no router "
            "state of its own")
    if cfg.router_input not in ("pre_attention", "post_attention"):
        raise ValueError(f"Unknown router_input {cfg.router_input!r}")
    if cfg.experts_held:
        first, held = cfg.experts_held
        if not (0 <= first and held >= 1 and first + held <= cfg.n_experts):
            raise ValueError(f"experts_held {cfg.experts_held} is not a "
                             f"share of n_experts={cfg.n_experts}")
    elif cfg.n_experts and (cfg.mlp != "gelu"
                            or cfg.router_input != "post_attention"):
        # ROADMAP Design: the two expert layers are to become one
        raise NotImplementedError(
            "the capacity expert layer (experts_held=()) has GELU experts "
            "and routes on the MLP's input; give experts_held=(0, n_experts)"
            " for the dropless layer")
    kinds = cfg.layer_kinds
    if any(m == "gated_deltanet" for _, _, m in kinds):
        if min(cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_size,
               cfg.gdn_value_size, cfg.gdn_conv_taps) < 1 \
                or cfg.gdn_value_heads % cfg.gdn_key_heads \
                or any(p == "rope" or w for p, w, m in kinds
                       if m == "gated_deltanet"):
            raise ValueError(
                "gated_deltanet wants key and value heads (value heads a "
                "multiple of key heads), their sizes and conv taps, and its "
                "layers place no positions and see every earlier token")
        if cfg.attention != "mha" or cfg.use_ring_attention:
            raise NotImplementedError(
                "gated DeltaNet layers stand beside plain attention layers "
                "(attention='mha'), and no ring step hands their state "
                "along the shards of the sequence")
    if (cfg.qk_norm or cfg.attn_output_gate) and cfg.attention != "mha":
        raise NotImplementedError(
            "qk_norm and attn_output_gate act on plain heads (attention="
            "'mha'); cca and mla norm their own")
    if cfg.shared_expert_gate and (not cfg.shared_experts
                                   or cfg.router != "linear"):
        raise NotImplementedError(
            "the shared experts' gate stands beside the linear router's "
            "shared experts (shared_experts, router='linear')")


# ---------------------------------------------------------------- params

def _group_configs(cfg: TransformerConfig):
    """The stack as groups of blocks of ONE shape each: (the leading dense
    layers' configuration or None, the other layers', the prediction
    module's one block's or None). Each is ``cfg`` with the other groups'
    fields cleared, so that what draws, shards or runs a stack of one shape
    serves all three."""
    rest = dataclasses.replace(cfg, n_layers=cfg.n_layers - cfg.dense_layers,
                               dense_layers=0, predict_ahead=0)
    dense = module = None
    if cfg.dense_layers:
        dense = dataclasses.replace(
            rest, n_layers=cfg.dense_layers, n_experts=0, experts_held=(),
            shared_experts=0, expert_ff=0, router="linear")
    if cfg.predict_ahead:
        module = dataclasses.replace(rest, n_layers=1)
    return dense, rest, module


def _mixer_of(name: str):
    """The mixer whose layers alone have the block leaf ``name``, or None for
    a leaf of every layer (norms, router, MLP, experts)."""
    if name.startswith("gdn_"):
        return "gated_deltanet"
    if name in ("wqkv", "wo", "q_norm", "k_norm"):
        return "attention"
    return None


def _init_blocks(k, cfg: TransformerConfig):
    """The stacked blocks of a stack of one shape, from 12 keys. A leaf that
    only one mixer's layers have is stacked over those layers alone."""
    d, f, h, L = cfg.d_model, cfg.d_ff, cfg.n_heads * cfg.head_dim, cfg.n_layers
    hkv = cfg.kv_heads * cfg.head_dim
    gated = 1 if cfg.mlp == "gelu" else 2        # gate | up side by side
    pd = cfg.param_dtype
    La, Lg = cfg.layers_of("attention"), cfg.layers_of("gated_deltanet")
    scale0 = jnp.zeros if cfg.norm_zero_centred else jnp.ones

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, pd) / math.sqrt(fan_in))

    blocks = {"ln1": scale0((L, d), pd), "ln2": scale0((L, d), pd)}
    if La:      # [q | k | v] and, gated, [... | the gate]
        blocks.update(
            wqkv=norm(k[2], (La, d, h * (1 + cfg.attn_output_gate)
                             + 2 * hkv), d),
            wo=norm(k[3], (La, h, d), h))
    kk = jax.random.split(k[10], 8)     # PR 32's leaves; k[0..9] as before
    kn = jax.random.split(k[11], 8)     # PR 34's; [6] and [7]: init_params
    kg = jax.random.split(kk[6], 8)     # the recurrent layers' and gates'
    if cfg.qk_norm:
        blocks.update(q_norm=scale0((La, cfg.head_dim), pd),
                      k_norm=scale0((La, cfg.head_dim), pd))
    if Lg:
        Hv, nk = cfg.gdn_value_heads, cfg.gdn_key_heads * cfg.gdn_key_size
        nv, taps = Hv * cfg.gdn_value_size, cfg.gdn_conv_taps
        blocks.update(
            gdn_wqkvz=norm(kg[0], (Lg, d, 2 * nk + 2 * nv), d),
            gdn_wba=norm(kg[1], (Lg, d, 2 * Hv), d),
            gdn_conv=norm(kg[2], (Lg, taps, 2 * nk + nv), taps),
            gdn_a_log=jnp.log(jax.random.uniform(kg[3], (Lg, Hv), pd,
                                                 0.0, 16.0)),
            gdn_dt_bias=jnp.ones((Lg, Hv), pd),
            gdn_norm=jnp.ones((Lg, cfg.gdn_value_size), pd),
            gdn_wo=norm(kg[4], (Lg, nv, d), nv))
    if cfg.attention == "cca":
        k0, k1 = cfg.cca_taps
        dh, hc = cfg.head_dim, cfg.n_heads + cfg.kv_heads
        blocks.update(
            cca_w0=norm(kk[0], (L, k0, hc * dh), k0),       # depthwise taps
            cca_b0=jnp.zeros((L, hc * dh), pd),
            cca_w1=norm(kk[1], (L, k1, hc, dh, dh), k1 * dh),   # per head
            cca_b1=jnp.zeros((L, hc * dh), pd),
            cca_tau=jnp.ones((L, cfg.kv_heads), pd))
    if cfg.attention == "mla":      # no wqkv: two latents and a shared key
        H, dn = cfg.n_heads, cfg.nope_head_size
        dr, dv = cfg.rope_head_size, cfg.v_head_size
        del blocks["wqkv"]
        blocks.update(
            wq_a=norm(kn[0], (L, d, cfg.q_rank), d),
            q_norm=jnp.ones((L, cfg.q_rank), pd),
            wq_b=norm(kn[1], (L, cfg.q_rank, H * (dn + dr)), cfg.q_rank),
            wkv_a=norm(kn[2], (L, d, cfg.kv_rank + dr), d),
            kv_norm=jnp.ones((L, cfg.kv_rank), pd),
            wkv_b=norm(kn[3], (L, cfg.kv_rank, H * (dn + dv)), cfg.kv_rank))
    if cfg.scaled_residuals:    # rows: s1, s2, s3, s4 and b1, b2, b3, b4
        blocks.update(res_scale=jnp.ones((L, 4, d), pd),
                      res_bias=jnp.zeros((L, 4, d), pd))
    if cfg.router == "mlp":
        R, out = cfg.router_hidden, cfg.n_experts + int(cfg.router_skip)
        blocks.update(
            router_down=norm(kk[2], (L, d, R), d),
            router_down_b=jnp.zeros((L, R), pd),
            router_gamma=jnp.ones((L, R), pd),
            router_w1=norm(kk[3], (L, R, R), R),
            router_c1=jnp.zeros((L, R), pd),
            router_w2=norm(kk[4], (L, R, R), R),
            router_c2=jnp.zeros((L, R), pd),
            router_w3=norm(kk[5], (L, R, out), R),
            router_beta=jnp.zeros((L, out), pd))    # no gradient reaches it
    if cfg.n_experts:
        E = cfg.n_experts           # the router's width, held or not
        held = cfg.experts_held[1] if cfg.experts_held else E
        fe = cfg.expert_ff or f
        if cfg.router != "mlp":
            blocks["router"] = norm(k[4], (L, d, E), d)
        if cfg.router == "sigmoid":
            blocks["router_beta"] = jnp.zeros((L, E), pd)   # no gradient
        blocks["we_in"] = norm(k[5], (L, held, d, gated * fe), d)
        blocks["we_out"] = norm(k[6], (L, held, fe, d), fe)
        if cfg.shared_experts:
            fs = cfg.shared_experts * fe
            blocks["ws_in"] = norm(kn[4], (L, d, gated * fs), d)
            blocks["ws_out"] = norm(kn[5], (L, fs, d), fs)
        if cfg.shared_expert_gate:
            blocks["ws_gate"] = norm(kg[5], (L, d, 1), d)
    else:
        blocks["w_in"] = norm(k[7], (L, d, gated * f), d)
        blocks["w_out"] = norm(k[8], (L, f, d), f)
    return blocks


def init_params(key, cfg: TransformerConfig):
    """Stacked-block params. Names are stable for checkpoints/sharding."""
    _check(cfg)
    k = jax.random.split(key, 12)
    d, pd = cfg.d_model, cfg.param_dtype
    dense, rest, module = _group_configs(cfg)

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, pd) / math.sqrt(fan_in))

    params = {
        "embed": norm(k[0], (cfg.vocab_size, d), d),  # scaled-init embedding
        "pos_embed": 0.02 * jax.random.normal(k[1], (cfg.max_seq, d), pd),
        "blocks": _init_blocks(k, rest),
        "ln_f": (jnp.zeros if cfg.norm_zero_centred else jnp.ones)((d,), pd),
    }
    if cfg.layer_positions:         # rotary or no positions: no table
        del params["pos_embed"]
    kn = jax.random.split(k[11], 8)
    if dense is not None:           # the leading layers, stacked apart
        params["dense_blocks"] = _init_blocks(jax.random.split(kn[6], 12),
                                              dense)
    if module is not None:
        km = jax.random.split(kn[7], 13)
        params["mtp"] = {
            "ln_h": jnp.ones((d,), pd), "ln_e": jnp.ones((d,), pd),
            "proj": norm(km[12], (2 * d, d), 2 * d),    # [hidden | embedding]
            "block": _init_blocks(km[:12], module),
            "ln_f": jnp.ones((d,), pd)}
    if not cfg.tie_embeddings:
        params["head"] = norm(k[9], (d, cfg.vocab_size), d)
    return params


def draft_config(cfg: TransformerConfig,
                 n_layers: int = 2) -> TransformerConfig:
    """Config for a layer-truncated draft model (ISSUE 19 speculative
    decoding): the target's shape with only the first ``n_layers``
    blocks — everything else (vocab, widths, max_seq, dtypes) must
    match so the draft can share embeddings/head and propose in the
    target's token space."""
    n = int(n_layers)
    if not (1 <= n <= cfg.n_layers):
        raise ValueError(f"draft n_layers={n} outside 1..{cfg.n_layers}")
    return dataclasses.replace(cfg, n_layers=n)


def draft_params(params, cfg: TransformerConfig, n_layers: int = 2):
    """Params for :func:`draft_config`'s truncated draft: the FIRST
    ``n_layers`` slices of the target's stacked block tensors, with
    embed/pos_embed/ln_f/head SHARED (same arrays, no copy) — a free
    draft, no training run needed. Returns ``(draft_cfg,
    draft_params)``. Acceptance depends entirely on how much of the
    target's next-token behaviour the early layers carry; the spec
    promotion race measures it rather than assuming it."""
    dcfg = draft_config(cfg, n_layers)
    blocks = {name: w[:dcfg.n_layers]
              for name, w in params["blocks"].items()}
    out = dict(params, blocks=blocks)
    return dcfg, out


def _block_pspecs(cfg: TransformerConfig):
    """PartitionSpecs of the stacked blocks of a stack of one shape."""
    specs = {
        "ln1": P(),
        "wqkv": P(None, None, "tp"),   # column parallel
        "wo": P(None, "tp", None),     # row parallel
        "ln2": P(),
    }
    small = ()
    if cfg.attention == "cca":
        small += ("cca_w0", "cca_b0", "cca_w1", "cca_b1", "cca_tau")
    if cfg.attention == "mla":      # the latents whole, the heads by column
        del specs["wqkv"]
        small += ("wq_a", "q_norm", "wkv_a", "kv_norm")
        specs.update(wq_b=P(None, None, "tp"), wkv_b=P(None, None, "tp"))
    if not cfg.layers_of("attention"):
        del specs["wqkv"], specs["wo"]
    if cfg.qk_norm:
        small += ("q_norm", "k_norm")
    if cfg.layers_of("gated_deltanet"):     # every chip holds them whole
        small += ("gdn_wqkvz", "gdn_wba", "gdn_conv", "gdn_a_log",
                  "gdn_dt_bias", "gdn_norm", "gdn_wo")
    if cfg.shared_expert_gate:
        small += ("ws_gate",)
    if cfg.scaled_residuals:
        small += ("res_scale", "res_bias")
    if cfg.router == "mlp":
        small += ("router_down", "router_down_b", "router_gamma", "router_w1",
                  "router_c1", "router_w2", "router_c2", "router_w3",
                  "router_beta")
    if cfg.n_experts:
        if cfg.router != "mlp":
            small += ("router",)
        if cfg.router == "sigmoid":
            small += ("router_beta",)
        specs["we_in"] = P(None, "ep", None, "tp")
        specs["we_out"] = P(None, "ep", "tp", None)
        if cfg.shared_experts:
            specs["ws_in"] = P(None, None, "tp")
            specs["ws_out"] = P(None, "tp", None)
    else:
        specs["w_in"] = P(None, None, "tp")
        specs["w_out"] = P(None, "tp", None)
    specs.update({name: P() for name in small})
    return specs


def param_pspecs(cfg: TransformerConfig):
    """PartitionSpecs per param (tp/ep sharding; fsdp composes on top)."""
    dense, rest, module = _group_configs(cfg)
    specs = {
        "embed": P("tp", None),          # vocab-sharded embedding
        "pos_embed": P(),
        "blocks": _block_pspecs(rest),
        "ln_f": P(),
    }
    if cfg.layer_positions:
        del specs["pos_embed"]
    if dense is not None:
        specs["dense_blocks"] = _block_pspecs(dense)
    if module is not None:
        specs["mtp"] = {"ln_h": P(), "ln_e": P(), "proj": P(),
                        "block": _block_pspecs(module), "ln_f": P()}
    if not cfg.tie_embeddings:
        specs["head"] = P(None, "tp")
    return specs


def shardings_for(mesh: Mesh, cfg: TransformerConfig, params_like=None):
    specs = param_pspecs(cfg)

    def to_sh(spec):
        spec = P(*(a if (a is None or a in mesh.axis_names) else None
                   for a in spec))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(to_sh, specs,
                                  is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------- forward

def _constrain(x, *spec):
    """with_sharding_constraint that silently no-ops outside jit/mesh."""
    try:
        return lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, RuntimeError):
        return x


def flash_engages(cfg, t) -> bool:
    """True when :func:`_attention` will run the pallas flash kernel for a
    length-``t`` sequence under ``cfg`` — THE single gate, shared with the
    bench's analytic flash-flops accounting (the kernel's matmuls are
    invisible to jaxpr flop tracing). Explicit ``True`` engages the kernel
    even off-TPU (interpret mode — slow but correct, and the only way CI
    covers the branch); "auto" stays TPU-only. Single-chip only either
    way: pallas_call has no SPMD partitioning rule, so a tp/sp-sharded
    mesh keeps the XLA fused path (which shards). Ring attention wins
    over flash when both are requested."""
    if cfg.use_ring_attention or jax.device_count() != 1:
        return False
    if cfg.use_flash_attention is True:
        return True
    return (cfg.use_flash_attention == "auto" and t >= cfg.flash_min_seq
            and jax.default_backend() == "tpu")


def attention_path(cfg, t, dtype) -> str:
    """The implementation :func:`_attention` runs for a length-``t``
    sequence of ``dtype`` activations under ``cfg``: ``"ring"`` |
    ``"flash"`` | ``"xla_bf16_scores"`` | ``"xla_sdpa"``. One ladder —
    ``_attention`` dispatches on it, and ``chip_smoke.py`` prints it, so
    what ran on the chip is named rather than inferred."""
    if cfg.use_ring_attention:
        return "ring"
    if flash_engages(cfg, t):
        return "flash"
    if cfg.attn_scores_bf16 and jnp.dtype(dtype) == jnp.bfloat16:
        return "xla_bf16_scores"
    return "xla_sdpa"


def _rope(x, theta, pos_offset=0, rotary=None):
    """Rotary positions on (B, T, H, Dh): dimension i pairs with i + Dh/2
    (the split-half layout), over the whole head, angles in float32. With
    ``rotary`` < Dh only the first ``rotary`` dimensions are rotated (i pairs
    with i + rotary/2) and the rest pass through."""
    if rotary is not None and rotary != x.shape[-1]:
        return jnp.concatenate(
            [_rope(x[..., :rotary], theta, pos_offset), x[..., rotary:]], -1)
    t, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = (pos_offset + jnp.arange(t, dtype=jnp.float32))[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _shift(x, n=1):
    """x[:, t - n] along the time axis of (B, T, ...), zeros on the left."""
    if n == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (n, 0)
    return jnp.pad(x[:, :-n], pad)


def _cca_qkv(cfg, h, blk, positions):
    """Compressed convolutional attention up to the softmax: the normed
    input (B, T, d) -> q (B, T, H·Dh), k and v (B, T, J·Dh) in the latent,
    q and k mixed, normed and (``positions == "rope"``) rotated. In float32
    from the projections' output on."""
    b, t, _ = h.shape
    H, J, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    hq, hk, f32 = H * dh, J * dh, jnp.float32
    with jax.named_scope("cca_proj"):
        qkv = jnp.einsum("btd,dz->btz", h, blk["wqkv"].astype(h.dtype))
        qkv = _constrain(qkv, "dp", "sp", "tp")
    with jax.named_scope("cca_mix"):
        v = qkv[..., hq + hk:]      # second half: the token before's
        v = jnp.concatenate([v[..., : hk // 2], _shift(v[..., hk // 2:])], -1)
        c = qkv[..., : hq + hk].astype(f32)
        q3 = c[..., :hq].reshape(b, t, J, H // J, dh)
        k3 = c[..., hq:].reshape(b, t, J, 1, dh)
        mq = ((q3 + k3) / 2).reshape(b, t, H, dh)
        mk = (jnp.mean(q3, axis=3) + k3[:, :, :, 0]) / 2
        k0, k1 = cfg.cca_taps
        w0, w1 = blk["cca_w0"].astype(f32), blk["cca_w1"].astype(f32)
        y = sum(_shift(c, k0 - 1 - a) * w0[a] for a in range(k0)) \
            + blk["cca_b0"].astype(f32)
        y = y.reshape(b, t, H + J, dh)
        # float32 operands at the default precision: one bf16 pass of the
        # MXU with a float32 result on the chip (XLA:CPU has no batched bf16
        # x bf16 -> f32 product)
        z = sum(jnp.einsum("bthd,hde->bthe", _shift(y, k1 - 1 - a), w1[a])
                for a in range(k1)) \
            + blk["cca_b1"].astype(f32).reshape(H + J, dh)

        def unit(a):    # every head to norm sqrt(dh)
            return a * lax.rsqrt(
                jnp.mean(jnp.square(a), -1, keepdims=True) + 1e-12 / dh)

        q = unit(z[:, :, :H] + mq)
        k = unit(z[:, :, H:] + mk) * blk["cca_tau"].astype(f32)[:, None]
        if positions == "rope":
            q = _rope(q, cfg.rope_theta, rotary=cfg.rotary_dims)
            k = _rope(k, cfg.rope_theta, rotary=cfg.rotary_dims)
        q, k = q.astype(h.dtype), k.astype(h.dtype)
    return q.reshape(b, t, hq), k.reshape(b, t, hk), v


def _mla_qkv(cfg, h, blk, positions):
    """Multi-head latent attention up to the softmax, in the training form:
    the normed input (B, T, d) -> q, k, v (B, T, H·Dh) with the heads
    ASSEMBLED, a head of q and of k the nope_head_size dimensions without
    positions and then the rope_head_size rotated ones (``positions ==
    "rope"``), the rotated key ONE head that every head reads. The
    up-projections are not absorbed into the query and the output: that
    form serves a cache of the latent and is a decode step's."""
    b, t, _ = h.shape
    H, dn, dr = cfg.n_heads, cfg.nope_head_size, cfg.rope_head_size
    with jax.named_scope("mla_q"):
        cq = jnp.einsum("btd,dr->btr", h, blk["wq_a"].astype(h.dtype))
        cq = _rmsnorm(cq, blk["q_norm"], cfg.norm_eps)
        q = jnp.einsum("btr,rz->btz", cq, blk["wq_b"].astype(h.dtype))
        q = _constrain(q, "dp", "sp", "tp").reshape(b, t, H, dn + dr)
    with jax.named_scope("mla_kv"):
        ckv = jnp.einsum("btd,dr->btr", h, blk["wkv_a"].astype(h.dtype))
        c = _rmsnorm(ckv[..., :cfg.kv_rank], blk["kv_norm"], cfg.norm_eps)
        kv = jnp.einsum("btr,rz->btz", c, blk["wkv_b"].astype(h.dtype))
        kv = _constrain(kv, "dp", "sp", "tp").reshape(
            b, t, H, dn + cfg.v_head_size)
    with jax.named_scope("attn_qkv"):   # a split, as the plain heads' is
        q_rope, k_rope = q[..., dn:], ckv[:, :, None, cfg.kv_rank:]
    if positions == "rope":
        with jax.named_scope("mla_rope"):
            q_rope = _rope(q_rope, cfg.rope_theta)
            k_rope = _rope(k_rope, cfg.rope_theta)
    with jax.named_scope("mla_q"):
        q = jnp.concatenate([q[..., :dn], q_rope], -1)
    with jax.named_scope("mla_kv"):
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (b, t, H, dr))], -1)
        v = kv[..., dn:]
    return (q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1))


def _attention(cfg, q, k, v, mask_bias=None, positions="learned", window=0):
    """``positions`` and ``window`` are the layer's kind (static): rotary
    q and k or none; the keys a query sees (0 = every earlier one). q, k and
    v come and the output goes as (B, T, heads·Dh), the projections' own
    layout, which the flash kernels read and write as they are."""
    b, t = q.shape[0], q.shape[1]

    def heads(x, n):
        return x.reshape(b, t, n, cfg.head_dim)

    if positions == "rope" and cfg.attention == "mha":  # others: theirs
        with jax.named_scope("attn_qkv"):
            q = _rope(heads(q, cfg.n_heads), cfg.rope_theta,
                      rotary=cfg.rotary_dims).reshape(q.shape)
            k = _rope(heads(k, cfg.kv_heads), cfg.rope_theta,
                      rotary=cfg.rotary_dims).reshape(k.shape)
    path = attention_path(cfg, t, q.dtype)
    if path == "flash":
        # the kernels open scopes of their own (flash_*), name their output
        # and lse for save_attn, and take (B, T, H·Dh) as it is
        from ..kernels.flash_attention import flash_attention_ntc
        return flash_attention_ntc(q, k, v, cfg.n_heads, causal=True,
                                   window=window or None)
    with jax.named_scope("attn_qkv"):
        q = heads(q, cfg.n_heads)
        k, v = heads(k, cfg.kv_heads), heads(v, cfg.kv_heads)
    # the ring's flash hops open scopes of their own and attn_core around
    # them: attn_core is not opened here
    if path == "ring":
        if window or cfg.kv_heads != cfg.n_heads:
            raise NotImplementedError(
                "ring attention has no window and no grouped K/V heads")
        from ..parallel.ring_attention import ring_attention_inner
        out = ring_attention_inner(q, k, v, causal=True)
    else:
        with jax.named_scope("attn_core"):
            if path == "xla_bf16_scores":
                out = _xla_attention_bf16_scores(q, k, v, window=window)
            else:
                out = jax.nn.dot_product_attention(
                    q, k, v, is_causal=True,
                    local_window_size=(window - 1, 0) if window else None)
    out = checkpoint_name(out, "attn_out")  # remat_policy="save_attn"
    with jax.named_scope("attn_core"):
        return out.reshape(b, t, cfg.n_heads * cfg.head_dim)


def _xla_attention_bf16_scores(q, k, v, causal=True, bias=None, window=0):
    """Attention with the (B,H,T,S) score matrix MATERIALIZED bf16:
    the QK^T matmul accumulates f32 in-register (BF16_BF16_F32) but stores
    bf16, and the f32 upcast for the softmax fuses into its reduce — so
    the two T^2 HBM tensors (scores, probs) are half the bytes of the
    stock XLA path's f32 logits. q/k/v are (B, T, H, D). ``bias`` is an
    additive mask broadcastable to (B, H, T, S) (e.g. padding mask −1e9,
    well inside bf16 range)."""
    t = q.shape[1]
    if k.shape[2] != q.shape[2]:        # grouped K/V heads, written out
        k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
        v = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
    scale = 1.0 / math.sqrt(q.shape[-1])
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)  # pre-scale q (exact
    # for power-of-two head dims), so no extra pass over the T^2 logits
    dot_kw = {"preferred_element_type": jnp.bfloat16}
    if jax.default_backend() == "tpu":
        # explicit MXU algorithm: bf16 inputs, f32 in-register accumulate,
        # bf16 store. XLA:CPU rejects this preset outright (tier-1 runs
        # the same path at toy shapes), so off-TPU the einsum falls back
        # to the default algorithm for the dtype — same math, CPU-legal.
        dot_kw["precision"] = lax.DotAlgorithmPreset.BF16_BF16_F32
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, **dot_kw)
    if bias is not None:
        logits = logits + bias.astype(jnp.bfloat16)
    if causal:
        neg = jnp.asarray(jnp.finfo(jnp.bfloat16).min / 2, jnp.bfloat16)
        mask = jnp.tril(jnp.ones((t, t), jnp.bool_))
        if window:
            mask = mask & ~jnp.tril(jnp.ones((t, t), jnp.bool_), -window)
        logits = jnp.where(mask[None, None, :, :], logits, neg)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1
                           ).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _remat_wrap(fn, policy: str):
    """jax.checkpoint around a block fn under one of the supported
    rematerialization policies (shared by the LM and BERT encoders)."""
    policies = {
        "full": None,
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # "save_attn": keep what attention produced and recompute everything
        # else (norms, projections, the MLP) from the block's input. The
        # XLA and ring paths name their output "attn_out" in `_attention`.
        # The flash kernel names the two residuals of its custom_vjp that
        # only it can rebuild (kernels/flash_attention.py::_flash_fwd): its
        # output, "attn_out" (ONE saved copy, as (B, T, H·Dh), what `wo`
        # reads), and the (B·H, 1, T) f32 log-sum-exp, "attn_lse". With
        # both saved the backward scan runs the two backward kernels only:
        # three Pallas calls a layer and step where "full" runs four (the
        # forward twice). The gated delta rule's kernel names its output
        # "attn_out" too and its chunk-start states "gdn_states"
        # (kernels/gated_delta.py::_rule_fwd): its backward kernel alone
        # runs in the backward pass. q, k and v are recomputed
        # from the block's input either way. B*T*D bf16 + B*H*T f32 a
        # layer (32 + 1 MiB at b16 T=1024 d1024).
        "save_attn":
            jax.checkpoint_policies.save_only_these_names(
                "attn_out", "attn_lse", "gdn_states"),
    }
    if policy not in policies:
        raise ValueError(f"Unknown remat_policy {policy!r}; "
                         f"expected one of {sorted(policies)}")
    pol = policies[policy]
    return jax.checkpoint(fn) if pol is None else jax.checkpoint(fn, policy=pol)


def _rmsnorm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _norm(cfg, x, w):
    """One of the stack's RMS norms with its stored scale ``w``: times w, or
    times 1 + w where the configuration's norms are zero-centred."""
    if cfg.norm_zero_centred:
        w = 1.0 + w.astype(jnp.float32)
    return _rmsnorm(x, w, cfg.norm_eps)


#: the scopes round the gated delta rule's call in :func:`_gated_deltanet`,
#: which its kernels' events carry (``gated_delta_rule``'s ``scopes``)
_GDN_RULE_SCOPES = ("attn_core", "gdn_rule")


def _gated_deltanet(cfg, h, blk):
    """A gated DeltaNet layer's mixer (``layer_mixers``) on the normed input
    h (B, T, d): its output projection's result (B, T, d). The convolution,
    beta, the decays and the gated norm in float32; the delta rule is
    ``kernels.gated_delta``'s, which reads q, k and v in the compute dtype
    from the convolution's (B, T, 2 Hk dk + Hv dv) output."""
    b, t, _ = h.shape
    Hk, Hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_size, cfg.gdn_value_size
    nk, nv, f32 = Hk * dk, Hv * dv, jnp.float32
    with jax.named_scope("attn_qkv"):
        qkvz = jnp.einsum("btd,dz->btz", h, blk["gdn_wqkvz"].astype(h.dtype))
        ba = jnp.einsum("btd,dz->btz", h, blk["gdn_wba"].astype(h.dtype),
                        preferred_element_type=f32)
        qkv, z = qkvz[..., :2 * nk + nv], qkvz[..., 2 * nk + nv:]
    with jax.named_scope("attn_core"):
        with jax.named_scope("gdn_conv"):
            c, wc = qkv.astype(f32), blk["gdn_conv"].astype(f32)
            taps = cfg.gdn_conv_taps
            c = jax.nn.silu(sum(_shift(c, taps - 1 - a) * wc[a]
                                for a in range(taps)))
            c = c.astype(h.dtype)   # [q | k | v], read so by the rule
            beta = jax.nn.sigmoid(ba[..., :Hv])
            g = -jnp.exp(blk["gdn_a_log"].astype(f32)) * jax.nn.softplus(
                ba[..., Hv:] + blk["gdn_dt_bias"].astype(f32))
        with jax.named_scope("gdn_rule"):
            o = gated_delta.gated_delta_rule(c, g, beta, Hk, dk, dv,
                                             scopes=_GDN_RULE_SCOPES)
            o = o.reshape(b, t, Hv, dv)
        with jax.named_scope("gdn_norm"):
            o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + cfg.norm_eps) * blk["gdn_norm"].astype(f32)
            o = o * jax.nn.silu(z.astype(f32).reshape(b, t, Hv, dv))
            o = o.reshape(b, t, nv).astype(h.dtype)
    with jax.named_scope("attn_wo"):
        return jnp.einsum("bth,hd->btd", o, blk["gdn_wo"].astype(h.dtype))


def _dense_mlp(cfg, x, w_in, w_out):
    h = jnp.einsum("btd,df->btf", x, w_in.astype(x.dtype))
    h = _constrain(h, "dp", "sp", "tp")
    h = _mlp_act(cfg, h)
    o = jnp.einsum("btf,fd->btd", h, w_out.astype(x.dtype))
    return o


def _mlp_act(cfg, h):
    """The MLP's nonlinearity on the (…, d_ff) or, gated, (…, 2·d_ff)
    output of its first product."""
    if cfg.mlp == "gelu":
        return jax.nn.gelu(h)
    gate, up = jnp.split(h, 2, axis=-1)
    return (jax.nn.relu if cfg.mlp == "reglu" else jax.nn.silu)(gate) * up


def _moe_mlp(cfg, x, router, we_in, we_out):
    """Top-k routed MoE with capacity; einsum dispatch (expert axis 'ep').

    Dispatch/combine are one-hot einsums — dense matmuls the MXU likes —
    with all_to_all inserted by XLA from the sharding constraints.
    """
    b, t, d = x.shape
    E = cfg.n_experts
    tokens = x.reshape(b * t, d)
    logits = jnp.einsum("nd,de->ne", tokens.astype(jnp.float32),
                        router.astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(gates, cfg.expert_top_k)             # (N, K)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    cap = max(1, int(cfg.capacity_factor * (b * t) * cfg.expert_top_k / E))
    # position of each token within its expert's buffer
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)          # (N, K, E)
    pos = jnp.cumsum(onehot.reshape(-1, E), axis=0).reshape(b * t, -1, E) - 1.0
    keep = (pos < cap) & (onehot > 0)
    disp = (onehot * keep).astype(x.dtype)                       # (N, K, E)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=x.dtype) * disp[..., None]
    # dispatch: (N,K,E,C) x (N,d) → (E,C,d)
    expert_in = jnp.einsum("nkec,nd->ecd", pos_oh, tokens)
    expert_in = _constrain(expert_in, "ep", None, None)
    h = jnp.einsum("ecd,edf->ecf", expert_in, we_in.astype(x.dtype))
    h = jax.nn.gelu(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, we_out.astype(x.dtype))
    expert_out = _constrain(expert_out, "ep", None, None)
    combine = (pos_oh * topv[:, :, None, None].astype(x.dtype))
    out = jnp.einsum("nkec,ecd->nd", combine, expert_out)
    # aux load-balancing loss (Switch-style)
    density = onehot.reshape(-1, E).mean(0)
    density_proxy = gates.mean(0)
    aux = E * jnp.sum(density * density_proxy)
    return out.reshape(b, t, d), aux.astype(jnp.float32)


def _router_logits(x, router):
    """(B, T, d) → (B·T, E) float32 logits, accumulated in float32."""
    with jax.named_scope("moe_router"):
        return jnp.einsum("btd,de->bte", x, router.astype(x.dtype),
                          preferred_element_type=jnp.float32
                          ).reshape(-1, router.shape[-1])


def _route_top_k(cfg, logits):
    """The linear router's choice: top-k of the (N, E) logits and the softmax
    over the kept ones, both (K, N)."""
    with jax.named_scope("moe_router"):
        top, chosen = lax.top_k(logits, cfg.expert_top_k)       # (N, K)
        weight = jax.nn.softmax(top, axis=-1).T                 # (K, N)
        return chosen.T, weight


def _route_sigmoid(cfg, logits, beta):
    """The sigmoid router's choice on the (N, E) float32 logits: top-k of
    score plus ``beta`` (a bias no gradient reaches), weighted by the chosen
    scores themselves over their sum, times ``router_scale``; both (K, N)."""
    with jax.named_scope("moe_router"):
        score = jax.nn.sigmoid(logits)
        _, chosen = lax.top_k(
            score + lax.stop_gradient(beta.astype(jnp.float32)),
            cfg.expert_top_k)                                   # (N, K)
        kept = jnp.take_along_axis(score, chosen, axis=-1)
        weight = cfg.router_scale * kept / (
            jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
        return chosen.T.astype(jnp.int32), weight.T


def _route_mlp(cfg, u, state, blk):
    """The mlp router on the MLP's input ``u`` (B, T, d) with the state
    ``state`` (B, T, R) float32 of the layer before (zeros for the first).
    Returns (the new state, choice (1, N) int32, weight (1, N) float32): the
    choice is the largest of probability plus ``router_beta``; an output of
    ``n_experts`` is the skip. Float32 throughout, the three small products
    at the highest precision: a token's choice is an argmax."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    with jax.named_scope("moe_router"):
        r = jnp.einsum("btd,dr->btr", u, blk["router_down"].astype(u.dtype),
                       preferred_element_type=f32)
        r = r + blk["router_down_b"].astype(f32) \
            + blk["router_gamma"].astype(f32) * state
        z = _rmsnorm(r, jnp.ones((), f32), cfg.norm_eps)
        for w, c in (("router_w1", "router_c1"), ("router_w2", "router_c2")):
            z = jax.nn.gelu(jnp.dot(z, blk[w].astype(f32), precision=hi)
                            + blk[c].astype(f32), approximate=False)
        logits = jnp.dot(z, blk["router_w3"].astype(f32), precision=hi)
        p = jax.nn.softmax(logits.reshape(-1, logits.shape[-1]), axis=-1)
        beta = lax.stop_gradient(blk["router_beta"].astype(f32))
        chosen = jnp.argmax(p + beta, axis=-1).astype(jnp.int32)    # (N,)
        weight = jnp.take_along_axis(p, chosen[:, None], axis=-1)[:, 0]
        return r, chosen[None], weight[None]


def _take_rows(x, idx):
    """x[idx] along axis 0 for indices known to be in bounds."""
    return x.at[idx].get(mode="promise_in_bounds")


# The K·N assignments of a routed layer are numbered K-MAJOR: assignment
# a = k·N + n is token n's k-th choice. An (A, d) array in assignment order
# is then a (K, N, d) array without moving a byte, and the sum over a
# token's K rows is a sum of K slabs; numbered token-major, (N, K, d) pads
# its K = 6 rows to the chip's tile of 8 and every reshape is a copy
# (1.8 ms each at 98,304 x 2560; my chip run, PR 28).
#
# A layer moves rows four times, and what each crosses differs (PR 36). The
# way OUT (tokens to expert order) is one gather of A rows from the N tokens
# and its gradient one gather of A rows by ``inv`` from the A rows of the
# grouped products' gradient, summed over a token's local rows. The way BACK
# (expert order to the tokens' weighted sum) is the same gather by ``inv``
# from the grouped products' A rows; ITS gradient alone stops at
# ``n_local``, the rows routed here: a pass of rows at a time, gathered from
# the N rows of the sum's gradient and scaled (:func:`_scaled_rows`). The
# two gathers by ``inv`` write every assignment's place, so the rows they
# cross are not bounded by the rows in use: XLA's row scatter, which would
# be, takes 137 to 540 ns a row on the v5e where its gather takes 41, and a
# Pallas kernel cannot slice one row of an array tiled in HBM (Mosaic: a
# slice along the second-minor dimension must be aligned to the tile's 8).

@jax.custom_vjp
def _rows_out(tokens, order, inv, local):
    """(N, d) tokens → (A, d) rows in expert order: row a is the token of
    assignment ``order[a]``. ``inv`` is the inverse permutation and
    ``local`` (K, N) says which assignments are to held experts: the
    gradient is a gather by ``inv`` and a sum over each token's local rows
    (those of absent experts were never computed), not a scatter-add."""
    return _take_rows(tokens, order % tokens.shape[0])


def _rows_out_fwd(tokens, order, inv, local):
    return _rows_out(tokens, order, inv, local), (inv, local)


def _rows_out_bwd(res, g):
    inv, local = res
    back = _take_rows(g, inv).reshape(*local.shape, g.shape[-1])
    return (jnp.sum(jnp.where(local[:, :, None], back, 0), axis=0,
                    dtype=jnp.float32).astype(g.dtype), None, None, None)


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


#: rows a pass of :func:`_scaled_rows` moves (256 to 1,024 read within a
#: tenth of each other at 2,048 and 2,560 columns; my chip runs, PR 36)
_ROWS_A_PASS = 512


def _pass_rows(rows):
    return min(_ROWS_A_PASS, rows)


def _rows_crossed(count, rows):
    """Rows the passes of :func:`_scaled_rows` cross for ``count`` of a
    buffer's ``rows``: whole passes."""
    chunk = _pass_rows(rows)
    return jnp.minimum(-(-count // chunk) * chunk, rows)


def _scaled_rows_loop(src, order, scale, count, into, *, chunk):
    rows = order.shape[0]

    def move(i, out):
        # the last chunk of a buffer that is no multiple of it steps back
        # and moves some rows twice
        at = jnp.minimum(i * chunk, rows - chunk)
        ids = lax.dynamic_slice(order, (at,), (chunk,))
        part = _take_rows(src, ids % src.shape[0]).astype(jnp.float32) \
            * _take_rows(scale, ids)[:, None]
        return lax.dynamic_update_slice(out, part.astype(out.dtype), (at, 0))

    return lax.fori_loop(0, -(-count // chunk), move, into)


# A primitive of its own and not a ``jax.jit`` inside the step: a
# primitive's lowering is emitted ONCE a module for each signature, as a
# private function that every layer's backward calls (``inline=False``), and
# no transformation looks inside it. A jitted callee under ``jax.checkpoint``
# is copied for every call site: remat's partial evaluation writes each call
# a new jaxpr, and a module's functions are cached by jaxpr.
_scaled_rows_p = jex_core.Primitive("scaled_rows")
_scaled_rows_p.def_impl(jax.jit(_scaled_rows_loop, static_argnames="chunk"))
_scaled_rows_p.def_abstract_eval(
    lambda src, order, scale, count, into, *, chunk: into)
mlir.register_lowering(
    _scaled_rows_p, mlir.lower_fun(_scaled_rows_loop, multiple_results=False),
    inline=False)


def _scaled_rows(src, order, scale, count, into):
    """``into`` (A, d) with ``into[a] = src[order[a] % N] * scale[order[a]]``
    (``src`` (N, d), ``scale`` (A,) float32, the product in float32) for the
    first ``count`` of its A rows, ``_ROWS_A_PASS`` rows a pass and
    ``ceil(count / that)`` passes; the rows after them keep what they held.
    (A buffer of the mover's own, ``lax.empty``, has no operand, so the
    compiler allocates every layer's at the start of the backward scan's
    body: 0.8 GB more at the smallthinker cell's sizes.) On the v5e a pass
    gathers at 27 to 52 ns a row, where one gather of all A rows takes 41 ns
    a row from a source too large to wait in VMEM (8 where it fits and is
    given the room, which a step does not always do; my chip runs, PR 36)."""
    return _scaled_rows_p.bind(src, order, scale, count, into,
                               chunk=_pass_rows(order.shape[0]))


@jax.custom_vjp
def _rows_back(rows, weight, order, inv, local, n_local):
    """(A, d) rows in expert order → (N, d): every token's local rows, each
    times its ``weight`` (K, N) float32, summed in float32 over the token's
    K assignments (the absent experts' rows are unset and count as zero).

    The gradient for ``rows`` is formed IN EXPERT ORDER, and only for the
    ``n_local`` rows the grouped products read (:func:`_scaled_rows`): row a
    is the gradient of token ``order[a] % N``'s sum times the weight of
    assignment ``order[a]``. Transposing the forward instead broadcasts the
    (N, d) gradient to a float32 (K, N, d), scales and rounds it in
    assignment order and then gathers all K·N rows of that by ``order``: the
    same numbers in the rows that are read, for two passes over K·N rows in
    float32 and a gather of K·N rows where a quarter of them is used."""
    return _rows_back_fwd(rows, weight, order, inv, local, n_local)[0]


def _parts(gathered, local):
    """(A, d) rows in assignment order → (K, N, d), zero where the
    assignment is not local."""
    return jnp.where(local[:, :, None],
                     gathered.reshape(*local.shape, -1), 0)


def _rows_back_fwd(rows, weight, order, inv, local, n_local):
    gathered = _take_rows(rows, inv)
    y = jnp.sum(_parts(gathered, local).astype(jnp.float32)
                * weight[:, :, None], axis=0).astype(rows.dtype)
    return y, (gathered, weight, order, local, n_local)


def _rows_back_bwd(res, g):
    gathered, weight, order, local, n_local = res
    d_weight = jnp.sum(g.astype(jnp.float32)
                       * _parts(gathered, local).astype(jnp.float32), axis=-1)
    # into the forward's gathered rows, which nothing reads after d_weight
    back = _scaled_rows(g, order, weight.reshape(-1), n_local, gathered)
    return back, d_weight, None, None, None, None


_rows_back.defvjp(_rows_back_fwd, _rows_back_bwd)


def _moe_share(cfg, x, chosen, weight, we_in, we_out):
    """The held experts' part of a routed expert layer, dropless.

    ``chosen`` (K, N) int32 are every token's K choices among the router's
    outputs (every published expert and, where the router has one, the
    skip) and ``weight`` (K, N) float32 what each choice's result is
    multiplied by: the router's business (:func:`_route_top_k`,
    :func:`_route_mlp`). ``we_in`` (held, d, f_in) and ``we_out`` (held,
    d_ff, d) are the experts ``cfg.experts_held`` = (first, held) names; a
    choice outside them, an absent expert's or the skip, is not local. The
    K·N assignments are sorted by expert, the absent experts' last; one
    gather fills a static (K·N, d) buffer (enough for EVERY assignment to be
    local, so no routing can drop one); grouped products over the held
    experts' group sizes compute exactly the rows routed here, the first
    ``n_local``, and leave the rest unset; the rows go back to their tokens,
    where the local ones are weighted and summed (:func:`_rows_back`, whose
    gradient fills the ``n_local`` rows in use and no others).
    Returns (y, stats, moved): stats = float32 [assignments,
    assignments to held experts, assignments dropped (rows the buffer
    could not take: 0), largest held expert's load over their mean] and,
    where the router has a skip, a fifth: tokens that took it; moved =
    float32 rows that gradient's passes crossed, ``n_local`` and the last
    pass's round-up."""
    b, t, d = x.shape
    n, k = b * t, chosen.shape[0]
    first, held = cfg.experts_held
    tokens = x.reshape(n, d)
    with jax.named_scope("moe_dispatch"):
        local = (chosen >= first) & (chosen < first + held)     # (K, N)
        slot = jnp.where(local, chosen - first, held).reshape(-1)    # (A,)
        order = jnp.argsort(slot, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32), unique_indices=True)
        sizes = jnp.sum(slot[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)                         # (held,)
        n_local = jnp.sum(sizes)
        rows = _rows_out(tokens, order, inv, local)
    with jax.named_scope("moe_experts"):
        hidden = _mlp_act(cfg, lax.ragged_dot(rows, we_in.astype(x.dtype),
                                              sizes))
        out = lax.ragged_dot(hidden, we_out.astype(x.dtype), sizes)
    with jax.named_scope("moe_combine"):
        y = _rows_back(out, weight, order, inv, local, n_local)  # (N, d)
    f32 = jnp.float32
    stats = [
        jnp.asarray(n * k, f32), n_local.astype(f32),
        jnp.maximum(n_local - rows.shape[0], 0).astype(f32),
        jnp.max(sizes).astype(f32) * held / jnp.maximum(n_local, 1).astype(f32)]
    if cfg.router_skip:
        stats.append(jnp.sum(chosen == cfg.n_experts, dtype=f32))
    return (y.reshape(b, t, d), jnp.stack(stats),
            _rows_crossed(n_local, n * k).astype(f32))


def embed(params, cfg: TransformerConfig, ids, pos_offset=0):
    """ids (B,T) → embedded activations (B,T,d) in compute dtype.

    ``pos_offset`` (static or traced int) shifts the learned position
    table — required when the SEQUENCE is explicitly sharded (shard_map
    ring step): shard i holds global positions [i·T_local, (i+1)·T_local)
    but sees a local (B, T_local) slice."""
    t = ids.shape[1]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], ids, axis=0).astype(cfg.dtype)
        if cfg.embed_scale:
            x = x * math.sqrt(cfg.d_model)
        if not cfg.layer_positions:     # else the layers place their own
            pos = lax.dynamic_slice_in_dim(params["pos_embed"],
                                           pos_offset, t, axis=0)
            x = x + pos.astype(cfg.dtype)
        return _constrain(x, "dp", "sp", None)


def _resolve_head(params, cfg: TransformerConfig):
    """(d, V) head matrix — shared by the naive and fused loss paths so
    tie_embeddings/untied resolution can't drift between them."""
    return params.get("head",
                      params["embed"].T if cfg.tie_embeddings else None)


def head_logits(params, cfg: TransformerConfig, x):
    """Final norm + LM head → f32 logits."""
    x = _norm(cfg, x, params["ln_f"])
    head = _resolve_head(params, cfg)
    logits = jnp.einsum("btd,dv->btv", x, head.astype(x.dtype))
    return _constrain(logits, "dp", "sp", "tp").astype(jnp.float32)


def head_logits_rows(params, cfg: TransformerConfig, x):
    """head_logits for (N, d) hidden ROWS (no time axis) → (N, V) f32.
    The serving engine's shape: one row per decode slot / per prefill's
    last position — never the (B, T, V) tensor a generation step doesn't
    need."""
    x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
    head = _resolve_head(params, cfg)
    return jnp.einsum("nd,dv->nv", x, head.astype(x.dtype)
                      ).astype(jnp.float32)


def hidden_rows(params, cfg: TransformerConfig, x):
    """The final-norm hidden rows themselves — (N, d) f32, no head
    matmul. The EMBED workload's representation (ISSUE 20): the same
    post-``ln_f`` activations ``head_logits_rows`` projects, surfaced
    for pooling instead of next-token prediction."""
    return _rmsnorm(x, params["ln_f"], cfg.norm_eps).astype(jnp.float32)


def generate(params, cfg: TransformerConfig, prompt_ids, max_new_tokens=32,
             *, key=None, temperature=0.0, top_k=0, eos_id=None,
             max_len=None):
    """Autoregressive generation from the LM — the zoo-level serving entry
    point. Prefills the prompt into a preallocated KV cache, then decodes
    one token per jitted donated-cache step; ``temperature=0`` is greedy,
    ``top_k`` restricts sampling to the k most likely tokens, and all
    randomness flows from the explicit PRNG ``key``. Returns the generated
    ids (without the prompt) as a numpy array — ``(B, n)`` for a batched
    prompt, ``(n,)`` for a single sequence. For sustained mixed-length
    traffic use ``serving.ContinuousBatchingScheduler`` on top of a shared
    ``serving.GenerationEngine`` instead of this one-shot helper."""
    from ..serving.engine import GenerationEngine
    eng = GenerationEngine(cfg, params, max_len=max_len)
    return eng.generate(prompt_ids, max_new_tokens, key=key,
                        temperature=temperature, top_k=top_k, eos_id=eos_id)


def apply_blocks(blocks, cfg: TransformerConfig, x, *, return_kv=False):
    """Scan the stacked transformer blocks over x. Returns (x, aux_sum).

    ``return_kv=True`` is the serving-plane prefill hook: the SAME block
    math additionally yields each layer's per-head key/value activations,
    stacked ``(L, B, T, Hkv, Dh)`` in compute dtype, and the return becomes
    ``(x, aux_sum, (k, v))``. Remat is skipped on that path — prefill is
    forward-only, there are no residuals to trade for recompute — which
    keeps the captured k/v out of any checkpoint policy's hands."""
    if cfg.dense_layers:
        raise NotImplementedError(
            "a stack with leading dense layers is two groups of blocks "
            "(params['dense_blocks'], params['blocks']): forward() and "
            "lm_loss() run both")
    x, auxes, kvs, _ = _run_blocks(blocks, cfg, x, return_kv)
    if return_kv:
        return x, jnp.sum(auxes), kvs
    return x, jnp.sum(auxes)


def _residual(cfg, x, y, blk, i):
    """The i-th residual merge of a block (0 after attention, 1 after the
    MLP): x + y, or with learned scales and biases on both terms."""
    y = _constrain(y, "dp", "sp", None)
    if not cfg.scaled_residuals:
        return x + y
    s = blk["res_scale"].astype(jnp.float32)
    b = blk["res_bias"].astype(jnp.float32)
    return ((x.astype(jnp.float32) * s[2 * i] + b[2 * i])
            + (y.astype(jnp.float32) * s[2 * i + 1] + b[2 * i + 1])
            ).astype(x.dtype)


def _run_blocks(blocks, cfg: TransformerConfig, x, return_kv=False):
    """(x, auxes (L,), kvs, what the held experts' layers tell or None:
    ``{"load": (L, 4 or 5) float32, "moved": (L,) float32}`` of
    :func:`_moe_share` and ``"choices"``: (L, K, N) int32). The scan runs
    over PERIODS of the layer pattern (``cfg.layer_kinds``; a period of one
    layer for a uniform stack): inside a period the layers' kinds are
    static, and each layer is rematerialized on its own. The scan carries
    ``(x, state)``: ``state`` is the mlp router's (B, T, router_hidden)
    float32, which every layer adds to its own and hands on, and None for
    the linear router."""
    _check(cfg)
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim

    def heads_normed(a, w, n):
        b, t = a.shape[0], a.shape[1]
        return _norm(cfg, a.reshape(b, t, n, cfg.head_dim), w).reshape(a.shape)

    def block(carry, blk, positions, window, mixer):
        x, state = carry
        with jax.named_scope("resid_norm"):
            h = _norm(cfg, x, blk["ln1"])
        gate = None
        if mixer == "gated_deltanet":
            if return_kv:
                raise NotImplementedError(
                    "a gated DeltaNet layer keeps a state, not keys and values")
        elif cfg.attention == "cca":
            q, k, v = _cca_qkv(cfg, h, blk, positions)
        elif cfg.attention == "mla":
            q, k, v = _mla_qkv(cfg, h, blk, positions)
        else:
            with jax.named_scope("attn_qkv"):
                qkv = jnp.einsum("btd,dz->btz", h,
                                 blk["wqkv"].astype(h.dtype))
                qkv = _constrain(qkv, "dp", "sp", "tp")
                if cfg.attn_output_gate:
                    q, k, v, gate = jnp.split(
                        qkv, (hq, hq + hkv, hq + 2 * hkv), axis=-1)
                else:
                    q, k, v = jnp.split(qkv, 3, axis=-1) if hkv == hq else \
                        jnp.split(qkv, (hq, hq + hkv), axis=-1)
                if cfg.qk_norm:
                    q = heads_normed(q, blk["q_norm"], cfg.n_heads)
                    k = heads_normed(k, blk["k_norm"], cfg.kv_heads)
        routed = None
        if cfg.experts_held and cfg.router_input == "pre_attention":
            routed = _router_logits(h, blk["router"])
        if mixer == "gated_deltanet":
            a = _gated_deltanet(cfg, h, blk)
        else:
            a = _attention(cfg, q, k, v, positions=positions, window=window)
            with jax.named_scope("attn_wo"):
                if gate is not None:
                    a = (a.astype(jnp.float32) * jax.nn.sigmoid(
                        gate.astype(jnp.float32))).astype(a.dtype)
                a = jnp.einsum("bth,hd->btd", a, blk["wo"].astype(h.dtype))
        with jax.named_scope("resid_norm"):
            x = _residual(cfg, x, a, blk, 0)
            h2 = _norm(cfg, x, blk["ln2"])
        told = None
        if cfg.experts_held:
            if cfg.router == "mlp":
                state, chosen, weight = _route_mlp(cfg, h2, state, blk)
            else:
                if routed is None:
                    routed = _router_logits(h2, blk["router"])
                if cfg.router == "sigmoid":
                    chosen, weight = _route_sigmoid(cfg, routed,
                                                    blk["router_beta"])
                else:
                    chosen, weight = _route_top_k(cfg, routed)
            m, load, moved = _moe_share(cfg, h2, chosen, weight,
                                        blk["we_in"], blk["we_out"])
            # a choice is an argmax: see make_train_step
            told = {"load": load, "moved": moved, "choices": chosen}
            if cfg.shared_experts:      # every token's, once on every chip
                with jax.named_scope("moe_shared"):
                    s = _dense_mlp(cfg, h2, blk["ws_in"], blk["ws_out"])
                    if cfg.shared_expert_gate:
                        s = (s.astype(jnp.float32) * jax.nn.sigmoid(jnp.einsum(
                            "btd,do->bto", h2, blk["ws_gate"].astype(h2.dtype),
                            preferred_element_type=jnp.float32))
                             ).astype(s.dtype)
                    m = m + s
            aux = 0.0
        elif cfg.n_experts:
            with jax.named_scope("moe_capacity"):
                m, aux = _moe_mlp(cfg, h2, blk["router"], blk["we_in"],
                                  blk["we_out"])
        else:
            with jax.named_scope("mlp"):
                m, aux = _dense_mlp(cfg, h2, blk["w_in"], blk["w_out"]), 0.0
        with jax.named_scope("resid_norm"):
            x = _residual(cfg, x, m, blk, 1)
        kv = None
        if return_kv:
            b, t = x.shape[0], x.shape[1]
            kv = (k.reshape(b, t, cfg.kv_heads, cfg.head_dim),
                  v.reshape(b, t, cfg.kv_heads, cfg.head_dim))
        return (x, state), (aux, kv, told)

    def of_kind(positions, window, mixer):
        fn = lambda c, blk: block(c, blk, positions, window, mixer)  # noqa: E731
        return fn if (return_kv or not cfg.remat) \
            else _remat_wrap(fn, cfg.remat_policy)

    kinds = cfg.layer_kinds
    blk_fns = [of_kind(*kind) for kind in kinds]
    period = len(blk_fns)
    if period == 1:     # a uniform stack keeps the scan it always had
        scan_body = blk_fns[0]
    else:
        # (L, ...) → (L / period, period, ...): one scan step is one period;
        # a leaf of one mixer's layers has as many rows a period as the
        # period has such layers
        periods = cfg.n_layers // period
        blocks = jax.tree_util.tree_map(
            lambda w: w.reshape(periods, w.shape[0] // periods, *w.shape[1:]),
            blocks)

        def layer(blks, i):
            """Layer i of the period: its mixer's leaves at its place among
            that mixer's layers, every layer's at i."""
            mixer = kinds[i][2]
            j = sum(kind[2] == mixer for kind in kinds[:i])
            return {name: w[i if _mixer_of(name) is None else j]
                    for name, w in blks.items()
                    if _mixer_of(name) in (None, mixer)}

        def scan_body(carry, blks):
            ys = []
            for i, blk_fn in enumerate(blk_fns):
                carry, y = blk_fn(carry, layer(blks, i))
                ys.append(y)
            return carry, jax.tree_util.tree_map(lambda *l: jnp.stack(l), *ys)

    state = None
    if cfg.router == "mlp":
        state = jnp.zeros((*x.shape[:2], cfg.router_hidden), jnp.float32)
    (x, _), (auxes, kvs, told) = lax.scan(scan_body, (x, state), blocks)
    if period > 1:      # (L / period, period, ...) → (L, ...)
        auxes, kvs, told = jax.tree_util.tree_map(
            lambda y: y.reshape(-1, *y.shape[2:]), (auxes, kvs, told))
    return x, auxes, kvs, told


def _run_stack(params, cfg: TransformerConfig, x):
    """The whole stack on embedded activations: the leading dense layers'
    group, where there is one, then the other layers'. Returns (x, auxes,
    what the held experts' layers tell or None) as :func:`_run_blocks`."""
    dense, rest, _ = _group_configs(cfg)
    if dense is not None:
        x = _run_blocks(params["dense_blocks"], dense, x)[0]
    x, auxes, _, told = _run_blocks(params["blocks"], rest, x)
    return x, auxes, told


def forward(params, cfg: TransformerConfig, ids, *, train=False, rng=None,
            pos_offset=0):
    """ids (B, T) int32 → logits (B, T, vocab). Returns (logits, aux_loss)."""
    x = embed(params, cfg, ids, pos_offset)
    x, auxes, _ = _run_stack(params, cfg, x)
    return head_logits(params, cfg, x), jnp.sum(auxes)


def _use_fused_loss(cfg: TransformerConfig, n_rows: int) -> bool:
    if cfg.fused_loss is True:
        return True
    if cfg.fused_loss is False:
        return False
    # "auto": fuse once the f32 logits tensor would exceed ~64 MB — below
    # that XLA's ordinary fusion handles it and chunking only adds scan
    # overhead
    return n_rows * cfg.vocab_size * 4 > 64 * 2 ** 20


def _chunk_terms(xc, tc, head, bias):
    """Of one chunk's float32 logits: ``exp(logits - row max)``, its row
    sums, and the per-row NLL ``log(sum) + max - target logit``."""
    logits = jnp.einsum("cd,dv->cv", xc, head).astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    top = logits.max(-1)
    e = jnp.exp(logits - top[:, None])
    total = e.sum(-1)
    tl = jnp.take_along_axis(logits, tc[:, None], -1)[:, 0]
    return e, total, jnp.log(total) + top - tl


@jax.custom_vjp
def _chunked_nll(xk, head, bias, wk, tk):
    """sum(w * nll) over (chunks, chunk, d) rows: one product a chunk."""
    def body(loss, sl):
        xc, tc, wc = sl
        with jax.named_scope("lm_head"):
            nll = _chunk_terms(xc, tc, head, bias)[2]
            return loss + (nll * wc).sum(), None

    return lax.scan(body, jnp.zeros((), jnp.float32), (xk, tk, wk),
                    reverse=True)[0]


def _chunked_nll_fwd(xk, head, bias, wk, tk):
    """The loss and, from the same logits, its whole gradient: per chunk
    ``p = (softmax - onehot) * w``, ``dx_c = p @ head^T`` (stacked) and
    ``dhead += x_c^T @ p`` (carried): three products a chunk. ``p`` is
    formed as ``jax.scipy.special.logsumexp``'s own gradient forms it,
    ``exp(logits - max) * (w / sum)``, in float32 before the cast, and the
    chunks run LAST TO FIRST, the order in which a backward scan sums them:
    a ``dhead`` carried in bf16 rounds by its order, and first to last read
    the untied head's gradient norm 1.5e-4 further from a float32 one on
    the chip."""
    def body(carry, sl):
        loss, dhead, dbias = carry
        xc, tc, wc = sl
        with jax.named_scope("lm_head"):
            e, total, nll = _chunk_terms(xc, tc, head, bias)
            hit = tc[:, None] == lax.broadcasted_iota(jnp.int32, e.shape, 1)
            p = e * (wc / total)[:, None] - jnp.where(hit, wc[:, None], 0.0)
            if bias is not None:
                dbias = dbias + p.sum(0).astype(dbias.dtype)
            p = p.astype(xc.dtype)
            dx = jnp.einsum("cv,dv->cd", p, head)
            dhead = dhead + jnp.einsum("cd,cv->dv", xc, p)
            return (loss + (nll * wc).sum(), dhead, dbias), (dx, nll)

    zeros = jax.tree_util.tree_map(jnp.zeros_like, (head, bias))
    (loss, dhead, dbias), (dx, nll) = lax.scan(
        body, (jnp.zeros((), jnp.float32), *zeros), (xk, tk, wk),
        reverse=True)
    return loss, (dx, dhead, dbias, nll)


def _chunked_nll_bwd(res, g):
    with jax.named_scope("lm_head"):
        dx, dhead, dbias, nll = jax.tree_util.tree_map(
            lambda r: (g * r).astype(r.dtype), res)
    return dx, dhead, dbias, nll, None      # integer targets: no cotangent


_chunked_nll.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


def _chunked_ce(x, head, targets, chunk, weights=None, bias=None):
    """WEIGHTED-SUM NLL of (N, d) hidden rows against (N,) targets WITHOUT
    materializing the (N, V) f32 logits: a scan over row chunks. Returns
    sum(w·nll) — the caller divides by its own denominator. ``weights``
    default to 1 per row; ``bias`` (V,) supports BERT's MLM output bias.

    There is no checkpoint (no remat) and no second loop for the backward: the
    loss is the last thing a forward does, so ``softmax - onehot`` is known
    the moment a chunk's logits are, and the forward rule of the
    ``custom_vjp`` forms ``dx`` and ``dhead`` from it in the same scan step.
    KEPT for the backward: ``dx`` (N, d), ``dhead`` (d, V) in ``head``'s
    dtype, ``dbias`` and the per-row NLL (``weights``' cotangent) — what
    the backward would hold anyway from its first moment — which it only
    scales by the scalar cotangent. Nothing V-wide per row is kept, and a
    chunk multiplies by the vocabulary three times (logits, dx, dhead), not
    four. A call that nothing differentiates runs one product a chunk."""
    n, d = x.shape
    chunk = min(chunk, n)
    pad = (-n) % chunk
    w = (jnp.ones((n,), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    targets = targets.astype(jnp.int32)
    if pad:     # pad rows carry weight 0
        x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)])
        targets = jnp.concatenate([targets, jnp.zeros((pad,), jnp.int32)])
        w = jnp.concatenate([w, jnp.zeros((pad,), jnp.float32)])
    return _chunked_nll(x.reshape(-1, chunk, d), head, bias,
                        w.reshape(-1, chunk), targets.reshape(-1, chunk))


def lm_loss(params, cfg: TransformerConfig, ids, targets, *, aux_weight=1e-2,
            pos_offset=0):
    return _lm_loss_stats(params, cfg, ids, targets, aux_weight=aux_weight,
                          pos_offset=pos_offset)[0]


def _lm_loss_stats(params, cfg: TransformerConfig, ids, targets, *,
                   aux_weight=1e-2, pos_offset=0):
    """(loss, what the step tells, or None): what the held experts' layers
    tell (see :func:`_run_blocks`) and, with a prediction module, its
    block's row after the stack's and ``"losses"``: float32 [the main loss,
    the predicted-token loss], apart."""
    b, t = ids.shape
    x = embed(params, cfg, ids, pos_offset)
    x, auxes, told = _run_stack(params, cfg, x)
    aux = jnp.sum(auxes)
    head = _resolve_head(params, cfg)
    fused = _use_fused_loss(cfg, b * t)

    def mean_nll(z, tgt, weights=None, rows=b * t):
        """Mean NLL of the normed (B, T, d) ``z`` over ``rows`` rows."""
        if fused:
            w = None if weights is None else weights.reshape(b * t)
            return _chunked_ce(z.reshape(b * t, -1), head.astype(z.dtype),
                               tgt.reshape(b * t), cfg.loss_chunk,
                               weights=w) / rows
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("btd,dv->btv", z, head.astype(z.dtype))
            logits = _constrain(logits, "dp", "sp", "tp").astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, tgt[..., None].astype(jnp.int32), -1)[..., 0]
            return (nll.mean() if weights is None
                    else (nll * weights).sum() / rows)

    with jax.named_scope("resid_norm"):
        z = _norm(cfg, x, params["ln_f"])
    main = mean_nll(z, targets)
    loss = main + aux_weight * aux
    if not cfg.predict_ahead:
        return loss, told
    with jax.named_scope("mtp"):
        m, module = params["mtp"], _group_configs(cfg)[2]
        # position i joins the stack's output (before the final norm) to the
        # embedding of the NEXT token, targets_i, and is scored on the token
        # after it, targets_{i+1}; the row's last position has none and
        # weighs 0 (it is computed, for the shapes' sake: causal attention
        # and per-token experts keep it from every other position)
        e = embed(params, cfg, targets, pos_offset)
        with jax.named_scope("resid_norm"):
            p = jnp.concatenate([_rmsnorm(x, m["ln_h"], cfg.norm_eps),
                                 _rmsnorm(e, m["ln_e"], cfg.norm_eps)], -1)
        p = jnp.einsum("btz,zd->btd", p, m["proj"].astype(p.dtype))
        p, _, _, told_m = _run_blocks(m["block"], module, p)
        after = jnp.roll(targets, -1, axis=1)
        has = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t))
        with jax.named_scope("resid_norm"):
            z = _rmsnorm(p, m["ln_f"], cfg.norm_eps)
        ahead = mean_nll(z, after, has.astype(jnp.float32),
                         rows=b * (t - 1))
    loss = loss + cfg.predict_weight * ahead
    if told is not None:    # the module's block counts as one more layer
        told = jax.tree_util.tree_map(
            lambda a, c: jnp.concatenate([a, c]), told, told_m)
    told = dict(told or {}, losses=jnp.stack([main, ahead]))
    return loss, told


def make_train_step(cfg: TransformerConfig, optimizer):
    """One jitted step: grads → optax update → new params. Shard via the
    caller's jit(in_shardings=...) or run as-is on one device. Returns
    (params, opt_state, loss) and, where the configuration holds a share of
    routed experts (``experts_held``), a fourth output, computed on the
    device beside the loss: ``{"load": the per-layer expert-load stats (L, 4
    or 5) float32 of :func:`_moe_share`, "moved": the rows its backward's
    row movement crossed, (L,) float32}`` (``obs.moe.record_expert_load``
    counts them) and ``"choices"``:
    the experts every token took in every layer, (L, K, B·T) int32. A choice
    is an argmax, and a tie within the compute dtype's rounding falls the
    other way in another precision: whoever compares the step with another
    computation of the same model hands it these choices, so that both
    differentiate one function. L counts the layers that route: not the
    leading dense ones, and a prediction module's block as one more, last.
    With such a module (``predict_ahead``) the fourth output also holds
    ``"losses"``: float32 [main, predicted-token], the two parts of the
    loss apart (``obs.lm.record_losses`` keeps them)."""

    def step(params, opt_state, ids, targets):
        (loss, told), grads = jax.value_and_grad(
            _lm_loss_stats, has_aux=True)(params, cfg, ids, targets)
        import optax as _optax
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = _optax.apply_updates(params, updates)
        if told is None:
            return params, opt_state, loss
        return params, opt_state, loss, told

    return step


def make_ring_train_step(cfg: TransformerConfig, optimizer, mesh: Mesh):
    """Training step with EXPLICIT ring sequence parallelism: the whole
    loss+grad runs under ``shard_map`` over the mesh's ('dp', 'sp') axes.
    Data (B, T) is sharded batch-over-dp and SEQUENCE-over-sp; params and
    optimizer state are replicated. Inside the mapped region
    `cfg.use_ring_attention` routes attention onto the ppermute ring
    (parallel/ring_attention.py — the (T,T) score matrix never exists on
    any one device), the position table is indexed at each shard's global
    offset, and loss/grads are pmean'd over both axes so the update is
    identical to a monolithic step up to float reassociation.

    Dense blocks only (MoE expert dispatch needs the 'ep' axis plumbing
    of the GSPMD path); requires cfg.use_ring_attention=True so the
    single-device fallback of `_attention` can never silently run full
    attention per shard."""
    if not cfg.use_ring_attention:
        raise ValueError("make_ring_train_step requires "
                         "cfg.use_ring_attention=True")
    if getattr(cfg, "n_experts", 0):
        raise NotImplementedError(
            "ring step is dense-only; MoE routes through the GSPMD path "
            "(make_train_step under jit with shardings_for)")
    import optax as _optax

    def local_step(params, opt_state, ids, targets):
        t_local = ids.shape[1]
        pos_offset = lax.axis_index("sp") * t_local

        def loss_fn(p):
            return lm_loss(p, cfg, ids, targets, pos_offset=pos_offset)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = lax.pmean(loss, ("dp", "sp"))
        grads = lax.pmean(grads, ("dp", "sp"))
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = _optax.apply_updates(params, updates)
        return params, opt_state, loss

    def step(params, opt_state, ids, targets):
        # dynamic_slice would silently CLAMP an out-of-table position
        # offset (shards would reuse the last rows instead of failing the
        # way the monolithic path does) — reject at trace time instead
        if ids.shape[1] > cfg.max_seq:
            raise ValueError(
                f"global sequence length {ids.shape[1]} exceeds "
                f"cfg.max_seq={cfg.max_seq}: position offsets past the "
                "table would clamp, not error")
        rep = jax.tree_util.tree_map(lambda _: P(), params)
        rep_opt = jax.tree_util.tree_map(lambda _: P(), opt_state)
        return jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(rep, rep_opt, P("dp", "sp"), P("dp", "sp")),
            out_specs=(rep, rep_opt, P()),
            check_vma=False,  # optax update replication is data-dependent
        )(params, opt_state, ids, targets)

    return jax.jit(step, donate_argnums=(0, 1))


# ------------------------------------------------------------- BERT family

@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_seq: int = 512
    type_vocab: int = 2
    num_labels: int = 2
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # r5: the transformer-LM sweep's two HBM cuts, applied to the encoder
    # (VERDICT r4 item 5). Defaults off = r4 behavior; bench flips both.
    remat: bool = False
    # "full" | "dots" | "dots_no_batch" | "save_attn" (keep what attention
    # produced, recompute the rest — see _remat_wrap)
    remat_policy: str = "full"
    attn_scores_bf16: bool = False


def bert_init(key, cfg: BertConfig):
    """BERT-base encoder (reference: SameDiff TF-import BERT path —
    BASELINE.json config 4). Bidirectional attention, learned positions,
    pooler + classification head for fine-tune."""
    k = jax.random.split(key, 8)
    d, f, h, L = cfg.d_model, cfg.d_ff, cfg.d_model, cfg.n_layers

    def norm(key, shape, fan_in):
        return jax.random.normal(key, shape, cfg.param_dtype) / math.sqrt(fan_in)

    return {
        "embed": norm(k[0], (cfg.vocab_size, d), d),
        "pos_embed": 0.02 * jax.random.normal(k[1], (cfg.max_seq, d), cfg.param_dtype),
        "type_embed": 0.02 * jax.random.normal(k[2], (cfg.type_vocab, d), cfg.param_dtype),
        "blocks": {
            "ln1": jnp.ones((L, d), cfg.param_dtype),
            "wqkv": norm(k[3], (L, d, 3 * h), d),
            "wo": norm(k[4], (L, h, d), h),
            "ln2": jnp.ones((L, d), cfg.param_dtype),
            "w_in": norm(k[5], (L, d, f), d),
            "w_out": norm(k[6], (L, f, d), f),
        },
        "pooler": norm(k[7], (d, d), d),
        "cls": jnp.zeros((d, cfg.num_labels), cfg.param_dtype),
        # MLM head: transform dense + norm scale + decoder bias; the decoder
        # weight is TIED to the token embedding (upstream BERT convention —
        # reference: BertIterator MLM pretraining task, SURVEY §2.7).
        "mlm_dense": norm(jax.random.fold_in(k[7], 1), (d, d), d),
        "mlm_ln": jnp.ones((d,), cfg.param_dtype),
        "mlm_bias": jnp.zeros((cfg.vocab_size,), cfg.param_dtype),
    }


def bert_forward(params, cfg: BertConfig, ids, type_ids=None, attn_mask=None):
    b, t = ids.shape
    x = jnp.take(params["embed"], ids, axis=0).astype(cfg.dtype)
    x = x + params["pos_embed"][:t].astype(cfg.dtype)
    if type_ids is not None:
        x = x + jnp.take(params["type_embed"], type_ids, axis=0).astype(cfg.dtype)
    nh = cfg.n_heads
    hd = cfg.d_model // nh
    bias = None
    if attn_mask is not None:
        bias = jnp.where(attn_mask[:, None, None, :] > 0, 0.0, -1e9).astype(jnp.float32)

    def block(x, blk):
        h = _rmsnorm(x, blk["ln1"])
        qkv = jnp.einsum("btd,dz->btz", h, blk["wqkv"].astype(h.dtype))
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, nh, hd)
        k = k.reshape(b, t, nh, hd)
        v = v.reshape(b, t, nh, hd)
        if cfg.attn_scores_bf16 and q.dtype == jnp.bfloat16:
            a = _xla_attention_bf16_scores(q, k, v, causal=False, bias=bias
                                           ).reshape(b, t, nh * hd)
        else:
            kw = {}
            if bias is not None:
                kw["bias"] = jnp.broadcast_to(bias, (b, nh, t, t))
            a = jax.nn.dot_product_attention(q, k, v, **kw
                                             ).reshape(b, t, nh * hd)
        a = checkpoint_name(a, "attn_out")  # remat_policy="save_attn" hook
        x = x + jnp.einsum("bth,hd->btd", a, blk["wo"].astype(h.dtype))
        h2 = _rmsnorm(x, blk["ln2"])
        m = jnp.einsum("btf,fd->btd",
                       jax.nn.gelu(jnp.einsum("btd,df->btf", h2,
                                              blk["w_in"].astype(h2.dtype))),
                       blk["w_out"].astype(h2.dtype))
        return x + m, 0.0

    if cfg.remat:
        block = _remat_wrap(block, cfg.remat_policy)
    x, _ = lax.scan(block, x, params["blocks"])
    pooled = jnp.tanh(x[:, 0] @ params["pooler"].astype(x.dtype))
    logits = pooled @ params["cls"].astype(x.dtype)
    return logits.astype(jnp.float32), x


def bert_classifier_loss(params, cfg: BertConfig, ids, labels, type_ids=None,
                         attn_mask=None):
    """labels: integer class ids (B,) or one-hot (B, num_labels) — the
    latter is what BertIterator emits (reference MultiDataSet contract)."""
    logits, _ = bert_forward(params, cfg, ids, type_ids, attn_mask)
    logp = jax.nn.log_softmax(logits, -1)
    if labels.ndim == 2:
        return -(logp * labels.astype(logp.dtype)).sum(-1).mean()
    return -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), -1).mean()


# ---------------------------------------------------------------- BERT MLM
def bert_mlm_logits(params, cfg: BertConfig, hidden):
    """MLM decoder over final hidden states: dense+gelu+norm, then project
    onto the TIED token embedding + bias. (B, T, vocab) float32 logits."""
    h = jax.nn.gelu(hidden @ params["mlm_dense"].astype(hidden.dtype))
    h = _rmsnorm(h, params["mlm_ln"])
    logits = jnp.einsum("btd,vd->btv", h, params["embed"].astype(h.dtype))
    return (logits + params["mlm_bias"].astype(logits.dtype)).astype(jnp.float32)


def bert_mask_tokens(key, ids, cfg: BertConfig, mask_token_id,
                     mask_prob: float = 0.15, special_mask=None):
    """Standard BERT masking (80% [MASK] / 10% random / 10% keep).

    Returns (masked_ids, labels, weights): `labels` are the original ids,
    `weights` 1.0 at selected positions. jit-friendly (static shapes, no
    data-dependent control flow). `special_mask` (B, T) bool marks positions
    never selected (CLS/SEP/PAD).
    """
    k_sel, k_op, k_rand = jax.random.split(key, 3)
    sel = jax.random.uniform(k_sel, ids.shape) < mask_prob
    if special_mask is not None:
        sel = jnp.logical_and(sel, jnp.logical_not(special_mask))
    op = jax.random.uniform(k_op, ids.shape)
    rand_ids = jax.random.randint(k_rand, ids.shape, 0, cfg.vocab_size)
    masked = jnp.where(op < 0.8, mask_token_id,
                       jnp.where(op < 0.9, rand_ids, ids))
    masked_ids = jnp.where(sel, masked, ids)
    return masked_ids, ids, sel.astype(jnp.float32)


def bert_mlm_loss(params, cfg: BertConfig, masked_ids, labels, weights,
                  type_ids=None, attn_mask=None, fused: bool = True):
    """Weighted cross-entropy over masked positions only. ``fused`` routes
    through the chunked CE (no (B, T, V) f32 logits materialized — the MLM
    decoder's dense+norm runs full-size, only the vocab projection is
    chunked)."""
    _, hidden = bert_forward(params, cfg, masked_ids, type_ids, attn_mask)
    denom = jnp.maximum(weights.sum(), 1.0)
    if fused:
        h = jax.nn.gelu(hidden @ params["mlm_dense"].astype(hidden.dtype))
        h = _rmsnorm(h, params["mlm_ln"])
        b, t, d = h.shape
        total = _chunked_ce(
            h.reshape(b * t, d), params["embed"].T.astype(h.dtype),
            labels.reshape(b * t), 1024,
            weights=weights.reshape(b * t), bias=params["mlm_bias"])
        return total / denom
    logp = jax.nn.log_softmax(bert_mlm_logits(params, cfg, hidden), -1)
    nll = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                               -1)[..., 0]
    return (nll * weights).sum() / denom


def make_bert_mlm_train_step(cfg: BertConfig, optimizer, mask_token_id,
                             mask_prob: float = 0.15, special_ids=None):
    """Jittable MLM pretrain step: (params, opt_state, rng, ids) ->
    (params, opt_state, rng, loss). Masking happens on-device inside jit.
    `special_ids` (e.g. PAD/CLS/SEP ids) are never selected as MLM targets;
    pass `attn_mask` so attention ignores padding (BertIterator provides
    both)."""
    import optax

    specials = (None if special_ids is None
                else jnp.asarray(list(special_ids), jnp.int32))

    def step(params, opt_state, rng, ids, type_ids=None, attn_mask=None):
        rng, sub = jax.random.split(rng)
        special_mask = (None if specials is None
                        else jnp.isin(ids, specials))
        masked_ids, labels, weights = bert_mask_tokens(
            sub, ids, cfg, mask_token_id, mask_prob,
            special_mask=special_mask)
        loss, grads = jax.value_and_grad(bert_mlm_loss)(
            params, cfg, masked_ids, labels, weights, type_ids, attn_mask)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, rng, loss

    return step
