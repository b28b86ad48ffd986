"""Driver "qwen3next_train": ``zoo.transformer.make_train_step`` for a
configuration that holds one chip's share of a Qwen3-Next-style decoder
(family "qwen3_next": three gated DeltaNet layers to a gated attention layer,
softmax-routed experts with a gated shared one), stepped, fetched and timed
by ``moe_train``'s own step, fetch and window: the loss and what the step
tells (its expert layers' rows of four and the experts every token took)
together, every ``loss_fetch_every`` steps, the rows counted by
``obs.moe.record_expert_load``; the choices go to the reference, since a
top-10 of 512 ties within bf16's rounding as any top-k does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from drivers._training import State, first_gradient_norms
from drivers.lm_train import CONTROL_PRODUCT  # noqa: F401
from drivers.moe_train import _fetch, _step, release, window  # noqa: F401
from drivers.zaya_train import _host_leaves
from reference import qwen3_next as ref


def program_config(config: dict):
    """The repo's TransformerConfig for a configuration file of family
    qwen3_next: the file's keys say what the model is, ``program`` how the
    step is run (fused loss, remat)."""
    from deeplearning4j_tpu.zoo import transformer as tfm
    sz = ref.sizes_of(config)
    knobs = {k: v for k, v in config["program"].items() if k != "entry"}
    period = sz["interval"]
    return tfm.TransformerConfig(
        vocab_size=sz["vocab"], d_model=sz["d"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], head_size=sz["head_dim"],
        n_layers=sz["layers"], d_ff=sz["ff"], max_seq=sz["positions"],
        layer_mixers=("gated_deltanet",) * (period - 1) + ("attention",),
        layer_positions=("none",) * (period - 1) + ("rope",),
        layer_windows=(0,) * period, rope_theta=sz["theta"],
        rotary_share=sz["rotary"] / sz["head_dim"], norm_eps=sz["eps"],
        norm_zero_centred=True, qk_norm=True, attn_output_gate=True,
        gdn_key_heads=sz["key_heads"], gdn_value_heads=sz["value_heads"],
        gdn_key_size=sz["key_size"], gdn_value_size=sz["value_size"],
        gdn_conv_taps=sz["taps"], embed_scale=False, mlp="swiglu",
        n_experts=sz["experts"], expert_top_k=sz["top_k"],
        experts_held=(sz["first"], sz["held"]), expert_ff=sz["expert_ff"],
        shared_experts=sz["shared"], shared_expert_gate=True,
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["param_dtype"]), **knobs)


def build_step(cfg, config: dict):
    """The timed program: (params, opt_state, ids, targets) -> (params,
    opt_state, loss, what the step tells, the choices among it). Tests plant
    faults by replacing this."""
    import optax

    from deeplearning4j_tpu.zoo import transformer as tfm
    hp = config["optimizer"]
    opt = optax.adamw(hp["learning_rate"], b1=hp["b1"], b2=hp["b2"],
                      eps=hp["eps"], weight_decay=hp["weight_decay"])
    return opt, jax.jit(tfm.make_train_step(cfg, opt), donate_argnums=(0, 1))


def setup(config: dict, traffic: dict, seed: int, probe) -> State:
    st = State()
    st.config, st.traffic, st.seed = config, traffic, seed
    cfg = program_config(config)    # first: a program without these fields
    sz = ref.sizes_of(config)       # fails here, before anything is drawn
    st.batch, st.seq = int(traffic["batch"]), int(traffic["seq"])
    if st.seq > sz["positions"]:
        raise ValueError("traffic seq exceeds the configuration's positions")
    st.ids, st.tgt = ref.make_batches(seed, int(traffic["pool_batches"]),
                                      st.batch, st.seq, sz["vocab"])
    st.params = ref.make_weights(seed, sz)
    # as zaya_train: the start waits on the host, and the step is traced
    # (the flash block race runs then) while the weights alone are live
    start = _host_leaves(st.params)
    opt, st.step = build_step(cfg, config)
    jax.eval_shape(st.step, st.params, jax.eval_shape(opt.init, st.params),
                   st.ids[0], st.tgt[0])
    st.opt_state = opt.init(st.params)
    n = int(traffic["check_steps"])
    losses, grad_norms, st.took = [], None, []
    for i in range(n):
        _step(st, i, probe)
        losses.append(_fetch(st, probe))
        # the experts every token took: (layers, K, batch, seq)
        took = np.asarray(st.load["choices"])
        st.took.append(took.reshape(*took.shape[:2], st.batch, st.seq))
        if i == 0:
            grad_norms = first_gradient_norms(st.opt_state,
                                              config["optimizer"]["b1"])
    now = _host_leaves(st.params)
    st.readings = {"losses": losses, "grad_norms": grad_norms,
                   "delta_norms": {k: float(np.sqrt(np.sum(np.square(
                       now[k].astype(np.float64) - start[k]))))
                       for k in start}}
    return st


def reference_readings(st: State, product=None, rows=None, handed=True,
                       fault=None) -> dict:
    """The plain reference over the steps ``setup`` followed, HANDED the
    experts the program's tokens took in those steps (``handed=False``: left
    to its own top-k), so that both differentiate one function; ``product``
    and ``rows`` are the control's and the half-batch fault's hooks,
    ``fault`` one of this model's own (``reference.qwen3_next.FAULTS``).
    Beside the readings, ``choice_mismatch``: the share of those steps'
    assignments that the reference's own top-k lacks."""
    n = len(st.readings["losses"])
    kw = {} if product is None else {"product": product}
    return ref.train_steps(st.seed, st.config, st.ids[:n], st.tgt[:n], n,
                           rows=rows, choices=st.took if handed else None,
                           fault=fault, **kw)


def gaps_of(got: dict, want: dict) -> dict:
    """The training cells' own gaps of ``got`` against the reference's
    readings ``want`` and ``choice_mismatch_share``, which holds the
    program's routing to the reference's: the share of the program's
    assignments that the reference's own top-k lacks (where ``got`` is itself
    a reference handed them, the control or a fault: that ``got``'s own
    top-k)."""
    import compare
    gaps = compare.training_gaps(got, want)
    gaps["choice_mismatch_share"] = got.get("choice_mismatch",
                                            want["choice_mismatch"])
    return gaps


def check(st: State) -> dict:
    """Free the program's state, follow the first steps with the reference,
    return the gaps."""
    release(st)
    return gaps_of(st.readings, reference_readings(st))
