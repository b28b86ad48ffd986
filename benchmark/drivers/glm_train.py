"""Driver "glm_train": ``zoo.transformer.make_train_step`` for a
configuration that holds one chip's share of a GLM-4.7-Flash-style decoder
(family "glm_lite": latent attention, a leading dense layer before
sigmoid-routed experts with a shared one, a prediction module behind the
shared head), stepped, fetched and timed by ``moe_train``'s own step, fetch
and window: the loss and what the step tells (its expert layers' rows of
four, the prediction module's block as one more layer; the experts every
token took; the main and the predicted-token loss apart) together, every
``loss_fetch_every`` steps, the rows counted by
``obs.moe.record_expert_load``. The two losses go to ``obs.lm.record_losses``
from here: after each of set-up's fetches and after the window's last.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from drivers._training import State, first_gradient_norms
from drivers.lm_train import CONTROL_PRODUCT, build_step  # noqa: F401
from drivers import moe_train
from drivers.moe_train import _step, release  # noqa: F401
from drivers.zaya_train import _host_leaves
from reference import glm_lite as ref


def program_config(config: dict):
    """The repo's TransformerConfig for a configuration file of family
    glm_lite: the file's keys say what the model is, ``program`` how the
    step is run (fused loss, remat)."""
    from deeplearning4j_tpu.zoo import transformer as tfm
    sz = ref.sizes_of(config)
    knobs = {k: v for k, v in config["program"].items() if k != "entry"}
    return tfm.TransformerConfig(
        vocab_size=sz["vocab"], d_model=sz["d"], n_heads=sz["heads"],
        n_layers=sz["layers"], d_ff=sz["ff"], max_seq=sz["positions"],
        attention="mla", q_rank=sz["q_rank"], kv_rank=sz["kv_rank"],
        nope_head_size=sz["nope"], rope_head_size=sz["rope"],
        v_head_size=sz["vd"], layer_positions=("rope",), layer_windows=(0,),
        rope_theta=sz["theta"], norm_eps=sz["eps"], embed_scale=False,
        mlp="swiglu", dense_layers=sz["dense"], expert_ff=sz["expert_ff"],
        shared_experts=sz["shared"], n_experts=sz["experts"],
        expert_top_k=sz["top_k"], experts_held=(sz["first"], sz["held"]),
        router="sigmoid", router_scale=sz["scale"],
        predict_ahead=sz["predict"], predict_weight=sz["predict_weight"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["param_dtype"]), **knobs)


def _fetch(st, probe) -> float:
    """``moe_train``'s fetch (the loss; the expert load to its counters), and
    the same step's two losses to the gauges."""
    from deeplearning4j_tpu.obs.lm import record_losses
    loss = moe_train._fetch(st, probe)
    st.last_losses = record_losses(jax.device_get(st.load["losses"]))
    return loss


def window(st: State, seconds: float, probe) -> dict:
    """``moe_train``'s window as it is (its fetches read the loss and the
    expert load); the gauges take the two losses of its last step."""
    from deeplearning4j_tpu.obs.lm import record_losses
    counters = moe_train.window(st, seconds, probe)
    record_losses(jax.device_get(st.load["losses"]))
    return counters


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
    losses, ahead, grad_norms, st.took = [], [], None, []
    for i in range(n):
        _step(st, i, probe)
        losses.append(_fetch(st, probe))
        ahead.append(st.last_losses["mtp"])
        # the experts every token took: (routing layers, K, batch, seq)
        took = np.asarray(st.load["choices"])
        st.took.append(took.reshape(*took.shape[:2], st.batch, st.seq))
        if i == 0:
            grad_norms = first_gradient_norms(st.opt_state,
                                              config["optimizer"]["b1"])
    now = _host_leaves(st.params)
    st.readings = {"losses": losses, "mtp_losses": ahead,
                   "grad_norms": grad_norms,
                   "delta_norms": {k: float(np.sqrt(np.sum(np.square(
                       now[k].astype(np.float64) - start[k]))))
                       for k in start}}
    return st


def reference_readings(st: State, product=None, rows=None, handed=True,
                       predict_weight=None) -> dict:
    """The plain reference over the steps ``setup`` followed, HANDED the
    experts the program's tokens took in those steps (``handed=False``: left
    to its own top-k), so that both differentiate one function; ``product``
    and ``rows`` are the control's and the half-batch fault's hooks,
    ``predict_weight=0`` this model's own fault: the prediction loss left
    out. Beside the readings, ``choice_mismatch``: the share of those steps'
    assignments that the reference's own top-k lacks."""
    n = len(st.readings["losses"])
    kw = {} if product is None else {"product": product}
    return ref.train_steps(st.seed, st.config, st.ids[:n], st.tgt[:n], n,
                           rows=rows, choices=st.took if handed else None,
                           predict_weight=predict_weight, **kw)


def gaps_of(got: dict, want: dict) -> dict:
    """The training cells' own gaps of ``got`` against the reference's
    readings ``want``, the predicted-token loss's at the first step beside
    the whole loss's, and ``choice_mismatch_share``, which holds the
    program's routing to the reference's: the share of the program's
    assignments that the reference's own top-k lacks (where ``got`` is itself
    a reference handed them, the control or a fault: that ``got``'s own
    top-k)."""
    import compare
    gaps = compare.training_gaps(got, want)
    gaps["mtp_loss_gap_step1"] = abs(
        got["mtp_losses"][0] - want["mtp_losses"][0]) / want["mtp_losses"][0]
    gaps["choice_mismatch_share"] = got.get("choice_mismatch",
                                            want["choice_mismatch"])
    return gaps


def check(st: State) -> dict:
    """Free the program's state, follow the first steps with the reference,
    return the gaps."""
    release(st)
    return gaps_of(st.readings, reference_readings(st))
