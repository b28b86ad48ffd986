"""Driver "zaya_train": ``zoo.transformer.make_train_step`` for a
configuration that holds one chip's share of a ZAYA1-style decoder (family
"zaya": compressed convolutional attention, a top-1 expert layer behind an
mlp router with a skip), stepped, fetched and timed by ``moe_train``'s own
step, fetch and window: the loss and what the step tells of its expert layers
(rows of five, the fifth the tokens that took the skip, and the expert every
token took) together, every ``loss_fetch_every`` steps, counted by
``obs.moe.record_expert_load``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from drivers._training import State, first_gradient_norms
from drivers.lm_train import CONTROL_PRODUCT, build_step  # noqa: F401
from drivers.moe_train import _fetch, _step, release, window  # noqa: F401
from reference import zaya as ref


def program_config(config: dict):
    """The repo's TransformerConfig for a configuration file of family
    zaya: the file's keys say what the block is, ``program`` how the step is
    run (fused loss, remat)."""
    from deeplearning4j_tpu.zoo import transformer as tfm
    sz = ref.sizes_of(config)
    knobs = {k: v for k, v in config["program"].items() if k != "entry"}
    return tfm.TransformerConfig(
        vocab_size=sz["vocab"], d_model=sz["d"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], head_size=sz["head_dim"],
        n_layers=sz["layers"], d_ff=sz["ff"], max_seq=sz["positions"],
        layer_positions=("rope",), layer_windows=(0,),
        rope_theta=sz["theta"], rotary_share=sz["rotary"] / sz["head_dim"],
        attention="cca", cca_taps=sz["taps"], norm_eps=sz["eps"],
        embed_scale=False, mlp="swiglu", scaled_residuals=True,
        n_experts=sz["experts"], expert_top_k=sz["top_k"],
        experts_held=(sz["first"], sz["held"]), router="mlp",
        router_hidden=sz["router_hidden"], router_skip=True,
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["param_dtype"]), **knobs)


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
    # the start, for the parameters' change, waits on the HOST: a second copy
    # on the device (2.8 GB) would set the peak of live buffers in set-up,
    # 2.8 GB over anything the window holds
    start = _host_leaves(st.params)
    opt, st.step = build_step(cfg, config)
    # trace the step while the weights alone are live: in a fresh checkout
    # the flash kernels' block race runs at trace time, and its 0.9 GB of
    # scratch would lie on top of the optimizer's state (the reported peak)
    jax.eval_shape(st.step, st.params, jax.eval_shape(opt.init, st.params),
                   st.ids[0], st.tgt[0])
    st.opt_state = opt.init(st.params)
    n = int(traffic["check_steps"])
    losses, grad_norms, st.took = [], None, []
    for i in range(n):
        _step(st, i, probe)
        losses.append(_fetch(st, probe))
        # the experts every token took: (layers, batch, seq) on the host
        st.took.append(np.asarray(st.load["choices"]).reshape(
            -1, st.batch, st.seq))
        if i == 0:
            grad_norms = first_gradient_norms(st.opt_state,
                                              config["optimizer"]["b1"])
    now = _host_leaves(st.params)
    st.readings = {"losses": losses, "grad_norms": grad_norms,
                   "delta_norms": {k: float(np.sqrt(np.sum(np.square(
                       now[k].astype(np.float64) - start[k]))))
                       for k in start}}
    return st


def _host_leaves(tree) -> dict:
    """{leaf path: numpy array}, named as ``reference.common.leaf_norms``
    names them."""
    return {jax.tree_util.keystr(p): np.asarray(x) for p, x in
            jax.tree_util.tree_leaves_with_path(jax.device_get(tree))}


def reference_readings(st: State, product=None, rows=None,
                       handed=True) -> dict:
    """The plain reference over the steps ``setup`` followed, HANDED the
    experts the program's tokens took in those steps (``handed=False``: left
    to its own argmax), so that both differentiate one function; ``product``
    and ``rows`` are the control's and the half-batch fault's hooks. Beside
    the readings, ``choice_mismatch``: the share of those steps' choices
    that the reference's own argmax would have made otherwise."""
    n = len(st.readings["losses"])
    kw = {} if product is None else {"product": product}
    return ref.train_steps(st.seed, st.config, st.ids[:n], st.tgt[:n], n,
                           rows=rows, choices=st.took if handed else None,
                           **kw)


def check(st: State) -> dict:
    """Free the program's state, follow the first steps with the reference,
    return the gaps: the training cells' own and ``choice_mismatch_share``,
    which holds the program's routing to the reference's now that the
    gradients no longer do."""
    import compare
    release(st)
    want = reference_readings(st)
    gaps = compare.training_gaps(st.readings, want)
    gaps["choice_mismatch_share"] = want["choice_mismatch"]
    return gaps
