"""Driver "lm_train": the jitted, donated ``zoo.transformer.make_train_step``
fed from a host pool of token batches.

``setup`` makes the weights on the device from the seed (the reference's
draw, handed to the program), builds ONE step object with its state and
drives it through its first ``check_steps`` steps by the window's own call
and feed, keeping the readings ``check`` compares; ``window`` goes on from
that same state until the deadline; ``check`` frees the program's state and
follows the same first steps with the plain reference.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from drivers._training import State, change_norms, first_gradient_norms
from reference import lm as ref
from reference import lowprec


def program_config(config: dict):
    """The repo's TransformerConfig for a configuration file of family lm."""
    from deeplearning4j_tpu.zoo import transformer as tfm
    sz = ref.sizes_of(config)
    knobs = {k: v for k, v in config["program"].items() if k != "entry"}
    return tfm.TransformerConfig(
        vocab_size=sz["vocab"], d_model=sz["d"], n_heads=sz["heads"],
        n_layers=sz["layers"], d_ff=sz["ff"], max_seq=sz["positions"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["param_dtype"]), **knobs)


def build_step(cfg, config: dict):
    """The timed program: (params, opt_state, ids, targets) ->
    (params, opt_state, loss). Tests plant faults by replacing this."""
    import optax

    from deeplearning4j_tpu.zoo import transformer as tfm
    hp = config["optimizer"]
    opt = optax.adamw(hp["learning_rate"], b1=hp["b1"], b2=hp["b2"],
                      eps=hp["eps"], weight_decay=hp["weight_decay"])
    return opt, jax.jit(tfm.make_train_step(cfg, opt), donate_argnums=(0, 1))


def _feed(st, i, probe):
    """Host pool -> device, as a loader would: batch i of the pool."""
    with probe.span("next_batch"):
        j = i % st.ids.shape[0]
        return jax.device_put(st.ids[j]), jax.device_put(st.tgt[j])


def _step(st, i, probe):
    ids, tgt = _feed(st, i, probe)
    with probe.span("step_dispatch"):
        st.params, st.opt_state, st.loss = st.step(st.params, st.opt_state,
                                                   ids, tgt)
    st.steps_done = i + 1


def _fetch(st, probe):
    with probe.span("loss_fetch"):
        return float(st.loss)


def setup(config: dict, traffic: dict, seed: int, probe) -> State:
    st = State()
    st.config, st.traffic, st.seed = config, traffic, seed
    sz = ref.sizes_of(config)
    st.batch, st.seq = int(traffic["batch"]), int(traffic["seq"])
    if st.seq > sz["positions"]:
        raise ValueError("traffic seq exceeds the configuration's positions")
    st.ids, st.tgt = ref.make_batches(seed, int(traffic["pool_batches"]),
                                      st.batch, st.seq, sz["vocab"])
    st.params = ref.make_weights(seed, sz)
    opt, st.step = build_step(program_config(config), config)
    st.opt_state = opt.init(st.params)
    n = int(traffic["check_steps"])
    losses, grad_norms = [], None
    for i in range(n):
        _step(st, i, probe)
        losses.append(_fetch(st, probe))
        if i == 0:
            grad_norms = first_gradient_norms(st.opt_state,
                                              config["optimizer"]["b1"])
    st.readings = {"losses": losses, "grad_norms": grad_norms,
                   "delta_norms": change_norms(
                       st.params, ref.make_weights(seed, sz))}
    jax.block_until_ready(st.params)
    return st


def window(st: State, seconds: float, probe) -> dict:
    every = int(st.traffic["loss_fetch_every"])
    first = st.steps_done
    t0 = time.perf_counter()
    deadline = t0 + seconds
    i = first
    while time.perf_counter() < deadline:
        _step(st, i, probe)
        i += 1
        if (i - first) % every == 0:
            _fetch(st, probe)
            probe.at_sync(i - first)
    last = _fetch(st, probe)
    elapsed = time.perf_counter() - t0
    steps = i - first
    return {"steps": steps, "tokens": steps * st.batch * st.seq,
            "window_s": elapsed, "last_loss": last,
            "attempted": steps, "failed": 0}


def release(st: State):
    """Drop the program's state so that its device memory is free."""
    for name in ("params", "opt_state", "step", "loss"):
        setattr(st, name, None)


def reference_readings(st: State, product=None, rows=None) -> dict:
    """The plain reference over the steps ``setup`` followed; ``product`` and
    ``rows`` are the control's and the half-batch fault's hooks."""
    n = len(st.readings["losses"])
    kw = {} if product is None else {"product": product}
    return ref.train_steps(st.seed, st.config, st.ids[:n], st.tgt[:n], n,
                           rows=rows, **kw)


#: the control's precision: the nearest under the bf16 the configuration states
CONTROL_PRODUCT = lowprec.FP8


def check(st: State) -> dict:
    """Free the program's state, follow the first steps with the reference,
    return the gaps."""
    import compare
    release(st)
    return compare.training_gaps(st.readings, reference_readings(st))
