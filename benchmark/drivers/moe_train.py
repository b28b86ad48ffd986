"""Driver "moe_train": ``zoo.transformer.make_train_step`` for a
configuration that holds one chip's share of a sparse-expert decoder
(family "smallthinker"), fed from a host pool of token batches as
``lm_train`` feeds its step.

The step hands back, beside the loss, the per-layer expert-load numbers it
computed on the device; they are fetched where the loss is fetched (one
blocking fetch for both, every ``loss_fetch_every`` steps) and counted into
the program's registry by ``obs.moe.record_expert_load``, so the counters
hold the FETCHED steps' assignments, not every step's.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp

from drivers._training import State, change_norms, first_gradient_norms
from drivers.lm_train import CONTROL_PRODUCT, _feed, build_step  # noqa: F401
from reference import smallthinker as ref


def program_config(config: dict):
    """The repo's TransformerConfig for a configuration file of family
    smallthinker: the file's keys say what the block is, ``program`` how the
    step is run (fused loss, remat)."""
    from deeplearning4j_tpu.zoo import transformer as tfm
    sz = ref.sizes_of(config)
    knobs = {k: v for k, v in config["program"].items() if k != "entry"}
    return tfm.TransformerConfig(
        vocab_size=sz["vocab"], d_model=sz["d"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], head_size=sz["head_dim"],
        n_layers=sz["layers"], d_ff=sz["ff"], max_seq=sz["positions"],
        layer_positions=tuple("rope" if r else "none" for r in sz["rope"]),
        layer_windows=tuple(sz["window"] if w else 0 for w in sz["windowed"]),
        rope_theta=sz["theta"], embed_scale=False, mlp="reglu",
        n_experts=sz["experts"], expert_top_k=sz["top_k"],
        experts_held=(sz["first"], sz["held"]), router_input="pre_attention",
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["param_dtype"]), **knobs)


def _step(st, i, probe):
    ids, tgt = _feed(st, i, probe)
    with probe.span("step_dispatch"):
        st.params, st.opt_state, st.loss, st.load = st.step(
            st.params, st.opt_state, ids, tgt)
    st.steps_done = i + 1


def _fetch(st, probe):
    """The loss and the expert-load numbers of the newest step, in one
    blocking fetch; the numbers go to the program's counters."""
    from deeplearning4j_tpu.obs.moe import record_expert_load
    with probe.span("loss_fetch"):
        loss, load = jax.device_get((st.loss, st.load))
    st.last_load = record_expert_load(load)
    return float(loss)


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
    opt, st.step = build_step(cfg, config)
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
    from deeplearning4j_tpu.obs import get_registry
    every = int(st.traffic["loss_fetch_every"])
    first = st.steps_done
    reg = get_registry()
    dropped0 = reg.get("dl4j_moe_dropped_total").value()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    i = first
    seen = []       # the fetched steps' (worst layer's load, local share)
    while time.perf_counter() < deadline:
        _step(st, i, probe)
        i += 1
        if (i - first) % every == 0:
            _fetch(st, probe)
            seen.append(st.last_load)
            probe.at_sync(i - first)
    last = _fetch(st, probe)
    elapsed = time.perf_counter() - t0
    steps = i - first
    seen.append(st.last_load)
    print("expert load by fetch, max over mean: " + " ".join(
        f"{s['max_over_mean']:.3f}" for s in seen) + "; local share: "
        + " ".join(f"{s['local_share']:.4f}" for s in seen), file=sys.stderr)
    return {"steps": steps, "tokens": steps * st.batch * st.seq,
            "window_s": elapsed, "last_loss": last,
            "attempted": steps, "failed": 0,
            # of the fetched steps of the window, over all layers
            "moe_dropped": reg.get("dl4j_moe_dropped_total").value() - dropped0,
            "moe_load_max_over_mean": max(s["max_over_mean"] for s in seen),
            "moe_local_share": sum(s["local_share"] for s in seen) / len(seen)}


def release(st: State):
    """Drop the program's state so that its device memory is free."""
    for name in ("params", "opt_state", "step", "loss", "load"):
        setattr(st, name, None)


def reference_readings(st: State, product=None, rows=None) -> dict:
    """The plain reference over the steps ``setup`` followed; ``product`` and
    ``rows`` are the control's and the half-batch fault's hooks."""
    n = len(st.readings["losses"])
    kw = {} if product is None else {"product": product}
    return ref.train_steps(st.seed, st.config, st.ids[:n], st.tgt[:n], n,
                           rows=rows, **kw)


def check(st: State) -> dict:
    """Free the program's state, follow the first steps with the reference,
    return the gaps."""
    import compare
    release(st)
    return compare.training_gaps(st.readings, reference_readings(st))
