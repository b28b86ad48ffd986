"""Pipeline parallelism — GPipe-style microbatched stages over the 'pp' axis.

Reference counterpart: none in DL4J (its scaleout is data-parallel only);
required by the goal spec. TPU-native design: the transformer's stacked
block params (leading L axis) are sharded over 'pp' (L/P blocks per stage);
inside ``shard_map`` a fill-drain loop streams M microbatches through the
ring, moving activations to the next stage with ``lax.ppermute`` each tick
(neighbor hop = pure ICI). Embedding/head are replicated; stage 0 embeds,
the last stage computes the LM loss, and the scalar is psum-broadcast so
every device returns the same value. ``jax.grad`` differentiates straight
through (ppermute's transpose is the reverse permute), so the SAME fill-
drain program serves forward and backward — no hand-written schedule.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..zoo import transformer as tfm


def _stage_loss_fn(cfg, n_stages, other_axes=(), aux_weight=1e-2):
    """Builds the per-device pipelined loss, to run inside shard_map."""
    if getattr(cfg, "router", "linear") == "mlp":
        raise NotImplementedError(
            "a stage hands the next the residual stream alone: the mlp "
            "router's state would start from zeros at every stage (ROADMAP "
            "Reach B9)")

    if "gated_deltanet" in getattr(cfg, "layer_mixers", ()):
        raise NotImplementedError(
            "a stage slices every leaf of params['blocks'] by layer, and "
            "layer_mixers 'gated_deltanet' stacks its gdn_* leaves over its "
            "own layers alone: a stage would have to hold whole periods and "
            "slice each leaf by its mixer's share of them")

    if getattr(cfg, "dense_layers", 0) or getattr(cfg, "predict_ahead", 0):
        raise NotImplementedError(
            "every stage holds a slice of ONE group of stacked blocks: "
            "leading dense layers (dense_layers) are a group of another "
            "shape, and the last stage runs no prediction module "
            "(predict_ahead) behind its head (ROADMAP Reach B10)")

    def fn(params, ids_mb, tgt_mb):
        # params['blocks'] leaves: (L/P, ...) local; embed/head replicated
        stage = lax.axis_index("pp")
        n_mb = ids_mb.shape[0]
        mb, t = ids_mb.shape[1], ids_mb.shape[2]
        d = cfg.d_model
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        buf = jnp.zeros((mb, t, d), cfg.dtype)
        total = jnp.zeros((), jnp.float32)
        aux_total = jnp.zeros((), jnp.float32)
        is_first = stage == 0
        is_last = stage == n_stages - 1
        for tick in range(n_mb + n_stages - 1):
            mb_idx = jnp.clip(tick, 0, n_mb - 1)
            fresh = tfm.embed(params, cfg, ids_mb[mb_idx])
            x = jnp.where(is_first & (tick < n_mb), fresh, buf)
            y, aux = tfm.apply_blocks(params["blocks"], cfg, x)
            # this stage does real work on ticks [stage, stage + n_mb)
            real_work = (tick >= stage) & (tick - stage < n_mb)
            aux_total = aux_total + jnp.where(real_work, aux.astype(jnp.float32), 0.0)
            out_idx = tick - (n_stages - 1)
            if 0 <= out_idx:
                logits = tfm.head_logits(params, cfg, y)
                tgt = tgt_mb[jnp.clip(out_idx, 0, n_mb - 1)]
                logp = jax.nn.log_softmax(logits, -1)
                nll = -jnp.take_along_axis(
                    logp, tgt[..., None].astype(jnp.int32), -1)[..., 0].mean()
                use = is_last & (out_idx < n_mb)
                total = total + jnp.where(use, nll, 0.0)
            buf = lax.ppermute(y, "pp", perm)
        # nll lives on the last stage only; MoE aux loss accrues on EVERY
        # stage (each holds L/P routed blocks) — both psum over the ring
        total = lax.psum(jnp.where(is_last, total, 0.0), "pp") / n_mb
        total = total + aux_weight * lax.psum(aux_total, "pp") / n_mb
        # average the data-parallel shards (tp copies identical; pmean no-op)
        for ax in other_axes:
            total = lax.pmean(total, ax)
        return total

    return fn


def make_pipeline_loss(mesh: Mesh, cfg: tfm.TransformerConfig):
    """Pipelined LM loss over mesh axes ('pp' required; 'dp'/'tp' optional).

    Call: loss = fn(params, ids (M, mb, T), targets (M, mb, T)).
    params['blocks'] leaves must have leading dim L divisible by pp size.
    """
    n_stages = mesh.shape["pp"]
    if cfg.n_layers % n_stages:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by pp={n_stages}")

    # spec trees: blocks sharded over pp on axis 0; everything else replicated
    def param_specs(params):
        return {
            k: (jax.tree_util.tree_map(lambda _: P("pp"), v) if k == "blocks"
                else jax.tree_util.tree_map(lambda _: P(), v))
            for k, v in params.items()
        }

    data_spec = P(None, "dp" if "dp" in mesh.axis_names else None, None)

    other_axes = tuple(a for a in mesh.axis_names if a != "pp")

    def build(params):
        specs = param_specs(params)
        fn = shard_map(
            _stage_loss_fn(cfg, n_stages, other_axes), mesh=mesh,
            in_specs=(specs, data_spec, data_spec),
            out_specs=P(), check_vma=False)
        return fn

    def loss(params, ids_mb, tgt_mb):
        return build(params)(params, ids_mb, tgt_mb)

    return loss


def make_pipeline_train_step(mesh: Mesh, cfg: tfm.TransformerConfig, optimizer):
    """Jitted pipelined train step: (params, opt_state, ids_mb, tgt_mb) →
    (params, opt_state, loss). Params stay pp-sharded throughout."""
    loss_fn = make_pipeline_loss(mesh, cfg)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, ids_mb, tgt_mb):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids_mb, tgt_mb)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def place_params_for_pipeline(mesh: Mesh, params):
    """Device_put params with blocks sharded over 'pp' (axis 0), rest replicated."""
    def sh(k):
        def inner(leaf):
            if k == "blocks":
                return NamedSharding(mesh, P("pp"))
            return NamedSharding(mesh, P())
        return inner
    return {k: jax.tree_util.tree_map(
        lambda a, _k=k: jax.device_put(a, sh(_k)(a)), v)
        for k, v in params.items()}
