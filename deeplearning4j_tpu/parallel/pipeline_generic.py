"""Generic pipeline parallelism — partition ANY sequential layer stack
(MultiLayerNetwork) into GPipe stages over the mesh 'pp' axis.

Reference counterpart: none in DL4J (data-parallel only); VERDICT r2 item 4
asked for a stage partitioner beyond the transformer-only pipeline in
``pipeline.py``. TPU-native design: stages are contiguous layer runs
balanced by parameter count; inside ``shard_map`` a fill-drain loop streams
M microbatches around the ring with ``lax.ppermute`` (neighbor hop = ICI),
and each device runs its own stage via ``lax.cond``-free ``lax.switch`` on
its 'pp' coordinate. Heterogeneous boundary activations are flattened and
zero-padded to one common buffer width so every stage exchanges the same
static shape — the price of generality XLA demands (the homogeneous
transformer pipeline in pipeline.py avoids the pad by stacking its
identical blocks instead).

Scope v2: stateful layers (BatchNorm running stats) ARE supported — the
states pytree rides the fill-drain loop as a carry; each stage updates its
own layers' stats per microbatch (GPipe semantics: BN batch statistics are
per-MICROBATCH, like upstream GPipe), and after the drain an
ownership-masked psum over 'pp' (+ pmean over dp axes) reassembles one
consistent tree. Dropout/weight-noise: pass ``rng`` to the loss/step —
masks are drawn per MICROBATCH (fold_in(microbatch, layer); GPipe
semantics, like the per-microbatch BN stats — NOT bit-equal to a
single-device full-batch mask). Single input/output still.

Memory: ``shard_params_pp`` lays params out 1/pp per device AT REST
(ZeRO-3 over the 'pp' axis) — params, Adam moments, and every optimizer
buffer scale with the stage count; the step transiently regathers (XLA
inserts the all-gather at the shard_map boundary). The homogeneous-stack
variant in pipeline.py partitions the transient too by stacking identical
blocks.

``jax.grad`` differentiates straight through the fill-drain loop
(ppermute's transpose is the reverse permute), so one program serves
forward and backward.
"""

from __future__ import annotations

import math
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nn.layers.base import Ctx
from ..nn.layers.core import LossLayer, OutputLayer
from ..nn.multi_layer_network import unwrap


def partition_layers(net, n_stages: int) -> List[List[int]]:
    """Contiguous stages balanced by parameter count (the final loss/output
    layer rides with the last stage). Greedy: close a stage once it holds
    its fair share of the remaining parameters."""
    sizes = []
    for i in range(len(net.layers)):
        p = net.params[f"layer_{i}"]
        sizes.append(sum(x.size for x in jax.tree_util.tree_leaves(p)))
    n = len(sizes)
    if n_stages > n:
        raise ValueError(f"{n_stages} stages > {n} layers")
    stages, start, remaining = [], 0, sum(sizes)
    for s in range(n_stages):
        stages_left = n_stages - s
        target = remaining / stages_left
        end, acc = start, 0
        # must leave >= 1 layer per remaining stage
        max_end = n - (stages_left - 1)
        while end < max_end and (acc < target or end == start):
            acc += sizes[end]
            end += 1
        stages.append(list(range(start, end)))
        remaining -= acc
        start = end
    return stages


def _boundary_shapes(net, stages, batch: int):
    """Per-stage input shapes (with batch dim) via abstract evaluation."""
    in_shape = (batch,) + tuple(net._init_input_shape)
    shapes = [in_shape]
    x = jax.ShapeDtypeStruct(in_shape, jnp.float32)

    def run_stage(idx_list, drop_output):
        def f(params, x):
            h = x
            for i in idx_list:
                layer = net.layers[i]
                if drop_output and i == len(net.layers) - 1 and isinstance(
                        unwrap(layer), (OutputLayer, LossLayer)):
                    break
                if i in net._preprocessors:
                    h = net._preprocessors[i](h)
                h, _ = layer.apply(params[f"layer_{i}"],
                                   net.states[f"layer_{i}"], h,
                                   Ctx(train=True, rng=None))
            return h
        return f

    for s, idx_list in enumerate(stages):
        x = jax.eval_shape(run_stage(idx_list, drop_output=True),
                           net.params, x)
        shapes.append(tuple(x.shape))
        x = jax.ShapeDtypeStruct(tuple(x.shape), jnp.float32)
    return shapes


def shard_params_pp(mesh: Mesh, params, min_size: int = 2 ** 12):
    """ZeRO-3-over-'pp' at-rest layout: shard each large leaf's first
    divisible axis over 'pp'. Apply to params BEFORE optimizer init so the
    Adam moments inherit the layout — at-rest model+optimizer memory then
    scales 1/pp; the pipelined step transiently regathers at the shard_map
    boundary (XLA inserts the all-gather)."""
    n = mesh.shape["pp"]

    def sh(leaf):
        if not hasattr(leaf, "shape") or leaf.size < min_size:
            return jax.device_put(leaf, NamedSharding(mesh, P()))
        for d, dim in enumerate(leaf.shape):
            if dim % n == 0:
                spec = [None] * leaf.ndim
                spec[d] = "pp"
                return jax.device_put(leaf, NamedSharding(mesh, P(*spec)))
        return jax.device_put(leaf, NamedSharding(mesh, P()))

    return jax.tree_util.tree_map(sh, params)


def make_mln_pipeline_loss(mesh: Mesh, net, microbatch: int):
    """Pipelined loss for a sequential net over mesh axes ('pp' required,
    'dp' optional). Stateless nets: ``loss = fn(params, x_mb, y_mb)``.
    Stateful nets (BatchNorm): ``(loss, new_states) = fn(params, states,
    x_mb, y_mb)`` — per-microbatch batch stats (GPipe semantics), final
    states reassembled from each stage's owner. At dp=1 the loss equals the
    single-device microbatched loop exactly (proven in
    tests/test_parallel.py); under dp>1 a BN layer normalizes each dp
    shard's mb/dp samples separately (standard sharded-BN semantics; stats
    are pmean'd), so BN values differ from single-device by the shard-local
    normalization, like every dp framework without SyncBN."""
    n_stages = mesh.shape["pp"]
    stateful = any(bool(s) for s in net.states.values())
    stages = partition_layers(net, n_stages)
    stage_of = {}
    for s, idx_list in enumerate(stages):
        for i in idx_list:
            stage_of[i] = s
    out_layer = unwrap(net.layers[-1])
    if not isinstance(out_layer, (OutputLayer, LossLayer)):
        raise ValueError("last layer must be an OutputLayer/LossLayer")
    last_i = len(net.layers) - 1
    shapes = _boundary_shapes(net, stages, microbatch)
    flat_sizes = [math.prod(s[1:]) for s in shapes]
    fmax = max(flat_sizes)

    from ..nn.weightnoise import maybe_apply_weight_noise
    needs_rng = any(getattr(l, "dropout", 0.0) > 0.0
                    or getattr(l, "weight_noise", None) is not None
                    for l in net.layers)

    def stage_fn(s):
        idx_list = stages[s]
        is_loss_stage = s == n_stages - 1

        def f(params, states, flat, tgt, mb_rng):
            # leading dim comes from the LOCAL array: under a dp axis,
            # shard_map hands each device its microbatch shard
            h = flat[:, :flat_sizes[s]].reshape(
                (flat.shape[0],) + shapes[s][1:])
            new_states = dict(states)
            for i in idx_list:
                layer = net.layers[i]
                if i == last_i and isinstance(unwrap(layer),
                                              (OutputLayer, LossLayer)):
                    break   # the loss computation below consumes h
                if i in net._preprocessors:
                    h = net._preprocessors[i](h)
                lrng = None if mb_rng is None else \
                    jax.random.fold_in(mb_rng, i)
                if getattr(layer, "dropout", 0.0) > 0.0 and lrng is not None:
                    # per-MICROBATCH masks (GPipe semantics, like the
                    # per-microbatch BN stats above)
                    keep = 1.0 - layer.dropout
                    m = jax.random.bernoulli(
                        jax.random.fold_in(lrng, 997), keep, h.shape)
                    h = jnp.where(m, h / keep, 0.0).astype(h.dtype)
                p_i = maybe_apply_weight_noise(
                    layer, params[f"layer_{i}"], lrng, True)
                h, s_new = layer.apply(p_i,
                                       states[f"layer_{i}"], h,
                                       Ctx(train=True, rng=lrng))
                new_states[f"layer_{i}"] = s_new
            out = h.reshape(h.shape[0], -1)
            pad = fmax - out.shape[1]
            if pad:
                out = jnp.pad(out, ((0, 0), (0, pad)))
            # loss lives INSIDE the last stage's branch so the other
            # stages never pay the output-head FLOPs (lax.switch executes
            # only the selected branch)
            if not is_loss_stage:
                return out, jnp.zeros((), jnp.float32), new_states
            hl = h
            if last_i in net._preprocessors:
                hl = net._preprocessors[last_i](hl)
            if isinstance(out_layer, OutputLayer):
                mb_loss = out_layer.compute_loss(
                    params[f"layer_{last_i}"], hl, tgt)
            else:
                mb_loss = out_layer.compute_loss(hl, tgt)
            return out, mb_loss.astype(jnp.float32), new_states
        return f

    fns = [stage_fn(s) for s in range(n_stages)]
    other_axes = tuple(a for a in mesh.axis_names
                       if a != "pp" and mesh.shape[a] > 1)

    def device_loss(params, states, x_mb, y_mb, rng=None):
        stage = lax.axis_index("pp")
        n_mb = x_mb.shape[0]
        mb_local = x_mb.shape[1]   # microbatch / dp under a dp axis
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        buf = jnp.zeros((mb_local, fmax), jnp.float32)
        total = jnp.zeros((), jnp.float32)
        is_first = stage == 0
        is_last = stage == n_stages - 1
        for tick in range(n_mb + n_stages - 1):
            # the microbatch THIS stage works on at this tick (stage s gets
            # live microbatch tick - s) — keys its dropout/weight-noise rng
            my_mb = jnp.clip(tick - stage, 0, n_mb - 1)
            if rng is None:
                mb_rng = None
            else:
                mb_rng = jax.random.fold_in(rng, my_mb)
                # de-correlate masks across DATA-sharding axes only ('dp'
                # is the sole axis data_spec shards over): without this
                # every dp device would draw the SAME per-position mask
                # for its shard. Non-data axes (tp/fsdp) hold replicated
                # activations and MUST keep identical masks or their
                # "replicated" values silently diverge.
                if "dp" in mesh.axis_names and mesh.shape["dp"] > 1:
                    mb_rng = jax.random.fold_in(mb_rng,
                                                lax.axis_index("dp"))
            mb_idx = jnp.clip(tick, 0, n_mb - 1)
            fresh = x_mb[mb_idx].reshape(mb_local, -1)
            if fresh.shape[1] < fmax:
                fresh = jnp.pad(fresh,
                                ((0, 0), (0, fmax - fresh.shape[1])))
            x = jnp.where(is_first & (tick < n_mb), fresh, buf)
            out_idx = tick - (n_stages - 1)
            tgt = y_mb[jnp.clip(out_idx, 0, n_mb - 1)]
            y, mb_loss, new_states = lax.switch(stage, fns, params, states,
                                                x, tgt, mb_rng)
            # only ticks carrying a real microbatch may advance the stats:
            # stage s sees live data at ticks [s, s + n_mb); outside that
            # (fill/drain) it re-ran a clipped mb whose stats must be
            # discarded
            if stateful:
                live = (tick >= stage) & (tick - stage < n_mb)
                states = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(live, new, old),
                    new_states, states)
            if out_idx >= 0:
                use = is_last & (out_idx < n_mb)
                total = total + jnp.where(use, mb_loss, 0.0)
            buf = lax.ppermute(y, "pp", perm)
        total = lax.psum(jnp.where(is_last, total, 0.0), "pp") / n_mb
        for ax in other_axes:
            total = lax.pmean(total, ax)
        if not stateful:
            return total, states
        # reassemble: each layer's state is authoritative on its OWNING
        # stage; masked psum over 'pp' broadcasts it to everyone, pmean
        # over dp axes averages the per-shard batch stats (all-float)
        merged = {}
        for i in range(len(net.layers)):
            key = f"layer_{i}"
            own = (stage == stage_of[i]).astype(jnp.float32)

            def pick(leaf, own=own):
                v = lax.psum(leaf.astype(jnp.float32) * own, "pp")
                for ax in other_axes:
                    v = lax.pmean(v, ax)
                return v.astype(leaf.dtype)

            merged[key] = jax.tree_util.tree_map(pick, states[key])
        return total, merged

    rep = jax.tree_util.tree_map(lambda _: P(), net.params)
    rep_states = jax.tree_util.tree_map(lambda _: P(), net.states)
    dp = "dp" if "dp" in mesh.axis_names else None

    def data_spec(arr_ndim):
        return P(*((None, dp) + (None,) * (arr_ndim - 2)))

    def loss_with_states(params, states, x_mb, y_mb, rng=None):
        if not needs_rng:
            rng = None   # dropout-free net: skip the whole rng machinery
        if rng is None:
            fn = shard_map(
                lambda p, s, x, y: device_loss(p, s, x, y, None),
                mesh=mesh,
                in_specs=(rep, rep_states, data_spec(x_mb.ndim),
                          data_spec(y_mb.ndim)),
                out_specs=(P(), rep_states), check_vma=False)
            return fn(params, states, x_mb, y_mb)
        fn = shard_map(device_loss, mesh=mesh,
                       in_specs=(rep, rep_states, data_spec(x_mb.ndim),
                                 data_spec(y_mb.ndim), P()),
                       out_specs=(P(), rep_states), check_vma=False)
        return fn(params, states, x_mb, y_mb, rng)

    if stateful:
        return loss_with_states

    def loss(params, x_mb, y_mb, rng=None):
        return loss_with_states(params, net.states, x_mb, y_mb, rng)[0]

    return loss


def make_mln_pipeline_train_step(mesh: Mesh, net, optimizer,
                                 microbatch: int):
    """Jitted pipelined train step for any sequential net. Stateless:
    (params, opt_state, x_mb, y_mb) → (params, opt_state, loss).
    Stateful (BatchNorm): (params, states, opt_state, x_mb, y_mb) →
    (params, states, opt_state, loss)."""
    loss_fn = make_mln_pipeline_loss(mesh, net, microbatch)
    stateful = any(bool(s) for s in net.states.values())

    if stateful:
        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def step_s(params, states, opt_state, x_mb, y_mb, rng=None):
            (loss, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, states, x_mb, y_mb, rng)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, new_states, opt_state, loss

        return step_s

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, x_mb, y_mb, rng=None):
        loss, grads = jax.value_and_grad(loss_fn)(params, x_mb, y_mb, rng)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


class _SequentialView:
    """MLN-shaped facade over a linear-chain ComputationGraph so the
    generic pipeline machinery applies unchanged. Params/states are
    re-keyed node-name → 'layer_i'; ``to_graph``/``from_graph`` convert."""

    def __init__(self, cg):
        from ..nn.layers.base import Layer as _Layer
        order = [n for n in cg.conf.topo_order if n not in cg.conf.inputs]
        for k, name in enumerate(order):
            node = cg.conf.nodes[name]
            if not isinstance(node.op, _Layer):
                raise ValueError(
                    f"CG pipeline needs a pure layer chain; '{name}' is a "
                    f"{type(node.op).__name__} vertex")
            expect = cg.conf.inputs[0] if k == 0 else order[k - 1]
            if list(node.inputs) != [expect]:
                raise ValueError(
                    f"CG pipeline needs a linear chain; '{name}' consumes "
                    f"{list(node.inputs)} (expected ['{expect}'])")
        self.names = order
        self.layers = [cg.conf.nodes[n].op for n in order]
        self.params = {f"layer_{i}": cg.params[n]
                       for i, n in enumerate(order)}
        self.states = {f"layer_{i}": cg.states[n]
                       for i, n in enumerate(order)}
        self._preprocessors = {i: cg._preprocessors[n]
                               for i, n in enumerate(order)
                               if n in cg._preprocessors}
        self._init_input_shape = tuple(cg._init_shapes[0])

    def to_graph(self, params):
        return {n: params[f"layer_{i}"] for i, n in enumerate(self.names)}

    def from_graph(self, params):
        return {f"layer_{i}": params[n] for i, n in enumerate(self.names)}


def make_cg_pipeline_train_step(mesh: Mesh, cg, optimizer, microbatch: int):
    """Pipeline a linear-chain ComputationGraph: returns (step, view) where
    ``view.params``/``view.states`` are the 'layer_i'-keyed starting pytree
    (use ``view.to_graph`` to map results back onto the graph)."""
    view = _SequentialView(cg)
    return make_mln_pipeline_train_step(mesh, view, optimizer,
                                        microbatch), view


def microbatches(x, y, microbatch: int):
    """Host-side reshape: (B, ...) → (M, mb, ...); B must divide evenly."""
    import numpy as np
    x, y = np.asarray(x), np.asarray(y)
    if x.shape[0] % microbatch:
        raise ValueError(f"batch {x.shape[0]} not divisible by "
                         f"microbatch {microbatch}")
    m = x.shape[0] // microbatch
    return (x.reshape((m, microbatch) + x.shape[1:]),
            y.reshape((m, microbatch) + y.shape[1:]))
