"""ComputationGraph — DAG network runtime.

Reference parity: ``org.deeplearning4j.nn.graph.ComputationGraph``
(init/fit/output/score/evaluate on multi-input multi-output DAGs).
The topological order traces into one jaxpr; multi-output losses sum with
per-output weights like the reference. Shares the train-step design of
MultiLayerNetwork (one jitted donated step).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..train.updaters import NoOp, build_optimizer
from ._fit_common import (build_train_step,
                          enable_gradient_anomaly_detection, fit_epochs)
from ._scan_common import check_scan_listeners, fit_scanned_epochs
from .graph import ComputationGraphConfiguration
from .layers.base import Ctx, Layer
from .layers.wrappers import unwrap
from .layers.core import LossLayer, OutputLayer
from .layers.samediff_layer import SameDiffOutputLayer
from .preprocessors import CnnToFeedForwardPreProcessor
from .vertices import GraphVertex
from .weightnoise import maybe_apply_weight_noise


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self._g = conf.globals_
        self.params: Dict[str, dict] = {}
        self.states: Dict[str, dict] = {}
        self._preprocessors: Dict[str, Any] = {}
        self._optimizer = None
        self._opt_state = None
        self.listeners: List[Any] = []
        self.initialized = False
        self._train_step = None
        self._scan_epoch = None
        self._infer_fn = None
        self.epoch_count = 0
        self._step_count = 0
        self._host_key = jax.random.PRNGKey(self._g.seed)
        self.output_loss_weights = {name: 1.0 for name in conf.outputs}
        # int n -> train-time forward runs as n jax.checkpoint segments
        # (activation rematerialization; see _forward_remat)
        self.remat_segments = None

    @property
    def remat_segments(self):
        return self._remat_segments

    @remat_segments.setter
    def remat_segments(self, n):
        """Changing the remat policy invalidates every compiled step that
        traced the old forward (same staleness rule as
        enable_gradient_anomaly_detection)."""
        if getattr(self, "_remat_segments", None) != n:
            self._invalidate()
            self._remat_plan_cache = {}
        self._remat_segments = n

    def _invalidate(self):
        """Drop every compiled function that closed over params/topology
        (mirrors MultiLayerNetwork._invalidate)."""
        self._train_step = None
        self._scan_epoch = None
        self._infer_fn = None
        self._rnn_stream_fn = None

    # ------------------------------------------------------------------ init
    def init(self, input_shapes=None):
        if input_shapes is None:
            if self.conf.input_types is None:
                raise ValueError("Provide input_shapes or set_input_types")
            input_shapes = [tuple(t[1]) for t in self.conf.input_types]
        self._init_shapes = [tuple(s) for s in input_shapes]  # for transfer
        shapes = {name: tuple(s) for name, s in zip(self.conf.inputs, input_shapes)}
        key = jax.random.PRNGKey(self._g.seed)
        for name in self.conf.topo_order:
            node = self.conf.nodes[name]
            in_shapes = [shapes[i] for i in node.inputs]
            if isinstance(node.op, Layer):
                from .multi_layer_network import _is_ff_layer
                if getattr(node.op, "multi_input", False):
                    key, sub = jax.random.split(key)
                    p, st, out = node.op.init(sub, in_shapes)
                    self.params[name] = p
                    self.states[name] = st
                    shapes[name] = out
                    continue
                s = in_shapes[0]
                if (_is_ff_layer(node.op) or isinstance(unwrap(node.op), OutputLayer)) \
                        and len(s) == 3:
                    pp = CnnToFeedForwardPreProcessor()
                    self._preprocessors[name] = pp
                    s = pp.out_shape(s)
                key, sub = jax.random.split(key)
                p, st, out = node.op.init(sub, s)
                self.params[name] = p
                self.states[name] = st
                shapes[name] = out
            else:
                shapes[name] = node.op.out_shape(in_shapes)
                self.params[name] = {}
                self.states[name] = {}
        self.output_shapes = {o: shapes[o] for o in self.conf.outputs}
        self.initialized = True
        return self

    # -------------------------------------------------------------- forward
    def _apply_node(self, idx, name, params, states, acts, pre_acts,
                    new_states, *, train, rng, fmask, lmask,
                    stop_at_output_preact):
        """Apply one topo-order node, writing into acts/pre_acts/new_states.

        ``idx`` is the GLOBAL topo position (the per-node rng is
        ``fold_in(rng, idx)``), so segmented execution reproduces the exact
        dropout/weight-noise draws of the monolithic walk."""
        node = self.conf.nodes[name]
        xs = [acts[i] for i in node.inputs]
        # named scope: the node's ops carry <name>.<Type> in the fused
        # executable's metadata (xprof layer map; trace-time only) —
        # mirrors MultiLayerNetwork._apply_one and obs.profiler naming
        scope = jax.named_scope(
            f"{name}.{type(unwrap(node.op)).__name__}".replace("/", "_"))
        with scope:
            self._apply_node_inner(
                name, node, xs, params, states, acts, pre_acts, new_states,
                train=train, rng=rng, idx=idx, fmask=fmask, lmask=lmask,
                stop_at_output_preact=stop_at_output_preact)

    def _apply_node_inner(self, name, node, xs, params, states, acts,
                          pre_acts, new_states, *, train, rng, idx, fmask,
                          lmask, stop_at_output_preact):
        if isinstance(node.op, Layer):
            if getattr(node.op, "multi_input", False):
                lrng = None if rng is None else jax.random.fold_in(rng, idx)
                ctx = Ctx(train=train, rng=lrng, mask=fmask, label_mask=lmask)
                if train and node.op.dropout > 0.0 and lrng is not None:
                    keep = 1.0 - node.op.dropout
                    dropped = []
                    for j, h in enumerate(xs):
                        m = jax.random.bernoulli(
                            jax.random.fold_in(lrng, 997 + j), keep, h.shape)
                        dropped.append(
                            jnp.where(m, h / keep, 0.0).astype(h.dtype))
                    xs = dropped
                p_n = maybe_apply_weight_noise(node.op, params[name],
                                               lrng, train)
                h, s_new = node.op.apply(p_n, states[name], xs, ctx)
                new_states[name] = s_new
                acts[name] = h
                return
            h = xs[0]
            if name in self._preprocessors:
                h = self._preprocessors[name](h)
            lrng = None if rng is None else jax.random.fold_in(rng, idx)
            ctx = Ctx(train=train, rng=lrng, mask=fmask, label_mask=lmask)
            if train and node.op.dropout > 0.0 and lrng is not None:
                keep = 1.0 - node.op.dropout
                m = jax.random.bernoulli(jax.random.fold_in(lrng, 997), keep, h.shape)
                h = jnp.where(m, h / keep, 0.0).astype(h.dtype)
            if stop_at_output_preact and name in self.conf.outputs and \
                    isinstance(unwrap(node.op),
                               (OutputLayer, LossLayer, SameDiffOutputLayer)):
                pre_acts[name] = h
                new_states[name] = states[name]
                acts[name] = h
                return
            p_n = maybe_apply_weight_noise(node.op, params[name],
                                           lrng, train)
            h, s_new = node.op.apply(p_n, states[name], h, ctx)
            new_states[name] = s_new
            acts[name] = h
        else:
            acts[name] = node.op.apply(xs)
            new_states[name] = states[name]

    def _as_input_dict(self, inputs):
        """Accept {name: arr}, [arr, ...] (zipped with conf.inputs), or a
        bare array (single-input graphs) — the MLN-compatible calling
        convention ParallelWrapper/ParallelInference use."""
        if isinstance(inputs, dict):
            return inputs
        if isinstance(inputs, (list, tuple)):
            if len(inputs) != len(self.conf.inputs):
                raise ValueError(
                    f"got {len(inputs)} feature arrays for a graph with "
                    f"{len(self.conf.inputs)} inputs {self.conf.inputs}")
            return {n: v for n, v in zip(self.conf.inputs, inputs)}
        return {self.conf.inputs[0]: inputs}

    def _as_label_dict(self, labels):
        if isinstance(labels, dict):
            return labels
        if isinstance(labels, (list, tuple)):
            if len(labels) != len(self.conf.outputs):
                raise ValueError(
                    f"got {len(labels)} label arrays for a graph with "
                    f"{len(self.conf.outputs)} outputs {self.conf.outputs}")
            return {n: v for n, v in zip(self.conf.outputs, labels)}
        return {self.conf.outputs[0]: labels}

    def _forward(self, params, states, inputs, *, train, rng,
                 fmask=None, lmask=None, stop_at_output_preact=False):
        inputs = self._as_input_dict(inputs)
        if train and getattr(self, "remat_segments", None):
            return self._forward_remat(
                params, states, inputs, train=train, rng=rng, fmask=fmask,
                lmask=lmask, stop_at_output_preact=stop_at_output_preact)
        acts = dict(inputs)
        new_states = {}
        pre_acts = {}
        for idx, name in enumerate(self.conf.topo_order):
            self._apply_node(idx, name, params, states, acts, pre_acts,
                             new_states, train=train, rng=rng, fmask=fmask,
                             lmask=lmask,
                             stop_at_output_preact=stop_at_output_preact)
        return acts, pre_acts, new_states

    # ------------------------------------------------------- segmented remat
    def _segment_plan(self, n_segments, input_names):
        """Partition topo_order into ``n_segments`` contiguous segments,
        cutting where the cross-boundary live set is smallest.

        Liveness: an activation is live after position i if its producer is
        at <= i and some consumer is at > i (graph outputs live to the end).
        Each cut carries exactly the live set, so ANY cut position is
        semantically valid — the live-set size only decides how much the
        checkpoint saves. For chain-of-blocks topologies (ResNet bottleneck
        stacks) the minimal-live cuts land on block boundaries where exactly
        one tensor crosses."""
        order = self.conf.topo_order
        n = len(order)
        last_use = {}
        for idx, name in enumerate(order):
            for i in self.conf.nodes[name].inputs:
                last_use[i] = idx
        for o in self.conf.outputs:
            last_use[o] = n
        producers = list(input_names) + order
        pos = {a: -1 for a in input_names}
        pos.update({name: idx for idx, name in enumerate(order)})

        def live_after(idx):
            return [a for a in producers
                    if pos[a] <= idx and last_use.get(a, -1) > idx]

        cuts = []
        span = n / n_segments
        for k in range(1, n_segments):
            ideal = int(round(k * span)) - 1
            lo = max((cuts[-1] + 1) if cuts else 0, int(ideal - span // 2))
            hi = min(n - 2, int(ideal + span // 2))
            if lo > hi:
                continue
            best = min(range(lo, hi + 1),
                       key=lambda i: (len(live_after(i)), abs(i - ideal)))
            cuts.append(best)
        if len(cuts) + 1 < n_segments:
            import warnings
            warnings.warn(
                f"remat_segments={n_segments} exceeds what this "
                f"{n}-node graph supports; using {len(cuts) + 1} "
                "checkpoint segments (activation footprint will be larger "
                "than configured)", stacklevel=3)
        bounds = [-1] + cuts + [n - 1]
        segments = []
        for a, b in zip(bounds[:-1], bounds[1:]):
            nodes = [(i, order[i]) for i in range(a + 1, b + 1)]
            carry_in = sorted(live_after(a)) if a >= 0 else sorted(input_names)
            carry_out = sorted(live_after(b)) if b < n - 1 else \
                sorted(set(self.conf.outputs))
            segments.append({"nodes": nodes, "carry_in": carry_in,
                             "carry_out": carry_out})
        return segments

    def _forward_remat(self, params, states, inputs, *, train, rng,
                      fmask=None, lmask=None, stop_at_output_preact=False):
        """_forward with each segment under ``jax.checkpoint``: only the
        cross-segment live activations are saved for the backward pass;
        everything inside a segment is recomputed. Trades (otherwise idle,
        on an HBM-bound step) MXU cycles for activation traffic — the same
        lever as the transformer's remat-full policy."""
        key = (int(self.remat_segments), tuple(sorted(inputs)))
        cache = getattr(self, "_remat_plan_cache", None)
        if cache is None:
            cache = self._remat_plan_cache = {}
        plan = cache.get(key)
        if plan is None:
            plan = cache[key] = self._segment_plan(self.remat_segments,
                                                   sorted(inputs))
        acts = dict(inputs)
        pre_acts = {}
        new_states = {}
        for seg in plan:
            seg_names = [nm for _, nm in seg["nodes"]]
            seg_params = {nm: params[nm] for nm in seg_names}
            seg_states = {nm: states[nm] for nm in seg_names}

            def seg_fn(p, s, carry, rng_, fmask_, lmask_, _seg=seg):
                a = dict(carry)
                pre = {}
                ns = {}
                for idx, nm in _seg["nodes"]:
                    self._apply_node(
                        idx, nm, p, s, a, pre, ns, train=train, rng=rng_,
                        fmask=fmask_, lmask=lmask_,
                        stop_at_output_preact=stop_at_output_preact)
                return ({k: a[k] for k in _seg["carry_out"] if k in a},
                        ns, pre)

            carry_in = {k: acts[k] for k in seg["carry_in"]}
            out, ns, pre = jax.checkpoint(seg_fn)(
                seg_params, seg_states, carry_in, rng, fmask, lmask)
            acts.update(out)
            new_states.update(ns)
            pre_acts.update(pre)
        return acts, pre_acts, new_states

    def output(self, *inputs):
        if self._infer_fn is None:
            def infer(params, states, inputs):
                acts, _, _ = self._forward(params, states, inputs, train=False, rng=None)
                return [acts[o] for o in self.conf.outputs]
            self._infer_fn = jax.jit(infer)
        ins = {n: jnp.asarray(x) for n, x in zip(self.conf.inputs, inputs)}
        outs = self._infer_fn(self.params, self.states, ins)
        return outs[0] if len(outs) == 1 else outs

    # ------------------------------------------------------- rnn streaming
    def rnn_time_step(self, *inputs):
        """Streaming inference through the DAG (reference:
        ComputationGraph.rnnTimeStep): feed a (B, T, C) chunk — or a (B, C)
        float single step — per graph input; recurrent layer carries
        persist on device across calls until rnn_clear_previous_state().
        Same one-jitted-scan design as MultiLayerNetwork.rnn_time_step."""
        from .layers.recurrent import (BaseRecurrent, Bidirectional,
                                       LastTimeStep)
        from .layers.wrappers import TimeDistributedLayer
        for name in self.conf.topo_order:
            op = self.conf.nodes[name].op
            if isinstance(op, Layer) and isinstance(
                    unwrap(op), (Bidirectional, LastTimeStep,
                                 TimeDistributedLayer)):
                raise NotImplementedError(
                    f"rnn_time_step cannot stream through node '{name}' "
                    f"({type(unwrap(op)).__name__}): it needs the full "
                    "sequence (reference rnnTimeStep has the same limit)")
        xs = [jnp.asarray(x) for x in inputs]
        integer = jnp.issubdtype(xs[0].dtype, jnp.integer)
        single = (xs[0].ndim == 2 and not integer) or \
            (xs[0].ndim == 1 and integer)
        if single:
            xs = [x[:, None] if x.ndim == 1 else x[:, None, :] for x in xs]
        batch = xs[0].shape[0]

        old = getattr(self, "_rnn_carries", None) or {}
        if getattr(self, "_rnn_carry_batch", None) != batch:
            old = {}
        carries = {}
        for name in self.conf.topo_order:
            op = self.conf.nodes[name].op
            ul = unwrap(op) if isinstance(op, Layer) else None
            if isinstance(ul, BaseRecurrent):
                carries[name] = old.get(name)
                if carries[name] is None:
                    dtype = ul.compute_dtype or (
                        xs[0].dtype if jnp.issubdtype(xs[0].dtype,
                                                      jnp.floating)
                        else self._g.param_dtype)
                    carries[name] = ul.init_carry(batch, dtype)
        self._rnn_carry_batch = batch

        if getattr(self, "_rnn_stream_fn", None) is None:
            def stream(params, states, carries, ins):
                def step(cs, xt):
                    acts = dict(xt)
                    new_cs = {}
                    for name in self.conf.topo_order:
                        node = self.conf.nodes[name]
                        vals = [acts[i] for i in node.inputs]
                        if isinstance(node.op, Layer):
                            h = vals if getattr(node.op, "multi_input",
                                                False) else vals[0]
                            if name in self._preprocessors:
                                h = self._preprocessors[name](h)
                            ul = unwrap(node.op)
                            if isinstance(ul, BaseRecurrent):
                                h, c = ul.step_apply(params[name], cs[name],
                                                     h, Ctx(train=False))
                                new_cs[name] = c
                            else:
                                h, _ = node.op.apply(params[name],
                                                     states[name], h,
                                                     Ctx(train=False))
                            acts[name] = h
                        else:
                            acts[name] = node.op.apply(vals)
                    return new_cs, [acts[o] for o in self.conf.outputs]

                cs, ys = jax.lax.scan(
                    step, carries,
                    {n: v.swapaxes(0, 1) for n, v in ins.items()})
                return [y.swapaxes(0, 1) for y in ys], cs

            self._rnn_stream_fn = jax.jit(stream)

        ins = {n: x for n, x in zip(self.conf.inputs, xs)}
        ys, carries = self._rnn_stream_fn(self.params, self.states,
                                          carries, ins)
        self._rnn_carries = carries
        ys = [y[:, 0] for y in ys] if single else ys
        return ys[0] if len(ys) == 1 else ys

    def rnn_clear_previous_state(self):
        self._rnn_carries = None
        self._rnn_carry_batch = None

    # ----------------------------------------------------------------- loss
    def _loss(self, params, states, inputs, labels, rng, fmask, lmask):
        labels = self._as_label_dict(labels)
        acts, pre_acts, new_states = self._forward(
            params, states, inputs, train=True, rng=rng, fmask=fmask, lmask=lmask,
            stop_at_output_preact=True)
        total = 0.0
        for name in self.conf.outputs:
            op = unwrap(self.conf.nodes[name].op)
            y = labels[name]
            w = self.output_loss_weights.get(name, 1.0)
            # output-node work happens here (forward stops at its
            # pre-activation) — scope it like _apply_node scopes the rest
            with jax.named_scope(
                    f"{name}.{type(op).__name__}.loss".replace("/", "_")):
                if isinstance(op, (OutputLayer, SameDiffOutputLayer)):
                    total = total + w * op.compute_loss(
                        params[name], pre_acts[name], y, mask=lmask)
                elif isinstance(op, LossLayer):
                    total = total + w * op.compute_loss(
                        pre_acts[name], y, mask=lmask)
                else:
                    raise ValueError(
                        f"output node '{name}' is not an output/loss layer")
        total = total + self._reg_score(params)
        return total, new_states

    def _reg_score(self, params):
        reg = 0.0
        for name, node in self.conf.nodes.items():
            op = node.op
            if not isinstance(op, Layer) or (op.l1 == 0.0 and op.l2 == 0.0):
                continue
            for k, w in params[name].items():
                if k in ("b", "beta", "mean", "var"):
                    continue
                if op.l1:
                    reg = reg + op.l1 * jnp.sum(jnp.abs(w))
                if op.l2:
                    reg = reg + 0.5 * op.l2 * jnp.sum(jnp.square(w))
        return reg

    # ------------------------------------------------------------ optimizer
    def _build_optimizer(self, ipe=1):
        g = self._g
        labels = {}
        has_override = False
        per_label = {"__default__": g.updater, "__frozen__": NoOp()}
        for name, node in self.conf.nodes.items():
            if isinstance(node.op, Layer) and node.op.frozen:
                lab = "__frozen__"
                has_override = True
            elif isinstance(node.op, Layer) and node.op.updater is not None:
                lab = f"__{name}__"
                per_label[lab] = node.op.updater
                has_override = True
            else:
                lab = "__default__"
            labels[name] = jax.tree_util.tree_map(lambda _: lab, self.params[name])
        self._optimizer = build_optimizer(
            g.updater, grad_norm=g.grad_norm, grad_norm_threshold=g.grad_norm_threshold,
            iters_per_epoch=ipe,
            param_labels=labels if has_override else None,
            per_label_updaters=per_label if has_override else None)
        self._opt_state = self._optimizer.init(self.params)
        upstream = getattr(self, "_upstream_adam_state", None)
        if upstream is not None:  # resume from an upstream DL4J zip
            from ..serde.upstream_dl4j import graft_adam_state
            self._opt_state = graft_adam_state(self._opt_state, upstream)
            self._upstream_adam_state = None

    def _apply_constraints(self, params):
        from ..train.constraints import apply_constraints
        for name, node in self.conf.nodes.items():
            op = node.op
            if not isinstance(op, Layer) or op.frozen:
                continue
            if op.constraints:
                params[name] = apply_constraints(params[name], op.constraints,
                                                 weights=True)
            if op.bias_constraints:
                params[name] = apply_constraints(params[name], op.bias_constraints,
                                                 weights=False, biases=True)
        return params

    def _get_train_step(self):
        if self._train_step is None:
            self._train_step, _ = build_train_step(self, "cg_train_step")
        return self._train_step

    # the module-level function, bound as a method
    enable_gradient_anomaly_detection = enable_gradient_anomaly_detection

    # ------------------------------------------------------------------ fit
    def fit(self, data, *, epochs: int = 1):
        """fit(MultiDataSetIterator | MultiDataSet | DataSet | iterator).
        The loop is ``_fit_common.fit_epochs``, as for MultiLayerNetwork."""
        from ..data.dataset import DataSet, MultiDataSet
        if isinstance(data, (DataSet, MultiDataSet)):
            iterator = [data]
        else:
            iterator = data
        if not self.initialized:
            first = next(iter(iterator))
            feats = first.features if isinstance(first, MultiDataSet) else [first.features]
            self.init([tuple(np.asarray(f).shape[1:]) for f in feats])
            if hasattr(iterator, "reset"):
                iterator.reset()
        if self._optimizer is None:
            try:
                ipe = len(iterator)
            except TypeError:
                ipe = 1
            self._build_optimizer(max(int(ipe), 1))

        def to_device(ds):
            if isinstance(ds, MultiDataSet):
                feats, labs = ds.features, ds.labels
                fmask = None if ds.features_masks is None else ds.features_masks[0]
                lmask = None if ds.labels_masks is None else ds.labels_masks[0]
            else:
                feats, labs = [ds.features], [ds.labels]
                fmask, lmask = ds.features_mask, ds.labels_mask
            inputs = {n: jnp.asarray(f) for n, f in zip(self.conf.inputs, feats)}
            return next(iter(inputs.values())).shape[0], (
                inputs,
                {n: jnp.asarray(l) for n, l in zip(self.conf.outputs, labs)},
                None if fmask is None else jnp.asarray(fmask),
                None if lmask is None else jnp.asarray(lmask))

        return fit_epochs(self, iterator, epochs, self._get_train_step(),
                          to_device)

    def fit_scanned(self, data, *, epochs: int = 1):
        """One jit dispatch per epoch: ``lax.scan`` of the train step over
        the stacked minibatches — same contract as
        ``MultiLayerNetwork.fit_scanned`` (bit-identical trajectory to
        ``fit``, equally-shaped mask-free batches, listeners replayed from
        the scanned loss history)."""
        from ..data.dataset import DataSet, MultiDataSet
        if isinstance(data, (DataSet, MultiDataSet)):
            batches = [data]
        else:
            batches = list(data)
        if not batches:
            return None

        def unpack(ds):
            if isinstance(ds, MultiDataSet):
                if ds.features_masks is not None or ds.labels_masks is not None:
                    raise ValueError("fit_scanned does not support masked "
                                     "batches; use fit()")
                return ds.features, ds.labels
            if ds.features_mask is not None or ds.labels_mask is not None:
                raise ValueError("fit_scanned does not support masked "
                                 "batches; use fit()")
            return [ds.features], [ds.labels]

        pairs = [unpack(ds) for ds in batches]
        shapes = {tuple(np.asarray(f).shape for f in fs)
                  + tuple(np.asarray(l).shape for l in ls)
                  for fs, ls in pairs}
        if len(shapes) > 1:
            raise ValueError("fit_scanned needs equally-shaped batches; "
                             "use fit()")
        check_scan_listeners(self)
        if not self.initialized:
            self.init([tuple(np.asarray(f).shape[1:])
                       for f in pairs[0][0]])
        if self._optimizer is None:
            self._build_optimizer(max(len(batches), 1))
        xs = {n: jnp.stack([jnp.asarray(fs[i]) for fs, _ in pairs])
              for i, n in enumerate(self.conf.inputs)}
        ys = {n: jnp.stack([jnp.asarray(ls[i]) for _, ls in pairs])
              for i, n in enumerate(self.conf.outputs)}
        return fit_scanned_epochs(
            self, self, self._get_train_step().__wrapped__, xs, ys, epochs)

    def score(self, ds):
        from ..data.dataset import MultiDataSet as MDS
        if isinstance(ds, MDS):
            feats, labs = ds.features, ds.labels
        else:
            feats, labs = [ds.features], [ds.labels]
        inputs = {n: jnp.asarray(f) for n, f in zip(self.conf.inputs, feats)}
        labels = {n: jnp.asarray(l) for n, l in zip(self.conf.outputs, labs)}
        loss, _ = self._loss(self.params, self.states, inputs, labels, None, None, None)
        return float(loss)

    def evaluate(self, iterator, top_n: int = 1):
        from ..eval.classification import Evaluation
        ev = Evaluation(top_n=top_n)
        for ds in iterator:
            preds = self.output(jnp.asarray(ds.features))
            if isinstance(preds, list):
                preds = preds[0]
            ev.eval(jnp.asarray(ds.labels), preds)
        if hasattr(iterator, "reset"):
            iterator.reset()
        return ev

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)

    def num_params(self):
        return sum(int(p.size) for p in jax.tree_util.tree_leaves(self.params))

    def params_flat(self):
        """Single flat vector. NOTE: order is jax tree-flatten order
        (sorted node name, then sorted param name within a node), NOT the
        reference's topological node order — self-consistent with
        set_params_flat, but do not zip against a reference-ordered flat
        checkpoint without reindexing."""
        leaves = jax.tree_util.tree_leaves(self.params)
        return jnp.concatenate([l.ravel() for l in leaves]) if leaves \
            else jnp.zeros((0,))

    def set_params_flat(self, flat):
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        out, off = [], 0
        for l in leaves:
            n = int(l.size)
            out.append(jnp.asarray(flat[off:off + n]).reshape(l.shape)
                       .astype(l.dtype))
            off += n
        self.params = jax.tree_util.tree_unflatten(treedef, out)
        self._invalidate()

    def clone(self):
        """Reference ComputationGraph.clone(): config deep-copied, params/
        states shared-by-value (jax arrays are immutable)."""
        import copy
        net = ComputationGraph(copy.deepcopy(self.conf))
        if self.initialized:
            # REAL copies: fit() donates param buffers, so sharing arrays
            # would let the clone's training invalidate the source's
            net.params = jax.tree_util.tree_map(jnp.copy, self.params)
            net.states = jax.tree_util.tree_map(jnp.copy, self.states)
            net._preprocessors = dict(self._preprocessors)
            net.output_shapes = dict(self.output_shapes)
            net._init_shapes = list(getattr(self, "_init_shapes", []))
            net.initialized = True
        # execution policy / loss weighting are config-level, not
        # init-dependent — copy them even for an uninitialized graph
        # (matches MultiLayerNetwork.clone())
        net.remat_segments = self.remat_segments
        net.output_loss_weights = dict(self.output_loss_weights)
        return net

    def summary(self):
        lines = ["=" * 72, f"{'Node':<26}{'Type':<26}{'Params':<12}", "=" * 72]
        total = 0
        for name in self.conf.topo_order:
            node = self.conf.nodes[name]
            n = sum(int(v.size) for v in jax.tree_util.tree_leaves(self.params.get(name, {})))
            total += n
            lines.append(f"{name:<26}{type(node.op).__name__:<26}{n:<12}")
        lines += ["=" * 72, f"Total params: {total}", "=" * 72]
        return "\n".join(lines)

    def save(self, path, save_updater: bool = False):
        from ..serde.model_serializer import save_model
        save_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path):
        from ..serde.model_serializer import load_model
        return load_model(path)
