"""MultiLayerNetwork — the sequential-network API, redesigned for XLA.

Reference parity: ``org.deeplearning4j.nn.multilayer.MultiLayerNetwork``
(init/fit/output/score/evaluate/params/summary, listeners, masking).

TPU-first redesign: instead of the reference's per-layer activate/
backpropGradient interpreter loop with workspaces, the WHOLE training
iteration — forward, loss, backward, updater, parameter update — is one
jitted pure function with params/opt-state donated (HBM reuse). Gradients
come from jax.value_and_grad over the composed forward; the updater chain is
optax. Listeners run on host between steps.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..train.updaters import NoOp, build_optimizer, gradient_normalization
from ._fit_common import (build_train_step,
                          enable_gradient_anomaly_detection, fit_epochs)
from ._scan_common import check_scan_listeners, fit_scanned_epochs
from .conf import MultiLayerConfiguration
from .layers.base import Ctx, Layer
from .layers.wrappers import unwrap
from .layers.core import LossLayer, OCNNOutputLayer, OutputLayer
from .layers.samediff_layer import SameDiffOutputLayer
from .preprocessors import CnnToFeedForwardPreProcessor
from .weightnoise import maybe_apply_weight_noise


def _is_ff_layer(layer: Layer) -> bool:
    from .layers.core import (DenseLayer, ElementWiseMultiplicationLayer,
                              EmbeddingLayer)
    from .layers.recurrent import LastTimeStep
    layer = unwrap(layer)
    return isinstance(layer, (DenseLayer, ElementWiseMultiplicationLayer)) and \
        not isinstance(layer, EmbeddingLayer)


def _is_rnn_layer(layer: Layer) -> bool:
    from .layers.attention import (RecurrentAttentionLayer, SelfAttentionLayer)
    from .layers.core import RnnOutputLayer
    from .layers.recurrent import BaseRecurrent, Bidirectional
    layer = unwrap(layer)
    return isinstance(layer, (BaseRecurrent, Bidirectional, SelfAttentionLayer,
                              RecurrentAttentionLayer, RnnOutputLayer))


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers: List[Layer] = conf.layers
        self._g = conf.globals_
        self.params: Dict[str, dict] = {}
        self.states: Dict[str, dict] = {}
        self._preprocessors: Dict[int, Any] = {}
        self._optimizer = None
        self._opt_state = None
        self._iters_per_epoch = 1
        self._step_count = 0
        self.epoch_count = 0
        self.listeners: List[Any] = []
        self.initialized = False
        self._train_step = None
        self._scan_epoch = None
        self._host_key = jax.random.PRNGKey(self._g.seed)
        # int n -> train-time forward runs as n jax.checkpoint segments
        # (activation remat; sequential analogue of
        # ComputationGraph.remat_segments)
        self.remat_segments = None

    @property
    def remat_segments(self):
        return self._remat_segments

    @remat_segments.setter
    def remat_segments(self, n):
        """Changing the remat policy invalidates every compiled step that
        traced the old forward."""
        if getattr(self, "_remat_segments", None) != n:
            self._train_step = None
            self._scan_epoch = None
            self._infer_fn = None
        self._remat_segments = n

    # ------------------------------------------------------------------ init
    def init(self, input_shape=None):
        """Resolve shapes layer-by-layer, create params (reference: init())."""
        if input_shape is None:
            if self.conf.input_type is not None:
                input_shape = tuple(self.conf.input_type[1])
            else:
                n_in = getattr(unwrap(self.layers[0]), "n_in", None)
                if not n_in:
                    raise ValueError("Provide input_shape or set_input_type on the config")
                input_shape = (int(n_in),)
        key = jax.random.PRNGKey(self._g.seed)
        shape = tuple(input_shape)
        self._init_input_shape = shape      # for TransferLearningHelper et al
        for i, layer in enumerate(self.layers):
            # auto preprocessor: conv/rnn activations into a flat FF layer
            if _is_ff_layer(layer) and len(shape) in (3, 4):  # cnn or cnn3d
                pp = CnnToFeedForwardPreProcessor()
                self._preprocessors[i] = pp
                shape = pp.out_shape(shape)
            if isinstance(unwrap(layer), OutputLayer) and not _is_rnn_layer(layer) and len(shape) in (3, 4):
                pp = CnnToFeedForwardPreProcessor()
                self._preprocessors[i] = pp
                shape = pp.out_shape(shape)
            key, sub = jax.random.split(key)
            p, s, shape = layer.init(sub, shape)
            self.params[f"layer_{i}"] = p
            self.states[f"layer_{i}"] = s
        self.output_shape = shape
        self.initialized = True
        return self

    # -------------------------------------------------------------- forward
    def _apply_one(self, i, params, states, h, new_states, *, train, rng,
                   fmask, lmask, stop_before_output):
        """Apply layer ``i`` to ``h``; returns (h, stopped). ``i`` keys the
        per-layer rng (fold_in), so segmented execution reproduces the
        monolithic walk's dropout/weight-noise draws exactly."""
        layer = self.layers[i]
        if stop_before_output and i == len(self.layers) - 1 and isinstance(
                unwrap(layer),
                (OutputLayer, LossLayer, SameDiffOutputLayer,
                 OCNNOutputLayer)):
            new_states[f"layer_{i}"] = states[f"layer_{i}"]
            return h, True
        if i in self._preprocessors:
            h = self._preprocessors[i](h)
        lrng = jax.random.fold_in(rng, i) if rng is not None else None
        ctx = Ctx(train=train, rng=lrng, mask=fmask, label_mask=lmask)
        # named scope = the profiler's layer map at the XLA level: the
        # fused executable's ops carry layer_i.<Type> in their metadata
        # (tensorboard xprof groups by it; trace-time only, zero runtime
        # cost). Same naming as obs.profiler's span attribution.
        with jax.named_scope(f"layer_{i}.{type(unwrap(layer)).__name__}"):
            if train and layer.dropout > 0.0 and lrng is not None:
                keep = 1.0 - layer.dropout
                dk = jax.random.fold_in(lrng, 997)
                m = jax.random.bernoulli(dk, keep, h.shape)
                h = jnp.where(m, h / keep, 0.0).astype(h.dtype)
            p_i = maybe_apply_weight_noise(layer, params[f"layer_{i}"],
                                           lrng, train)
            h, s_new = layer.apply(p_i, states[f"layer_{i}"], h, ctx)
        new_states[f"layer_{i}"] = s_new
        return h, False

    def _forward(self, params, states, x, *, train, rng, fmask=None, lmask=None,
                 stop_before_output=False):
        """Pure forward. Returns (activation, new_states)."""
        if train and getattr(self, "remat_segments", None):
            return self._forward_remat(
                params, states, x, train=train, rng=rng, fmask=fmask,
                lmask=lmask, stop_before_output=stop_before_output)
        new_states = {}
        h = x
        for i in range(len(self.layers)):
            h, stopped = self._apply_one(
                i, params, states, h, new_states, train=train, rng=rng,
                fmask=fmask, lmask=lmask,
                stop_before_output=stop_before_output)
            if stopped:
                break
        return h, new_states

    def _forward_remat(self, params, states, x, *, train, rng, fmask=None,
                      lmask=None, stop_before_output=False):
        """_forward with contiguous layer chunks under ``jax.checkpoint``:
        only chunk-boundary activations are saved for backward; in-chunk
        activations recompute. The sequential counterpart of
        ComputationGraph._forward_remat (single carried tensor, so the
        segment plan is just an even index split)."""
        n = len(self.layers)
        if int(self.remat_segments) > n:
            import warnings
            warnings.warn(
                f"remat_segments={int(self.remat_segments)} exceeds what "
                f"this {n}-layer net supports; using {n} checkpoint "
                "segments (activation footprint will be larger than "
                "configured)", stacklevel=3)
        nseg = max(1, min(int(self.remat_segments), n))
        bounds = [round(k * n / nseg) for k in range(nseg + 1)]
        h = x
        new_states = {}
        for a, b in zip(bounds[:-1], bounds[1:]):
            if a == b:
                continue

            def seg_fn(p, s, hh, rng_, fmask_, lmask_, _a=a, _b=b):
                ns = {}
                for i in range(_a, _b):
                    hh, stopped = self._apply_one(
                        i, p, s, hh, ns, train=train, rng=rng_,
                        fmask=fmask_, lmask=lmask_,
                        stop_before_output=stop_before_output)
                    if stopped:
                        break
                return hh, ns

            seg_params = {f"layer_{i}": params[f"layer_{i}"]
                          for i in range(a, b)}
            seg_states = {f"layer_{i}": states[f"layer_{i}"]
                          for i in range(a, b)}
            h, ns = jax.checkpoint(seg_fn)(seg_params, seg_states, h, rng,
                                           fmask, lmask)
            new_states.update(ns)
        return h, new_states

    def output(self, x, train: bool = False):
        """Inference forward (reference: output()). Jit-cached."""
        x = jnp.asarray(x)
        fn = self._get_infer_fn()
        return fn(self.params, self.states, x)

    def _get_infer_fn(self):
        if not hasattr(self, "_infer_fn") or self._infer_fn is None:
            def infer(params, states, x):
                y, _ = self._forward(params, states, x, train=False, rng=None)
                return y
            self._infer_fn = jax.jit(infer)
        return self._infer_fn

    def feed_forward(self, x, train: bool = False):
        """Per-layer activations list (reference: feedForward())."""
        x = jnp.asarray(x)
        acts = [x]
        h = x
        for i, layer in enumerate(self.layers):
            if i in self._preprocessors:
                h = self._preprocessors[i](h)
            ctx = Ctx(train=train, rng=None)
            h, _ = layer.apply(self.params[f"layer_{i}"], self.states[f"layer_{i}"], h, ctx)
            acts.append(h)
        return acts

    # ----------------------------------------------------------------- loss
    def _loss(self, params, states, x, y, rng, fmask, lmask):
        h, new_states = self._forward(params, states, x, train=True, rng=rng,
                                      fmask=fmask, lmask=lmask, stop_before_output=True)
        out_layer = unwrap(self.layers[-1])
        i = len(self.layers) - 1
        # the output layer's work happens HERE (the forward stops before
        # it) — scope it like _apply_one scopes every other layer
        with jax.named_scope(
                f"layer_{i}.{type(out_layer).__name__}.loss"):
            return self._loss_tail(out_layer, i, params, states, new_states,
                                   h, y, lmask)

    def _loss_tail(self, out_layer, i, params, states, new_states, h, y,
                   lmask):
        if isinstance(out_layer, OutputLayer):
            if i in self._preprocessors:
                h = self._preprocessors[i](h)
            from .layers.core import CenterLossOutputLayer
            if isinstance(out_layer, CenterLossOutputLayer):
                loss = out_layer.compute_loss(params[f"layer_{i}"], h, y, mask=lmask,
                                              state=states[f"layer_{i}"])
                new_states[f"layer_{i}"] = out_layer.update_state(
                    states[f"layer_{i}"], jax.lax.stop_gradient(h), y)
            else:
                loss = out_layer.compute_loss(params[f"layer_{i}"], h, y, mask=lmask)
        elif isinstance(out_layer, SameDiffOutputLayer):
            if i in self._preprocessors:
                h = self._preprocessors[i](h)
            loss = out_layer.compute_loss(params[f"layer_{i}"], h, y, mask=lmask)
        elif isinstance(out_layer, OCNNOutputLayer):
            if i in self._preprocessors:
                h = self._preprocessors[i](h)
            loss = out_layer.compute_loss(params[f"layer_{i}"], h, y, mask=lmask,
                                          state=states[f"layer_{i}"])
            new_states[f"layer_{i}"] = out_layer.update_state(
                states[f"layer_{i}"], h, params[f"layer_{i}"])
        elif isinstance(out_layer, LossLayer):
            loss = out_layer.compute_loss(h, y, mask=lmask)
        else:
            raise ValueError("Last layer must be an OutputLayer or LossLayer for fit()")
        loss = loss + self._reg_score(params)
        return loss, new_states

    def _reg_score(self, params):
        reg = 0.0
        for i, layer in enumerate(self.layers):
            if layer.l1 == 0.0 and layer.l2 == 0.0:
                continue
            for k, w in params[f"layer_{i}"].items():
                if k in ("b", "beta", "mean", "var"):
                    continue
                if layer.l1:
                    reg = reg + layer.l1 * jnp.sum(jnp.abs(w))
                if layer.l2:
                    reg = reg + 0.5 * layer.l2 * jnp.sum(jnp.square(w))
        return reg

    # ------------------------------------------------------------ optimizer
    def _param_labels(self):
        labels = {}
        has_override = False
        for i, layer in enumerate(self.layers):
            if layer.frozen:
                lab = "__frozen__"
                has_override = True
            elif layer.updater is not None:
                lab = f"__layer_{i}__"
                has_override = True
            else:
                lab = "__default__"
            labels[f"layer_{i}"] = jax.tree_util.tree_map(lambda _: lab, self.params[f"layer_{i}"])
        return (labels if has_override else None)

    def _build_optimizer(self, iters_per_epoch=1):
        g = self._g
        labels = self._param_labels()
        per_label = None
        if labels is not None:
            per_label = {"__default__": g.updater, "__frozen__": NoOp()}
            for i, layer in enumerate(self.layers):
                if layer.updater is not None and not layer.frozen:
                    per_label[f"__layer_{i}__"] = layer.updater
        # l1/l2 handled inside loss (reg term differentiates through); don't
        # double-apply in the optimizer chain.
        self._optimizer = build_optimizer(
            g.updater, grad_norm=g.grad_norm, grad_norm_threshold=g.grad_norm_threshold,
            iters_per_epoch=iters_per_epoch,
            param_labels=labels, per_label_updaters=per_label)
        self._opt_state = self._optimizer.init(self.params)
        upstream = getattr(self, "_upstream_adam_state", None)
        if upstream is not None:  # resume from an upstream DL4J zip — graft
            # here so EVERY optimizer consumer (fit/fit_scanned/
            # ParallelWrapper) picks the restored m/v/count up
            from ..serde.upstream_dl4j import graft_adam_state
            self._opt_state = graft_adam_state(self._opt_state, upstream)
            self._upstream_adam_state = None

    def _apply_constraints(self, params):
        from ..train.constraints import apply_constraints
        for i, layer in enumerate(self.layers):
            if layer.frozen:      # frozen params must stay bit-identical
                continue
            if layer.constraints:
                params[f"layer_{i}"] = apply_constraints(
                    params[f"layer_{i}"], layer.constraints, weights=True)
            if layer.bias_constraints:
                params[f"layer_{i}"] = apply_constraints(
                    params[f"layer_{i}"], layer.bias_constraints,
                    weights=False, biases=True)
        return params

    def _get_train_step(self):
        if self._train_step is None:
            self._train_step, _ = build_train_step(self, "mln_train_step")
        return self._train_step

    # the module-level function, bound as a method
    enable_gradient_anomaly_detection = enable_gradient_anomaly_detection

    # ------------------------------------------------------------------ fit
    def fit(self, data, labels=None, *, epochs: int = 1):
        """fit(DataSetIterator) | fit(DataSet) | fit(features, labels).

        Reference: MultiLayerNetwork.fit — one optimizer step per minibatch,
        listeners invoked per iteration, epoch counter maintained. The loop
        is ``_fit_common.fit_epochs``.
        """
        from ..data.dataset import DataSet
        if labels is not None:
            data = DataSet(jnp.asarray(data), jnp.asarray(labels))
        if isinstance(data, DataSet):
            iterator = [data]
        else:
            iterator = data
        if not self.initialized:
            first = next(iter(iterator))
            self.init(tuple(np.asarray(first.features).shape[1:]))
            if hasattr(iterator, "reset"):
                iterator.reset()
        if self._optimizer is None:
            try:
                ipe = len(iterator)
            except TypeError:
                ipe = 1
            self._iters_per_epoch = max(int(ipe), 1)
            self._build_optimizer(self._iters_per_epoch)
            restored = getattr(self, "_restored_opt_state", None)
            if restored is not None:  # resume updater state from checkpoint
                self._opt_state = jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(self._opt_state),
                    jax.tree_util.tree_leaves(restored))
                self._restored_opt_state = None

        def to_device(ds):
            x = jnp.asarray(ds.features)
            return x.shape[0], (
                x, jnp.asarray(ds.labels),
                None if ds.features_mask is None else jnp.asarray(ds.features_mask),
                None if ds.labels_mask is None else jnp.asarray(ds.labels_mask))

        return fit_epochs(self, iterator, epochs, self._get_train_step(),
                          to_device)

    def fit_scanned(self, data, *, epochs: int = 1):
        """TPU-idiomatic epoch loop: ONE jit dispatch per epoch.

        Stacks the epoch's minibatches to (K, B, ...) and runs the train
        step as a ``lax.scan`` over them, so per-step dispatch overhead
        (pytree flatten + launch latency — comparable to the whole step
        for small models) is paid once
        per EPOCH instead of once per batch. Semantics vs :meth:`fit`:
        identical parameter trajectory (same step math, same rng chain);
        listeners fire per-iteration AFTER the epoch's dispatch from the
        scanned loss history (one device fetch for all K losses), so
        listeners that inspect model state mid-epoch (checkpointing,
        evaluative) see the post-epoch model and are rejected loudly.

        Requires equally-shaped, mask-free minibatches (the stacked scan
        is a single compiled program). The reference has no analogue —
        this is what an XLA-native training loop looks like.

        TPU-targeted: XLA:CPU lowers conv/matmul inside loop bodies to a
        slow generic path (measured 14x vs the per-step loop for a conv
        step), so on CPU prefer fit(); on TPU loop bodies get the same
        MXU codegen as straight-line code and the dispatch saving is the
        whole point.
        """
        from ..data.dataset import DataSet
        if isinstance(data, DataSet):
            batches = [data]
        else:
            batches = list(data)
        if not batches:
            return None
        if any(b.features_mask is not None or b.labels_mask is not None
               for b in batches):
            raise ValueError("fit_scanned does not support masked batches; "
                             "use fit()")
        shapes = {(np.asarray(b.features).shape, np.asarray(b.labels).shape)
                  for b in batches}
        if len(shapes) > 1:
            raise ValueError(f"fit_scanned needs equally-shaped batches, "
                             f"got {sorted(shapes)}; use fit()")
        check_scan_listeners(self)
        if not self.initialized:
            self.init(tuple(np.asarray(batches[0].features).shape[1:]))
        if self._optimizer is None:
            self._iters_per_epoch = len(batches)
            self._build_optimizer(self._iters_per_epoch)
        xs = jnp.stack([jnp.asarray(b.features) for b in batches])
        ys = jnp.stack([jnp.asarray(b.labels) for b in batches])
        return fit_scanned_epochs(
            self, self, self._get_train_step().__wrapped__, xs, ys, epochs)

    # ---------------------------------------------------------------- score
    def score(self, dataset=None):
        """Loss (incl. regularization) on a DataSet (reference: score())."""
        if dataset is None:
            raise ValueError("score() requires a DataSet")
        x = jnp.asarray(dataset.features)
        y = jnp.asarray(dataset.labels)
        fmask = None if dataset.features_mask is None else jnp.asarray(dataset.features_mask)
        lmask = None if dataset.labels_mask is None else jnp.asarray(dataset.labels_mask)
        loss, _ = self._loss(self.params, self.states, x, y, None, fmask, lmask)
        return float(loss)

    def gradient_and_score(self, dataset):
        """(gradients pytree, score) — reference computeGradientAndScore()."""
        x = jnp.asarray(dataset.features)
        y = jnp.asarray(dataset.labels)
        (loss, _), grads = jax.value_and_grad(self._loss, has_aux=True)(
            self.params, self.states, x, y, None, None, None)
        return grads, float(loss)

    # ------------------------------------------------------------- evaluate
    def evaluate(self, iterator, top_n: int = 1):
        from ..eval.classification import Evaluation
        ev = Evaluation(top_n=top_n)
        for ds in iterator:
            preds = self.output(jnp.asarray(ds.features))
            mask = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
            ev.eval(jnp.asarray(ds.labels), preds, mask=mask)
        if hasattr(iterator, "reset"):
            iterator.reset()
        return ev

    def evaluate_regression(self, iterator):
        from ..eval.regression import RegressionEvaluation
        ev = RegressionEvaluation()
        for ds in iterator:
            preds = self.output(jnp.asarray(ds.features))
            ev.eval(jnp.asarray(ds.labels), preds)
        if hasattr(iterator, "reset"):
            iterator.reset()
        return ev

    def evaluate_roc(self, iterator, threshold_steps: int = 0):
        from ..eval.roc import ROC
        roc = ROC(threshold_steps)
        for ds in iterator:
            preds = self.output(jnp.asarray(ds.features))
            roc.eval(jnp.asarray(ds.labels), preds)
        if hasattr(iterator, "reset"):
            iterator.reset()
        return roc

    # ------------------------------------------------------------ listeners
    # ------------------------------------------------- streaming inference
    def rnn_time_step(self, x):
        """Stateful streaming inference — reference rnnTimeStep: feed one
        step (B, C) or a chunk (B, T, C); every recurrent layer's hidden
        state persists across calls until rnn_clear_previous_state(). One
        jitted scan per chunk; the carry pytree lives on device between
        calls (no host round-trip in a generation loop)."""
        from .layers.recurrent import (BaseRecurrent, Bidirectional,
                                       LastTimeStep)
        from .layers.wrappers import TimeDistributedLayer
        for layer in self.layers:
            if isinstance(unwrap(layer), (Bidirectional, LastTimeStep,
                                          TimeDistributedLayer)):
                raise NotImplementedError(
                    f"rnn_time_step cannot stream through "
                    f"{type(unwrap(layer)).__name__}: it needs the full "
                    f"sequence (reference rnnTimeStep has the same limit)")
        x = jnp.asarray(x)
        # 2-D *integer* input is a (B, T) token-id chunk for embedding-fronted
        # models, NOT a single (B, C) feature step; only float 2-D is a step.
        integer = jnp.issubdtype(x.dtype, jnp.integer)
        single = (x.ndim == 2 and not integer) or (x.ndim == 1 and integer)
        if single:
            x = x[:, None] if x.ndim == 1 else x[:, None, :]
        batch = x.shape[0]

        def carry_dtype(ul):
            # must match what the cell emits: the post-cast compute dtype
            if ul.compute_dtype is not None:
                return ul.compute_dtype
            return x.dtype if jnp.issubdtype(x.dtype, jnp.floating) \
                else self._g.param_dtype

        old = getattr(self, "_rnn_carries", None) or {}
        if getattr(self, "_rnn_carry_batch", None) != batch:
            old = {}  # batch changed: stale state is meaningless
        carries = {}
        for i, layer in enumerate(self.layers):
            ul = unwrap(layer)
            if isinstance(ul, BaseRecurrent):
                key = f"layer_{i}"
                carries[key] = old.get(key)
                if carries[key] is None:  # keep rnn_set_previous_state values
                    carries[key] = ul.init_carry(batch, carry_dtype(ul))
        self._rnn_carry_batch = batch

        if getattr(self, "_rnn_stream_fn", None) is None:
            def stream(params, states, carries, xs):
                def step(cs, xt):
                    h = xt
                    new_cs = {}
                    for i, layer in enumerate(self.layers):
                        key = f"layer_{i}"
                        if i in self._preprocessors:  # same as _forward
                            h = self._preprocessors[i](h)
                        ul = unwrap(layer)
                        if isinstance(ul, BaseRecurrent):
                            h, c = ul.step_apply(params[key], cs[key], h,
                                                 Ctx(train=False))
                            new_cs[key] = c
                        else:
                            h, _ = layer.apply(params[key], states[key], h,
                                               Ctx(train=False))
                    return new_cs, h
                cs, ys = jax.lax.scan(step, carries, xs.swapaxes(0, 1))
                return ys.swapaxes(0, 1), cs
            self._rnn_stream_fn = jax.jit(stream)

        y, carries = self._rnn_stream_fn(self.params, self.states, carries, x)
        self._rnn_carries = carries
        return y[:, 0] if single else y

    def rnn_clear_previous_state(self):
        """Reference rnnClearPreviousState: drop all streaming state."""
        self._rnn_carries = None
        self._rnn_carry_batch = None

    def rnn_get_previous_state(self, layer_idx: int):
        carries = getattr(self, "_rnn_carries", None) or {}
        return carries.get(f"layer_{layer_idx}")

    def rnn_set_previous_state(self, layer_idx: int, state):
        carries = dict(getattr(self, "_rnn_carries", None) or {})
        carries[f"layer_{layer_idx}"] = state
        self._rnn_carries = carries
        # record the batch the injected state implies so the next
        # rnn_time_step keeps it instead of re-initializing
        leaf = jax.tree_util.tree_leaves(state)[0]
        self._rnn_carry_batch = leaf.shape[0]

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)

    # ----------------------------------------------------------- params API
    def num_params(self) -> int:
        return sum(int(p.size) for p in jax.tree_util.tree_leaves(self.params))

    def get_param(self, layer_idx: int, name: str):
        return self.params[f"layer_{layer_idx}"][name]

    def set_param(self, layer_idx: int, name: str, value):
        self.params[f"layer_{layer_idx}"][name] = jnp.asarray(value)
        self._invalidate()

    def params_flat(self):
        """Single flat vector, reference INDArray params() order: layer order."""
        leaves = jax.tree_util.tree_leaves(self.params)
        return jnp.concatenate([l.ravel() for l in leaves]) if leaves else jnp.zeros((0,))

    def set_params_flat(self, flat):
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        out, off = [], 0
        for l in leaves:
            n = int(l.size)
            out.append(jnp.asarray(flat[off:off + n]).reshape(l.shape).astype(l.dtype))
            off += n
        self.params = jax.tree_util.tree_unflatten(treedef, out)
        self._invalidate()

    def _invalidate(self):
        self._infer_fn = None
        self._train_step = None
        self._scan_epoch = None
        self._rnn_stream_fn = None

    def clone(self):
        import copy
        net = MultiLayerNetwork(copy.deepcopy(self.conf))
        net.remat_segments = self.remat_segments
        if self.initialized:
            # REAL copies: fit() donates param buffers, so sharing arrays
            # would let the clone's training invalidate the source's
            net.params = jax.tree_util.tree_map(jnp.copy, self.params)
            net.states = jax.tree_util.tree_map(jnp.copy, self.states)
            net._preprocessors = dict(self._preprocessors)
            # a net restored without input_type has params but never ran
            # shape resolution — clone what exists
            if hasattr(self, "output_shape"):
                net.output_shape = self.output_shape
            net.initialized = True
        return net

    # -------------------------------------------------------------- summary
    def summary(self) -> str:
        lines = ["=" * 72,
                 f"{'LayerName (idx)':<28}{'Output Shape':<20}{'Param Count':<12}",
                 "=" * 72]
        total = 0
        for i, layer in enumerate(self.layers):
            p = self.params.get(f"layer_{i}", {})
            n = sum(int(v.size) for v in jax.tree_util.tree_leaves(p))
            total += n
            name = layer.name or type(layer).__name__
            lines.append(f"{name + f' ({i})':<28}{'-':<20}{n:<12}")
        lines += ["=" * 72, f"Total params: {total}", "=" * 72]
        return "\n".join(lines)

    # ----------------------------------------------------------------- save
    def save(self, path, save_updater: bool = False):
        from ..serde.model_serializer import save_model
        save_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path):
        from ..serde.model_serializer import load_model
        return load_model(path)
