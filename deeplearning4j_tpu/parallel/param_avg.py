"""Parameter-averaging distributed training.

Reference parity: ``org.deeplearning4j.spark.impl.paramavg
.ParameterAveragingTrainingMaster`` (and ParallelWrapper's
``averagingFrequency`` mode): each worker trains locally for
`averaging_frequency` steps on its own shard of the data stream, then
parameters (and optionally updater state) are averaged across workers.

TPU-first redesign: instead of shipping parameters through a Spark driver,
the whole averaging round is ONE XLA program — `shard_map` over the mesh's
'dp' axis gives every device its own parameter/optimizer replica (stacked
leading device axis), `lax.scan` runs the local steps on-device, and a
`psum`-mean over ICI replaces the driver aggregation. Host code only feeds
batches.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import data_parallel_mesh


class ParameterAveragingTrainer:
    """Train `net` with periodic parameter averaging over the dp mesh axis.

    averaging_frequency=1 with plain SGD is numerically identical to
    synchronous gradient averaging (averaging linear steps == stepping on
    the averaged gradient); larger frequencies trade sync cost for
    staleness exactly like the reference's Spark mode.
    """

    def __init__(self, net, mesh: Optional[Mesh] = None,
                 averaging_frequency: int = 5,
                 average_updater_state: bool = True):
        if not net.initialized:
            raise ValueError("initialize the network first (net.init(...))")
        if averaging_frequency < 1:
            raise ValueError("averaging_frequency must be >= 1")
        self.net = net
        self.mesh = mesh or data_parallel_mesh()
        if "dp" not in self.mesh.axis_names:
            raise ValueError("mesh needs a 'dp' axis")
        self.freq = int(averaging_frequency)
        self.average_updater_state = average_updater_state
        self.n = int(np.prod([s for a, s in zip(self.mesh.axis_names,
                                                self.mesh.devices.shape)
                              if a == "dp"]))
        self._round = None

    # ------------------------------------------------------------------ build
    def _build(self):
        net = self.net
        if net._optimizer is None:
            net._build_optimizer(1)
        optimizer = net._optimizer
        freq, n = self.freq, self.n

        def local_round(params, opt_state, states, xs, ys, rngs, fms, lms):
            """Runs on ONE device's replica. shard_map blocks keep the
            sharded leading axis at local size 1 — strip it, run `freq`
            sequential local steps over the (freq, b, ...) microbatches,
            psum-average, and re-add the axis for the stacked output."""
            unblk = partial(jax.tree_util.tree_map, lambda a: a[0])
            params, opt_state, states = (unblk(params), unblk(opt_state),
                                         unblk(states))
            xs, ys, rngs = xs[0], ys[0], rngs[0]
            fms = None if fms is None else fms[0]
            lms = None if lms is None else lms[0]

            def one(carry, inp):
                p, o, s = carry
                x, y, rng, fm, lm = inp
                (loss, s2), grads = jax.value_and_grad(
                    net._loss, has_aux=True)(p, s, x, y, rng, fm, lm)
                updates, o2 = optimizer.update(grads, o, p)
                p2 = optax.apply_updates(p, updates)
                p2 = net._apply_constraints(p2)
                return (p2, o2, s2), loss

            (params, opt_state, states), losses = lax.scan(
                one, (params, opt_state, states), (xs, ys, rngs, fms, lms))
            # driver aggregation -> psum over ICI
            params = jax.tree_util.tree_map(
                lambda a: lax.psum(a, "dp") / n, params)
            avg_if_float = lambda a: (lax.psum(a, "dp") / n  # noqa: E731
                                      if jnp.issubdtype(jnp.asarray(a).dtype,
                                                        jnp.floating) else a)
            if self.average_updater_state:
                opt_state = jax.tree_util.tree_map(avg_if_float, opt_state)
            states = jax.tree_util.tree_map(avg_if_float, states)
            loss = lax.pmean(jnp.mean(losses), "dp")
            reblk = partial(jax.tree_util.tree_map, lambda a: a[None])
            return reblk(params), reblk(opt_state), reblk(states), loss

        # every leaf is stacked over a leading device axis; batches are
        # (n*freq*b, ...) reshaped to (n, freq, b, ...) and split over dp
        def round_fn(stacked_params, stacked_opt, stacked_states, xs, ys,
                     rngs, fms, lms):
            sm = shard_map(
                local_round, mesh=self.mesh,
                in_specs=(P("dp"),) * 8,
                out_specs=(P("dp"), P("dp"), P("dp"), P()),
                check_vma=False)
            return sm(stacked_params, stacked_opt, stacked_states, xs, ys,
                      rngs, fms, lms)

        self._round = jax.jit(round_fn, donate_argnums=(0, 1, 2))
        return self._round

    # ------------------------------------------------------------------- fit
    def _stack(self, tree):
        """Replicate each leaf to a stacked (n, ...) array sharded over dp —
        device_put with the stacked sharding places one replica per device
        (broadcasting on the default device would transiently hold n full
        replicas of params + optimizer state on one chip)."""
        sh = NamedSharding(self.mesh, P("dp"))
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(
                np.broadcast_to(np.asarray(a)[None],
                                (self.n,) + tuple(np.shape(a))), sh), tree)

    def _unstack(self, tree):
        return jax.tree_util.tree_map(lambda a: a[0], tree)

    def fit(self, iterator, *, epochs: int = 1):
        """Feeds rounds of n_workers * averaging_frequency microbatches.
        A tail of fewer microbatches than a full round is trained with
        plain synchronous steps via one `net.fit` call on the averaged
        params (exact, no staleness; epoch_count advances once per epoch
        either way)."""
        net = self.net
        # peek without consuming (lists/tuples only; generator iterators hit
        # the same loud guard in _run_round on the first full round)
        probe = iterator[0] if isinstance(iterator, (list, tuple)) \
            and len(iterator) else None
        if probe is not None and isinstance(probe.features, (list, tuple)):
            raise NotImplementedError(
                "ParameterAveragingTrainer stacks single-arm DataSet "
                "batches; for MultiDataSet (multi-input/multi-output) "
                "training use ParallelWrapper instead")
        round_fn = self._round or self._build()
        if net._optimizer is None:
            net._build_optimizer(1)
        sp = self._stack(net.params)
        so = self._stack(net._opt_state)
        ss = self._stack(net.states)
        last = None
        need = self.n * self.freq
        for _ in range(epochs):
            buf = []
            tail_handled = False
            for ds in iterator:
                if isinstance(ds.features, (list, tuple)):
                    # generators bypass the list peek above; guard every
                    # batch so the tail path never feeds MultiDataSets
                    # into DataSet.merge
                    raise NotImplementedError(
                        "ParameterAveragingTrainer stacks single-arm "
                        "DataSet batches; for MultiDataSet use "
                        "ParallelWrapper instead")
                buf.append(ds)
                if len(buf) == need:
                    sp, so, ss, last = self._run_round(round_fn, sp, so, ss,
                                                       buf)
                    buf = []
            if buf:
                # flush the remainder synchronously on the averaged params,
                # one step PER microbatch (batch_size keeps the source
                # granularity); ONE net.fit call = one epoch_count bump +
                # one on_epoch_end
                from ..data.iterators import ListDataSetIterator
                net.params = self._unstack(sp)
                net._opt_state = self._unstack(so)
                net.states = self._unstack(ss)
                last_f = net.fit(ListDataSetIterator(
                    buf, batch_size=buf[0].num_examples()))
                last = jnp.asarray(last_f if last_f is not None else 0.0)
                tail_handled = True
                sp, so, ss = (self._stack(net.params),
                              self._stack(net._opt_state),
                              self._stack(net.states))
            if hasattr(iterator, "reset"):
                iterator.reset()
            if not tail_handled:
                net.epoch_count += 1
                for listener in net.listeners:
                    if hasattr(listener, "on_epoch_end"):
                        listener.on_epoch_end(net)
        net.params = self._unstack(sp)
        net._opt_state = self._unstack(so)
        net.states = self._unstack(ss)
        net._invalidate()
        return None if last is None else float(last)

    @staticmethod
    def _stack_masks(masks, shaped_like):
        """None-mix handling: all None -> None; else missing masks become
        all-ones of the present mask's per-example shape."""
        if all(m is None for m in masks):
            return None
        proto = next(m for m in masks if m is not None)
        filled = [np.ones_like(proto) if m is None else np.asarray(m)
                  for m in masks]
        return np.stack(filled).reshape(
            shaped_like + filled[0].shape[1:])

    def _run_round(self, round_fn, sp, so, ss, buf):
        net = self.net
        if isinstance(buf[0].features, (list, tuple)):
            raise NotImplementedError(
                "ParameterAveragingTrainer stacks single-arm DataSet "
                "batches; for MultiDataSet (multi-input/multi-output) "
                "training use ParallelWrapper instead")
        buf_x = [np.asarray(ds.features) for ds in buf]
        buf_y = [np.asarray(ds.labels) for ds in buf]
        b = buf_x[0].shape[0]
        if any(x.shape[0] != b for x in buf_x):
            raise ValueError("all microbatches in a round must share a "
                             "batch size (got mixed sizes)")
        lead = (self.n, self.freq, b)
        xs = np.stack(buf_x).reshape(lead + buf_x[0].shape[1:])
        ys = np.stack(buf_y).reshape(lead + buf_y[0].shape[1:])
        fms = self._stack_masks([ds.features_mask for ds in buf], lead)
        lms = self._stack_masks([ds.labels_mask for ds in buf], lead)
        net._host_key, sub = jax.random.split(net._host_key)
        rngs = jax.random.split(sub, self.n * self.freq).reshape(
            self.n, self.freq, 2)
        sp, so, ss, loss = round_fn(
            sp, so, ss, jnp.asarray(xs), jnp.asarray(ys), rngs,
            None if fms is None else jnp.asarray(fms),
            None if lms is None else jnp.asarray(lms))
        net._step_count += self.n * self.freq
        if net.listeners:
            # listeners read model state (checkpoint/eval): expose the
            # just-averaged replica, not the pre-fit params (a[0] makes a
            # fresh buffer, safe across the next round's donation)
            net.params = self._unstack(sp)
            net._opt_state = self._unstack(so)
            net.states = self._unstack(ss)
            lv = float(loss)
            for listener in net.listeners:
                listener.iteration_done(net, net._step_count,
                                        net.epoch_count, lv)
        return sp, so, ss, loss
