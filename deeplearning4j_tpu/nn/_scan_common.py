"""The one scanned epoch behind the three ``fit_scanned`` (MLN / CG /
ParallelWrapper): the listener/anomaly gate, the ``lax.scan`` of the train
step over an epoch's stacked batches with its jit, the loop over epochs and
the post-epoch listener replay. A class keeps its validation and its
stacking of the epoch (and hands over its jit arguments)."""

from __future__ import annotations

import jax
import numpy as np
from jax import lax


def check_scan_listeners(net):
    """Scanned epochs fetch losses after the dispatch: only listeners that
    opted into deferred scores may run, and per-step anomaly gating cannot."""
    for ls in net.listeners:
        if not getattr(ls, "deferred_score_ok", False):
            raise ValueError(
                f"listener {type(ls).__name__} needs exact per-"
                "iteration model state; use fit()")
    if getattr(net, "_anomaly_detector", None) is not None:
        raise ValueError("gradient anomaly detection gates per step; "
                         "use fit()")


def fit_scanned_epochs(owner, net, step, xs, ys, epochs, **jit_kwargs):
    """``epochs`` dispatches of ONE program each: the raw (unjitted) train
    ``step`` of ``net`` scanned over the K stacked batches ``(xs, ys)``,
    the same rng chain as ``fit()``. The jit (params, states and optimizer
    state donated; ``jit_kwargs`` from the caller) is cached as
    ``owner._scan_epoch``, where whoever owns the step invalidates it.
    Returns the last loss as a float, None without an epoch."""
    if getattr(owner, "_scan_epoch", None) is None:
        def scan_epoch(params, states, opt_state, rng, xs, ys):
            def body(carry, xy):
                p, s, o, k = carry
                x, y = xy
                p, s, o, loss, _, k = step(p, s, o, x, y, k, None, None)
                return (p, s, o, k), loss
            (params, states, opt_state, rng), losses = lax.scan(
                body, (params, states, opt_state, rng), (xs, ys))
            return params, states, opt_state, rng, losses
        owner._scan_epoch = jax.jit(scan_epoch, donate_argnums=(0, 1, 2),
                                    **jit_kwargs)
    n_batches = int(jax.tree_util.tree_leaves(xs)[0].shape[0])
    losses = None
    for _ in range(epochs):
        (net.params, net.states, net._opt_state, net._host_key,
         losses) = owner._scan_epoch(net.params, net.states, net._opt_state,
                                     net._host_key, xs, ys)
        net._step_count += n_batches
        net.epoch_count += 1
        replay_scan_listeners(net, losses, n_batches)
    return None if losses is None else float(np.asarray(losses)[-1])


def replay_scan_listeners(net, losses, n_batches):
    """Fire per-iteration listeners from the scanned loss history (ONE
    device fetch for all K losses), then epoch-end hooks."""
    if not net.listeners:
        return
    host_losses = np.asarray(losses)
    base = net._step_count - n_batches
    for i, lv in enumerate(host_losses):
        for listener in net.listeners:
            listener.iteration_done(net, base + i + 1,
                                    net.epoch_count - 1, float(lv))
    for listener in net.listeners:
        if hasattr(listener, "on_epoch_end"):
            listener.on_epoch_end(net)
