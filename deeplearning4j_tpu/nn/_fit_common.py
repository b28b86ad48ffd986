"""The one training loop behind ``MultiLayerNetwork.fit``,
``ComputationGraph.fit`` and ``ParallelWrapper.fit``: the jitted step
(``build_train_step``), the anomaly-detection switch, and the epoch loop
(``fit_epochs``), which decides in what order one iteration fetches, copies,
dispatches, syncs and reports. A class supplies its network, the step's
``jax.jit`` arguments and one function from a batch to the step's arguments
on the device; its ``fit()`` keeps the prologue (initialise, build or restore
the optimizer) that differs by what it restores. The scanned epoch is in
``_scan_common``."""

from __future__ import annotations

import jax
import optax

from ..obs import get_registry
from ..obs.spans import span


def build_train_step(net, name, **jit_kwargs):
    """``(sentinel, step)``: the whole training iteration of ``net`` as one
    pure function ``(params, states, opt_state, x, y, rng, fmask, lmask) ->
    (params, states, opt_state, loss, stats, next_rng)``, and the
    ``CompileSentinel`` ``name`` over its jit with params, states and
    optimizer state donated. ``jit_kwargs`` go to ``jax.jit``
    (``ParallelWrapper``'s ``in_shardings``)."""
    optimizer = net._optimizer
    with_stats = getattr(net, "_anomaly_detector", None) is not None
    # numerics sentinel (ISSUE 13): a detector with gate_updates=False
    # (policy "warn") observes grad stats WITHOUT the in-jit finiteness
    # gate — the poisoned update is applied, which is exactly what "warn"
    # promises
    gate = with_stats and getattr(net._anomaly_detector, "gate_updates", True)

    def step(params, states, opt_state, x, y, rng, fmask, lmask):
        # the per-step key split happens INSIDE the jitted step and the
        # next chain key rides the outputs: the fit loop never dispatches a
        # separate host-side split per batch (a real extra device launch
        # per step)
        use_rng, next_rng = jax.random.split(rng)
        (loss, new_states), grads = jax.value_and_grad(
            net._loss, has_aux=True)(params, states, x, y, use_rng,
                                     fmask, lmask)
        # the layers' ops carry their node's scope (<node>.<Type>); this is
        # the one name for what is no layer's
        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(grads, opt_state,
                                                      params)
            new_params = net._apply_constraints(
                optax.apply_updates(params, updates))
        stats = None
        if with_stats:
            # A non-finite batch becomes a whole-step no-op (params, opt
            # state, BN running stats) so the detector can raise without
            # the run already being poisoned.
            from ..train.anomaly import maybe_stats_and_gate
            stats, new_params, new_opt_state, new_states = \
                maybe_stats_and_gate(
                    gate, grads, params, new_params, opt_state,
                    new_opt_state, states, new_states)
        return new_params, new_states, new_opt_state, loss, stats, next_rng

    # compile sentinel (ISSUE 12): counts/times every compile of the
    # donated step and warns on post-warmup retraces — the wrapper is
    # transparent (fit_scanned's `.__wrapped__` and floor probes' `.lower`
    # delegate through)
    from ..obs.compiles import CompileSentinel
    return CompileSentinel(name, jax.jit(
        step, donate_argnums=(0, 1, 2), **jit_kwargs)), step


def enable_gradient_anomaly_detection(net, detector=None):
    """Failure detection (SURVEY §2.9): per-layer gradient stats computed
    inside the jitted step, checked host-side each iteration. Pass a
    configured ``train.anomaly.GradientAnomalyDetector`` or None for
    defaults. Call with detector=False to disable."""
    from ..train.anomaly import GradientAnomalyDetector
    if detector is False:
        net._anomaly_detector = None
    else:
        net._anomaly_detector = detector or GradientAnomalyDetector()
    net._train_step = None  # rebuild with/without stats
    net._scan_epoch = None
    return net


def fit_counters():
    """``(batches, staged_ahead)``: batches ``fit()`` dispatched, and those
    of them whose copy was issued while an earlier step was unsynced."""
    reg = get_registry()
    return (reg.counter("dl4j_fit_batches_total",
                        "batches fit() dispatched"),
            reg.counter("dl4j_fit_staged_ahead_total",
                        "batches copied to the device while an earlier "
                        "step was still unsynced"))


def stage_batch(batches, k, to_device):
    """Batch ``k`` of the call: ``fit.next`` (until the iterator hands it
    over) and ``fit.h2d`` (``to_device``: the ``jnp.asarray`` calls, which
    return before the bytes have moved; attrs ``bytes``), both carrying
    ``batch`` = k wherever they lie. Returns ``(ds, examples, arrays)``, or
    ``None`` where the source is exhausted.

    ``ds`` rides along so that the host arrays outlive the copy that reads
    them asynchronously. Holding it is all that takes today: no iterator
    writes into a batch it has handed over (``AsyncDataSetIterator._unpack``
    builds fresh arrays from the ring's bytes, and a batch sent by reference
    is the source's own), so nothing here aliases a reused buffer.
    """
    with span("fit.next", attrs={"batch": k}):
        ds = next(batches, None)
    if ds is None:
        return None
    with span("fit.h2d", attrs={"batch": k}) as h2d:
        examples, arrays = to_device(ds)
        h2d.set_attr("bytes", sum(
            a.nbytes for a in jax.tree_util.tree_leaves(arrays)))
    return ds, examples, arrays


def fit_epochs(net, iterator, epochs, step_fn, to_device):
    """``epochs`` passes of ``step_fn`` over ``iterator``, one batch staged
    ahead; the last loss as a float, or None where no batch came.
    ``to_device(ds)`` gives ``(examples, (x, y, fmask, lmask))``: the rows
    the batch came with (before any padding) and the step's arguments on
    the device.

    One ``fit`` root span a call (attrs ``epochs``) and one
    ``fit.iteration`` a pass (attrs ``batch``: the k-th batch of this call,
    the one the pass dispatches, and ``examples``) whose children, in order,
    are ``fit.dispatch`` (the step call on batch k's device arrays),
    ``fit.next`` and ``fit.h2d`` of batch k+1, then ``fit.loss_sync``
    (``float(loss)`` of step k, where listeners ask for it) and
    ``fit.listeners`` of step k. Every child carries its own ``batch``, so
    ``fit.next`` and ``fit.h2d`` of batch k+1 lie in the pass of batch k:
    its copy runs beside step k, and the listeners of step k still see the
    parameters as step k left them, before step k+1 is dispatched. The
    epoch's first ``fit.next`` and ``fit.h2d`` lie directly under ``fit``.
    The pass whose ``fit.next`` finds the iterator exhausted, the epoch's
    last batch's, carries ``end`` (an epoch without a batch leaves none).
    Where the score fetch is deferred, the pass of batch k ends with the
    ``fit.loss_sync`` and ``fit.listeners`` of batch k-1 instead, and the
    epoch's last pair lies directly under ``fit``. Either way batch k+1's
    copy is issued before the host waits for a loss, an exception out of
    its fetch reaches the caller after every finished step's report, and
    two batches are resident on the device at a time, the one in the step
    and the one staged."""
    anomaly_check = None
    if getattr(net, "_anomaly_detector", None) is not None:
        from ..train.anomaly import DelayedAnomalyCheck
        anomaly_check = DelayedAnomalyCheck(net._anomaly_detector)

    # Listener score fetches are deferred ONE iteration when every
    # attached listener opts in (`deferred_score_ok`, the pure logging
    # ones): float(loss) blocks until the step finishes, so fetching
    # step k-1's loss while step k is in flight keeps the device
    # pipeline full. Listeners that read model state at the reported
    # iteration (checkpointing, eval, NaN watchdog) keep the exact
    # synchronous semantics — params must match the (step, score) pair.
    defer_ok = all(getattr(ls, "deferred_score_ok", False)
                   for ls in net.listeners)
    pending = None
    last = None
    k = 0

    def report(loss_d, si, ei, batch):
        with span("fit.loss_sync", attrs={"batch": batch}):
            lv = float(loss_d)
        with span("fit.listeners", attrs={"batch": batch}):
            for listener in net.listeners:
                listener.iteration_done(net, si, ei, lv)

    def flush_pending():
        nonlocal pending
        if pending is not None:
            args, pending = pending, None
            report(*args)

    n_batches, n_ahead = fit_counters()
    with span("fit", attrs={"epochs": epochs}):
        # DL4J's fit wraps the source in an AsyncDataSetIterator so batch
        # prep runs on a background thread while the device computes; do
        # the same when the iterator opts in (async_supported). Started
        # inside the root span, so the producer's spans join this call's
        # trace.
        from ..data.async_iter import maybe_wrap_async
        run_iter, wrapped = maybe_wrap_async(iterator)
        try:
            for e in range(epochs):
                batches = iter(run_iter)
                staged = stage_batch(batches, k, to_device)
                while staged is not None:
                    with span("fit.iteration", attrs={"batch": k}) as iteration:
                        # `held`: batch k's host arrays, referenced until
                        # the next pass, so past the sync of the step that
                        # reads their copy (see stage_batch)
                        held, examples, (x, y, fmask, lmask) = staged
                        # examples-throughput telemetry (MetricsListener)
                        net._last_batch_size = int(examples)
                        iteration.set_attr("examples", net._last_batch_size)
                        with span("fit.dispatch", attrs={"batch": k}):
                            (net.params, net.states, net._opt_state, loss,
                             gstats, net._host_key) = step_fn(
                                net.params, net.states, net._opt_state, x,
                                y, net._host_key, fmask, lmask)
                        net._step_count += 1
                        n_batches.inc()
                        if anomaly_check is not None and gstats is not None:
                            anomaly_check.push(gstats, net._step_count)
                        last = loss
                        try:
                            # batch k+1 crosses to the device while step k runs
                            staged = stage_batch(batches, k + 1, to_device)
                            if staged is None:
                                iteration.set_attr("end", True)
                            else:
                                n_ahead.inc()
                        finally:
                            # step k's report, also where the iterator raised
                            if net.listeners:
                                if defer_ok:
                                    # step k-1's loss, while step k is in flight
                                    flush_pending()
                                    pending = (loss, net._step_count,
                                               net.epoch_count, k)
                                else:
                                    report(loss, net._step_count,
                                           net.epoch_count, k)
                        k += 1
                net.epoch_count += 1
                if e < epochs - 1:
                    if hasattr(run_iter, "reset"):
                        run_iter.reset()
                elif wrapped is not None:
                    # final epoch: close the wrapper FIRST so reset doesn't
                    # spin up a producer whose prefetch is thrown away
                    wrapped.close()
                    wrapped = None
                    if hasattr(iterator, "reset"):
                        iterator.reset()
                elif hasattr(run_iter, "reset"):
                    run_iter.reset()
                flush_pending()   # all iteration_done before on_epoch_end
                for listener in net.listeners:
                    if hasattr(listener, "on_epoch_end"):
                        listener.on_epoch_end(net)
        finally:
            # a mid-epoch exception must still deliver the completed step's
            # deferred callback (scores would end one step short) — but it
            # must never MASK the original error, and runs before close()
            try:
                flush_pending()
            except Exception:  # noqa: BLE001 — original exception wins
                pass
            if wrapped is not None:
                wrapped.close()
    if anomaly_check is not None:
        anomaly_check.flush()
    return None if last is None else float(last)
