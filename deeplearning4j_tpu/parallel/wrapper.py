"""ParallelWrapper / ParallelInference — data-parallel training & inference.

Reference parity: ``org.deeplearning4j.parallelism.ParallelWrapper``
(replicate model over N devices, split each batch, average gradients) and
``ParallelInference`` (round-robin batched inference workers).

TPU-first redesign: no worker threads, no averaging step, no parameter
server. The SAME jitted train step as single-device, compiled over a mesh:
params replicated (or fsdp-sharded), batch sharded over dp. XLA inserts the
gradient all-reduce over ICI where the reference moved gradients over PCIe/
Aeron. `fit()` is a drop-in for MultiLayerNetwork/ComputationGraph fit.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nn._fit_common import build_train_step, fit_epochs
from ..nn._scan_common import check_scan_listeners, fit_scanned_epochs
from ..obs import get_registry
from .mesh import data_parallel_mesh, shard_params_fsdp


def _unpack_batch(ds):
    """DataSet or MultiDataSet -> (x, y, fmask, lmask). Multi-arm features/
    labels become tuples (CG._as_input_dict zips them with conf.inputs /
    conf.outputs); MultiDataSet masks (plural attrs) collapse to the single
    mask the network applies, or raise if there are several."""
    feats = ds.features
    labs = ds.labels
    if isinstance(feats, (list, tuple)) or isinstance(labs, (list, tuple)):
        def one(ms, what):
            if ms is None:
                return None
            ms = [m for m in ms if m is not None]
            if len(ms) > 1:
                raise NotImplementedError(
                    f"ParallelWrapper supports at most one {what} mask per "
                    "MultiDataSet (the network applies a single mask)")
            return ms[0] if ms else None
        return (tuple(feats) if isinstance(feats, (list, tuple)) else feats,
                tuple(labs) if isinstance(labs, (list, tuple)) else labs,
                one(getattr(ds, "features_masks", None), "features"),
                one(getattr(ds, "labels_masks", None), "labels"))
    return feats, labs, getattr(ds, "features_mask", None), \
        getattr(ds, "labels_mask", None)


def _padder(pad, zero=False):
    """Pad `pad` rows onto axis 0: repeat the last row (batch arrays) or
    zeros (masks, so padded rows drop out of the loss)."""
    def f(a):
        a = np.asarray(a)
        tail = (np.zeros((pad,) + a.shape[1:], a.dtype) if zero
                else np.repeat(a[-1:], pad, 0))
        return np.concatenate([a, tail])
    return f


class ParallelWrapper:
    """Data-parallel trainer over a mesh's 'dp' (and optional 'fsdp') axis."""

    def __init__(self, net, mesh: Optional[Mesh] = None, use_fsdp: bool = False,
                 drift_audit: bool = True):
        if not net.initialized:
            raise ValueError("initialize the network first (net.init(...))")
        self.net = net
        self.mesh = mesh or data_parallel_mesh()
        self.use_fsdp = use_fsdp and "fsdp" in self.mesh.axis_names
        # ISSUE 13: checksum the per-device param replicas at the end of
        # each fit call (dl4j_replica_* — the dp lockstep audit)
        self.drift_audit = bool(drift_audit)
        self._step = None
        self._scan_epoch = None
        self._rep = NamedSharding(self.mesh, P())
        batch_axes = tuple(a for a in ("dp", "fsdp") if a in self.mesh.axis_names)
        self._batch_sh = NamedSharding(self.mesh, P(batch_axes or None))
        # batches divide only the axes they are SHARDED over — padding to
        # mesh.size on a dp×tp mesh would add unmasked duplicate rows
        self._batch_div = int(np.prod([self.mesh.shape[a]
                                       for a in batch_axes])) if batch_axes \
            else 1
        if "tp" in self.mesh.axis_names:
            # tensor parallel: layers that declare param_pspecs (tp.py's
            # Column/RowParallelDense, ShardedSelfAttention) get their
            # Megatron sharding; GSPMD inserts the psums when the step
            # compiles. With use_fsdp, params the tp resolver left
            # replicated get the fsdp layout instead (the two compose).
            from .tp import network_param_shardings
            self._param_sh = network_param_shardings(self.mesh, net)
            if self.use_fsdp:
                fsdp_sh = shard_params_fsdp(self.mesh, net.params)
                self._param_sh = jax.tree_util.tree_map(
                    lambda t, f: f if t.spec == P() else t,
                    self._param_sh, fsdp_sh)
        elif self.use_fsdp:
            self._param_sh = shard_params_fsdp(self.mesh, net.params)
        else:
            self._param_sh = jax.tree_util.tree_map(lambda _: self._rep, net.params)
        # place params/states once
        net.params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, s), net.params, self._param_sh)
        net.states = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._rep), net.states)

    @property
    def workers(self) -> int:
        return self.mesh.size

    def _build_step(self):
        net = self.net
        if net._optimizer is None:
            net._build_optimizer(1)
            # re-place fresh opt state
            net._opt_state = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, self._rep), net._opt_state)
        # the compiled step traced the net's detector, gate and remat policy
        # (net._loss routes on it) — record them so a later toggle forces a
        # rebuild
        self._built_for = self._step_key()
        # the single-device step (stats, gate, constraints and all), compiled
        # over the mesh; `_step_raw`, unjitted, is what fit_scanned scans over
        self._step, self._step_raw = build_train_step(
            net, "pw_train_step",
            in_shardings=(self._param_sh,
                          jax.tree_util.tree_map(lambda _: self._rep, net.states),
                          None,  # opt state: let the compiler propagate
                          self._batch_sh, self._batch_sh, self._rep,
                          self._batch_sh, self._batch_sh))
        return self._step

    def _step_key(self):
        """What of the net a compiled step depends on beyond its shapes."""
        det = getattr(self.net, "_anomaly_detector", None)
        return (det is not None,
                det is not None and getattr(det, "gate_updates", True),
                getattr(self.net, "remat_segments", None))

    def _current_step(self):
        if self._step is not None and self._built_for != self._step_key():
            self._step = None        # toggled since compile — rebuild
            self._scan_epoch = None  # scans over _step_raw — same staleness
        return self._step or self._build_step()

    def _to_device(self, ds):
        """A batch as the step's arguments; a final partial batch is padded
        to divide the mesh's batch axes, and counts the rows it came with."""
        x, y, fmask, lmask = _unpack_batch(ds)
        rows = (x[0] if isinstance(x, tuple) else x).shape[0]
        if rows % self._batch_div:
            # padding is host work — device-resident arrays fetch once here
            # (partial final batch only); full batches pass straight through
            # without a host bounce
            pad = self._batch_div - rows % self._batch_div
            x = jax.tree_util.tree_map(_padder(pad), x)
            y = jax.tree_util.tree_map(_padder(pad), y)
            # padded rows masked out entirely
            fmask, lmask = (None if m is None else jax.tree_util.tree_map(
                _padder(pad, zero=True), m) for m in (fmask, lmask))
        # placed by the step's in_shardings, not here
        return rows, jax.tree_util.tree_map(jnp.asarray, (x, y, fmask, lmask))

    def fit(self, iterator, *, epochs: int = 1):
        """``nn._fit_common.fit_epochs`` on the mesh-compiled step, between
        the per-replica memory census and the replica drift audit."""
        net = self.net
        step_fn = self._current_step()
        # memory census (ISSUE 12), per replica: an fsdp-sharded param
        # tree reports what EACH device holds — the gauge the ZeRO
        # update-sharding PR (ROADMAP item 4) reads for its per-chip
        # memory-drop proof. Once per fit call, off the batch loop.
        try:
            from ..obs import memory as obs_memory
            components = {"params": net.params}
            if getattr(net, "_opt_state", None) is not None:
                components["optimizer"] = net._opt_state
            if getattr(net, "states", None) is not None:
                components["states"] = net.states
            obs_memory.emit_census(components, source="parallel_fit",
                                   per_replica=True)
        except Exception:  # noqa: BLE001 — census is decoration
            pass
        last = fit_epochs(net, iterator, epochs, step_fn, self._to_device)
        # drift audit (ISSUE 13): per-device checksums over the
        # replicated params at the end of every fit call — the dp
        # replicas hold COPIES of the same logical array and must be
        # bit-identical; zero drift here is the lockstep proof the
        # ZeRO update-sharding equivalence case (ROADMAP 4) cites.
        # Once per fit (not per batch): the audit fetches every
        # replica's copy to host. Decoration — never takes down a fit.
        if self.drift_audit and self.workers > 1:
            try:
                self.audit_drift()
            except Exception:  # noqa: BLE001 — audit is decoration
                pass
        return last

    def audit_drift(self):
        """Checksum every device's copy of the replicated params NOW
        (``obs.numerics.audit_params``) and return the verdict:
        ``{round, replicas, max_drift, bit_identical}``. fsdp/tp-sharded
        leaves are skipped — each device holds a different slice, there
        is no cross-replica copy to compare."""
        from ..obs import numerics as obs_numerics
        return obs_numerics.audit_params(self.net.params,
                                         source="parallel_fit")

    def fit_scanned(self, data, *, epochs: int = 1):
        """One jit dispatch per EPOCH across the dp mesh: the epoch's
        equally-shaped minibatches stack to (K, B, ...) sharded over the
        batch axes, and the dp train step runs as a ``lax.scan`` over K.
        Composes the two throughput levers — data-parallel sharding and
        the scanned epoch loop (net.fit_scanned) — so per-step dispatch
        overhead (the quantity `bench.py dpoverhead` measures) is paid
        once per epoch. Same restrictions as net.fit_scanned: no masks,
        no anomaly gating, deferred-score listeners only; single-arm
        DataSet batches (MultiDataSet: use fit())."""
        net = self.net
        batches = [data] if not isinstance(data, (list, tuple)) else list(data)
        if not batches:
            return None
        if any(isinstance(b.features, (list, tuple)) for b in batches):
            raise ValueError("fit_scanned supports single-arm DataSet "
                             "batches; use fit() for MultiDataSet")
        if any(getattr(b, "features_mask", None) is not None
               or getattr(b, "labels_mask", None) is not None
               for b in batches):
            raise ValueError("fit_scanned does not support masked batches; "
                             "use fit()")
        shapes = {(np.shape(b.features), np.shape(b.labels))
                  for b in batches}
        if len(shapes) > 1:
            raise ValueError(f"fit_scanned needs equally-shaped batches, "
                             f"got {sorted(shapes)}; use fit()")
        if batches[0].features.shape[0] % self._batch_div:
            raise ValueError(
                f"batch size {batches[0].features.shape[0]} must divide the "
                f"mesh batch axes ({self._batch_div}) — fit_scanned does "
                "not pad")
        check_scan_listeners(net)
        if epochs <= 0:
            return None
        self._current_step()
        xs = jnp.stack([jnp.asarray(b.features) for b in batches])
        ys = jnp.stack([jnp.asarray(b.labels) for b in batches])
        # stacked batches: leading K axis replicated, batch axes sharded
        stacked_sh = NamedSharding(self.mesh, P(None, *self._batch_sh.spec))
        return fit_scanned_epochs(
            self, net, self._step_raw, xs, ys, epochs,
            in_shardings=(self._param_sh,
                          jax.tree_util.tree_map(lambda _: self._rep,
                                                 net.states),
                          None, self._rep, stacked_sh, stacked_sh))


class ParallelInference:
    """Sharded batched inference (reference ParallelInference).

    Splits incoming batches over the dp axis; with `dynamic_batching`,
    requests accumulate to `max_batch` before one device sweep. With
    ``max_wait_ms`` set, a partial batch is flushed by a deadline timer
    once its OLDEST request has waited that long — a trickle of traffic
    below `max_batch` no longer waits forever for a flush it can't
    trigger. Every ``submit`` returns a Future for that request's rows,
    resolved at whichever flush carries them (size threshold, deadline,
    or an explicit ``flush()``).
    """

    def __init__(self, net, mesh: Optional[Mesh] = None, max_batch: int = 64,
                 max_wait_ms: Optional[float] = None):
        self.net = net
        self.mesh = mesh or data_parallel_mesh()
        self.max_batch = max_batch
        self.max_wait_ms = max_wait_ms
        self._rep = NamedSharding(self.mesh, P())
        batch_axes = tuple(a for a in ("dp",) if a in self.mesh.axis_names)
        self._batch_sh = NamedSharding(self.mesh, P(batch_axes or None))
        self._batch_div = (self.mesh.shape["dp"]
                           if "dp" in self.mesh.axis_names else 1)
        # Keep a LOCAL placed copy of params/states on THIS mesh: a net
        # trained under a different mesh (e.g. dp×tp ParallelWrapper) hands
        # us arrays from a foreign mesh, and mutating the net would break
        # the trainer's compiled step. Layers that declare tp pspecs stay
        # sharded when this mesh has a tp axis; everything else (including
        # tp shards when the axis is absent) gathers to replicated.
        from .tp import network_param_shardings
        self._param_sh = network_param_shardings(self.mesh, net)
        self._params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, s), net.params, self._param_sh)
        self._states = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._rep), net.states)
        self._infer = None
        self._pending = []
        self._pending_ts = []  # enqueue time per request (queue-wait metric)
        self._pending_futures = []   # one Future per submitted request
        self._lock = threading.RLock()
        self._timer: Optional[threading.Timer] = None
        if max_wait_ms is not None:
            # a deadline timer firing DURING interpreter shutdown
            # dispatches into a jax runtime that is mid-teardown and
            # aborts the process (std::terminate). atexit runs before
            # jax's own exit hooks (LIFO; jax registered at import), so
            # cancel-or-drain the timer while the runtime is still up.
            import atexit
            import weakref
            ref = weakref.ref(self)
            atexit.register(lambda: (lambda s: s and s._drain_timer())(
                ref()))

    def _drain_timer(self):
        """Cancel a pending deadline timer; if its callback is already
        mid-flush, wait for it to finish (process-exit path)."""
        with self._lock:
            t, self._timer = self._timer, None
        if t is not None:
            t.cancel()
            if t.is_alive():
                t.join(timeout=30)

    def refresh(self):
        """Re-copy the net's current params (e.g. after more training)."""
        self._params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, s), self.net.params,
            self._param_sh)
        self._states = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._rep), self.net.states)
        return self

    def _build(self):
        net = self.net
        from ..nn.computation_graph import ComputationGraph

        if isinstance(net, ComputationGraph):
            def infer(params, states, x):
                acts, _, _ = net._forward(params, states, x, train=False,
                                          rng=None)
                outs = [acts[o] for o in net.conf.outputs]
                return outs[0] if len(outs) == 1 else outs
        else:
            def infer(params, states, x):
                y, _ = net._forward(params, states, x, train=False, rng=None)
                return y

        self._infer = jax.jit(infer, in_shardings=(
            self._param_sh,
            jax.tree_util.tree_map(lambda _: self._rep, self._states),
            self._batch_sh))
        return self._infer

    def output(self, x):
        fn = self._infer or self._build()
        multi = isinstance(x, (list, tuple))   # multi-input ComputationGraph
        xs = [np.asarray(a) for a in x] if multi else [np.asarray(x)]
        n = self._batch_div
        orig = xs[0].shape[0]
        if orig % n:
            pad_fn = _padder(n - orig % n)
            xs = [pad_fn(a) for a in xs]
        arg = tuple(jnp.asarray(a) for a in xs) if multi else jnp.asarray(xs[0])
        out = fn(self._params, self._states, arg)
        if isinstance(out, (list, tuple)):   # multi-output ComputationGraph
            return [np.asarray(o)[:orig] for o in out]
        return np.asarray(out)[:orig]

    def submit(self, x):
        """Dynamic batching: queue a request. Flushes inline (and returns
        the whole batch's parts, legacy contract) when the size threshold
        is met; otherwise returns this request's Future, which resolves
        at the flush that carries it — the deadline timer's flush when
        ``max_wait_ms`` is set, or an explicit ``flush()``."""
        x = np.asarray(x)
        with self._lock:
            if self._pending and x.shape[1:] != self._pending[0].shape[1:]:
                raise ValueError(
                    f"mixed-shape submission: request rows have shape "
                    f"{x.shape[1:]} but the pending dynamic batch holds "
                    f"{self._pending[0].shape[1:]} — flush() concatenates "
                    "on axis 0, so per-request trailing dims must match "
                    "(flush or use a separate ParallelInference per shape)")
            fut: Future = Future()
            self._pending.append(x)
            self._pending_ts.append(time.perf_counter())
            self._pending_futures.append(fut)
            get_registry().counter(
                "dl4j_inference_requests_total",
                "Requests submitted to dynamic batching").inc()
            if sum(p.shape[0] for p in self._pending) >= self.max_batch:
                return self._flush_locked()
            if self.max_wait_ms is not None and self._timer is None:
                t = threading.Timer(self.max_wait_ms / 1e3,
                                    lambda: self._deadline_flush(t))
                t.daemon = True
                t.start()
                self._timer = t
        return fut

    def _deadline_flush(self, timer):
        """Timer callback: the oldest pending request hit max_wait_ms —
        sweep whatever is queued. Results reach callers via the Futures
        submit returned. ``timer`` identity-guards the race where a
        fired-but-lock-blocked timer outlives the flush that retired it:
        a stale callback must neither flush the NEXT batch early nor
        orphan that batch's live timer handle."""
        with self._lock:
            if self._timer is not timer:
                return
            self._timer = None
            if self._pending:
                get_registry().counter(
                    "dl4j_inference_deadline_flushes_total",
                    "Dynamic batches flushed by the max_wait_ms deadline "
                    "rather than the size threshold").inc()
                self._flush_locked()

    def flush(self):
        with self._lock:
            return self._flush_locked()

    def _flush_locked(self):
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if not self._pending:
            return []
        sizes = [p.shape[0] for p in self._pending]
        batch = np.concatenate(self._pending)
        # serving-plane telemetry: how full each device sweep runs under
        # the offered traffic, and how long requests waited to board it —
        # the two dials continuous batching tunes (μ-cuDNN occupancy
        # analysis; ROADMAP item 1 inherits these for free)
        reg = get_registry()
        now = time.perf_counter()
        wait_h = reg.histogram(
            "dl4j_inference_queue_wait_seconds",
            "Time a request waited in the dynamic-batching queue")
        for ts in self._pending_ts:
            wait_h.observe(now - ts)
        reg.gauge(
            "dl4j_inference_batch_occupancy",
            "Rows in the last dynamic batch / max_batch").set(
            batch.shape[0] / max(self.max_batch, 1))
        reg.counter("dl4j_inference_batches_total",
                    "Dynamic batches swept through the device").inc()
        futures = self._pending_futures
        self._pending = []
        self._pending_ts = []
        self._pending_futures = []
        try:
            out = self.output(batch)
        except Exception as e:
            for f in futures:       # a deadline-flush caller only has the
                try:                # Future to learn of the failure from
                    f.set_exception(e)
                except InvalidStateError:
                    pass            # caller cancelled while queued
            raise
        parts, off = [], 0
        for s, f in zip(sizes, futures):
            parts.append(out[off:off + s])
            try:
                f.set_result(out[off:off + s])
            except InvalidStateError:
                pass   # this caller cancelled; its rows still ship in
                       # the parts list, the OTHER futures must resolve
            off += s
        return parts
