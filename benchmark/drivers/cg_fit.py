"""Driver "cg_fit": ``ComputationGraph.fit(DataSetIterator)`` over a host pool
of float32 image batches, through the async prefetch ``fit()`` itself wraps
round the iterator.

``setup`` builds ONE network, writes the benchmark's own weights (the
reference's draw from the seed) over the program's, and drives it through
its first ``check_steps`` iterations by one short ``fit()`` over the pool's
first batches: the window's own call and feed, which also compiles the train
step and starts the prefetch thread once. ``window`` is ONE ``fit()`` call on
the same network over an iterator that cycles the pool and stops offering
batches once the deadline has passed, timed by a ``TrainingListener``.
``check`` frees the network and follows the same first iterations with the
plain reference.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from drivers._training import State, change_norms, first_gradient_norms
from reference import lowprec
from reference import resnet as ref


def build_net(config: dict):
    """The timed program's network. Tests plant faults by replacing this."""
    from deeplearning4j_tpu.zoo.resnet import ResNet50
    knobs = {k: v for k, v in config["program"].items() if k != "entry"}
    return ResNet50(num_classes=int(config["num_classes"]),
                    input_shape=tuple(config["input_shape"]),
                    compute_dtype=jnp.dtype(config["compute_dtype"]),
                    **knobs).init()


def make_iterator(xs, ys, batch, n_batches=None, deadline=None):
    """A DataSetIterator over the pool: batch i is pool[i % len(pool)];
    it ends after ``n_batches`` or once ``deadline`` (perf_counter) has
    passed. Deriving from BaseDatasetIterator makes ``fit()`` wrap it in its
    AsyncDataSetIterator (``async_supported``)."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import BaseDatasetIterator

    class PoolIterator(BaseDatasetIterator):
        def __init__(self):
            super().__init__(batch)
            self.offered = 0

        def total_examples(self):
            return (n_batches if n_batches is not None else len(xs)) * batch

        def has_next(self):
            if n_batches is not None:
                return self._cursor < n_batches
            return time.perf_counter() < deadline

        def next(self, num=None):
            j = self._cursor % len(xs)
            self._cursor += 1
            self.offered += 1
            return DataSet(xs[j], ys[j])

    return PoolIterator()


def _listener(st, at_sync):
    from deeplearning4j_tpu.nn.listeners import TrainingListener

    class Timed(TrainingListener):
        def __init__(self):
            self.times, self.scores, self.first_grad = [], [], None
            self.resumed = []     # when the listener handed control back

        def iteration_done(self, model, iteration, epoch, score):
            self.times.append(time.perf_counter())
            self.scores.append(float(score))
            if self.first_grad is None:
                self.first_grad = first_gradient_norms(
                    model._opt_state, st.config["optimizer"]["b1"])
            at_sync(len(self.times))
            self.resumed.append(time.perf_counter())

    return Timed()


def setup(config: dict, traffic: dict, seed: int, probe) -> State:
    st = State()
    st.config, st.traffic, st.seed = config, traffic, seed
    st.batch = int(traffic["batch"])
    st.xs, st.ys = ref.make_batches(seed, int(traffic["pool_batches"]),
                                    st.batch, config)
    st.net = build_net(config)
    weights = ref.make_weights(seed, config)
    want = jax.tree_util.tree_structure(st.net.params)
    have = jax.tree_util.tree_structure(
        {**{k: {} for k in st.net.params}, **weights})
    if want != have:
        raise ValueError("the benchmark's weights do not match the "
                         f"program's parameters: {have} vs {want}")
    st.net.params = {**{k: {} for k in st.net.params}, **weights}
    n = int(traffic["check_steps"])
    rec = _listener(st, lambda done: None)     # no trace stretch in set-up
    st.net.set_listeners(rec)
    st.net.fit(make_iterator(st.xs, st.ys, st.batch, n_batches=n))
    kept = {k: v for k, v in st.net.params.items() if v}
    st.readings = {"losses": rec.scores, "grad_norms": rec.first_grad,
                   "delta_norms": change_norms(
                       kept, ref.make_weights(seed, config))}
    if len(rec.scores) != n:
        raise RuntimeError(f"fit() ran {len(rec.scores)} iterations, not {n}")
    jax.block_until_ready(st.net.params)
    return st


def window(st: State, seconds: float, probe) -> dict:
    rec = _listener(st, probe.at_sync)
    rec.first_grad = {}          # read in set-up only
    st.net.set_listeners(rec)
    t0 = time.perf_counter()
    it = make_iterator(st.xs, st.ys, st.batch, deadline=t0 + seconds)
    with probe.span("fit"):
        st.net.fit(it)
    n = len(rec.times)
    if n == 0:
        raise RuntimeError("fit() finished no iteration in the window")
    elapsed = rec.times[-1] - t0
    # an iteration's time runs from the previous listener's return, so the
    # profiler's own start and stop (inside the listener) are not in it
    gaps = [b - a for a, b in zip([t0] + rec.resumed[:-1], rec.times)]
    return {"iterations": n, "samples": n * st.batch, "window_s": elapsed,
            "iter_s": gaps, "iter_ms_first8": [round(1e3 * g) for g in gaps[:8]],
            "iter_ms_median": 1e3 * sorted(gaps)[n // 2],
            "iter_ms_max": 1e3 * max(gaps), "last_loss": rec.scores[-1],
            "offered": it.offered, "attempted": n, "failed": 0}


def release(st: State):
    st.net = None


def reference_readings(st: State, product=None, rows=None) -> dict:
    n = len(st.readings["losses"])
    kw = {} if product is None else {"product": product}
    return ref.train_steps(st.seed, st.config, st.xs[:n], st.ys[:n], n,
                           rows=rows, **kw)


#: the control's precision: the nearest under the bf16 the configuration states
CONTROL_PRODUCT = lowprec.FP8


def check(st: State) -> dict:
    import compare
    release(st)
    return compare.training_gaps(st.readings, reference_readings(st))
