"""Spans and counters inside ``fit()`` and its async prefetch (ISSUE 26),
and the loop's order since it stages one batch ahead (ISSUE 29): one loop
(``nn/_fit_common.py``) with three callers since ISSUE 30, so every case
runs for ``MultiLayerNetwork``, ``ComputationGraph`` and ``ParallelWrapper``
over either.

One ``fit`` root per call; one ``fit.iteration`` per batch whose children
lie inside it, in order, on one clock, the fetch and copy of batch k+1
between the dispatch of step k and its loss; the producer thread's
``data.*`` spans in the same trace under the same ``batch`` numbers; the
five ``dl4j_data_*`` counters at the ring's boundaries and the two
``dl4j_fit_*`` of the loop; that the order changes no score, no parameter
and no listener's view; what a span costs; and the keys ``record()`` and
the JSONL have always had.
"""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.data.async_iter import AsyncDataSetIterator
from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.layers.base import InputType
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.listeners import TrainingListener
from deeplearning4j_tpu.nn.multi_layer_network import MultiLayerNetwork

#: a batch divides the 8-device mesh the ParallelWrapper cases run on
N_BATCHES, BATCH = 5, 8
#: the children of batch k's ``fit.iteration`` in the order the loop runs
#: them, each with the batch it carries less k
ORDER = [("fit.dispatch", 0), ("fit.next", 1), ("fit.h2d", 1),
         ("fit.loss_sync", 0), ("fit.listeners", 0)]
COUNTERS = ["dl4j_data_batches_total", "dl4j_data_oversize_batches_total",
            "dl4j_data_packed_bytes_total",
            "dl4j_data_pack_discarded_bytes_total",
            "dl4j_data_consumer_waits_total",
            "dl4j_fit_batches_total", "dl4j_fit_staged_ahead_total"]


class _Scores(TrainingListener):
    def __init__(self):
        self.scores = []

    def iteration_done(self, model, iteration, epoch, score):
        self.scores.append(score)


def _data(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(N_BATCHES * BATCH, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, len(x))]
    return x, y


def _graph():
    g = NeuralNetConfiguration.builder().seed(1).graph_builder() \
        .add_inputs("in")
    g.add_layer("d", DenseLayer(n_in=6, n_out=8, activation="relu"), "in")
    g.add_layer("out", OutputLayer(n_in=8, n_out=3, activation="softmax",
                                   loss="mcxent"), "d")
    g.set_outputs("out")
    g.set_input_types(InputType.feed_forward(6))
    return ComputationGraph(g.build()).init()


def _mln():
    conf = NeuralNetConfiguration.builder().seed(1).list() \
        .layer(DenseLayer(n_in=6, n_out=8, activation="relu")) \
        .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                           loss="mcxent")) \
        .build()
    return MultiLayerNetwork(conf).init()


class _Dp:
    """``ParallelWrapper(net, dp=8).fit`` under the network's own names
    (listeners, parameters and counts are the wrapped network's), so that
    one test body drives all three callers of the loop."""

    def __init__(self, net):
        import jax
        from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        self.net = net
        self.wrapper = ParallelWrapper(net, mesh=make_mesh(dp=8))

    def __getattr__(self, name):
        return getattr(self.net, name)

    def fit(self, data, epochs=1):
        from deeplearning4j_tpu.data.dataset import DataSet
        return self.wrapper.fit([data] if isinstance(data, DataSet) else data,
                                epochs=epochs)


def _dp_graph():
    return _Dp(_graph())


def _dp_mln():
    return _Dp(_mln())


NETS = pytest.mark.parametrize(
    "make_net", [_graph, _mln, _dp_graph, _dp_mln],
    ids=["ComputationGraph", "MultiLayerNetwork",
         "ParallelWrapper-ComputationGraph",
         "ParallelWrapper-MultiLayerNetwork"])


def _fit_and_collect(net, epochs=1):
    """Spans of ONE fit() call over an iterator that opts into the async
    wrapper, as {name: [spans in start order]}, and the root."""
    net.set_listeners(_Scores())
    x, y = _data()
    tracer = obs.get_tracer()
    before = {id(s) for s in tracer.spans()}
    net.fit(ArrayDataSetIterator(x, y, BATCH), epochs=epochs)
    mine = sorted((s for s in tracer.spans() if id(s) not in before),
                  key=lambda s: s.t0_ns)
    roots = [s for s in mine if s.name == "fit"]
    assert len(roots) == 1
    root = roots[0]
    mine = [s for s in mine if s.trace_id == root.trace_id]
    by_name = {}
    for s in mine:
        by_name.setdefault(s.name, []).append(s)
    return root, by_name, mine


@NETS
def test_fit_span_tree_on_one_clock(make_net):
    root, by_name, mine = _fit_and_collect(make_net())
    assert root.parent_id is None and root.attrs == {"epochs": 1}
    me = threading.current_thread().name

    iterations = by_name["fit.iteration"]
    assert [s.attrs["batch"] for s in iterations] == list(range(N_BATCHES))
    assert all(s.attrs["examples"] == BATCH for s in iterations)
    # one ``end`` marker: on the last batch's pass, whose fit.next finds
    # the source exhausted (and so has no fit.h2d after it)
    assert [bool(s.attrs.get("end")) for s in iterations] \
        == [False] * (N_BATCHES - 1) + [True]
    for it in iterations:
        assert it.parent_id == root.span_id and it.thread == me
        assert root.t0_ns <= it.t0_ns <= it.t1_ns <= root.t1_ns

    for it in iterations:
        k = it.attrs["batch"]
        kids = [s for s in mine if s.parent_id == it.span_id]
        want = [(n, k + d) for n, d in ORDER
                if not (it.attrs.get("end") and n == "fit.h2d")]
        assert [(s.name, s.attrs["batch"]) for s in kids] == want
        assert all(s.thread == me for s in kids)
        # inside the parent, one after the other, on perf_counter_ns: so
        # batch k+1's copy is issued before the host waits for loss k
        edges = [it.t0_ns]
        for s in kids:
            edges += [s.t0_ns, s.t1_ns]
        edges.append(it.t1_ns)
        assert edges == sorted(edges)
    for k in range(N_BATCHES - 1):
        assert by_name["fit.h2d"][k + 1].t1_ns \
            <= by_name["fit.loss_sync"][k].t0_ns
    # every batch is fetched and copied once, in order; the epoch's first
    # pair lies directly under ``fit``, before the first pass
    assert [s.attrs["batch"] for s in by_name["fit.next"]] \
        == list(range(N_BATCHES + 1))
    assert [s.attrs["batch"] for s in by_name["fit.h2d"]] \
        == list(range(N_BATCHES))
    for s in (by_name["fit.next"][0], by_name["fit.h2d"][0]):
        assert s.parent_id == root.span_id and s.thread == me
        assert s.t1_ns <= iterations[0].t0_ns
    assert [s.attrs["bytes"] for s in by_name["fit.h2d"]] \
        == [BATCH * (6 + 3) * 4] * N_BATCHES

    # the producer's side: same trace, another thread, the same numbering
    produced = [s for s in by_name["data.produce"] if "batch" in s.attrs]
    assert [s.attrs["batch"] for s in produced] == list(range(N_BATCHES))
    assert sum(bool(s.attrs.get("end")) for s in by_name["data.produce"]) == 1
    for p in produced:
        assert p.parent_id == root.span_id and p.trace_id == root.trace_id
        assert p.thread != me
        kids = [s for s in mine if s.parent_id == p.span_id]
        assert [s.name for s in kids] in (
            ["data.source_next", "data.pack", "data.put"],
            ["data.source_next", "data.put"])       # no native ring: no pack
        for s in kids:
            assert s.thread == p.thread
            assert s.attrs["batch"] == p.attrs["batch"]
            assert p.t0_ns <= s.t0_ns <= s.t1_ns <= p.t1_ns
        # a batch is produced before the training thread has it in hand
        got = by_name["fit.next"][p.attrs["batch"]]
        assert p.t0_ns <= got.t1_ns
    for s in by_name.get("data.unpack", []):
        assert by_name["fit.next"][s.attrs["batch"]].span_id == s.parent_id


def test_fit_root_per_call_and_epochs():
    net = _graph()
    root1, _, _ = _fit_and_collect(net)
    root2, by_name, _ = _fit_and_collect(net, epochs=2)
    assert root1.trace_id != root2.trace_id
    assert root2.attrs == {"epochs": 2}
    batches = [s.attrs["batch"] for s in by_name["fit.iteration"]
               if "batch" in s.attrs]
    assert batches == list(range(2 * N_BATCHES))    # k runs on over epochs
    assert sorted(s.attrs["batch"] for s in by_name["data.produce"]
                  if "batch" in s.attrs) == batches


@NETS
def test_deferred_scores_keep_their_batch(make_net):
    """Logging listeners get step k-1's score while step k is in flight:
    the sync and listener spans say whose they are."""
    net = make_net()
    net.set_listeners(_Deferred())
    x, y = _data()
    tracer = obs.get_tracer()
    before = {id(s) for s in tracer.spans()}
    net.fit(ArrayDataSetIterator(x, y, BATCH))
    mine = [s for s in tracer.spans() if id(s) not in before]
    syncs = sorted((s for s in mine if s.name == "fit.loss_sync"),
                   key=lambda s: s.t0_ns)
    assert [s.attrs["batch"] for s in syncs] == list(range(N_BATCHES))
    by_id = {s.span_id: s for s in mine}
    parents = [by_id[s.parent_id] for s in syncs]
    assert [p.attrs.get("batch") for p in parents[:-1]] \
        == list(range(1, N_BATCHES))
    assert parents[-1].name == "fit"
    assert len(net.listeners[0].scores) == N_BATCHES


class _Deferred(_Scores):
    deferred_score_ok = True


#: the loop's three callers, and both of its branches under each
LOOPS = pytest.mark.parametrize(
    "make_net, listener",
    [(_graph, _Scores), (_mln, _Scores), (_mln, _Deferred),
     (_graph, _Deferred), (_dp_graph, _Scores), (_dp_mln, _Scores),
     (_dp_graph, _Deferred), (_dp_mln, _Deferred)],
    ids=["ComputationGraph", "MultiLayerNetwork",
         "MultiLayerNetwork-deferred", "ComputationGraph-deferred",
         "ParallelWrapper-ComputationGraph",
         "ParallelWrapper-MultiLayerNetwork",
         "ParallelWrapper-ComputationGraph-deferred",
         "ParallelWrapper-MultiLayerNetwork-deferred"])


def _leaves(net):
    import jax
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(net.params)]


def _one_by_one(net, epochs):
    """The sequential order: ``fit(DataSet)`` once a batch, nothing staged
    (a single batch has no batch k+1). After each call, the step count and
    the bytes of the first parameter leaf."""
    from deeplearning4j_tpu.data.dataset import DataSet
    x, y = _data()
    seen = []
    for _ in range(epochs):
        for i in range(0, len(x), BATCH):
            net.fit(DataSet(x[i:i + BATCH], y[i:i + BATCH]))
            seen.append((net._step_count, _leaves(net)[0].tobytes()))
    return seen


@LOOPS
def test_staged_fit_is_bit_identical_to_one_batch_a_call(make_net, listener):
    x, y = _data()
    staged, plain = make_net(), make_net()
    staged.set_listeners(listener())
    plain.set_listeners(listener())
    staged.fit(ArrayDataSetIterator(x, y, BATCH), epochs=2)
    _one_by_one(plain, epochs=2)
    assert len(staged.listeners[0].scores) == 2 * N_BATCHES
    assert staged.listeners[0].scores == plain.listeners[0].scores
    assert staged._step_count == plain._step_count == 2 * N_BATCHES
    for a, b in zip(_leaves(staged), _leaves(plain)):
        assert a.tobytes() == b.tobytes()


@NETS
def test_listener_of_step_k_sees_the_parameters_of_step_k(make_net):
    """Batch k+1 is fetched and copied before step k's listeners run, but
    dispatched after them: they see the model as step k left it."""
    class Watch(_Scores):
        def __init__(self):
            super().__init__()
            self.seen = []

        def iteration_done(self, model, iteration, epoch, score):
            assert iteration == model._step_count
            self.seen.append((iteration, _leaves(model)[0].tobytes()))

    x, y = _data()
    net = make_net()
    net.set_listeners(Watch())
    net.fit(ArrayDataSetIterator(x, y, BATCH), epochs=2)
    want = _one_by_one(make_net(), epochs=2)
    assert [k for k, _ in want] == list(range(1, 2 * N_BATCHES + 1))
    assert net.listeners[0].seen == want
    assert len({leaf for _, leaf in want}) == len(want)     # each step moved it


@LOOPS
def test_on_epoch_end_follows_the_epochs_last_report(make_net, listener):
    """Once an epoch, after that epoch's last ``iteration_done``, also
    where the report is one step late."""
    class Epochs(listener):
        def __init__(self):
            super().__init__()
            self.ends = []

        def on_epoch_end(self, model):
            self.ends.append((len(self.scores), model.epoch_count))

    x, y = _data()
    net = make_net()
    net.set_listeners(Epochs())
    net.fit(ArrayDataSetIterator(x, y, BATCH), epochs=3)
    assert net.listeners[0].ends \
        == [(N_BATCHES, 1), (2 * N_BATCHES, 2), (3 * N_BATCHES, 3)]


def test_parallel_partial_last_batch_counts_its_rows_before_padding():
    """3 rows on the 8-device mesh are padded to 8 for the step;
    ``examples`` and ``_last_batch_size`` say 3, ``bytes`` what was sent."""
    net = _dp_mln()
    x, y = _data()
    rows = BATCH + 3
    tracer = obs.get_tracer()
    before = {id(s) for s in tracer.spans()}
    net.fit(ArrayDataSetIterator(x[:rows], y[:rows], BATCH))
    mine = sorted((s for s in tracer.spans() if id(s) not in before),
                  key=lambda s: s.t0_ns)
    assert [s.attrs["examples"] for s in mine if s.name == "fit.iteration"] \
        == [BATCH, 3]
    assert net._last_batch_size == 3 and net._step_count == 2
    assert [s.attrs["bytes"] for s in mine if s.name == "fit.h2d"] \
        == [BATCH * (6 + 3) * 4] * 2


class _Breaks:
    """A plain iterable (no async wrapper) whose fourth ``next`` raises."""

    def __iter__(self):
        from deeplearning4j_tpu.data.dataset import DataSet
        x, y = _data()
        for i in range(3):
            yield DataSet(x[i * BATCH:(i + 1) * BATCH],
                          y[i * BATCH:(i + 1) * BATCH])
        raise OSError("the source broke")


class _BreaksBehindTheWrapper(ArrayDataSetIterator):
    """The same through ``fit()``'s own AsyncDataSetIterator, which hands
    the producer thread's exception over as a RuntimeError."""

    def __init__(self):
        super().__init__(*_data(), BATCH)
        self.calls = 0

    def next(self, num=None):
        self.calls += 1
        if self.calls == 4:
            raise OSError("the source broke")
        return super().next(num)


@pytest.mark.parametrize("source, error",
                         [(_Breaks, OSError),
                          (_BreaksBehindTheWrapper, RuntimeError)],
                         ids=["plain", "async"])
@LOOPS
def test_a_failing_fetch_loses_no_finished_steps_report(make_net, listener,
                                                        source, error):
    """Batch 3's fetch raises inside the pass of batch 2, after step 2 was
    dispatched: its score still reaches the listener, then the caller gets
    the exception."""
    net = make_net()
    net.set_listeners(listener())
    before = _counter_values()
    with pytest.raises(error):
        net.fit(source())
    assert len(net.listeners[0].scores) == 3 and net._step_count == 3
    d = {n: _counter_values()[n] - before[n] for n in COUNTERS}
    assert d["dl4j_fit_batches_total"] == 3
    assert d["dl4j_fit_staged_ahead_total"] == 2


@pytest.mark.parametrize("epochs", [1, 2])
@LOOPS
def test_fit_counters_count_batches_and_those_staged_ahead(make_net, listener,
                                                           epochs):
    """Every batch but an epoch's first is copied while the step before it
    is in flight; a single DataSet has no batch to stage."""
    from deeplearning4j_tpu.data.dataset import DataSet
    x, y = _data()
    net = make_net()
    net.set_listeners(listener())
    before = _counter_values()
    net.fit(ArrayDataSetIterator(x, y, BATCH), epochs=epochs)
    d = {n: _counter_values()[n] - before[n] for n in COUNTERS}
    assert d["dl4j_fit_batches_total"] == epochs * N_BATCHES
    assert d["dl4j_fit_staged_ahead_total"] == epochs * (N_BATCHES - 1)
    before = _counter_values()
    net.fit(DataSet(x[:BATCH], y[:BATCH]), epochs=epochs)
    d = {n: _counter_values()[n] - before[n] for n in COUNTERS}
    assert d["dl4j_fit_batches_total"] == epochs
    assert d["dl4j_fit_staged_ahead_total"] == 0


def _counter_values():
    reg = obs.get_registry()
    return {n: (reg.get(n).value() if reg.get(n) else 0.0) for n in COUNTERS}


#: one batch of ``_data()``: 4 x 6 features + 4 x 3 labels, float32; its npz
#: is some 500 bytes longer
RAW_BYTES = BATCH * (6 + 3) * 4


@pytest.mark.parametrize(
    "slot_size, oversize, packed",
    [(64, True, False), (RAW_BYTES + 8, True, True), (1 << 20, False, True)],
    ids=["slot_smaller_than_batch", "slot_between_arrays_and_npz",
         "batch_fits_slot"])
def test_data_counters_at_the_ring(slot_size, oversize, packed):
    """A batch whose arrays exceed the slot is sent unpacked and nothing
    is packed for it; one whose arrays fit and whose npz does not is the
    one case that packs and discards."""
    x, y = _data()
    source = ArrayDataSetIterator(x, y, BATCH)
    before = _counter_values()      # the producer starts with the wrapper
    tracer = obs.get_tracer()
    spans_before = {id(s) for s in tracer.spans()}
    with tracer.span("test.ring") as root:     # the producer's spans' parent
        it = AsyncDataSetIterator(source, queue_size=2, slot_size=slot_size)
    try:
        got = list(it)
    finally:
        it.close()
    after = _counter_values()
    d = {n: after[n] - before[n] for n in COUNTERS}
    packs = [s for s in tracer.spans() if id(s) not in spans_before
             and s.name == "data.pack" and s.trace_id == root.trace_id]
    assert len(got) == N_BATCHES
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(b.features) for b in got]), x)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(b.labels) for b in got]), y)
    assert d["dl4j_data_batches_total"] == N_BATCHES
    if not _has_native():
        # queue path: nothing is packed, so nothing can be discarded
        assert d["dl4j_data_packed_bytes_total"] == 0
        assert d["dl4j_data_pack_discarded_bytes_total"] == 0
        assert d["dl4j_data_oversize_batches_total"] == 0
        assert packs == []
        return
    assert d["dl4j_data_oversize_batches_total"] \
        == (N_BATCHES if oversize else 0)
    if not packed:
        assert packs == []
        assert d["dl4j_data_packed_bytes_total"] == 0
        assert d["dl4j_data_pack_discarded_bytes_total"] == 0
        return
    assert sorted(s.attrs["batch"] for s in packs) == list(range(N_BATCHES))
    assert all(s.attrs["oversize"] == oversize for s in packs)
    assert all(RAW_BYTES < s.attrs["bytes"] for s in packs)
    assert d["dl4j_data_packed_bytes_total"] \
        == sum(s.attrs["bytes"] for s in packs) > 0
    assert d["dl4j_data_pack_discarded_bytes_total"] \
        == (d["dl4j_data_packed_bytes_total"] if oversize else 0)


def _has_native():
    from deeplearning4j_tpu.utils import native
    return native.load() is not None


def test_queue_path_packs_and_discards_nothing():
    x, y = _data()
    before = _counter_values()
    it = AsyncDataSetIterator(ArrayDataSetIterator(x, y, BATCH),
                              use_native=False)
    try:
        assert len(list(it)) == N_BATCHES
    finally:
        it.close()
    d = {n: _counter_values()[n] - before[n] for n in COUNTERS}
    assert d["dl4j_data_batches_total"] == N_BATCHES
    assert d["dl4j_data_packed_bytes_total"] == 0
    assert d["dl4j_data_pack_discarded_bytes_total"] == 0


@pytest.mark.parametrize("use_native", [True, False], ids=["ring", "queue"])
def test_consumer_wait_is_counted_once_a_batch(use_native):
    """A consumer faster than its producer finds nothing at its first
    look: one count each time, however long it then polls."""
    x, y = _data()

    class Slow(ArrayDataSetIterator):
        def next(self, num=None):
            time.sleep(0.05)
            return super().next(num)

    before = _counter_values()
    it = AsyncDataSetIterator(Slow(x, y, BATCH), slot_size=1 << 16,
                              use_native=use_native)
    try:
        assert len(list(it)) == N_BATCHES
    finally:
        it.close()
    d = {n: _counter_values()[n] - before[n] for n in COUNTERS}
    # waiting for the end of the source is not waiting for a batch
    assert 2 <= d["dl4j_data_consumer_waits_total"] <= N_BATCHES


def test_hand_assembled_span_names_no_thread():
    """Only ``Tracer.span`` knows which thread ran a span: one built from
    ``start_ts`` and ``time_s`` (scaleout hub, reqtrace, compiles) does
    not take the assembling thread's name."""
    sp = obs.Span("round", "t" * 16, "s" * 16, start_ts=time.time(),
                  time_s=0.5)
    assert sp.thread is None and sp.record()["thread"] is None
    assert sp.time_s == 0.5
    named = obs.Span("round", "t" * 16, "s" * 16, thread="hub")
    assert named.thread == "hub"


def test_span_cost_is_within_budget():
    """10,000 empty open/close pairs: the budget is 5 us each; the limit
    is ten times that, so a loaded test machine does not fail it."""
    tracer = obs.Tracer(max_spans=1000)
    n = 10_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracer.span("empty"):
            pass
    each = (time.perf_counter() - t0) / n
    assert each < 50e-6, f"{each * 1e6:.1f} us a span"
    assert tracer.dropped == n - 1000 and len(tracer.spans()) == 1000


def test_record_and_jsonl_keep_their_keys(tmp_path):
    tracer = obs.Tracer()
    wall0 = time.time()
    with tracer.span("outer", attrs={"k": 1}) as outer:
        with tracer.span("inner", sync=None) as inner:
            pass
    had = {"kind", "name", "trace_id", "span_id", "parent_id", "start_ts",
           "time_s", "synced", "attrs"}
    rec = outer.record()
    assert had <= set(rec) and rec["kind"] == "span"
    assert set(rec) - had == {"t0_ns", "t1_ns", "thread"}
    # both derived from the one clock: epoch seconds and a duration
    assert wall0 - 1 <= rec["start_ts"] <= time.time() + 1
    assert rec["time_s"] == (outer.t1_ns - outer.t0_ns) / 1e9 > 0
    assert rec["thread"] == threading.current_thread().name
    assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns
    # ids: 16 hex characters, distinct, one prefix a process
    ids = {outer.span_id, outer.trace_id, inner.span_id}
    assert len(ids) == 3 and all(len(i) == 16 for i in ids)
    assert all(int(i, 16) >= 0 for i in ids)
    assert len({i[:8] for i in ids}) == 1

    path = tmp_path / "spans.jsonl"
    assert tracer.export_jsonl(path) == 2
    loaded = obs.load_spans(path)
    assert [r["name"] for r in loaded] == ["inner", "outer"]
    assert all(had <= set(r) for r in loaded)
    assert loaded[1] == rec

    # a span assembled by hand from epoch seconds lands on the same clock
    by_hand = obs.Span(name="x", trace_id="t", span_id="s",
                       start_ts=rec["start_ts"], time_s=0.25)
    assert abs(by_hand.t0_ns - outer.t0_ns) < 1_000     # float rounding
    assert by_hand.time_s == 0.25 and by_hand.attrs == {}
