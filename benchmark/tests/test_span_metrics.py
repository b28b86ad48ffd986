"""The readers of the program's own spans and counters (``span_ms``,
``span_cover``, ``counter_ratio``) on fabricated spans and counters, and
the metric files that name them."""
import json
from pathlib import Path

import pytest

from deeplearning4j_tpu import obs
from reducers import counter_ratio, span_cover, span_ms

BENCH = Path(__file__).resolve().parents[1]
MS = 1_000_000      # ns


def _fit_call(trace, t0, next_ms, h2d_ms=2, pack_ms=50, self_ms=1):
    """The spans one fit() call leaves: per batch k an iteration of
    next + h2d + dispatch (1 ms) + self, a producer pack, and the closing
    pass that found the iterator exhausted."""
    out, t, root_id = [], t0, f"{trace}-root"
    for k, nxt in enumerate(next_ms):
        it_id, start = f"{trace}-it{k}", t
        for name, ms in (("fit.next", nxt), ("fit.h2d", h2d_ms),
                         ("fit.dispatch", 1)):
            out.append(obs.Span(name, trace, f"{trace}-{name}{k}", it_id,
                                attrs={"batch": k}, t0_ns=t,
                                t1_ns=t + ms * MS))
            t += ms * MS
        t += self_ms * MS
        out.append(obs.Span("fit.iteration", trace, it_id, root_id,
                            attrs={"batch": k, "examples": 4}, t0_ns=start,
                            t1_ns=t))
        out.append(obs.Span("data.pack", trace, f"{trace}-pack{k}",
                            f"{trace}-produce{k}", thread="producer",
                            attrs={"batch": k, "bytes": 10, "oversize": True},
                            t0_ns=start, t1_ns=start + (pack_ms + k) * MS))
    out.append(obs.Span("fit.iteration", trace, f"{trace}-end", root_id,
                        attrs={"end": True}, t0_ns=t, t1_ns=t + 500 * MS))
    out.append(obs.Span("fit", trace, root_id, None, attrs={"epochs": 1},
                        t0_ns=t0, t1_ns=t + 500 * MS))
    return out


@pytest.fixture
def tracer():
    t = obs.get_tracer()
    kept = t.spans()
    t.clear()
    yield t
    t.clear()
    t.add_spans(kept)


def ctx(**counters):
    return {"counters": counters}


def test_nothing_to_read_is_none(tracer):
    assert span_ms.read(ctx(), "fit.next") is None
    assert span_cover.read(ctx()) is None
    # spans, but no fit root among them
    tracer.add_spans([obs.Span("serving.decode", "t", "s", None,
                               t0_ns=0, t1_ns=MS)])
    assert span_ms.read(ctx(), "fit.next") is None
    assert span_cover.read(ctx(pre_trace_steps=4), "pre_trace_steps") is None
    # one iteration is no median
    tracer.add_spans(_fit_call("one", 0, [5]))
    assert span_ms.read(ctx(), "fit.next") is None


def test_newest_fit_root_and_the_first_cut(tracer, capsys):
    tracer.add_spans(_fit_call("setup", 0, [900, 900, 900]))
    tracer.add_spans(_fit_call("window", 10_000 * MS,
                               [10, 20, 30, 40, 400, 400]))
    # all six iterations of the window's call, none of set-up's
    assert span_ms.read(ctx(), "fit.next", q=50) == 30
    assert span_ms.read(ctx(), "fit.next", q=100) == 400
    # cut to the iterations before the profiler came on
    c = ctx(pre_trace_steps=4)
    assert span_ms.read(c, "fit.next", q=50, first="pre_trace_steps") == 20
    assert span_ms.read(c, "fit.next", q=90, first="pre_trace_steps") == 40
    assert span_ms.read(c, "fit.h2d", first="pre_trace_steps") == 2
    # a list of names: their sum per batch (h2d 2 + dispatch 1)
    assert span_ms.read(c, ["fit.h2d", "fit.dispatch"],
                        first="pre_trace_steps") == 3
    assert span_ms.read(c, ["fit.next", "fit.no_such_span"], q=90,
                        first="pre_trace_steps") == 40
    # a producer-thread span is matched to the iterations by its batch
    assert span_ms.read(c, "data.pack", q=100, first="pre_trace_steps") == 53
    assert span_ms.read(ctx(), "data.pack", q=100) == 55
    assert "fit.next over 4 of 4 iterations, median 20.000 ms, p90 40.000" \
        in capsys.readouterr().err
    # the profiler never came on: nothing before it
    assert span_ms.read(ctx(pre_trace_steps=0), "fit.next",
                        first="pre_trace_steps") is None
    assert span_ms.read(ctx(), "fit.no_such_span") is None


def test_cover_share(tracer):
    tracer.add_spans(_fit_call("window", 0, [7, 7, 7, 7], self_ms=0))
    assert span_cover.read(ctx()) == pytest.approx(100.0)
    tracer.clear()
    # 6 + 2 + 1 ms in children, 1 ms outside any: 90 %
    tracer.add_spans(_fit_call("window", 0, [6, 6, 6, 6], self_ms=1))
    assert span_cover.read(ctx()) == pytest.approx(90.0)
    tracer.clear()
    # the cut takes the first two (96 + 3 of 100), not the last two
    tracer.add_spans(_fit_call("window", 0, [96, 96, 6, 6], self_ms=1))
    assert span_cover.read(ctx(pre_trace_steps=2), "pre_trace_steps") \
        == pytest.approx(99.0)
    # the closing pass (no batch) is not an iteration
    assert span_cover.read(ctx()) == pytest.approx(100 * 216 / 220)


def test_counter_ratio():
    reg = obs.get_registry()
    num = reg.counter("dl4j_benchtest_discarded_total")
    den = reg.counter("dl4j_benchtest_packed_total")
    args = {"num": num.name, "den": den.name}
    assert counter_ratio.read(ctx(), **args) is None        # 0 / 0
    num_before, den_before = num.value(), den.value()
    den.inc(400 - den_before)
    assert counter_ratio.read(ctx(), **args) == 100.0 * num_before / 400
    num.inc(100)
    assert counter_ratio.read(ctx(), **args) \
        == pytest.approx(100.0 * (num_before + 100) / 400)
    assert counter_ratio.read(ctx(), num="dl4j_no_such_total",
                              den=den.name) is None
    assert counter_ratio.read(ctx(), num=num.name,
                              den="dl4j_no_such_total") is None


def test_metric_files_name_their_readers():
    """Every per-layer metric this PR adds: a file by its name, a reader by
    the file's, a note that says which span or counter feeds it."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    added = [m for m in bench["per_layer"]
             if m["name"] in ("fit_next_wait_ms.fit", "fit_asarray_ms.fit",
                              "producer_pack_ms.fit", "pack_discarded_pct.fit",
                              "consumer_wait_pct.fit", "oversize_batch_pct.fit",
                              "fit_dispatch_sync_ms.fit",
                              "fit_span_cover_pct.fit")]
    assert len(added) == 8
    for m in added:
        assert m["workloads"] == ["resnet50-fit-b256"]
        assert m["moves"] == "fit_samples_per_s"
        spec = json.loads(
            (BENCH / "layer_metrics" / f"{m['name']}.json").read_text())
        assert (BENCH / "reducers" / f"{spec['reducer']}.py").is_file()
        fed_by = [v for arg in spec["args"].values()
                  for v in (arg if isinstance(arg, list) else [arg])
                  if isinstance(v, str) and v != "pre_trace_steps"]
        assert fed_by or spec["reducer"] == "span_cover"
        assert all(v in spec["note"] for v in fed_by)


# the tiny checkout of the rehearsal: the whole of run.py but the look for
# a chip, so the new metrics are read the way a traced chip run reads them
from test_rehearsal import FIT, _run, checkout, tiny_resnet  # noqa: E402,F401


def test_traced_fit_rehearsal_reports_the_new_metrics(checkout, capsys):  # noqa: F811
    r = _run(checkout, capsys, FIT, trace=1)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    spans = ["fit_next_wait_ms.fit", "fit_asarray_ms.fit",
             "fit_dispatch_sync_ms.fit", "fit_span_cover_pct.fit"]
    assert set(spans) <= set(m), sorted(m)
    assert all(m[k] > 0 for k in spans)
    assert 90 < m["fit_span_cover_pct.fit"] <= 100
    # the two shares of the batches the producer handed over
    assert 0 <= m["consumer_wait_pct.fit"] <= 100
    assert m["oversize_batch_pct.fit"] == 0
    from deeplearning4j_tpu.utils import native
    if native.load() is not None:
        # a batch of 32 small images fits a ring slot: packed, not discarded
        assert m["producer_pack_ms.fit"] > 0
        assert m["pack_discarded_pct.fit"] == 0
    else:
        assert "producer_pack_ms.fit" not in m
        assert "pack_discarded_pct.fit" not in m
