"""The regular-expression scope reader and the registry reader, on the trace
PR 25 recorded on the v5e (``data/lm.xplane.pb.gz``: 395 device events, 291
with a ``tf_op``, none under a scope of the program: it predates them)."""
import gzip
import json
from pathlib import Path

import pytest

import xplane
from reducers import registry_value, scope_regex_share, scope_time_share

DATA = Path(__file__).resolve().parent / "data"
METRICS = Path(__file__).resolve().parents[1] / "layer_metrics"
#: every scope the program had at PR 25, as the .lm metric files name them
PR25 = "flash_|moe_|cca_|mla_|lm_head|mtp"


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "lm.xplane.pb").write_bytes(
        gzip.decompress((DATA / "lm.xplane.pb.gz").read_bytes()))
    return d.parents[2]


@pytest.fixture()
def ctx(trace_dir, monkeypatch):
    monkeypatch.setattr(scope_time_share, "TRACE_DIR", trace_dir)
    return {"trace": xplane.load(trace_dir), "counters": {"traced_steps": 5},
            "config": {}, "traffic": {}}


@pytest.mark.parametrize("pattern", [
    "whil", "jit", "checkpoint", "rematted_computation|closed_call",
    r"[^/()]*->btz(?:[/):]|$)", "no_such_scope"])
def test_matched_and_rest_are_the_whole_busy_time(ctx, pattern):
    """The events a pattern finds and the others are every event on the ops
    line but the containers, whose own time is their children's: together
    the busy time but for what a loop holds between its operations."""
    inside, outside, _ = scope_regex_share.split_seconds(ctx, pattern)
    assert inside + outside == pytest.approx(ctx["trace"].busy_s, rel=2e-4)
    matched = scope_regex_share.read(ctx, pattern) or 0.0
    rest = scope_regex_share.read(ctx, pattern, rest=True)
    assert matched + rest == pytest.approx(100.0, abs=0.02)


def test_the_rest_of_pr25s_scopes_is_everything(ctx):
    """The recorded step has no scope of the program's: nothing is matched,
    the 104 events without any ``tf_op`` count as unscoped like the rest, and
    only the Pallas kernels, found by ``ops``, leave the remainder."""
    tf_ops = scope_time_share.scopes_of(xplane.find_xplane(
        scope_time_share.TRACE_DIR))
    assert len(tf_ops) == 395
    assert sum(not v for v in tf_ops.values()) == 104
    tr = ctx["trace"]
    inside, outside, n = scope_regex_share.split_seconds(ctx, PR25)
    assert (inside, n) == (0.0, 0)
    every = sum(min(e, tr.t1) - max(s, tr.t0)
                for s, e, name in tr.ops_in_window()
                if not xplane._is_container(name)) / 1e9
    assert outside == pytest.approx(every)
    assert scope_regex_share.read(ctx, PR25, rest=True) == pytest.approx(
        100.0 * every / tr.busy_s)
    # what scope_time_share finds by the same names is the other part
    kernels, _ = scope_time_share.scope_seconds(ctx, ["no_such"],
                                                ["custom-call"])
    assert kernels > 0
    assert scope_regex_share.read(ctx, PR25, ops=["custom-call"], rest=True) \
        == pytest.approx(100.0 * (every - kernels) / tr.busy_s)


def test_a_pattern_matches_a_components_end_not_an_operands_name(ctx):
    """``<node>.<Type>`` style: anchored at a component's start, ended by
    the pattern's own lookahead. ``->btz`` ends the einsum's component;
    ``->bt`` does not end one. An event's HLO line also names its operands
    (``fusion(%custom-call.26, ...)``): the pattern looks at the scope alone."""
    ends = scope_regex_share.read(ctx, r"[^/()]*->btz(?:[/):]|$)")
    assert ends == pytest.approx(
        scope_time_share.read(ctx, ["btd,dz->btz"]))
    assert scope_regex_share.read(ctx, r"[^/()]*->bt(?:[/):]|$)") is None
    # a component inside a wrapper: transpose(jvp(jit(_take)))
    assert scope_regex_share.read(ctx, r"jit\(_take\)") > 0
    assert scope_regex_share.read(ctx, r"_take") > 0      # jit( is one too
    assert scope_regex_share.read(ctx, r"ake") is None     # not a start
    tr = ctx["trace"]
    assert any("%custom-call.26" in n and not n.startswith("%custom-call.26 ")
               for _, _, n in tr.ops_in_window())
    assert scope_regex_share.read(ctx, r"[^/()]*custom-call\.26") is None
    assert scope_regex_share.read(ctx, "no_such", ops=["custom-call.26"]) \
        == pytest.approx(scope_time_share.read(ctx, ["no_such"],
                                               ["custom-call.26"]))


def test_the_node_pattern_of_the_fit_metrics(ctx, monkeypatch):
    """The fit cell's patterns on name stacks as ComputationGraph writes
    them: a node's component in a wrapper or bare, its loss, and what must
    not match (a parameter's path, an einsum with an ellipsis)."""
    conv = json.loads((METRICS / "conv_time_share_pct.fit.json").read_text())
    rest = json.loads(
        (METRICS / "unscoped_time_share_pct.fit.json").read_text())
    events = [n for _, _, n in ctx["trace"].ops_in_window()
              if not xplane._is_container(n)]
    stacks = {
        "jit(step)/jvp(res2a_conv.ConvolutionLayer)/conv_general_dilated:":
            (True, True),
        "jit(step)/transpose(jvp(stem_bn.BatchNormalization))/mul:":
            (False, True),
        "jit(step)/jvp(out.OutputLayer.loss)/reduce_sum:": (False, True),
        "jit(step)/optimizer/add:": (False, True),
        "jit(step)/res2a_conv.ConvolutionLayerish/mul:": (False, True),
        "opt_state[0].mu['stem_conv']['W']:": (False, False),
        "jit(step)/jvp()/...d,df->...f/dot_general:": (False, False),
        "jit(step)/jvp()/add:": (False, False),
        "": (False, False),
    }
    names = sorted(set(events))[:len(stacks)]
    for (stack, (is_conv, is_named)), name in zip(stacks.items(), names):
        monkeypatch.setattr(scope_time_share, "scopes_of",
                            lambda path, s=stack, n=name: {n: s})
        found = scope_regex_share.split_seconds(ctx, conv["args"]["pattern"])
        assert (found[2] > 0) == is_conv, stack
        found = scope_regex_share.split_seconds(ctx, rest["args"]["pattern"])
        assert (found[2] > 0) == is_named, stack


def test_nothing_to_read_is_none_not_zero(ctx):
    assert scope_regex_share.read(ctx, "moe_") is None
    assert scope_regex_share.read(ctx, "moe_", ops=["ragged-dot"]) is None
    ctx["trace"] = None
    assert scope_regex_share.read(ctx, "whil") is None
    assert scope_regex_share.read(ctx, "whil", rest=True) is None


def test_registry_value_reads_one_series_or_nothing():
    from deeplearning4j_tpu.obs import get_registry
    reg = get_registry()
    plain = reg.counter("dl4j_test_registry_value_total", "a test's")
    plain.inc(2.5)
    by = reg.counter("dl4j_test_registry_value_by_phase_total", "a test's",
                     labelnames=("phase",))
    by.inc(3.0, phase="trace")
    read = registry_value.read
    assert read({}, "dl4j_test_registry_value_total") == plain.value()
    assert read({}, "dl4j_test_registry_value_total", scale=2.0) \
        == 2.0 * plain.value()
    assert read({}, "dl4j_test_registry_value_by_phase_total",
                labels={"phase": "trace"}) == by.value(phase="trace")
    # a series nothing has moved yet is 0, an instrument nobody registered
    # is nothing
    assert read({}, "dl4j_test_registry_value_by_phase_total",
                labels={"phase": "lower"}) == 0.0
    assert read({}, "dl4j_no_such_counter_total") is None
