"""The scope readers on the trace PR 25 recorded on the v5e
(``data/lm.xplane.pb.gz``): the hand-written protobuf reader finds each
device event's ``tf_op``, and events are matched by the instruction's own
name or by a path component of the scope, never by a substring of the
whole HLO line (which also names the instruction's operands)."""
import gzip
from pathlib import Path

import pytest

import xplane
from reducers import scope_roofline, scope_time_share

DATA = Path(__file__).resolve().parent / "data"


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
            "config": {}, "traffic": {},
            "peaks": {"flops_per_s": {"bf16": 197e12}, "hbm_bytes_per_s": 819e9}}


def test_every_device_event_has_a_name_and_most_a_scope(trace_dir):
    scopes = scope_time_share.scopes_of(xplane.find_xplane(trace_dir))
    assert len(scopes) == 395
    assert sum(bool(v) for v in scopes.values()) == 291
    name = next(n for n in scopes if n.startswith("%convert_element_type.69 "))
    assert scopes[name] == "jit(step)/jvp()/convert_element_type:"


def test_events_are_matched_by_their_own_name_not_their_operands(ctx):
    tr = ctx["trace"]
    own = [n for _, _, n in tr.ops_in_window()
           if n.startswith("%custom-call.26")]
    anywhere = [n for _, _, n in tr.ops_in_window() if "%custom-call.26" in n]
    assert own and len(anywhere) > len(own)     # its reader names it too
    _, n = scope_time_share.scope_seconds(ctx, ["no_such_scope"],
                                          ["custom-call.26"])
    assert n == len(own)
    seconds, n = scope_time_share.scope_seconds(ctx, ["no_such_scope"],
                                                ["fusion.269"])
    assert n == 120
    assert seconds == pytest.approx(tr.event_time_s("%fusion.269 = ")[0])


def test_scope_is_a_path_component_inside_any_transform(ctx):
    # "jit(step)/transpose(jvp())/while/body/..." holds the scope "while"
    # as a component; "whil" is a prefix of it, "hile" is not
    by_prefix, n = scope_time_share.scope_seconds(ctx, ["whil"])
    assert n > 0 and by_prefix > 0
    assert scope_time_share.scope_seconds(ctx, ["hile"]) == (0.0, 0)
    share = scope_time_share.read(ctx, ["whil"])
    assert 0 < share <= 100


def test_nothing_to_read_is_none_not_zero(ctx):
    assert scope_time_share.read(ctx, ["moe_"], ["ragged-dot"]) is None
    assert scope_roofline.read(ctx, ["moe_"], "moe_flops:experts_flops_bytes",
                               [2, 8192, 2560, 768, 16, 6, 4, 0.25]) is None
    # a counter the program does not hand back: nothing, and no raise
    assert scope_roofline.read(ctx, ["whil"], "moe_flops:experts_flops_bytes",
                               [2, 8192, 2560, 768, 16, 6, 4,
                                "moe_local_share"]) is None
    ctx["trace"] = None
    assert scope_time_share.read(ctx, ["whil"]) is None


def test_roofline_share_of_a_scope(ctx):
    share = scope_roofline.read(ctx, ["whil"], "moe_flops:experts_flops_bytes",
                                [2, 8192, 2560, 768, 16, 6, 4, 0.25])
    seconds, _ = scope_time_share.scope_seconds(ctx, ["whil"])
    assert share == pytest.approx(100 * (5 * 3478923509760.0 / 197e12) / seconds)
