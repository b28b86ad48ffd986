"""The trace reducer on a trace recorded on the v5e by this PR's own chip
run (cell gpt2m-train-b16, five traced steps; gzipped to keep the tree
small)."""
import gzip
import json
from pathlib import Path

import pytest

import xplane

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    pb = tmp_path_factory.mktemp("trace") / "lm.xplane.pb"
    pb.write_bytes(gzip.decompress((DATA / "lm.xplane.pb.gz").read_bytes()))
    return xplane.load(str(pb))


def test_union_merges_overlaps():
    assert xplane._union([(0, 5), (3, 8), (10, 12), (12, 13)]) == [[0, 8], [10, 13]]


def test_short_name():
    name = ("%fusion.269 = bf16[16,1024,1024]{1,2,0:T(8,128)(2,1)S(1)} "
            "fusion(bf16[16,1024,1024]{1,2,0} %get-tuple-element.1)")
    assert xplane.short_name(name) == "fusion.269 bf16[16,1024,1024]"
    assert xplane._is_container("%while.15 = (s32[]{:T(128)}) while(...)")
    assert not xplane._is_container(name)


def test_busy_union_and_window(trace):
    expected = json.loads((DATA / "lm.expected.json").read_text())
    assert trace.window_from == "bench_stretch"
    assert trace.window_s == pytest.approx(expected["window_s"], rel=1e-9)
    assert trace.busy_s == pytest.approx(expected["busy_s"], rel=1e-9)
    # five steps of about 0.484 s, the device busy nearly throughout
    assert 2.3 < trace.window_s < 2.6
    assert 0.99 < trace.busy_s / trace.window_s <= 1.0
    # the while loops contain their bodies: a plain sum would count twice
    plain = sum(e - s for s, e, _ in trace.ops_in_window()) / 1e9
    assert plain > 1.5 * trace.busy_s


def test_event_name_sum(trace):
    expected = json.loads((DATA / "lm.expected.json").read_text())
    seconds, n = trace.event_time_s("tpu_custom_call")
    # forward, recomputed forward, backward dq, backward dkv: 4 kernels x
    # 24 layers x 5 steps
    assert n == 4 * 24 * 5
    assert seconds == pytest.approx(expected["pallas_s"], rel=1e-9)
    assert trace.event_time_s("no-such-kernel") == (0.0, 0)


def test_gap_attribution(trace):
    gaps = trace.gaps()
    assert gaps == sorted(gaps, reverse=True)
    assert sum(g for g, _ in gaps) == pytest.approx(trace.idle_s(), rel=1e-6)
    labels = {label for _, label in gaps}
    assert labels & {"next_batch", "loss_fetch", "step_dispatch",
                     "np.asarray(jax.Array)"}
    br = trace.breakdown()
    assert len(br["device_ops"]) == 10 and len(br["idle_gaps"]) <= 10
    assert all(not n.startswith("while") for n, _ in br["device_ops"])
