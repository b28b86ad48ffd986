"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

What the v5e's trace looks like (looked at by hand, PR 25): one plane per
chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed HLO operation, named by its whole HLO line (``%fusion.269 =
bf16[...] fusion(...)``; a Pallas kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"`` named after the jaxpr scope it sits
in, ``%closed_call.10``, ``%checkpoint.20``, not after the kernel), with a
``%while`` event spanning the ops of its body on the same line; beside it
``XLA Modules`` (one event per program run), ``Steps`` and ``Async XLA Ops``
(copies in flight, which overlap the ops and are not counted). The host's
plane ``/host:CPU`` has one line per thread; ``jax.profiler.TraceAnnotation``
spans appear on the calling thread's line under their own names, on the
same clock as the device lines (the stretch opened 0.35 ms before the first
device op of the first traced step).

- the traced window is the benchmark's own ``bench_stretch`` annotation
  (where the host tracer is off, ``trace_host_level`` 0 in the traffic file,
  its length by the host's clock, ending at the last device op);
- busy time is the union of the device-op intervals inside the window (a
  ``while`` event and the ops nested in it overlap, the union counts them
  once), averaged over the chips used;
- an idle gap is a maximal interval of the window with no device op; it is
  labelled with the innermost annotation of the benchmark's thread that
  covers its midpoint.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

STRETCH = "bench_stretch"
OPS_LINE = "XLA Ops"
#: events on the ops line that contain other ops: their time is their
#: children's, so they are left out of per-op sums (not out of the union)
CONTAINERS = ("while", "conditional", "call")


def _union(intervals):
    """Merged, sorted list of (start, end)."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def short_name(name: str) -> str:
    """``%fusion.269 = bf16[16,1024,1024]{...} fusion(...)`` ->
    ``fusion.269 bf16[16,1024,1024]``: an event's name is its whole HLO
    line, too long to report."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not rest:
        return head
    kind = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head} {kind}" if not rest.startswith("(") else head


def _is_container(name: str) -> bool:
    base = name.split(" = ", 1)[0].split(".")[0].lstrip("%")
    return base in CONTAINERS


class Trace:
    def __init__(self, device_ops, host_spans, stretch_s=None):
        #: {chip index: [(start_ns, end_ns, name)]}
        self.device_ops = device_ops
        #: [(start_ns, end_ns, name)] of the thread that holds the stretch
        self.host_spans = host_spans
        stretch = [s for s in host_spans if s[2] == STRETCH]
        all_ops = [o for ops in device_ops.values() for o in ops]
        if stretch:
            self.t0, self.t1 = stretch[0][0], stretch[0][1]
            self.window_from = STRETCH
        elif all_ops and stretch_s:
            # host tracer off: the stretch as the host's clock timed it,
            # ending (at a sync point) with the last device op
            self.t1 = max(o[1] for o in all_ops)
            self.t0 = self.t1 - stretch_s * 1e9
            self.window_from = "host clock, ending at the last device op"
        elif all_ops:
            self.t0 = min(o[0] for o in all_ops)
            self.t1 = max(o[1] for o in all_ops)
            self.window_from = "first to last device op"
        else:
            self.t0 = self.t1 = 0.0
            self.window_from = "empty"
        self.window_s = (self.t1 - self.t0) / 1e9
        self._busy = {}
        for chip, ops in device_ops.items():
            clipped = [(max(s, self.t0), min(e, self.t1)) for s, e, _ in ops
                       if e > self.t0 and s < self.t1]
            self._busy[chip] = _union(clipped)
        per_chip = [sum(e - s for s, e in u) / 1e9 for u in self._busy.values()]
        self.busy_s = sum(per_chip) / len(per_chip) if per_chip else 0.0

    # ------------------------------------------------------------ queries
    def ops_in_window(self):
        for ops in self.device_ops.values():
            for s, e, name in ops:
                if e > self.t0 and s < self.t1:
                    yield s, e, name

    def event_time_s(self, pattern: str) -> tuple:
        """(seconds, count) of the window's device events whose name holds
        ``pattern``, summed over events and averaged over chips."""
        total, n = 0.0, 0
        for s, e, name in self.ops_in_window():
            if pattern in name and not _is_container(name):
                total += min(e, self.t1) - max(s, self.t0)
                n += 1
        chips = max(len(self.device_ops), 1)
        return total / 1e9 / chips, n // chips

    def idle_s(self) -> float:
        return max(self.window_s - self.busy_s, 0.0)

    def gaps(self):
        """[(seconds, label)] of the idle gaps on the first chip, longest
        first."""
        if not self._busy:
            return []
        chip = min(self._busy)
        edges = [self.t0]
        for s, e in self._busy[chip]:
            edges += [s, e]
        edges.append(self.t1)
        out = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                out.append(((b - a) / 1e9, self._label((a + b) / 2)))
        return sorted(out, reverse=True)

    def _label(self, t) -> str:
        best = None
        for s, e, name in self.host_spans:
            if s <= t <= e and name != STRETCH:
                if best is None or (e - s) < best[0]:
                    best = (e - s, name)
        return best[1] if best else "host_outside_spans"

    # ------------------------------------------------------------ reports
    def breakdown(self) -> dict:
        per = defaultdict(float)
        for s, e, name in self.ops_in_window():
            if not _is_container(name):
                per[short_name(name)] += (min(e, self.t1) - max(s, self.t0)) / 1e9
        chips = max(len(self.device_ops), 1)
        ops = sorted(((n, t / chips) for n, t in per.items()),
                     key=lambda x: -x[1])[:10]
        by_label = defaultdict(float)
        for secs, label in self.gaps():
            by_label[label] += secs
        gaps = sorted(by_label.items(), key=lambda x: -x[1])[:10]
        return {"device_ops": [[n, t] for n, t in ops],
                "idle_gaps": [[n, t] for n, t in gaps]}

    def notes(self):
        yield (f"trace: window {self.window_s:.4f} s from {self.window_from}, "
               f"busy {self.busy_s:.4f} s on {len(self.device_ops)} chip(s), "
               f"{sum(len(o) for o in self.device_ops.values())} device events")


def find_xplane(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(trace_dir_or_file, chips: int = 1, stretch_s=None) -> Trace:
    from jax.profiler import ProfileData
    path = str(trace_dir_or_file)
    if not path.endswith(".pb"):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    device_ops, host_lines = {}, []
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            chip = int(name.rsplit(":", 1)[1].split()[0])
            if chip >= chips:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[chip] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif name.startswith("/host:"):
            for line in plane.lines:
                # "$..." events are the Python tracer's function calls
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events if not e.name.startswith("$")]
                if any(n == STRETCH for _, _, n in evs):
                    host_lines = evs
    return Trace(device_ops, host_lines, stretch_s)
