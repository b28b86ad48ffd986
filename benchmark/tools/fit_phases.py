#!/usr/bin/env python3
"""Run one traced run of a ``fit()`` cell in this process and print, from the
program's own spans, the median of every ``fit.*`` and ``data.*`` span per
iteration in three phases: before the profiler came on, during the traced
stretch, and after ``stop_trace``; then the sum that should match
``host_gap_ms.fit`` (``fit.next`` + ``fit.h2d`` + ``fit.dispatch`` +
``fit.listeners`` + the iteration's self time, by their medians before the
profiler). The spans go to ``<out>.spans.jsonl``.

    python3 benchmark/tools/fit_phases.py <out> --workload resnet50-fit-b256 --seed 7 --seconds 30 --trace 1
"""
import contextlib
import io
import json
import statistics
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import run  # noqa: E402

HOST_SIDE = ("fit.next", "fit.h2d", "fit.dispatch", "fit.listeners", "self")


class Tee(io.StringIO):
    def write(self, s):
        sys.__stdout__.write(s)
        return super().write(s)


def phase_table(its, spans, cuts):
    """{phase: {span name: median ms per iteration}} for the phases
    ``cuts`` = {phase: (first batch, one past the last)}."""
    per = {}          # (name, batch) -> ms
    for s in spans:
        k = s.attrs.get("batch")
        if k is not None and s.name != "fit.iteration":
            per[(s.name, k)] = per.get((s.name, k), 0.0) + 1e3 * s.time_s
    ids = {s.span_id: s.attrs["batch"] for s in its}
    covered = {}
    for s in spans:
        if s.parent_id in ids:
            k = ids[s.parent_id]
            covered[k] = covered.get(k, 0.0) + 1e3 * s.time_s
    for s in its:
        k = s.attrs["batch"]
        per[("fit.iteration", k)] = 1e3 * s.time_s
        per[("self", k)] = 1e3 * s.time_s - covered.get(k, 0.0)
    table = {}
    for phase, (lo, hi) in cuts.items():
        row = {}
        for name in sorted({n for n, _ in per}):
            vals = [v for (n, k), v in per.items() if n == name and lo <= k < hi]
            if vals:
                row[name] = statistics.median(vals)
        table[phase] = row
    return table


def main(argv):
    out, argv = argv[0], argv[1:]
    captured = Tee()
    with contextlib.redirect_stdout(captured):
        rc = run.main(argv)
    if rc:
        return rc
    result = json.loads(captured.getvalue().strip().splitlines()[-1])
    from deeplearning4j_tpu.obs import get_tracer
    from reducers.span_ms import fit_iterations
    its, spans = fit_iterations({"counters": {}})
    if not its:
        print("fit_phases: the program recorded no fit spans", file=sys.stderr)
        return 0
    workload = argv[argv.index("--workload") + 1]
    _, _, traffic = run.find_cell(run.load_json(run.ROOT / "BENCHMARK.json"),
                                  workload)
    a = int(traffic.get("trace_from_step", 5))
    b = a + int(traffic.get("trace_steps", 5))
    # batch 0 of a call starts the prefetch thread; batches a and b hold the
    # profiler's own start and stop inside fit.listeners
    cuts = {"before": (1, a - 1), "traced": (a, b - 1),
            "after": (b, len(its))}
    table = phase_table(its, spans, cuts)
    names = sorted({n for row in table.values() for n in row})
    print(f"fit_phases: {len(its)} iterations; median ms per iteration in "
          f"batches {cuts}")
    print(f"{'span':18s}" + "".join(f"{p:>12s}" for p in table))
    for n in names:
        print(f"{n:18s}" + "".join(
            f"{table[p][n]:12.3f}" if n in table[p] else f"{'-':>12s}"
            for p in table))
    for k, what in ((a - 1, "start_trace"), (b - 1, "stop_trace")):
        ms = [1e3 * s.time_s for s in spans
              if s.name == "fit.listeners" and s.attrs.get("batch") == k]
        if ms:
            print(f"fit_phases: fit.listeners of batch {k} (holds {what}) "
                  f"{ms[0]:.1f} ms")
    host = sum(table["before"].get(n, 0.0) for n in HOST_SIDE)
    gap = result["metrics"].get("host_gap_ms.fit", {}).get("value")
    print(f"fit_phases: host side before the profiler "
          f"({' + '.join(HOST_SIDE)}) {host:.3f} ms; host_gap_ms.fit {gap}")
    path = Path(f"{out}.spans.jsonl")
    path.parent.mkdir(parents=True, exist_ok=True)
    path.unlink(missing_ok=True)
    get_tracer().export_jsonl(path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
