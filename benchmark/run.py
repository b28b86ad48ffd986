#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in this process, on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data this file looks up by the names
in BENCHMARK.json: ``configs/<config>.json`` (the file the configuration
entry names), ``traffic/<traffic>.json`` (whose ``driver`` names
``drivers/<driver>.py``), ``end_to_end/<metric>.json`` and
``layer_metrics/<metric>.json`` (each naming a reader in ``reducers/``), and
``limits/<workload>.json``. This file holds no name of a cell, a
configuration or a metric.

The last line of standard output is the result object; the numbers compared
for ``correct`` are the last lines of standard error and the last key of the
result. Exit codes: 0 a result was printed; 3 no TPU, an unknown device kind
or too few chips; 4 not a checkout of the system.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import contextlib        # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
STATE = HERE / ".state"
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, load_json(ROOT / entry["file"]), \
        load_json(HERE / "traffic" / f"{cell['traffic']}.json")


def metrics_for(bench: dict, kind: str, cell: dict) -> list:
    """The metrics of ``kind`` that this cell reports: those that list it
    under ``workloads``, or list nothing (and, per layer, move a metric the
    cell reports)."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if kind == "end_to_end":
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def require_chip(chips: int, peaks: dict):
    """The devices to measure on, or exit 3: never a CPU fallback."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" or d0.device_kind not in peaks \
            or len(devs) < chips:
        print(f"refusing to measure: platform {d0.platform!r}, kind "
              f"{d0.device_kind!r}, {len(devs)} device(s); need {chips} TPU "
              f"chip(s) of a kind in peaks.json {sorted(peaks)}",
              file=sys.stderr)
        raise SystemExit(3)
    return devs


class Probe:
    """What a driver is handed to mark its calls and its sync points. With
    ``--trace 1`` the profiler runs from sync point ``trace_from_step`` to
    sync point ``trace_from_step + trace_steps`` of the window, inside the
    benchmark's own ``bench_stretch`` annotation. The part of the window
    before that is what the traced run's own rates are taken over: once the
    profiler has run, a host-fed loop stays slower for the rest of the
    process (fit() iterations 327 -> 480 ms; my chip run, PR 25)."""

    def __init__(self, trace: bool, traffic: dict):
        self.trace = trace
        self.start_at = int(traffic.get("trace_from_step", 5))
        self.stop_at = self.start_at + int(traffic.get("trace_steps", 5))
        #: 2 keeps JAX's own host spans beside the benchmark's annotations;
        #: 0 records the device alone, for traffic whose host threads would
        #: flood the trace (the stretch is then timed by the host's clock)
        self.host_level = int(traffic.get("trace_host_level", 2))
        self.dir = STATE / "trace"
        self.profiler_s = 0.0
        self.stretch_s = 0.0
        self.t_window = None       # set by main() as the window opens
        self.pre_trace_steps = 0   # steps and seconds of the window before
        self.pre_trace_s = 0.0     # the profiler came on
        self.traced_steps = 0
        self._stack = None
        self.done = False

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def at_sync(self, steps_done: int):
        if not self.trace or self.done:
            return
        import jax
        t = time.perf_counter()
        if self._stack is None and steps_done >= self.start_at:
            self.pre_trace_steps = steps_done
            self.pre_trace_s = t - self.t_window
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no per-call Python events
            opts.host_tracer_level = self.host_level
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self._stack = contextlib.ExitStack()
            self._stack.enter_context(
                jax.profiler.TraceAnnotation("bench_stretch"))
            self._from, self._t_from = steps_done, time.perf_counter()
        elif self._stack is not None and steps_done >= self.stop_at:
            self.stretch_s = t - self._t_from
            self._stack.close()
            jax.profiler.stop_trace()
            self.traced_steps = steps_done - self._from
            self.done = True
        self.profiler_s += time.perf_counter() - t

    def abandon(self):
        if self._stack is not None and not self.done:
            import jax
            self._stack.close()
            jax.profiler.stop_trace()
            self.done = True


def read_metrics(metrics: list, folder: str, ctx: dict) -> dict:
    out = {}
    for m in metrics:
        spec = load_json(HERE / folder / f"{m['name']}.json")
        reader = importlib.import_module(f"reducers.{spec['reducer']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, require=require_chip) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "deeplearning4j_tpu" / "__init__.py").is_file():
        print("not a checkout of the system: deeplearning4j_tpu/ is missing",
              file=sys.stderr)
        return 4
    for p in (str(ROOT), str(HERE)):
        if p not in sys.path:
            sys.path.insert(0, p)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell, config, traffic = find_cell(bench, args.workload)
    peaks_table = load_json(HERE / "peaks.json")

    # the program's autotune records (the flash block race) live in the
    # checkout, so a cell's later runs read them instead of racing again
    STATE.mkdir(exist_ok=True)
    os.environ["DL4J_TPU_DATA"] = str(STATE)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = require(int(cell["chips"]), peaks_table)
    from deeplearning4j_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(time.perf_counter())
        if name == COMPILE_EVENT else None)

    driver = importlib.import_module(f"drivers.{traffic['driver']}")
    probe = Probe(bool(args.trace), traffic)
    print(f"cell {cell['name']}: driver {traffic['driver']}, seed "
          f"{args.seed}, cache {cache_dir}, state {STATE}", file=sys.stderr)
    state = driver.setup(config, traffic, args.seed, probe)
    probe.t_window = t_window = time.perf_counter()
    counters = driver.window(state, args.seconds, probe)
    t_end = time.perf_counter()
    probe.abandon()
    stats = [d.memory_stats() or {} for d in devices[: int(cell["chips"])]]
    # what the chip held at its fullest: live buffers at their peak plus the
    # scratch the runtime reserves for the loaded programs' temporaries,
    # which the v5e's runtime reports apart (peak_bytes_reserved)
    peak_bytes = max((s.get("peak_bytes_in_use", 0)
                      + s.get("peak_bytes_reserved", 0) for s in stats),
                     default=0)
    print("memory_stats " + json.dumps(stats[0]), file=sys.stderr)

    counters.update({
        "setup_s": t_window - T_START,
        "compiles_in_window": sum(t_window <= t <= t_end for t in compiles),
        "compiles_in_setup": sum(t < t_window for t in compiles),
        "peak_bytes": peak_bytes,
        "profiler_s": probe.profiler_s,
        "traced_steps": probe.traced_steps,
        "pre_trace_steps": probe.pre_trace_steps,
        "pre_trace_s": probe.pre_trace_s,
    })
    d0 = devices[0]
    peaks = peaks_table.get(d0.device_kind, {})
    ctx = {"counters": counters, "config": config, "traffic": traffic,
           "peaks": peaks, "chips": int(cell["chips"]), "trace": None}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    traced = {}
    if args.trace:
        import xplane
        ctx["trace"] = tr = xplane.load(probe.dir, int(cell["chips"]),
                                        stretch_s=probe.stretch_s)
        device["busy_s"], device["window_s"] = tr.busy_s, tr.window_s
        traced["breakdown"] = tr.breakdown()
        for line in tr.notes():
            print(line, file=sys.stderr)
        metrics = read_metrics(metrics_for(bench, "per_layer", cell),
                               "layer_metrics", ctx)
    else:
        metrics = read_metrics(metrics_for(bench, "end_to_end", cell),
                               "end_to_end", ctx)
    print("counters " + json.dumps(
        {k: v for k, v in counters.items()
         if not isinstance(v, list) or len(v) <= 8}),
        file=sys.stderr)

    # correctness last: the window is closed, the peak is read, and check()
    # frees the program's state before the reference runs
    import compare
    t_check = time.perf_counter()
    gaps = driver.check(state)
    limits = load_json(HERE / "limits" / f"{cell['name']}.json")
    correct, compared = compare.judge(gaps, limits)
    print(f"check took {time.perf_counter() - t_check:.1f} s",
          file=sys.stderr)
    compare.print_compared(compared, correct)
    result = {"correct": bool(correct),
              "attempted": int(counters["attempted"]),
              "failed": int(counters["failed"]),
              "metrics": metrics, "device": device, **traced,
              "compared": compared}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
