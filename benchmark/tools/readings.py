#!/usr/bin/env python3
"""Readings the limits are set from, at the cell's own size, on the chip.

For each seed: the program's readings through the driver's own set-up (its
first steps by the window's call and feed) against the plain reference: the
LOWER readings. For the first ``--control-seeds`` seeds also the control
(the reference in the nearest precision below the configuration's) and the
half-batch fault (the reference on half of each batch's rows, the mean taken
over them), each against the reference: the UPPER readings. One JSON line
per seed on standard output and in ``chiprun_out/readings.<workload>.jsonl``.

    python3 benchmark/tools/readings.py --workload W --seeds 11 12 ... --control-seeds 3
"""
import argparse
import importlib
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parent), str(HERE)]
import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()
    import os
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell, config, traffic = run.find_cell(bench, args.workload)
    run.STATE.mkdir(exist_ok=True)
    os.environ["DL4J_TPU_DATA"] = str(run.STATE)
    run.require_chip(int(cell["chips"]), run.load_json(HERE / "peaks.json"))
    from deeplearning4j_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import compare
    driver = importlib.import_module(f"drivers.{traffic['driver']}")
    probe = run.Probe(False, traffic)
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    with open(out / f"readings.{args.workload}.jsonl", "a") as f:
        for k, seed in enumerate(args.seeds):
            t = time.time()
            st = driver.setup(config, traffic, seed, probe)
            driver.release(st)
            want = driver.reference_readings(st)
            rec = {"seed": seed,
                   "program": compare.training_gaps(st.readings, want)}
            if k < args.control_seeds:
                rec["control"] = compare.training_gaps(
                    driver.reference_readings(st, product=driver.CONTROL_PRODUCT),
                    want)
                half = slice(0, int(traffic["batch"]) // 2)
                rec["half_batch"] = compare.training_gaps(
                    driver.reference_readings(st, rows=half), want)
            rec["seconds"] = round(time.time() - t, 1)
            rec["ref_losses"] = want["losses"]
            line = json.dumps(rec)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()


if __name__ == "__main__":
    main()
