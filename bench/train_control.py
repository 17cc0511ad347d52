#!/usr/bin/env python3
"""The readings that a train cell's limits are set from, at the cell's own
size, all in one process.

    python3 bench/train_control.py --workload qwen05.train --seeds 1 2 3 \
        --faults 2

For each seed it runs the entry through the cell's compared steps (the
same set-up as a run, with a window of one step) and reads the program's
numbers against the plain float32 reference (the lower readings); then the
controls, the reference with every contraction in int8, in float8 e4m3 or
in float8 e5m2 put in the program's place (one precision below the
bfloat16 the configuration states); and, on the first ``--faults`` seeds,
faults planted in the reference put in the program's place: half of each
batch left out, the update doubled, the middle layer skipped, the causal
mask left out.  One JSON line per seed and stand-in, with ``correct``: the
run's decision, each number against the configuration's limit.  The
benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAULTS = ("half_batch", "double_update", "skip_layer", "no_causal_mask")
CONTROLS = ("int8", "e4m3", "e5m2")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=0,
                    help="plant the faults on this many of the seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]

    from bench import cell, run
    from bench.kinds import train

    entry, config, traffic = cell.load_cell(args.workload)
    devices = run.devices_or_exit(entry["chips"])
    run.enable_compile_cache()
    watch = run.CompileWatch()

    limits = config["limits"]

    def emit(seed, who, numbers, t0):
        compared = [k for k in numbers if k in limits]
        if compared:
            numbers = dict(numbers, correct=all(
                numbers[k] <= limits[k] for k in compared))
        print(json.dumps({"seed": seed, "who": who, **numbers,
                          "seconds": round(time.perf_counter() - t0, 3),
                          "memory_peak_bytes": (devices[0].memory_stats()
                                                or {}).get(
                                                    "peak_bytes_in_use")}),
              flush=True)

    for i, seed in enumerate(args.seeds):
        w = train.drive(config, traffic, seed, 0.0, watch, cell.WORK,
                        run.log)
        prog = {"loss": w.losses, **w.readings}
        del w
        t0 = time.perf_counter()
        ref = train.reference_readings(config, traffic, seed)
        emit(seed, "reference", {"loss": ref["loss"]}, t0)
        emit(seed, "program", train.compare(prog, ref), t0)
        stand_ins = [(c, None) for c in CONTROLS] + [
            ("f32", f) for f in FAULTS if i < args.faults]
        for mode, fault in stand_ins:
            t0 = time.perf_counter()
            other = train.reference_readings(config, traffic, seed,
                                             mode=mode, fault=fault)
            emit(seed, fault or mode, train.compare(other, ref), t0)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
