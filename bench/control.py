#!/usr/bin/env python3
"""The control of ``correct``: the plain reference in float32, one
precision below the float64 the configurations state, put in the
program's place at a cell's own size.

    python3 bench/control.py --workload dse5.reselect --seeds 1 2 3

For each seed it traces the cell's workloads with the seeded inputs,
prices every point of the space with the reference in float32 and
compares those records with the float64 reference exactly as a run's
records are compared (:func:`bench.check.compare`).  It prints each
seed's ``price_gap`` beside the configuration's limit; the control has to
read above it.  The benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import numpy as np
    from bench import cell, check, reference
    from bench.inputs import seeded_workloads
    from repro.core.trace import trace_structural
    from repro.workloads import build

    _, config, _ = cell.load_cell(args.workload)
    space, geometries = cell.build_space(config)
    limit = config["limits"]["price_gap"]
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        with seeded_workloads(config["inputs"], seed, config.get("scales")):
            streams = {}
            for w in config["workloads"]:
                fn, wargs = build(w)
                streams[w] = reference.stream_of(
                    trace_structural(fn, *wargs).columns)
        ref64 = check.Reference(streams, geometries, config["cim_set"])
        ref32 = check.Reference(streams, geometries, config["cim_set"],
                                dtype=np.float32)
        sweep = {"records": check.reference_records(space, ref32),
                 "streams": {}, "traces": {}, "selections": {}}
        numbers = check.compare([sweep], space, config, ref64)
        rows.append({"seed": seed, "price_gap": numbers["price_gap"],
                     "limit": limit, "fails": numbers["price_gap"] > limit,
                     "seconds": time.perf_counter() - t0})
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "control": rows}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
