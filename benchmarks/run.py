"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run table6     # one artifact
    PYTHONPATH=src python -m benchmarks.run --list     # enumerate artifacts

    # per-stage analysis throughput (trace/IDG/selection/pricing), written
    # as JSON; --timing-workloads restricts to a subset (CI runs the
    # smallest workload only), and --timing-gate BASELINE fails the run if
    # selection+pricing throughput regresses >25% vs the committed,
    # calibration-scaled baseline:
    PYTHONPATH=src python -m benchmarks.run --timing-json BENCH_analysis.json
    PYTHONPATH=src python -m benchmarks.run --timing-json out.json \\
        --timing-workloads NB --timing-gate benchmarks/baselines/timing_nb.json
"""
from __future__ import annotations

import sys
import time

from benchmarks import (analysis_timing, fig12_macr_validation, fig13_macr,
                        fig14_cache_cfg, fig15_levels, fig16_tech,
                        fig17_host, fig_adaptive, fig_tpu_dse, roofline,
                        table3_energy, table5_validation, table6_speedup,
                        tpu_macr)

ALL = {
    "table3": table3_energy,
    "table5": table5_validation,
    "fig12": fig12_macr_validation,
    "fig13": fig13_macr,
    "table6": table6_speedup,
    "fig14": fig14_cache_cfg,
    "fig15": fig15_levels,
    "fig16": fig16_tech,
    "fig17": fig17_host,
    "fig_adaptive": fig_adaptive,
    "tpu_macr": tpu_macr,
    "fig_tpu_dse": fig_tpu_dse,
    "roofline": roofline,
}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--list" in argv:
        for name, mod in ALL.items():
            doc = next(iter((mod.__doc__ or "").strip().splitlines()), "")
            print(f"{name:10s} {doc}")
        print(f"{'--timing-json PATH':18s} "
              f"{(analysis_timing.__doc__ or '').strip().splitlines()[0]}")
        return 0
    if "--timing-json" in argv:
        argv = list(argv)

        def take_value(flag: str):
            i = argv.index(flag)
            if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
                print(f"{flag} requires a value "
                      f"(e.g. {flag} BENCH_analysis.json)")
                raise SystemExit(2)
            value = argv[i + 1]
            del argv[i:i + 2]
            return value

        json_path = take_value("--timing-json")
        workloads = None
        if "--timing-workloads" in argv:
            workloads = tuple(take_value("--timing-workloads").split(","))
        gate_path = (take_value("--timing-gate")
                     if "--timing-gate" in argv else None)
        trace_path = (take_value("--trace")
                      if "--trace" in argv else None)
        doc = analysis_timing.main(workloads=workloads, json_path=json_path,
                                   gate_path=gate_path,
                                   trace_path=trace_path)
        if doc.get("gate", {}).get("failures"):
            return 1
        if not argv:                       # timing only, no named artifacts
            return 0
        # fall through: any remaining names run as usual after the timing
    picks = argv or list(ALL)
    t0 = time.time()
    for name in picks:
        if name not in ALL:
            print(f"unknown benchmark {name!r}; known: {sorted(ALL)}")
            return 1
        ALL[name].main()
    print(f"\n[benchmarks] done in {time.time() - t0:.1f}s "
          f"({len(picks)} artifacts under benchmarks/artifacts/)", flush=True)
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
