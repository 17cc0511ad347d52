"""Sweeps of an Eva-CiM design space: the kind of a configuration that
names no ``kind``.

The run draws the workloads' inputs from ``--seed``, fills the mix's store
and warms up with one sweep, then runs
``DSEEngine(executor="thread").run(space)`` sweep after sweep until
``--seconds`` have passed; the window ends with the sweep in progress.
After the window the records of every sweep, and the stream, replay and
selections of one sweep drawn from the seed, are compared with the plain
reference (:mod:`bench.check`).  ``--trace 1`` reads the program's spans
over the window and a profiler trace of part of it.
"""
from __future__ import annotations

import os
import random
import resource
import shutil
import time

from bench import check

NUMBERS = check.NUMBERS
END_TO_END = ("points_per_s", "peak_rss_gb", "setup_s")


def measurements(span_records, points, device, observed, reference,
                 geometries, device_kind):
    """Spans of the window, the profiled part's device trace reduced
    (None where the profile caught no device), and the sizes of every
    replay and placement launch, per workload."""
    from bench import measure
    from bench.peaks import peak

    replay_sizes, place_sizes = {}, {}
    for (w, c), cols in observed["traces"].items():
        replay_sizes[w] = (len(cols[0]), list(geometries.values()))
    for (w, c, levels) in observed["selections"]:
        if w not in place_sizes:
            cands, _ = reference.select(w, c, levels)
            place_sizes[w] = (sum(x["leaves"] for x in cands),
                              sum(len(x["load_seqs"]) + len(x["store_seqs"])
                                  for x in cands), len(cands))
    return measure.Measurements(span_records, points, device,
                                peak(device_kind), replay_sizes, place_sizes)


def run(ctx):
    """One run of a sweep cell."""
    from bench import cell as cells
    from bench.inputs import seeded_workloads
    from bench.measure import KERNEL_MODULES, SPAN_NAMES
    from bench.kinds import Outcome
    from repro import obs
    from repro.core import accel

    args, config, traffic, log = ctx.args, ctx.config, ctx.traffic, ctx.log
    os.environ["EVA_CIM_ACCEL"] = "jax"
    watch = ctx.watch
    space, geometries = cells.build_space(config)
    n_points = len(space)
    # every sweep's records are kept; one sweep, drawn from the seed, keeps
    # its analysis cache for the replay and selection comparison, so what
    # the harness holds does not grow with the sweeps of the window
    draw = random.Random(args.seed)
    records, kept = [], None
    with seeded_workloads(config["inputs"], args.seed, config.get("scales")):
        sweeper = cells.Sweeps(traffic, space)
        try:
            sweeper.fill()
            sweeper.run()               # warms every shape the window uses
            setup_s = time.perf_counter() - ctx.t0
            setup_compiles = watch.count
            log(f"set-up {setup_s:.3f} s, {setup_compiles} compilations "
                f"({watch.seconds:.3f} s)")

            tracer = profile = None
            if args.trace:
                from bench.spans import annotating_tracer
                tracer = obs.enable(annotating_tracer())
                prof_dir = cells.WORK / "profile"
                shutil.rmtree(prof_dir, ignore_errors=True)
                profile = ctx.Profile(prof_dir, **traffic["profile"])
            t_open = time.perf_counter()
            if profile is not None:
                profile.start()
            while True:
                cache, results = sweeper.run()
                records.append(results.records)
                if draw.randrange(len(records)) == 0:
                    kept = (len(records) - 1, cache)
                del cache, results
                if time.perf_counter() - t_open >= args.seconds:
                    break
            window_s = time.perf_counter() - t_open
            if profile is not None:
                profile.join()
            if tracer is not None:
                obs.disable()
        finally:
            sweeper.close()
    window_compiles = watch.count - setup_compiles
    rss_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    stats = ctx.devices[0].memory_stats() or {}
    points = n_points * len(records)
    log(f"window {window_s:.3f} s, {len(records)} sweeps, {points} "
        f"points, fallbacks {accel.fallbacks()}")

    # the program's state goes before the reference runs
    full = check.observe(kept[1], space, records[kept[0]], with_streams=True)
    observed = [full if i == kept[0] else check.records_only(recs)
                for i, recs in enumerate(records)]
    del kept, records
    streams = {w: s for w, (_, s) in full["streams"].items()}
    reference = check.Reference(streams, geometries, config["cim_set"])
    t_ref = time.perf_counter()
    numbers = check.compare(observed, space, config, reference)
    log(f"reference {time.perf_counter() - t_ref:.3f} s; window "
        f"compilations {window_compiles}")
    for (w, c), (*_, counters) in full["traces"].items():
        log(f"replay {w} {c}: " + ", ".join(
            f"{k} {counters[k]}" for k in ("L1_misses", "L1_writebacks",
                                           "L2_hits", "L2_misses")))

    m = None
    if args.trace:
        device = ctx.device_trace(profile, SPAN_NAMES, KERNEL_MODULES)
        m = measurements(tracer.spans(), points, device, full, reference,
                         geometries, ctx.devices[0].device_kind)
    end_to_end = {
        "points_per_s": {"value": points / window_s, "unit": "points/s"},
        "peak_rss_gb": {"value": rss_gb, "unit": "GB"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    return Outcome(attempted=points, failed=numbers["failed_points"],
                   end_to_end=end_to_end, measurements=m, numbers=numbers,
                   memory_peak_bytes=stats.get("peak_bytes_in_use"),
                   window_compiles=window_compiles)
