#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process is started on.

    python3 bench/run.py --workload dse5.reselect --seed 7 --seconds 40 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration (a
design space, ``bench/configs/``) and a traffic mix (``bench/traffic/``).
A run checks that the default device is a TPU, draws the workloads'
inputs from ``--seed``, fills the mix's store and warms up with one
sweep, then runs
``DSEEngine(executor="thread").run(space)`` sweep after sweep until
``--seconds`` have passed; the window ends with the sweep in progress.
After the window the records of every sweep, and the stream, replay and
selections of one sweep drawn from the seed, are compared with the plain
reference (:mod:`bench.check`).  ``--trace 1`` reports the per-layer metrics
(``bench/metrics/``) from the program's spans and from a profiler trace
of part of the window instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers compared
for ``correct`` are the last lines of standard error and the last key of
that object.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse
import importlib.util
import json
import os
import pathlib
import random
import resource
import shutil
import sys
import threading

ROOT = pathlib.Path(__file__).resolve().parents[1]
METRICS = ROOT / "bench" / "metrics"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileWatch:
    """Counts compilations (backend compiles and persistent-cache loads)
    and sums their trace + lower + compile seconds, process-wide."""
    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self._EVENTS:
            with self._lock:
                self.seconds += secs
                self.count += event == self._EVENTS[-1]

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.count += 1


def devices_or_exit(chips: int, require_tpu: bool = True):
    """The devices of this run; exits 2, with no result, off the chip."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        log(f"the default jax device is {devices[0].platform!r}, not a TPU: "
            f"this benchmark measures nothing off the chip")
        raise SystemExit(2)
    if len(devices) < chips:
        log(f"{chips} chips asked for, {len(devices)} present")
        raise SystemExit(2)
    return devices[:chips]


def metric_readers(names):
    """The per-layer metric readers, one file each under bench/metrics/."""
    out = {}
    for name in names:
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name.replace('.', '_')}", METRICS / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod.read
    return out


class Profile(threading.Thread):
    """Profiles ``span_s`` seconds of the window, ``lead_s`` after it
    opens, with the Python tracer off.

    The trace's clock starts when ``start_trace`` is called, but the
    device is traced only once it returns (some 50-120 ms later on a
    v5e), so the profiled window on the trace's clock, ``window_ns``,
    runs from that return to the call of ``stop_trace``."""

    def __init__(self, path: pathlib.Path, lead_s: float, span_s: float):
        super().__init__(daemon=True)
        self.path, self.lead_s, self.span_s = path, lead_s, span_s
        self.window_ns = None

    def run(self):
        import jax
        time.sleep(self.lead_s)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        t0 = time.perf_counter_ns()
        jax.profiler.start_trace(str(self.path), profiler_options=opts)
        lo = time.perf_counter_ns() - t0
        time.sleep(self.span_s)
        self.window_ns = (lo, time.perf_counter_ns() - t0)
        jax.profiler.stop_trace()


def _measurements(span_records, points, profile, observed, reference,
                  geometries, device_kind):
    """Spans of the window, the profiled part's device trace reduced, and
    the sizes of every replay and placement launch, per workload."""
    from bench import devtrace, measure
    from bench.peaks import peak

    device = None
    if profile is not None and profile.window_ns:
        try:
            path = next(profile.path.rglob("*.xplane.pb"))
            ev = devtrace.events_of(str(path), measure.SPAN_NAMES)
            n_dev = sum(len(v) for c in ev["chips"].values()
                        for v in c.values())
            log(f"profile {path.stat().st_size} bytes, {n_dev} device "
                f"events, {len(ev['host'])} host spans")
            for chip, lines in ev["chips"].items():
                mods = {}
                for name, _, _ in lines[devtrace.MODULES_LINE]:
                    head = name.split("(", 1)[0]
                    mods[head] = mods.get(head, 0) + 1
                starts = [s for v in lines.values() for _, s, _ in v]
                log(f"{chip}: lines {ev['lines'].get(chip)}; "
                    + ", ".join(f"{k} {len(v)}" for k, v in lines.items())
                    + f"; events from {min(starts, default=None)} to "
                    f"{max(starts, default=None)} ns; modules "
                    + ", ".join(f"{k} x{n}" for k, n in sorted(
                        mods.items(), key=lambda kv: -kv[1])[:6]))
        finally:
            shutil.rmtree(profile.path, ignore_errors=True)
        if not ev["chips"]:
            log("the profile holds no TPU device plane")
            return measure.Measurements(span_records, points, None,
                                        peak(device_kind), {}, {})
        device = devtrace.reduce(ev, profile.window_ns,
                                 measure.KERNEL_MODULES)
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


def run(args, require_tpu: bool = True, root: pathlib.Path = ROOT) -> dict:
    """One run of one cell; returns the result line's object.  The tests
    drive it on the CPU (``require_tpu=False``) over a ``BENCHMARK.json``
    of their own under ``root``."""
    from bench import cell as cells
    bench_cell, config, traffic = cells.load_cell(args.workload, root)
    devices = devices_or_exit(bench_cell["chips"], require_tpu)

    import jax
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["EVA_CIM_ACCEL"] = "jax"
    cache_dir = ROOT / ".jax_cache"
    cache_dir.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    from repro.compile_cache import enable_compile_cache
    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an evicting cache keeps an access-time file beside each
    # entry, and a machine that sets a size limit then fails every write
    jax.config.update("jax_compilation_cache_max_size", -1)
    watch = CompileWatch()

    from bench import check
    from bench.inputs import seeded_workloads
    from repro import obs
    from repro.core import accel

    bench = json.loads((root / "BENCHMARK.json").read_text())
    per_layer = [m for m in bench["per_layer"]
                 if args.workload in m.get("workloads", [args.workload])]
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
            setup_s = time.perf_counter() - T0
            setup_compiles = watch.count
            log(f"set-up {setup_s:.3f} s, {setup_compiles} compilations "
                f"({watch.seconds:.3f} s)")

            tracer = profile = None
            if args.trace:
                from bench.spans import annotating_tracer
                tracer = obs.enable(annotating_tracer())
                prof_dir = cells.WORK / "profile"
                shutil.rmtree(prof_dir, ignore_errors=True)
                profile = Profile(prof_dir, **traffic["profile"])
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
    stats = devices[0].memory_stats() or {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
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
    limits = config["limits"]
    numbers = check.compare(observed, space, config, reference)
    log(f"reference {time.perf_counter() - t_ref:.3f} s; window "
        f"compilations {window_compiles}")
    for (w, c), (*_, counters) in full["traces"].items():
        log(f"replay {w} {c}: " + ", ".join(
            f"{k} {counters[k]}" for k in ("L1_misses", "L1_writebacks",
                                           "L2_hits", "L2_misses")))

    breakdown = None
    if args.trace:
        m = _measurements(tracer.spans(), points, profile, full,
                          reference, geometries, devices[0].device_kind)
        device["busy_s"], device["window_s"] = m.busy_s, m.window_s
        breakdown = m.breakdown
        readers = metric_readers(e["name"] for e in per_layer)
        metrics = {}
        for entry in per_layer:
            value = readers[entry["name"]](m)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
    else:
        metrics = {
            "points_per_s": {"value": points / window_s, "unit": "points/s"},
            "peak_rss_gb": {"value": rss_gb, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in check.NUMBERS}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    for k, v in checks.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    out = {"correct": correct, "attempted": points,
           "failed": numbers["failed_points"], "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["window_compiles"] = window_compiles
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    out = run(args)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
