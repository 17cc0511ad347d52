#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process is started on.

    python3 bench/run.py --workload dse5.reselect --seed 7 --seconds 40 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``).  The
configuration's ``kind`` names the module under ``bench/kinds/`` that runs
it (a configuration without one is a CiM design space, ``sweep``):

``sweep``   sweeps of the Eva-CiM design space through the DSE engine
            (:mod:`bench.kinds.sweep`);
``train``   training steps of a model through the repo's training entry
            point (:mod:`bench.kinds.train`).

A run checks that the default device is a TPU, sets up the persistent
compile cache and the compile watch, and hands the cell to its kind, which
sets up, warms up every shape, measures for ``--seconds`` and compares what
the window produced with a plain reference.  ``--trace 1`` reports the
per-layer metrics (``bench/metrics/``) instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers compared
for ``correct`` are the last lines of standard error and the last key of
that object.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse
import importlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import threading
from typing import Dict, Optional, Sequence

ROOT = pathlib.Path(__file__).resolve().parents[1]


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


def metric_readers(names, root: pathlib.Path = ROOT):
    """The per-layer metric readers, one file each under
    ``<root>/bench/metrics/``."""
    out = {}
    for name in names:
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name.replace('.', '_')}",
            root / "bench" / "metrics" / f"{name}.py")
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


def device_trace(profile: Optional[Profile], span_names: Sequence[str],
                 kernel_modules: Dict[str, str], scopes: Sequence[str] = (),
                 op_prefixes: Sequence[str] = ()) -> Optional[Dict]:
    """The profiled part of the window reduced (:func:`bench.devtrace.reduce`),
    or None where nothing was profiled or the profile caught no device.
    The profile's files are removed."""
    from bench import devtrace

    if profile is None or not profile.window_ns:
        return None
    try:
        path = next(profile.path.rglob("*.xplane.pb"))
        ev = devtrace.events_of(str(path), span_names)
        n_dev = sum(len(v) for c in ev["chips"].values() for v in c.values())
        log(f"profile {path.stat().st_size} bytes, {n_dev} device "
            f"events, {len(ev['host'])} host spans")
        for chip, lines in ev["chips"].items():
            mods = {}
            for name, _, _ in lines[devtrace.MODULES_LINE]:
                head = name.split("(", 1)[0]
                mods[head] = mods.get(head, 0) + 1
            starts = [e[1] for v in lines.values() for e in v]
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
        return None
    out = devtrace.reduce(ev, profile.window_ns, kernel_modules, scopes,
                          op_prefixes)
    for name, path in out["top_paths"]:
        log(f"top op {name[:60]!r}: path {path!r}")
    return out


def kind_of(config: Dict):
    """The module under ``bench/kinds/`` that runs ``config``."""
    return importlib.import_module(f"bench.kinds.{config.get('kind', 'sweep')}")


def enable_compile_cache() -> None:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache``."""
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    cache_dir = ROOT / ".jax_cache"
    cache_dir.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    from repro.compile_cache import enable_compile_cache as enable
    jax.config.update("jax_compilation_cache_dir", enable())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an evicting cache keeps an access-time file beside each
    # entry, and a machine that sets a size limit then fails every write
    jax.config.update("jax_compilation_cache_max_size", -1)


def run(args, require_tpu: bool = True, root: pathlib.Path = ROOT) -> dict:
    """One run of one cell; returns the result line's object.  The tests
    drive it on the CPU (``require_tpu=False``) over a ``BENCHMARK.json``
    of their own under ``root``."""
    from bench import cell as cells
    bench_cell, config, traffic = cells.load_cell(args.workload, root)
    devices = devices_or_exit(bench_cell["chips"], require_tpu)
    enable_compile_cache()
    watch = CompileWatch()

    from bench.kinds import Context
    kind = kind_of(config)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    per_layer = [m for m in bench["per_layer"]
                 if args.workload in m.get("workloads", [args.workload])]
    res = kind.run(Context(args, config, traffic, devices, watch, T0, log,
                           Profile, device_trace))

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": res.memory_peak_bytes}
    breakdown = None
    if args.trace:
        m = res.measurements
        device["busy_s"], device["window_s"] = m.busy_s, m.window_s
        breakdown = m.breakdown
        readers = metric_readers((e["name"] for e in per_layer), root)
        metrics = {}
        for entry in per_layer:
            value = readers[entry["name"]](m)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
    else:
        metrics = res.end_to_end
    limits = config["limits"]
    checks = {k: {"value": res.numbers[k], "limit": limits[k]}
              for k in kind.NUMBERS}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    for k, v in checks.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    out = {"correct": correct, "attempted": res.attempted,
           "failed": res.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["window_compiles"] = res.window_compiles
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
