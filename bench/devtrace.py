"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

The trace is read into plain event lists first (:func:`events_of`), so
the arithmetic (:func:`reduce`) can be checked on hand-built events:

* busy: the union of the intervals of the device's ``XLA Ops`` events
  inside the traced window, averaged over the chips; idle share is one
  less busy over the window;
* kernel time: the summed durations of the ``XLA Modules`` events whose
  name starts with a kernel's jit name, each attributed to the host span
  (a ``TraceAnnotation`` of :func:`bench.spans.annotating_tracer`) that
  was open when the module started;
* ``device_ops``: the ten device operations that took the most time;
* ``idle_gaps``: the ten longest gaps between busy intervals, each
  labelled by the innermost host span open at its midpoint.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from bench.spans import union_ns

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Event = Tuple[str, int, int]           # (name, start ns, duration ns)
HostEvent = Tuple[str, int, int, int]  # ... and the host thread's line


def events_of(path: str, span_names: Sequence[str]) -> Dict:
    """Device ops and modules per chip, and the program's host spans,
    from one profiler trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips: Dict[str, Dict[str, List[Event]]] = {}
    host: List[HostEvent] = []
    line_names: Dict[str, List[str]] = {}
    prefixes = tuple(span_names)
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = chips.setdefault(plane.name, {OPS_LINE: [],
                                                  MODULES_LINE: []})
            for line in plane.lines:
                line_names.setdefault(plane.name, []).append(line.name)
                if line.name in lines:
                    lines[line.name] += [(e.name, int(e.start_ns),
                                          int(e.duration_ns))
                                         for e in line.events]
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                host += [(e.name, int(e.start_ns), int(e.duration_ns), i)
                         for e in line.events
                         if e.name.split("|", 1)[0] in prefixes]
    return {"chips": chips, "host": host, "lines": line_names}


def _clip(events: Sequence[Event], lo: int, hi: int):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _host_at(host: Sequence[HostEvent], t: int,
             labelled: bool = False) -> Optional[str]:
    """The innermost (latest-starting) host span open at ``t``; with
    ``labelled``, the innermost span that names a workload on the thread
    of that innermost span."""
    open_ = [h for h in host if h[1] <= t < h[1] + h[2]]
    if not open_:
        return None
    inner = max(open_, key=lambda h: h[1])
    if labelled:
        same = [h for h in open_ if h[3] == inner[3] and "|" in h[0]]
        return max(same, key=lambda h: h[1])[0] if same else None
    return inner[0]


def reduce(ev: Dict, window_ns: Tuple[int, int],
           kernels: Dict[str, str]) -> Dict:
    """Busy and window seconds, per-kernel module time and launches, and
    the breakdown lists, over ``window_ns`` = (start, end) on the trace's
    clock.  ``kernels`` maps a kernel name to its jit module name."""
    lo, hi = window_ns
    chips = ev["chips"]
    if not chips:
        raise ValueError("the trace holds no TPU device plane")
    busy = []
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[int, int]] = []
    launches: Dict[str, List[Tuple[Optional[str], float]]] = {
        k: [] for k in kernels}
    for lines in chips.values():
        ops = list(_clip(lines[OPS_LINE], lo, hi))
        busy.append(union_ns((a, b) for _, a, b in ops))
        for name, a, b in ops:
            op_time[name] = op_time.get(name, 0.0) + (b - a) / 1e9
        merged: List[List[int]] = []
        for _, a, b in sorted(ops, key=lambda e: e[1]):
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        edges = [lo] + [x for m in merged for x in m] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, s, d in lines[MODULES_LINE]:
            if s < lo or s + d > hi:
                continue                       # only whole launches count
            for k, module in kernels.items():
                if name.startswith(module):
                    launches[k].append((_host_at(ev["host"], s, True),
                                        d / 1e9))
    window_s = (hi - lo) / 1e9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    long_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": window_s,
        "launches": launches,
        # an op's name is its HLO text; its head names it well enough
        "device_ops": [[n[:120], t] for n, t in top_ops],
        "idle_gaps": [[_host_at(ev["host"], (a + b) // 2) or "no program span",
                       (b - a) / 1e9] for a, b in long_gaps],
    }
