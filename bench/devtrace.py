"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

The trace is read into plain event lists first (:func:`events_of`), so
the arithmetic (:func:`reduce`) can be checked on hand-built events:

* busy: the union of the intervals of the device's ``XLA Ops`` events
  inside the traced window, averaged over the chips; idle share is one
  less busy over the window;
* scope time: for each named scope asked for (``jax.named_scope``), the
  union of the intervals of the ops whose name path (the ``op_name`` of
  the op's instruction in its module's HLO, which the profile keeps:
  :mod:`bench.xspace`; e.g. ``jit(step)/transpose(jvp(moe))/dot_general``)
  holds the scope as one of its parts, a transformation's wrapping
  (``jvp(...)``, ``transpose(...)``) taken off, averaged over the chips;
  the union, because a ``while`` op's interval holds those of its body's
  ops.  An op with no path is under no scope, and a scope none of whose
  ops ran in the window is left out;
* kernel ops: for each name prefix asked for, the device seconds and the
  HLO head of every custom-call op wholly inside the window whose name
  starts with it;
* kernel time: the summed durations of the ``XLA Modules`` events whose
  name starts with a kernel's jit name, each attributed to the host span
  (a ``TraceAnnotation`` of :func:`bench.spans.annotating_tracer`) that
  was open when the module started;
* ``device_ops``: the ten device operations that took the most time;
* ``idle_gaps``: the ten longest gaps between busy intervals, each
  labelled by the innermost host span open at its midpoint.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench import xspace
from bench.spans import union_ns

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Event = Tuple[str, int, int]           # (name, start ns, duration ns)
OpEvent = Tuple[str, int, int, Optional[str]]  # ... and the name path
HostEvent = Tuple[str, int, int, int]  # ... and the host thread's line
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")


def _module_at(modules: Sequence[Event], starts: Sequence[int],
               t: int) -> Optional[str]:
    """The name of the module event, of ``modules`` sorted by their
    ``starts``, that is running at ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][1] + modules[i][2]:
        return modules[i][0]
    return None


def events_of(path: str, span_names: Sequence[str]) -> Dict:
    """Device ops (with their name paths) and modules per chip, and the
    program's host spans, from one profiler trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips: Dict[str, Dict[str, List]] = {}
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
    # the HLO of the modules that ran on a chip (all, where the profile
    # names none), matched by name or by the name before its program id
    ran = {m for lines in chips.values() for m, _, _ in lines[MODULES_LINE]}
    heads = {m.split("(", 1)[0] for m in ran}
    with open(path, "rb") as f:
        data = f.read()
    try:
        find = xspace.path_finder(
            data, lambda m: not ran or m.split("(", 1)[0] in heads)
    except (ValueError, IndexError):   # a profile it cannot read: no paths
        find = lambda module, name: None   # noqa: E731
    for lines in chips.values():
        modules = sorted(lines[MODULES_LINE], key=lambda e: e[1])
        starts = [s for _, s, _ in modules]
        lines[OPS_LINE] = [(name, s, d,
                            find(_module_at(modules, starts, s), name))
                           for name, s, d in lines[OPS_LINE]]
    return {"chips": chips, "host": host, "lines": line_names}


def _clip(events: Sequence[OpEvent], lo: int, hi: int):
    """``(name, start, end, path)`` of each op cut to the window; an op
    given without a path has none."""
    for name, s, d, *path in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b, (path[0] if path else None)


def in_scope(path: Optional[str], scope: str) -> bool:
    """Whether the name path ``path`` runs under the named scope
    ``scope``: one of its parts is the scope, or wraps it in
    transformations (``transpose(jvp(scope))``)."""
    for part in (path or "").split("/"):
        while part != scope:
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
        if part == scope:
            return True
    return False


def hlo_head(name: str) -> str:
    """An op's HLO text up to its attributes: its name, result shape,
    opcode and operands."""
    return name.split(", custom_call_target=", 1)[0]


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
           kernels: Dict[str, str], scopes: Sequence[str] = (),
           op_prefixes: Sequence[str] = ()) -> Dict:
    """Busy and window seconds, per-kernel module time and launches, the
    device time under each of ``scopes``, the custom-call ops of each of
    ``op_prefixes``, and the breakdown lists, over ``window_ns`` =
    (start, end) on the trace's clock.  ``kernels`` maps a kernel name to
    its jit module name."""
    lo, hi = window_ns
    chips = ev["chips"]
    if not chips:
        raise ValueError("the trace holds no TPU device plane")
    busy = []
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[int, int]] = []
    launches: Dict[str, List[Tuple[Optional[str], float]]] = {
        k: [] for k in kernels}
    scope_ns: Dict[str, int] = {}
    prefix_ops: Dict[str, List[List]] = {p: [] for p in op_prefixes}
    op_path: Dict[str, Optional[str]] = {}
    for lines in chips.values():
        ops = list(_clip(lines[OPS_LINE], lo, hi))
        busy.append(union_ns((a, b) for _, a, b, _ in ops))
        for name, a, b, path in ops:
            op_time[name] = op_time.get(name, 0.0) + (b - a) / 1e9
            op_path.setdefault(name, path)
        for scope in scopes:
            under = [(a, b) for _, a, b, path in ops if in_scope(path, scope)]
            if under:
                scope_ns[scope] = scope_ns.get(scope, 0) + union_ns(under)
        for name, s, d, *_ in lines[OPS_LINE]:
            if s < lo or s + d > hi or " custom-call(" not in name:
                continue                       # only whole ops count
            for prefix in op_prefixes:
                if name.lstrip("%").startswith(prefix):
                    prefix_ops[prefix].append([d / 1e9, hlo_head(name)])
        merged: List[List[int]] = []
        for _, a, b, _ in sorted(ops, key=lambda e: e[1]):
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
        "scopes": {s: ns / len(chips) / 1e9 for s, ns in scope_ns.items()},
        "ops": prefix_ops,
        "top_paths": [[n[:120], op_path[n]] for n, _ in top_ops],
        # an op's name is its HLO text; its head names it well enough
        "device_ops": [[n[:120], t] for n, t in top_ops],
        "idle_gaps": [[_host_at(ev["host"], (a + b) // 2) or "no program span",
                       (b - a) / 1e9] for a, b in long_gaps],
    }
