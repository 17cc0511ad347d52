"""Reduction of the program's ``repro.obs`` spans to per-layer self-times.

A span's self-time is its duration less the part of it that its child
spans cover.  ``repro.obs.export.stage_attribution`` subtracts the sum of
the children's durations; here the children's intervals are united
first, because the engine prices points on a thread pool and overlapping
children would otherwise subtract the same wall time several times over.
For children that never overlap the two agree.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Length of the union of ``(start, end)`` intervals."""
    total = 0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(spans: Sequence[Dict]) -> Dict[str, float]:
    """Seconds of self-time summed per span name."""
    by_id = {s["span_id"]: s for s in spans}
    children: Dict[str, List[Tuple[int, int]]] = {}
    for s in spans:
        parent = by_id.get(s.get("parent_id"))
        if parent is None:
            continue
        lo = max(s["ts_ns"], parent["ts_ns"])
        hi = min(s["ts_ns"] + s["dur_ns"], parent["ts_ns"] + parent["dur_ns"])
        if hi > lo:
            children.setdefault(parent["span_id"], []).append((lo, hi))
    out: Dict[str, float] = {}
    for s in spans:
        covered = union_ns(children.get(s["span_id"], ()))
        own = max(0, s["dur_ns"] - covered)
        out[s["name"]] = out.get(s["name"], 0.0) + own / 1e9
    return out


def annotating_tracer():
    """A ``repro.obs`` tracer whose spans also open a
    ``jax.profiler.TraceAnnotation``, so that the profiler's trace holds
    each program span on the device's clock, labelled
    ``<span name>|<workload>``."""
    import jax
    from repro import obs

    class _Annotated:
        __slots__ = ("span", "label", "ann")

        def __init__(self, span, label):
            self.span, self.label, self.ann = span, label, None

        def set(self, **attrs):
            return self.span.set(**attrs)

        def __enter__(self):
            self.ann = jax.profiler.TraceAnnotation(self.label)
            self.ann.__enter__()
            return self.span.__enter__()

        def __exit__(self, *exc):
            try:
                return self.span.__exit__(*exc)
            finally:
                self.ann.__exit__(*exc)

    class AnnotatingTracer(obs.Tracer):
        def span(self, name, cat="misc", **attrs):
            label = name if "workload" not in attrs \
                else f"{name}|{attrs['workload']}"
            return _Annotated(super().span(name, cat, **attrs), label)

    return AnnotatingTracer()
