"""repro.obs — span tracing for the Eva-CiM pipeline itself.

The paper's thesis is attribution (where do a workload's energy and
time go?); this package applies the same discipline to the framework:
every pipeline stage — trace VM, replay, IDG analysis, selection,
pricing, store I/O, jit launches, adaptive rounds, daemon requests —
opens a :class:`Span`, and the finished spans export to Perfetto
(Chrome trace-event JSON), NDJSON, or a per-stage attribution table.

Tracing is off by default and free when off::

    from repro import obs
    tracer = obs.enable()
    ...run a sweep...
    tracer.export_chrome("trace.json")       # open in ui.perfetto.dev
    print(obs.attribution_markdown(obs.stage_attribution(tracer.spans())))
    obs.disable()

See ``docs/architecture.md`` ("Tracing") for the span taxonomy.
"""
from repro.obs.tracer import (NULL_SPAN, Span, TraceContext, Tracer, active,
                              attach, current, disable, enable,
                              ingest, span, tracer)
from repro.obs.export import (attribution_markdown, build_tree,
                              export_chrome, export_ndjson,
                              stage_attribution)

__all__ = [
    "NULL_SPAN", "Span", "TraceContext", "Tracer",
    "active", "attach", "current", "disable", "enable",
    "ingest", "span", "tracer",
    "attribution_markdown", "build_tree", "export_chrome",
    "export_ndjson", "stage_attribution",
]
