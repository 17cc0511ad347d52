"""The runners of the benchmark's kinds of configuration, one module each
under this package, found by a configuration's ``kind``
(:func:`bench.run.kind_of`).  A runner's ``run(ctx)`` takes a
:class:`Context` and returns an :class:`Outcome`; its module names the
numbers it compares (``NUMBERS``, each with a limit in the configuration)
and the end-to-end metrics it reports (``END_TO_END``)."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional


@dataclasses.dataclass
class Context:
    """What a runner is handed: the command line, the cell's
    configuration and traffic mix, the devices, the compile watch, the
    process's start on the host clock, and the harness's logger, profiler
    thread and device-trace reduction."""
    args: Any
    config: Dict
    traffic: Dict
    devices: list
    watch: Any
    t0: float
    log: Callable
    Profile: type
    device_trace: Callable


@dataclasses.dataclass
class Outcome:
    """What a runner hands back for the result line.

    ``end_to_end`` maps each end-to-end metric of the kind to its value
    and unit; ``measurements`` is what the per-layer readers read (traced
    runs only); ``numbers`` holds each number compared for ``correct``,
    whose limits the configuration's ``limits`` hold."""
    attempted: int
    failed: int
    end_to_end: Dict[str, Dict[str, Any]]
    measurements: Any
    numbers: Dict[str, Any]
    memory_peak_bytes: Optional[int]
    window_compiles: int
