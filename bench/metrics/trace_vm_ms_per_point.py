"""Trace-VM time per design point: self-time of ``cache.trace_vm``
(interpreting each workload once per sweep), in ms.  Moves
``points_per_s``."""


def read(m):
    return m.ms_per_point("cache.trace_vm")
