"""What a traced run measured, in the form the per-layer metric readers
(``bench/metrics/<metric>.py``, each a ``read(m) -> float | None``) take.

A reader returns ``None`` where the run gave it nothing to read: a span
that never ran in the window, or a kernel with no whole launch inside the
profiled part of it.

The replay kernel is timed by its host span, ``accel.replay_batch``, which
returns when the device has replayed the whole stream (one launch runs
for seconds, so the host's share of the span is small); a profile of a
whole replay holds an event per scan step and operation, more than a run
can collect and read in its time.  The placement kernels are timed by the
device trace.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench import kernels, spans

# jit module name of the placement kernels in the device trace, as the
# program names it today (the jitted function ``kernel`` in
# core/accel/place.py)
KERNEL_MODULES = {"place": "jit_kernel"}
# the program's spans that the trace keeps on the device's clock
SPAN_NAMES = ("dse.run", "engine.warm", "backend.evaluate",
              "backend.analyze", "backend.select", "backend.price",
              "cache.trace_vm", "cache.trace", "cache.replay_batch",
              "cache.idg", "cache.select", "accel.replay_batch",
              "accel.place", "store.load_l1", "store.save_l1",
              "store.load_l2", "store.save_l2")


def pow2(n: int) -> int:
    """The replay's padded scan length for ``n`` accesses (at least 64)."""
    return 1 << (max(n, 64) - 1).bit_length()


class Measurements:
    """Span self-times over the whole window; device time, kernel
    launches and their costs over the profiled part of it."""

    def __init__(self, span_records: List[Dict], points: int,
                 device: Optional[Dict], peaks: Dict[str, float],
                 replay_sizes: Dict[str, Tuple[int, list]],
                 place_sizes: Dict[str, Tuple[int, int, int]]):
        self.self_s = spans.self_times(span_records)
        by_id = {s["span_id"]: s for s in span_records}

        def workload(s):
            while s is not None and "workload" not in s["attrs"]:
                s = by_id.get(s["parent_id"])
            return None if s is None else s["attrs"]["workload"]

        # (workload, seconds, accesses) of every replay call in the window
        self.replays = [(workload(s), s["dur_ns"] / 1e9,
                         s["attrs"]["n_accesses"])
                        for s in span_records
                        if s["name"] == "accel.replay_batch"]
        self.points = points
        self.device = device
        self.peaks = peaks
        self.replay_sizes = replay_sizes
        self.place_sizes = place_sizes

    def ms_per_point(self, *names: str) -> Optional[float]:
        seen = [self.self_s[n] for n in names if n in self.self_s]
        if not seen or not self.points:
            return None
        return 1e3 * sum(seen) / self.points

    def launches(self, kernel: str) -> List[Tuple[str, float]]:
        """``(workload, device seconds)`` of each whole launch of
        ``kernel`` in the profiled window whose workload is known."""
        if self.device is None:
            return []
        out = []
        for label, secs in self.device["launches"][kernel]:
            workload = label.split("|", 1)[1] if label else None
            if workload is not None:
                out.append((workload, secs))
        return out

    def roofline(self, kernel: str) -> Optional[float]:
        if kernel == "replay":
            got = [(w, s) for w, s, _ in self.replays]
            costs = [kernels.replay_cost(n, self.replay_sizes[w][1])
                     for w, _, n in self.replays]
        else:
            got = self.launches(kernel)
            costs = [kernels.place_cost(*self.place_sizes[w])
                     for w, _ in got]
        total = sum(s for _, s in got)
        if not got or total <= 0:
            return None
        share, _ = kernels.roofline_share(costs, total, self.peaks)
        return share

    @property
    def busy_s(self) -> Optional[float]:
        return None if self.device is None else self.device["busy_s"]

    @property
    def window_s(self) -> Optional[float]:
        return None if self.device is None else self.device["window_s"]

    @property
    def breakdown(self) -> Optional[Dict]:
        if self.device is None:
            return None
        return {"device_ops": self.device["device_ops"],
                "idle_gaps": self.device["idle_gaps"]}


def replay_steps(m: Measurements) -> int:
    """Padded scan steps of the window's replay calls."""
    return sum(pow2(n) for _, _, n in m.replays)
