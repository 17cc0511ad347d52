"""Operations and bytes that the replay and placement kernels need, from
their shapes alone, and a kernel's share of its roofline.

The counts are the algorithm's own need, not what the kernels happen to
do: padding, the replay's masked probes of absent ways and the placement
kernels' one-hot contraction are left out.

* Cache replay, per access and per geometry: every level reads and writes
  one set row (per way a 4-byte tag, a 4-byte LRU stamp and a dirty
  byte) and its MSHR row (per entry a 4-byte line and a 4-byte stamp);
  the access reads its line and write flag (5 bytes) once and writes its
  service level, merge flag and bank (9 bytes) per geometry.  Operations:
  one compare per way and per MSHR entry of every level.
* Placement, per call: every leaf row reads its level and segment id (8
  bytes), every converted-access row its level, line and segment id (12
  bytes), and the call writes three 4-byte results per candidate.
  Operations: a max, a compare and an add per leaf, and per access the
  compares of sorting ``n`` keys (``n log2 n``) plus one per boundary.

Both are far from any compute peak, so the bound that applies is HBM
bandwidth.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Sequence, Tuple

Geometry = Sequence[Tuple[str, int, int, int, int]]   # per level


def replay_cost(n_access: int, geometries: Iterable[Geometry]
                ) -> Tuple[float, float]:
    """``(operations, bytes)`` of replaying ``n_access`` accesses under
    every geometry of one batch."""
    ops = nbytes = 0.0
    n_geo = 0
    for geo in geometries:
        n_geo += 1
        for _, _, ways, _, mshrs in geo:
            nbytes += n_access * 2 * (ways * 9 + mshrs * 8)
            ops += n_access * (ways + mshrs)
        nbytes += n_access * 9
    return ops, nbytes + (n_access * 5 if n_geo else 0)


def place_cost(n_leaf: int, n_access: int, n_seg: int
               ) -> Tuple[float, float]:
    """``(operations, bytes)`` of one placement call."""
    sort = n_access * math.log2(n_access) if n_access > 1 else 0.0
    ops = 3.0 * n_leaf + sort + n_access
    nbytes = 8.0 * n_leaf + 12.0 * n_access + 12.0 * n_seg
    return ops, nbytes


def roofline_share(costs: Iterable[Tuple[float, float]], kernel_s: float,
                   peaks: Dict[str, float]) -> Tuple[float, str]:
    """Percent of the roofline reached: the least time the chip could
    take for ``costs`` (the larger of operations over the compute peak
    and bytes over the HBM peak, per call) over the measured kernel time;
    and which of the two bounds it."""
    least = {"compute": 0.0, "hbm": 0.0}
    total = 0.0
    for ops, nbytes in costs:
        t_c = ops / peaks["flops_per_s"]
        t_m = nbytes / peaks["hbm_bytes_per_s"]
        least["compute" if t_c > t_m else "hbm"] += max(t_c, t_m)
        total += max(t_c, t_m)
    bound = max(least, key=least.get)
    return 100.0 * total / kernel_s, bound
