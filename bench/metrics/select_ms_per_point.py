"""Candidate selection per design point: self-time of ``cache.select``
and of the placement launch under it, ``accel.place``; the IDG build and
layer-1 lookups under it are not counted.  In ms.  Moves
``points_per_s``."""


def read(m):
    return m.ms_per_point("cache.select", "accel.place")
