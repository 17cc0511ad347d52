"""Placement's host work per design point: self-time of
``accel.place.pack`` (flat arrays, gathers, the int32 guard, the kernel
lookup) and ``accel.place.unpack`` (the ``Candidate`` objects), in ms.
Moves ``points_per_s``."""


def read(m):
    return m.ms_per_point("accel.place.pack", "accel.place.unpack")
