"""Pricing per design point: self-time of ``backend.price``, in ms.
Moves ``points_per_s``."""


def read(m):
    return m.ms_per_point("backend.price")
