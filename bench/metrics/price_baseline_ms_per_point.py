"""Pricing of the host-only run per design point: self-time of
``price.baseline``, in ms.  Moves ``points_per_s``."""


def read(m):
    return m.ms_per_point("price.baseline")
