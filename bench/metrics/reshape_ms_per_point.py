"""Trace reshaping after selection per design point: self-time of
``select.reshape``, in ms.  Moves ``points_per_s``."""


def read(m):
    return m.ms_per_point("select.reshape")
