"""IDG/flow construction per design point: self-time of ``cache.idg``,
in ms.  Moves ``points_per_s``."""


def read(m):
    return m.ms_per_point("cache.idg")
