"""Cache-replay time per design point: self-time of ``cache.replay_batch``
and of the device launch under it, ``accel.replay_batch``, which blocks on
the device until the columns come back; the trace VM under it is not
counted.  In ms.  Moves ``points_per_s``."""


def read(m):
    return m.ms_per_point("cache.replay_batch", "accel.replay_batch")
