"""Analysis-store time per design point: self-time of the ``store.*``
load and save spans, in ms.  Moves ``points_per_s``."""


def read(m):
    return m.ms_per_point("store.load_l1", "store.save_l1", "store.load_l2",
                          "store.save_l2")
