"""Engine self-time per design point: ``dse.run`` less its children
(warm-up pass, evaluations), in ms.  Moves ``points_per_s``."""


def read(m):
    return m.ms_per_point("dse.run")
