"""Share of its roofline that the placement kernel reaches, in %: the
least time of its launches in the profiled window (bytes over HBM
bandwidth bound them; see bench/kernels.py) over their device time.
Moves ``points_per_s``."""


def read(m):
    return m.roofline("place")
