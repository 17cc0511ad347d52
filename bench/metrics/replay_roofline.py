"""Share of its roofline that the replay kernel reaches, in %: the least
time of the window's replay calls (bytes over HBM bandwidth bound them;
see bench/kernels.py) over the time of their ``accel.replay_batch``
spans, which hold the device's replay and little else.  Moves
``points_per_s``."""


def read(m):
    return m.roofline("replay")
