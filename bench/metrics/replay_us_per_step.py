"""Time of the replay kernel per padded scan step, in us: the replay calls
of the window (``accel.replay_batch``, which returns when the device has
replayed every geometry of the batch) over the scan steps they ran, each
call's access count padded to a power of two.  Moves ``points_per_s``."""
from bench.measure import replay_steps


def read(m):
    if not m.replays:
        return None
    return 1e6 * sum(s for _, s, _ in m.replays) / replay_steps(m)
