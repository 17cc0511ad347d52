"""Idle share of the device over the profiled window, in %: one less the
union of its operations' intervals over the window.  Moves
``points_per_s``."""


def read(m):
    if m.device is None or m.window_s <= 0:
        return None
    return 100.0 * (1.0 - m.busy_s / m.window_s)
