"""The host's wait on the placement kernel per design point: self-time
of ``accel.place.device`` (dispatch, the device run and the copy back),
in ms.  Moves ``points_per_s``."""


def read(m):
    return m.ms_per_point("accel.place.device")
