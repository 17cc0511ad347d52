"""Algorithm 1's tree extraction per design point: self-time of
``select.partition`` (the partition memo's lookup, and on a miss the
reverse-order walk with its producer tables), in ms.  Moves
``points_per_s``."""


def read(m):
    return m.ms_per_point("select.partition")
