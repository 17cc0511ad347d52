"""Median of a train cell's window step times on the host clock, in ms: the
chip's step without the single stalled steps that ``tokens_per_s`` counts.
Moves ``tokens_per_s``."""


def read(m):
    med = m.step_median_s()
    return None if med is None else 1e3 * med
