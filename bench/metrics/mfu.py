"""Model FLOPs utilization of a train cell, in %: the model FLOPs of the
window's steps (``bench/model_flops.py``, no recomputation counted) over
the window's seconds on the host clock and the chip's bf16 peak
(``bench/peaks.py``).  Moves ``tokens_per_s``."""


def read(m):
    return m.mfu()
