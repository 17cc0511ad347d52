"""Model FLOPs utilization of a train cell, in %: the model FLOPs of the
window's steps (the reference's ``train_flops_per_token``, no
recomputation counted) over the window's seconds on the host clock and the
chip's bf16 peak (``bench/peaks.py``).  Moves ``tokens_per_s``."""


def read(m):
    return m.mfu()
