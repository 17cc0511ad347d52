"""Model FLOPs of a training step of a dense decoder, from its sizes
(:func:`bench.model_reference.dims_of`).

Per token, forward and backward (PaLM's convention, Chowdhery et al. 2022,
appendix B): ``6 N`` for the ``N`` weights of the layers' matrix
multiplications, ``6 d V`` for the output head (tied or not; the
embedding lookup is no multiplication), and ``12 L d S`` for the
attention scores and their weighting of the values over a sequence of
``S``.  Biases, norms, softmax and the optimizer are left out, and so is
any recomputation (rematerialisation), so the share of the peak that this
count gives cannot pass what the chip did.
"""
from __future__ import annotations

from typing import Dict


def layer_weights(dims: Dict) -> int:
    """Weights of one layer's matrix multiplications."""
    d, F = dims["d"], dims["F"]
    q, kv = dims["H"] * dims["dh"], dims["Hkv"] * dims["dh"]
    return d * q + 2 * d * kv + q * d + 3 * d * F


def train_flops_per_token(dims: Dict, seq_len: int) -> float:
    L, d = dims["L"], dims["d"]
    return (6.0 * L * layer_weights(dims) + 6.0 * d * dims["V"]
            + 12.0 * L * d * seq_len)
