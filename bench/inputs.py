"""Seeded inputs for the Table-IV workloads.

Each configuration file gives, per workload, one domain per argument of
its ``build()`` and, under ``scales``, the ``build(scale)`` it runs at
(1 where it names none).  The shapes and dtypes stay those of that
``build(scale)``; only the values are drawn, from ``--seed`` and the
workload's name:

  ``{"int": [lo, hi]}``          integers in ``[lo, hi)`` (indices, symbols,
                                 fixed-point scores)
  ``{"normal": s}``              normal floats of standard deviation ``s``
  ``{"graph": p}``               undirected Erdos-Renyi adjacency, edge
                                 probability ``p``, zero diagonal
  ``{"weighted_graph": [p, lo, hi, inf]}``
                                 the same graph as edge weights in
                                 ``[lo, hi)``, ``inf`` where there is no
                                 edge, 0 on the diagonal

The program sees only the generated arrays: the benchmark swaps each
workload's builder in the registry for one that returns them, for the
length of a run.
"""
from __future__ import annotations

import contextlib
import zlib
from typing import Dict, List, Sequence

import numpy as np


def _draw(domain: dict, shape, dtype, rng: np.random.Generator) -> np.ndarray:
    (kind, arg), = domain.items()
    if kind == "int":
        lo, hi = arg
        return rng.integers(lo, hi, shape).astype(dtype)
    if kind == "normal":
        return (rng.normal(size=shape) * arg).astype(dtype)
    if kind in ("graph", "weighted_graph"):
        p = arg if kind == "graph" else arg[0]
        n = shape[0]
        adj = (rng.random((n, n)) < p).astype(np.int64)
        np.fill_diagonal(adj, 0)
        adj = np.maximum(adj, adj.T)
        if kind == "graph":
            return adj.astype(dtype)
        _, lo, hi, inf = arg
        w = np.where(adj > 0, rng.integers(lo, hi, (n, n)), inf)
        np.fill_diagonal(w, 0)
        return w.astype(dtype)
    raise ValueError(f"unknown input domain {kind!r}")


def generate(domains: Sequence[dict], template: Sequence, seed: int,
             workload: str) -> List[np.ndarray]:
    """Arrays shaped like ``template`` with values drawn from ``domains``."""
    if len(domains) != len(template):
        raise ValueError(f"{workload}: {len(domains)} input domains for "
                         f"{len(template)} arguments")
    rng = np.random.default_rng([seed % (1 << 64),
                                 zlib.crc32(workload.encode())])
    return [_draw(d, np.shape(t), np.dtype(t.dtype), rng)
            for d, t in zip(domains, template)]


class _Fixed:
    """A workload builder that returns one program with fixed inputs."""

    def __init__(self, fn, args):
        self.fn, self.args = fn, tuple(args)

    def __call__(self, scale: int = 1):
        return self.fn, self.args


@contextlib.contextmanager
def seeded_workloads(domains: Dict[str, Sequence[dict]], seed: int,
                     scales: Dict[str, int] = None):
    """Registry entries of ``domains``' workloads replaced by builders of
    seeded inputs at ``scales``; the originals come back on exit."""
    import jax.numpy as jnp
    from repro import workloads

    saved = {}
    try:
        for name, doms in domains.items():
            builder = workloads.WORKLOADS[name]
            fn, args = builder((scales or {}).get(name, 1))
            drawn = generate(doms, args, seed, name)
            saved[name] = builder
            workloads.WORKLOADS[name] = _Fixed(
                fn, [jnp.asarray(a) for a in drawn])
        yield
    finally:
        workloads.WORKLOADS.update(saved)
