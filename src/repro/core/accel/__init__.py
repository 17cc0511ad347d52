"""Accelerator-resident analysis hot loops (``EVA_CIM_ACCEL={numpy,jax}``).

The two numpy hot loops of the analysis pipeline — the per-geometry cache
replay (:meth:`repro.core.cache.CacheHierarchy.replay`) and the vectorized
placement half of Algorithm 1 (:func:`repro.core.offload._place`) — have
jax twins in this package:

  * :mod:`repro.core.accel.replay` — one jitted ``lax.scan`` over the
    structural access stream, ``vmap``-ped across every cache geometry of
    a sweep, reproducing the LRU/MSHR/writeback state machine bit-exactly
    (columns *and* counters);
  * :mod:`repro.core.accel.place` — the reduceat/bincount segment
    reductions of placement as jitted ``segment_max``/``segment_sum`` +
    sort-based unique counting, with optional Pallas kernels
    (:mod:`repro.core.accel.pallas_ops`) for the segment-reduce steps.

The numpy implementations stay in place as the reference oracle: the jax
path is *differentially tested* against them (``tests/test_accel.py``).
A trace beyond the kernels' int32 address budget is the one case that
still takes the numpy path under ``EVA_CIM_ACCEL=jax``; each such
fallback is counted (:func:`fallbacks`, exported by the DSE service as
``accel.fallbacks``), so a run that was meant to stay on the device can
check that it did.

Backend selection
-----------------
``backend()`` reads the ``EVA_CIM_ACCEL`` environment variable ("numpy"
by default); :func:`set_backend` / :func:`use_backend` override it
in-process (tests, benchmarks).  Everything downstream —
``attach_cache_results``, ``_place``, ``AnalysisCache.replay_group``, the
engine/service warm paths — consults this one switch, so
``EVA_CIM_ACCEL=jax`` flips the whole pipeline at once while keeping
every artifact byte-identical.

Compile accounting
------------------
Every jitted entry point registers itself here; :func:`jit_compiles`
reports the total number of compiled specializations (the sum of the jit
caches' sizes).  The DSE service exposes it as the ``accel.jit_compiles``
metric so "a repeated sweep triggers zero recompilations" is observable.
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import List, Optional

BACKENDS = ("numpy", "jax")
ENV_VAR = "EVA_CIM_ACCEL"

_override: Optional[str] = None
_JITTED: List[object] = []                 # jitted fns, for compile counting
_fallback_lock = threading.Lock()
_fallbacks = 0                             # jax -> numpy fallbacks so far


def backend() -> str:
    """The active analysis backend: the in-process override if one is set,
    else ``$EVA_CIM_ACCEL``, else ``"numpy"``."""
    name = _override or os.environ.get(ENV_VAR, "numpy") or "numpy"
    if name not in BACKENDS:
        raise ValueError(f"unknown {ENV_VAR} backend {name!r}; "
                         f"known: {BACKENDS}")
    return name


def enabled() -> bool:
    """True when the jax path should be attempted."""
    return backend() == "jax"


def set_backend(name: Optional[str]) -> None:
    """Override the env switch in-process (``None`` restores env lookup)."""
    global _override
    if name is not None and name not in BACKENDS:
        raise ValueError(f"unknown accel backend {name!r}; known: {BACKENDS}")
    _override = name


@contextlib.contextmanager
def use_backend(name: str):
    """Scoped backend override — the differential tests run both sides."""
    prev = _override
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def register_jitted(fn):
    """Track a jitted callable for :func:`jit_compiles` accounting."""
    _JITTED.append(fn)
    return fn


def jit_compiles() -> int:
    """Total compiled specializations across the accel jit entry points.

    A repeated sweep over the same workloads/geometries must leave this
    number unchanged — the service's warm-path test asserts exactly that
    through the ``accel.jit_compiles`` metric."""
    return sum(int(fn._cache_size()) for fn in _JITTED)


def fallbacks() -> int:
    """jax -> numpy fallbacks so far in this process: calls made under the
    jax backend that the kernels declined (int32 address overflow)."""
    with _fallback_lock:
        return _fallbacks


def _counted(out):
    """Pass a kernel result through, counting a declined call."""
    global _fallbacks
    if out is None:
        with _fallback_lock:
            _fallbacks += 1
    return out


def replay_columns(addrs, is_writes, geometries):
    """Batched replay under the active backend; ``None`` means "use the
    numpy oracle" (backend is numpy, or a counted address overflow)."""
    if not enabled():
        return None
    from repro import obs
    from repro.core.accel.replay import replay_columns_batch
    if obs.tracer() is None:               # keep the untraced launch bare
        return _counted(replay_columns_batch(addrs, is_writes, geometries))
    before = jit_compiles()
    with obs.span("accel.replay_batch", cat="jit",
                  n_geometries=len(geometries),
                  n_accesses=int(len(addrs))) as sp:
        out = replay_columns_batch(addrs, is_writes, geometries)
        sp.set(jit_compiles=jit_compiles() - before)
        return _counted(out)


def place_candidates(part, ct, cfg):
    """Jax placement under the active backend; ``None`` → numpy oracle
    (backend is numpy, or a counted address overflow)."""
    if not enabled():
        return None
    from repro import obs
    from repro.core.accel.place import place_candidates_jax
    if obs.tracer() is None:               # hot per-config path: one read
        return _counted(place_candidates_jax(part, ct, cfg))
    before = jit_compiles()
    with obs.span("accel.place", cat="jit") as sp:
        out = place_candidates_jax(part, ct, cfg)
        sp.set(jit_compiles=jit_compiles() - before)
        return _counted(out)
