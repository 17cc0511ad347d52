"""Sweep executor: memoized trace analysis + fanned-out per-config pricing.

The Eva-CiM pipeline splits cleanly into phases with very different
costs and very different dependence on the swept axes (timings: columnar
core, mid-size Table-IV workload):

  ========================  =====================  ========================
  phase                     depends on             cost
  ========================  =====================  ========================
  structural trace          workload only          ~100 ms (trace VM, once)
  cache replay + flow       + cache geometry       ~20 ms each (columns)
  candidate selection       + cim_levels/cim_set   partition ~100 ms once,
                                                   placement ~ms per config
  pricing (energy/cycles)   + tech, host           ~ms (np.bincount)
  ========================  =====================  ========================

:class:`AnalysisCache` memoizes the layers by their exact dependence
keys — including a per-*workload* structural-trace memo above layer 1, so
a Fig. 14 geometry sweep interprets each program once and only replays
its access stream per geometry — a Fig. 16 technology sweep re-runs
*nothing* but pricing, and a Fig. 15 level sweep re-runs placement only
(the structural candidate partition is shared through the columnar
trace's memo; see :mod:`repro.core.offload`).  Backing the
cache with a persistent :class:`~repro.dse.store.AnalysisStore`
(``AnalysisCache(store=...)`` / ``DSEEngine(store=...)``) extends both
memo layers across *processes*: repeated CLI sweeps and spawned
``executor="process"`` workers load the artifacts from disk instead of
re-tracing.  The :class:`DSEEngine` walks a
:class:`~repro.dse.space.SweepSpace` in deterministic order, warms the
cache once per analysis key, and fans the cheap pricing phase out over a
worker pool ("thread", "process", or "serial") — results always come back
in SweepPoint order regardless of executor scheduling.

The three-phase split itself is owned by a pluggable
:class:`~repro.dse.backends.AnalysisBackend` (``DSEEngine(backend=...)``):
the table above describes the default CiM pipeline
(:class:`~repro.dse.backends.CimBackend`), while
:class:`~repro.dse.backends.TpuBackend` runs the same engine/cache/store
machinery over jaxpr/HLO fusion analyses of the arch registry's train
steps (generic artifacts memoized via :meth:`AnalysisCache.artifact`).
"""
from __future__ import annotations

import dataclasses
import multiprocessing
import os
import pathlib
import shutil
import tempfile
import threading
import time
import weakref
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

from repro import obs
from repro.core.host_model import DEFAULT_HOST, HostModel
from repro.core.offload import (OffloadConfig, OffloadResult, TraceAnalysis,
                                analyze_trace, rehydrate_analysis)
from repro.core.reshape import ReshapedTrace, reshape
from repro.core.trace import (StructuralTrace, TraceResult,
                              attach_cache_results,
                              attach_cache_results_batch, trace_structural)
from repro.dse.backends import AnalysisBackend, CimBackend
from repro.dse.results import SweepRecord, SweepResults
from repro.dse.space import CacheOption, SweepPoint, SweepSpace
from repro.dse.store import AnalysisStore


class AnalysisCache:
    """Layered memo of the config-independent sweep artifacts.

    Layer 1 — ``(workload, cache)``  -> traced program + IDG/flow tables.
    Layer 2 — ``(layer-1 key, offload config)`` -> selected candidates +
    reshaped trace.  Hit/build counters are exposed for tests and reports
    (the "trace analysis ran exactly once per workload" guarantee).

    ``store`` (an :class:`~repro.dse.store.AnalysisStore` or a directory
    path) layers an on-disk lookup between the in-memory memo and a fresh
    build: misses consult the store first, and every artifact built here is
    persisted, so the build counters stay an honest measure of *global*
    analysis work — a warm store means ``trace_builds == 0`` even in a new
    process.  A geometry variant loaded from the store joins its
    workload's structural memo (``ColumnarTrace._struct``) only when its
    structural columns equal those of the structural trace already held
    for the workload; ``struct_shared`` counts the loads that did.
    """

    def __init__(self, store: Optional[Union[AnalysisStore, str,
                                             pathlib.Path]] = None):
        if store is not None and not isinstance(store, AnalysisStore):
            store = AnalysisStore(store)
        self.store = store
        self._lock = threading.RLock()
        self._structural: Dict[str, StructuralTrace] = {}  # lint: guarded-by(_lock)
        self._traces: Dict[Tuple, TraceResult] = {}        # lint: guarded-by(_lock)
        self._analyses: Dict[Tuple, TraceAnalysis] = {}    # lint: guarded-by(_lock)
        self._offloads: Dict[Tuple, Tuple[OffloadResult, ReshapedTrace]] = {}  # lint: guarded-by(_lock)
        self._blobs: Dict[Tuple, Any] = {}  # generic backend artifacts; lint: guarded-by(_lock)
        self._key_locks: Dict[Tuple, threading.Lock] = {}  # lint: guarded-by(_lock)
        self.trace_builds = 0    # lint: guarded-by(_lock)
        self.trace_hits = 0      # lint: guarded-by(_lock)
        self.offload_builds = 0  # lint: guarded-by(_lock)
        self.offload_hits = 0    # lint: guarded-by(_lock)
        self.replay_batches = 0  # lint: guarded-by(_lock)
        self.struct_shared = 0   # lint: guarded-by(_lock)

    def _key_lock(self, key: Tuple) -> threading.Lock:
        """Per-key build lock: concurrent misses on one key build once."""
        with self._lock:
            lk = self._key_locks.get(key)
            if lk is None:
                lk = self._key_locks[key] = threading.Lock()
            return lk

    def _prune_lock(self, key: Tuple) -> None:
        """Release a build lock's table entry once its layer completed.

        The lock exists to serialize the *first* build of a key; after the
        artifact is memoized every later lookup is a plain memo hit, so
        keeping one ``threading.Lock`` per (workload, cache, offload) key
        alive forever only leaks memory across long adaptive runs.
        Threads already blocked on the popped lock still hold a reference
        and proceed normally — they just find the memo populated."""
        with self._lock:
            self._key_locks.pop(key, None)

    # ------------------------------------------------------------ layer 1
    def _structural_trace(self, workload: str) -> StructuralTrace:
        """The geometry-independent trace, interpreted once per workload —
        every cache geometry of a sweep replays its access stream instead
        of re-running the trace VM."""
        from repro.workloads import build          # late: keep core importable
        skey = ("structural", workload)
        with obs.span("cache.trace_vm", cat="trace", workload=workload) as sp:
            with self._key_lock(skey):
                try:
                    with self._lock:
                        st = self._structural.get(workload)
                    if st is None:
                        sp.set(source="build")
                        fn, args = build(workload)
                        st = trace_structural(fn, *args)
                        with self._lock:
                            self._structural[workload] = st
                    else:
                        sp.set(source="memo")
                    return st
                finally:
                    self._prune_lock(skey)

    def _load_from_store(self, workload: str, cache: CacheOption
                     ) -> Optional[Tuple[TraceResult, bool]]:
        """Layer 1 of ``(workload, cache)`` from the store into the memo:
        ``(trace, shared)``, or ``None`` on a store miss.

        When the workload already has a structural trace here and the
        loaded structural columns equal it, the loaded trace is rebuilt on
        top of that trace (``shared`` is true): it then shares its
        ``_struct`` memo, so the partition, IDG tables, flow and placement
        arrays are built once per workload, not once per cache.  Unequal
        columns keep the loaded trace and its own memo."""
        loaded = self.store.load_layer1(workload, cache.levels)
        if loaded is None:
            return None
        tr, flow = loaded
        with self._lock:
            known = self._structural.get(workload)
        shared = known is not None \
            and known.columns.same_structure(tr.trace)
        if shared:
            ct = tr.trace
            tr = TraceResult(known.columns.with_mem_results(
                ct.level, ct.hit, ct.bank, ct.mshr),
                tr.cache, tr.outputs, structural=known)
        key = (workload, cache.levels)
        with self._lock:
            self._traces[key] = tr
            if shared:
                self.struct_shared += 1
            if tr.structural is not None \
                    and workload not in self._structural:
                self._structural[workload] = tr.structural
            if flow is not None and key not in self._analyses:
                self._analyses[key] = rehydrate_analysis(tr, flow)
        return tr, shared

    def trace(self, workload: str, cache: CacheOption) -> TraceResult:
        key = (workload, cache.levels)             # full geometry, not name
        with obs.span("cache.trace", cat="replay", workload=workload,
                      cache=cache.name) as sp, self._key_lock(key):
            try:
                with self._lock:
                    hit = self._traces.get(key)
                    if hit is not None:
                        self.trace_hits += 1
                        sp.set(source="memo", layer=1)
                        return hit
                if self.store is not None:
                    loaded = self._load_from_store(workload, cache)
                    if loaded is not None:
                        tr, shared = loaded
                        sp.set(source="store", layer=1,
                               struct="shared" if shared else "own")
                        return tr
                with self._lock:
                    self.trace_builds += 1
                sp.set(source="build", layer=1)
                tr = attach_cache_results(self._structural_trace(workload),
                                          cache.levels)
                with self._lock:
                    self._traces[key] = tr
                if self.store is not None:
                    self.store.save_layer1(workload, cache.levels, tr)
                return tr
            finally:
                self._prune_lock(key)

    def replay_group(self, workload: str,
                     caches: Sequence[CacheOption]) -> None:
        """Warm layer 1 for every geometry of one workload at once.

        The numpy path (or a single-geometry group) degrades to per-key
        :meth:`trace` calls.  Under ``EVA_CIM_ACCEL=jax`` all geometries
        still missing from memo *and* store are replayed in ONE batched
        accelerator call (:func:`~repro.core.trace.attach_cache_results_batch`
        vmaps the cache state machine across the batch), so a sweep's N
        geometries cost one kernel launch instead of N replays —
        ``replay_batches`` counts those launches.  Counter semantics match
        :meth:`trace`: memo hits bump ``trace_hits``, store loads bump
        neither, and each geometry actually replayed bumps
        ``trace_builds``.  The span's ``n_shared`` counts the store loads
        that joined the workload's structural memo, and ``struct`` reads
        ``"shared"`` when any did."""
        uniq: List[CacheOption] = []
        seen = set()
        for c in caches:
            if c.levels not in seen:
                seen.add(c.levels)
                uniq.append(c)
        from repro.core import accel
        if not accel.enabled() or len(uniq) <= 1:
            for c in uniq:
                self.trace(workload, c)
            return
        gkey = ("replay_group", workload) + tuple(c.levels for c in uniq)
        with obs.span("cache.replay_batch", cat="replay", workload=workload,
                      n_geometries=len(uniq)) as gsp, self._key_lock(gkey):
            try:
                missing: List[CacheOption] = []
                loads = []                   # store loads: shared memo or not
                for c in uniq:
                    key = (workload, c.levels)
                    with self._lock:
                        if key in self._traces:
                            self.trace_hits += 1
                            continue
                    if self.store is not None:
                        loaded = self._load_from_store(workload, c)
                        if loaded is not None:
                            loads.append(loaded[1])
                            continue
                    missing.append(c)
                gsp.set(n_replayed=len(missing),
                        source="build" if missing else "memo")
                if loads:
                    gsp.set(struct="shared" if any(loads) else "own",
                            n_shared=sum(loads))
                if not missing:
                    return
                st = self._structural_trace(workload)
                trs = attach_cache_results_batch(st,
                                                 [c.levels for c in missing])
                with self._lock:
                    self.trace_builds += len(missing)
                    self.replay_batches += 1
                    for c, tr in zip(missing, trs):
                        self._traces[(workload, c.levels)] = tr
                if self.store is not None:
                    for c, tr in zip(missing, trs):
                        self.store.save_layer1(workload, c.levels, tr)
            finally:
                self._prune_lock(gkey)

    def trace_analysis(self, workload: str, cache: CacheOption
                       ) -> TraceAnalysis:
        """IDG/flow artifacts for a trace, built lazily on first use —
        callers that only need the raw trace never pay for the flow index."""
        key = (workload, cache.levels)
        with obs.span("cache.idg", cat="analysis", workload=workload,
                      cache=cache.name) as sp, \
                self._key_lock(("analysis",) + key):
            try:
                with self._lock:
                    hit = self._analyses.get(key)
                if hit is not None:
                    sp.set(source="memo")
                    return hit
                tr = self.trace(workload, cache)
                with self._lock:           # a store hit may have rehydrated it
                    hit = self._analyses.get(key)
                if hit is not None:
                    sp.set(source="store")
                    return hit
                sp.set(source="build")
                analysis = analyze_trace(tr)
                with self._lock:
                    self._analyses[key] = analysis
                if self.store is not None:
                    # upgrade the layer-1 artifact in place: trace + flow
                    self.store.save_layer1(workload, cache.levels, tr,
                                           flow=analysis.flow)
                return analysis
            finally:
                self._prune_lock(("analysis",) + key)

    # ------------------------------------------------------------ layer 2
    def offload(self, workload: str, cache: CacheOption,
                cfg: OffloadConfig) -> Tuple[OffloadResult, ReshapedTrace]:
        # the frozen OffloadConfig is hashable-by-value: using it directly
        # keeps the key complete if new knobs are ever added to it
        key = (workload, cache.levels, cfg)
        with obs.span("cache.select", cat="select", workload=workload,
                      cache=cache.name) as sp, self._key_lock(key):
            try:
                with self._lock:
                    hit = self._offloads.get(key)
                    if hit is not None:
                        self.offload_hits += 1
                        sp.set(source="memo", layer=2)
                        return hit
                if self.store is not None:
                    loaded = self.store.load_layer2(workload, cache.levels,
                                                    cfg)
                    if loaded is not None:
                        with self._lock:
                            self._offloads[key] = loaded
                        sp.set(source="store", layer=2)
                        return loaded
                with self._lock:
                    self.offload_builds += 1
                sp.set(source="build", layer=2)
                analysis = self.trace_analysis(workload, cache)
                result = analysis.select(cfg)
                with obs.span("select.reshape", cat="select") as rsp:
                    reshaped = reshape(analysis.trace, result)
                    rsp.set(n_host_seqs=len(reshaped.host_seqs))
                with self._lock:
                    self._offloads[key] = (result, reshaped)
                if self.store is not None:
                    self.store.save_layer2(workload, cache.levels, cfg,
                                           result, reshaped)
                return result, reshaped
            finally:
                self._prune_lock(key)

    # ---------------------------------------------------- generic artifacts
    def artifact(self, layer: int, key: Tuple, build: Callable[[], Any],
                 store_spec: Optional[dict] = None) -> Any:
        """Backend-agnostic layered memo (see :mod:`repro.dse.backends`).

        ``layer`` picks the counter pair the lookup accounts under — 1 for
        the expensive analysis phase (``trace_builds``/``trace_hits``), 2
        for selection (``offload_builds``/``offload_hits``) — so non-CiM
        backends report cost through the exact counters tests and sweep
        reports already assert on.  ``store_spec`` (a JSON-able key spec
        that must include the backend's name + version stamp) additionally
        persists the artifact through the
        :class:`~repro.dse.store.AnalysisStore`: store loads count as
        neither build nor memo hit, mirroring the CiM layers, so
        ``trace_builds == 0`` still means "a warm run did no analysis
        work".  Per-key build locks: concurrent misses build once."""
        builds, hits = (("trace_builds", "trace_hits") if layer == 1
                        else ("offload_builds", "offload_hits"))
        full_key = (layer,) + key
        with obs.span(f"cache.artifact.l{layer}",
                      cat=("analysis" if layer == 1 else "select"),
                      layer=layer, key=str(key[:2])) as sp, \
                self._key_lock(("blob",) + full_key):
            try:
                with self._lock:
                    if full_key in self._blobs:
                        setattr(self, hits, getattr(self, hits) + 1)
                        sp.set(source="memo")
                        return self._blobs[full_key]
                if self.store is not None and store_spec is not None:
                    payload = self.store.load_blob(layer, store_spec)
                    if payload is not None:
                        value = payload["artifact"]
                        with self._lock:
                            self._blobs[full_key] = value
                        sp.set(source="store")
                        return value
                with self._lock:
                    setattr(self, builds, getattr(self, builds) + 1)
                sp.set(source="build")
                value = build()
                with self._lock:
                    self._blobs[full_key] = value
                if self.store is not None and store_spec is not None:
                    self.store.save_blob(layer, store_spec,
                                         {"artifact": value})
                return value
            finally:
                self._prune_lock(("blob",) + full_key)

    def stats(self) -> Dict[str, int]:
        out = {"trace_builds": self.trace_builds,
               "trace_hits": self.trace_hits,
               "offload_builds": self.offload_builds,
               "offload_hits": self.offload_hits,
               "replay_batches": self.replay_batches,
               "struct_shared": self.struct_shared}
        if self.store is not None:
            out.update(self.store.stats())
        return out


# ======================================================================
# Engine
# ======================================================================
# Per-process worker caches for "process" mode, keyed by the store they
# route through (workers of one run all see the same store, but a process
# pool can outlive one engine/run).
_WORKER_CACHES: Dict[Tuple[Optional[str], Optional[int]], AnalysisCache] = {}


def _worker_init() -> None:
    """Process-pool initializer: keep the worker's jax on the CPU.

    An accelerator belongs to one process, and the coordinator that
    spawned the pool may hold it; a worker that opened it would fail or
    hang.  Runs before any task, so before the worker's jax picks a
    backend (environment too, for anything the worker starts)."""
    import jax
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def _worker_chunk(points: Sequence[SweepPoint], host: HostModel,
                  backend: AnalysisBackend,
                  store_root: Optional[str] = None,
                  store_version: Optional[int] = None,
                  trace_ctx: Optional[obs.TraceContext] = None
                  ) -> Tuple[List[SweepRecord], Dict[str, int], List[Dict]]:
    """Price a run of points inside one process-pool worker.

    Workers route every analysis miss through the shared on-disk
    :class:`~repro.dse.store.AnalysisStore` at ``store_root``: the first
    worker to need a key builds it once and publishes the artifact, every
    other process (and every later run) loads it — one *global* analysis
    per key, not one per worker.  ``backend`` is the engine's (pickled
    along: backends are small frozen dataclasses).  Returns the records
    plus this chunk's delta of the cache+store counters, so the parent can
    report true build totals across all workers, plus the finished span
    dicts collected under ``trace_ctx`` (empty when the parent was not
    tracing) for the coordinator's tracer to :func:`repro.obs.ingest`."""
    cache_key = (store_root, store_version)
    cache = _WORKER_CACHES.get(cache_key)
    if cache is None:
        store = (AnalysisStore(store_root, version=store_version)
                 if store_root is not None else None)
        cache = _WORKER_CACHES[cache_key] = AnalysisCache(store=store)
    before = cache.stats()
    spans: List[Dict] = []
    if trace_ctx is not None:
        # spans land in a worker-local tracer keyed to this pid; drain()
        # ships exactly this chunk's spans (workers run chunks serially)
        worker_tracer = obs.enable()
        with obs.attach(trace_ctx):
            with obs.span("worker.chunk", cat="engine",
                          workload=points[0].workload,
                          n_points=len(points), pid=os.getpid()):
                records = [backend.evaluate(cache, p, host) for p in points]
        spans = worker_tracer.drain()
    else:
        records = [backend.evaluate(cache, p, host) for p in points]
    delta = {k: v - before.get(k, 0) for k, v in cache.stats().items()
             if not k.startswith("store_bytes")}   # gauges, not counters
    return records, delta, spans


class DSEEngine:
    """Parallel design-space-exploration executor.

    ``executor``:
      * ``"thread"`` (default) — one shared :class:`AnalysisCache`; pricing
        fans out over threads (pricing is numpy/dict-walking, mostly
        GIL-bound, but trace analysis never repeats: exactly one per
        (workload, cache) per engine).
      * ``"process"`` — points are chunked by analysis key and each chunk
        runs in a spawned worker process (full CPU parallelism across
        workloads).  Workers share artifacts through an on-disk
        :class:`~repro.dse.store.AnalysisStore` — the engine's ``store``
        if it has one, else a per-engine scratch store — so every analysis
        key is built exactly once *globally*, including across repeated
        ``run()`` calls.  Spawn semantics apply: call it from a real
        module (under ``if __name__ == "__main__":`` in scripts), not
        stdin.  Workers run jax on the CPU only: an accelerator stays
        with the coordinating process.
      * ``"serial"`` — no pool at all; useful for debugging and exact
        cost accounting.

    ``store`` — a persistent :class:`~repro.dse.store.AnalysisStore` (or a
    directory path) shared across processes and invocations; shorthand for
    ``cache=AnalysisCache(store=...)``.

    ``host`` — the default :class:`~repro.core.host_model.HostModel` used
    to price points that do not carry their own (a
    ``SweepSpace(hosts=...)`` axis overrides it per point).

    ``backend`` — the :class:`~repro.dse.backends.AnalysisBackend` that
    owns the analyze → select → price split behind this engine; defaults
    to the paper's CiM pipeline
    (:class:`~repro.dse.backends.CimBackend`).  Pass
    ``TpuBackend()`` to sweep :class:`~repro.dse.space.TpuOption` axes
    over the arch registry's train steps instead — same engine, caching,
    executors, and reporting.
    """

    def __init__(self, cache: Optional[AnalysisCache] = None,
                 host: HostModel = DEFAULT_HOST,
                 executor: str = "thread",
                 max_workers: Optional[int] = None,
                 store: Optional[Union[AnalysisStore, str,
                                       pathlib.Path]] = None,
                 backend: Optional[AnalysisBackend] = None):
        if executor not in ("thread", "process", "serial"):
            raise ValueError(f"unknown executor {executor!r}")
        if cache is not None and store is not None:
            raise ValueError("pass either cache= or store= (to combine them, "
                             "build AnalysisCache(store=...) yourself)")
        self.analysis = cache or AnalysisCache(store=store)
        self.host = host
        self.backend = backend or CimBackend()
        self.executor = executor
        self.max_workers = max_workers or min(8, os.cpu_count() or 1)
        self._scratch_store: Optional[AnalysisStore] = None

    def _worker_store(self) -> AnalysisStore:
        """Store handed to process workers: the engine's persistent one, or
        a lazily created per-engine scratch directory (cleaned up with the
        engine) so multi-process sweeps never rebuild an analysis key —
        not across workers, and not across repeated ``run()`` calls."""
        if self.analysis.store is not None:
            return self.analysis.store
        if self._scratch_store is None:
            tmp = tempfile.mkdtemp(prefix="evacim-scratch-store-")
            self._scratch_store = AnalysisStore(tmp)
            weakref.finalize(self, shutil.rmtree, tmp, True)
        return self._scratch_store

    # ------------------------------------------------------------ pieces
    def evaluate(self, point: SweepPoint) -> SweepRecord:
        """Price one design point (memoized analysis)."""
        return self.backend.evaluate(self.analysis, point, self.host)

    @staticmethod
    def _chunks(points: Sequence[SweepPoint]) -> List[List[SweepPoint]]:
        """Contiguous runs sharing one analysis key (enumeration order is
        workload-major, so one pass suffices)."""
        chunks: List[List[SweepPoint]] = []
        for p in points:
            if chunks and chunks[-1][0].analysis_key == p.analysis_key:
                chunks[-1].append(p)
            else:
                chunks.append([p])
        return chunks

    # -------------------------------------------------------------- run
    def run(self, space: Union[SweepSpace, Sequence[SweepPoint]]
            ) -> SweepResults:
        """Price a full :class:`~repro.dse.space.SweepSpace` — or any
        explicit subset of points (adaptive refinement rounds price exactly
        the new neighborhood, not a cross-product).  A point sequence is
        re-indexed to its position in the sequence, so record order always
        matches input order and repeated incremental calls compose; the
        returned ``stats`` are this call's counter deltas (per-round cost
        accounting comes for free)."""
        t0 = time.perf_counter()
        if isinstance(space, SweepSpace):
            points = space.points()
        else:
            points = [dataclasses.replace(p, index=i)
                      for i, p in enumerate(space)]
        records: List[Optional[SweepRecord]] = [None] * len(points)
        stats_before = self.analysis.stats()

        worker_stats: Optional[Dict[str, int]] = None
        with obs.span("dse.run", cat="engine", executor=self.executor,
                      backend=self.backend.name, n_points=len(points)):
            if self.executor == "serial":
                for p in points:
                    records[p.index] = self.evaluate(p)
            elif self.executor == "process":
                chunks = self._chunks(points)
                store = self._worker_store()
                trace_ctx = obs.current()    # pickled into every chunk
                # spawn, not fork: the parent holds live jax/XLA threads
                ctx = multiprocessing.get_context("spawn")
                with ProcessPoolExecutor(max_workers=self.max_workers,
                                         mp_context=ctx,
                                         initializer=_worker_init) as pool:
                    futs = [pool.submit(_worker_chunk, c, self.host,
                                        self.backend, str(store.root),
                                        store.version, trace_ctx)
                            for c in chunks]
                    worker_stats = {}
                    for fut in futs:
                        recs, delta, spans = fut.result()
                        obs.ingest(spans)
                        for rec in recs:
                            records[rec.index] = rec
                        for k, v in delta.items():
                            worker_stats[k] = worker_stats.get(k, 0) + v
                # workers wrote behind this process's back: re-walk the store
                # so the byte gauges below reflect their artifacts
                if self.analysis.store is not None:
                    self.analysis.store.invalidate_usage_cache()
            else:
                # warm the analysis cache serially (deterministic build
                # order, exactly one expensive analysis pass per key), then
                # fan out; the backend sees the whole key set at once so it
                # can batch — under EVA_CIM_ACCEL=jax the CiM warm path
                # replays all of a workload's geometries in one vmapped
                # kernel launch
                warm_keys = [c[0] for c in self._chunks(points)]
                with obs.span("engine.warm", cat="engine",
                              n_keys=len(warm_keys)):
                    self.backend.warm_many(self.analysis, warm_keys)
                trace_ctx = obs.current()
                if trace_ctx is None:
                    eval_fn = self.evaluate
                else:
                    # contextvars don't follow submit(): re-attach the run
                    # context in each pool thread so spans parent correctly
                    def eval_fn(point: SweepPoint) -> SweepRecord:
                        with obs.attach(trace_ctx):
                            return self.evaluate(point)
                with ThreadPoolExecutor(max_workers=self.max_workers) as pool:
                    for rec in pool.map(eval_fn, points):
                        records[rec.index] = rec

        # stats cover THIS run only, whatever the executor: thread/serial
        # report the shared-cache counter delta, process mode the summed
        # per-worker deltas (each chunk is one analysis key, so they agree)
        stats_after = self.analysis.stats()
        stats = worker_stats if worker_stats is not None else {
            k: v - stats_before.get(k, 0) for k, v in stats_after.items()}
        # store_bytes_* are gauges (current on-disk footprint), not
        # counters — report the absolute value, never a delta
        for k, v in stats_after.items():
            if k.startswith("store_bytes"):
                stats[k] = v
        return SweepResults(records=list(records), stats=stats,
                            elapsed_s=time.perf_counter() - t0)
