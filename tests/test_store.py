"""repro.dse.store: persisted-vs-fresh artifact equality, versioned
invalidation, corrupted-file recovery, cross-engine zero-rebuild runs,
concurrent same-key races (one blob, consistent counters), directory
format-marker compatibility, and backend-namespaced coexistence (CiM +
TPU artifacts in one cache dir)."""
import dataclasses
import json
import pickle
import threading

import numpy as np
import pytest

from repro.core import profile_system
from repro.core.offload import OffloadConfig
from repro.dse import (AnalysisCache, AnalysisStore, DSEEngine,
                       StoreFormatError, SweepSpace, TpuBackend, TpuOption)
from repro.dse.space import CacheOption
from repro.dse.store import STORE_FORMAT, workload_fingerprint

CACHE = CacheOption.of("32K+256K")
CFG = OffloadConfig()

# the cheapest TPU-mode sweep: one arch, two fusion thresholds
TPU_SPACE = SweepSpace(workloads=("xlstm-125m",),
                       tpus=(TpuOption.of("v5e"),
                             TpuOption(TpuOption.of("v5e").chip, 1 << 18)))


# ----------------------------------------------------------------- keys
def test_keys_are_content_addressed(tmp_path):
    store = AnalysisStore(tmp_path)
    k1 = store.layer1_key("NB", CACHE.levels)
    assert k1 == store.layer1_key("NB", CACHE.levels)        # deterministic
    assert k1 != store.layer1_key("KM", CACHE.levels)        # workload
    other = CacheOption.of("64K+256K")
    assert k1 != store.layer1_key("NB", other.levels)        # geometry
    k2 = store.layer2_key("NB", CACHE.levels, CFG)
    assert k2 != k1
    assert k2 != store.layer2_key("NB", CACHE.levels,
                                  OffloadConfig(cim_levels=("L1",)))
    # fingerprints hash the builder module's source, not just the name
    assert workload_fingerprint("NB") != workload_fingerprint("LCS")


# ------------------------------------------------------------ round-trip
def test_roundtrip_persisted_equals_fresh(tmp_path):
    """A second process (fresh cache, same store) must price identically —
    and without building anything."""
    c1 = AnalysisCache(store=AnalysisStore(tmp_path))
    tr1 = c1.trace("NB", CACHE)
    res1, rs1 = c1.offload("NB", CACHE, CFG)
    assert c1.trace_builds == 1 and c1.offload_builds == 1

    c2 = AnalysisCache(store=AnalysisStore(tmp_path))      # "new process"
    tr2 = c2.trace("NB", CACHE)
    res2, rs2 = c2.offload("NB", CACHE, CFG)
    assert c2.trace_builds == 0 and c2.offload_builds == 0
    assert c2.store.l1_hits >= 1 and c2.store.l2_hits == 1

    # instruction stream survives byte-for-byte (repr covers every field)
    assert len(tr2.trace) == len(tr1.trace)
    assert repr(tr2.trace[0]) == repr(tr1.trace[0])
    assert repr(tr2.trace[-1]) == repr(tr1.trace[-1])
    assert [c.level for c in res2.candidates] == \
        [c.level for c in res1.candidates]
    assert rs2.host_seqs == rs1.host_seqs

    rep1 = profile_system(tr1, offload=res1, reshaped=rs1)
    rep2 = profile_system(tr2, offload=res2, reshaped=rs2)
    assert rep2.energy_improvement == rep1.energy_improvement
    assert rep2.speedup == rep1.speedup
    assert rep2.macr == rep1.macr


def test_layer1_upgraded_with_flow_tables(tmp_path):
    """trace() persists the raw trace; trace_analysis() upgrades the same
    artifact with the flow index so later processes skip analyze_trace."""
    store = AnalysisStore(tmp_path)
    c1 = AnalysisCache(store=store)
    c1.trace("NB", CACHE)
    _, flow = store.load_layer1("NB", CACHE.levels)
    assert flow is None
    c1.trace_analysis("NB", CACHE)
    _, flow = store.load_layer1("NB", CACHE.levels)
    assert flow is not None

    c2 = AnalysisCache(store=AnalysisStore(tmp_path))
    an = c2.trace_analysis("NB", CACHE)
    assert c2.trace_builds == 0
    assert an.flow.reg_consumers                    # rehydrated, non-empty


# ------------------------------------------------------------ invalidation
def test_analysis_version_in_selection_keys(tmp_path, monkeypatch):
    """Selection/flow artifacts are additionally keyed by ANALYSIS_VERSION:
    an algorithm change invalidates them while the trace stays reusable."""
    store = AnalysisStore(tmp_path)
    c = AnalysisCache(store=store)
    c.offload("NB", CACHE, CFG)

    import repro.dse.store as store_mod
    monkeypatch.setattr(store_mod, "ANALYSIS_VERSION",
                        store_mod.ANALYSIS_VERSION + 1)
    bumped = AnalysisStore(tmp_path)
    assert bumped.load_layer2("NB", CACHE.levels, CFG) is None
    tr, flow = bumped.load_layer1("NB", CACHE.levels)
    assert tr is not None and flow is None      # trace reusable, flow not


def test_version_bump_invalidates(tmp_path):
    c1 = AnalysisCache(store=AnalysisStore(tmp_path, version=1))
    c1.trace("NB", CACHE)

    bumped = AnalysisStore(tmp_path, version=2)
    assert bumped.load_layer1("NB", CACHE.levels) is None   # unreachable
    c2 = AnalysisCache(store=bumped)
    c2.trace("NB", CACHE)
    assert c2.trace_builds == 1                             # forced rebuild

    # the old version's artifact is untouched (keys don't collide)
    assert AnalysisStore(tmp_path, version=1).load_layer1(
        "NB", CACHE.levels) is not None


# ---------------------------------------------------------- format marker
def test_fresh_store_writes_format_marker(tmp_path):
    AnalysisStore(tmp_path)
    marker = tmp_path / "FORMAT.json"
    assert json.loads(marker.read_text()) == {"store_format": STORE_FORMAT}
    AnalysisStore(tmp_path)                       # reopening is fine


def test_newer_format_directory_refuses_to_open(tmp_path):
    (tmp_path / "FORMAT.json").write_text(
        json.dumps({"store_format": STORE_FORMAT + 1}))
    with pytest.raises(StoreFormatError, match="newer|STORE_FORMAT"):
        AnalysisStore(tmp_path)
    # ...and through the engine, the error carries the directory name
    with pytest.raises(StoreFormatError, match=str(tmp_path)):
        DSEEngine(store=tmp_path)


def test_older_or_corrupt_marker_is_upgraded(tmp_path):
    (tmp_path / "FORMAT.json").write_text(
        json.dumps({"store_format": STORE_FORMAT - 1}))
    AnalysisStore(tmp_path)                       # per-file stamps protect loads
    assert json.loads((tmp_path / "FORMAT.json").read_text()) == \
        {"store_format": STORE_FORMAT}
    (tmp_path / "FORMAT.json").write_text("not json{")
    AnalysisStore(tmp_path)
    assert json.loads((tmp_path / "FORMAT.json").read_text()) == \
        {"store_format": STORE_FORMAT}


def test_cli_clear_error_on_newer_store(tmp_path, capsys):
    """examples/dse_cim.py must exit 2 with a one-line error (no
    traceback) when --cache-dir points at a newer-format store."""
    import importlib.util
    import pathlib
    cli_path = (pathlib.Path(__file__).resolve().parents[1]
                / "examples" / "dse_cim.py")
    spec = importlib.util.spec_from_file_location("dse_cim_cli", cli_path)
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    (tmp_path / "FORMAT.json").write_text(json.dumps({"store_format": 99}))
    rc = cli.main(["--workload", "NB", "--cache-dir", str(tmp_path)])
    err = capsys.readouterr().err
    assert rc == 2
    assert "STORE_FORMAT=99" in err
    assert "Traceback" not in err
    rc = cli.main(["--backend", "tpu", "--workload", "xlstm-125m",
                   "--chips", "v5e", "--thresholds", "16K",
                   "--cache-dir", str(tmp_path)])
    assert rc == 2
    assert "STORE_FORMAT=99" in capsys.readouterr().err


# --------------------------------------------------------------- recovery
def test_corrupt_file_recovery(tmp_path):
    store = AnalysisStore(tmp_path)
    AnalysisCache(store=store).trace("NB", CACHE)
    files = list((tmp_path / "layer1").glob("*.npz"))
    assert len(files) == 1
    files[0].write_bytes(b"not an npz archive")

    fresh = AnalysisStore(tmp_path)
    assert fresh.load_layer1("NB", CACHE.levels) is None
    assert fresh.corrupt_drops == 1
    assert not files[0].exists()                    # dropped, not retried

    c = AnalysisCache(store=fresh)                  # rebuild + re-publish
    c.trace("NB", CACHE)
    assert c.trace_builds == 1
    assert AnalysisStore(tmp_path).load_layer1("NB", CACHE.levels) is not None


def test_bad_payload_is_dropped_and_repaired(tmp_path):
    """An archive whose envelope verifies but whose payload fails
    rehydration must be unlinked — save_layer1 skips existing files, so a
    merely-ignored artifact would never be repaired."""
    import numpy as np
    from repro.dse.store import NPZ_FORMAT
    store = AnalysisStore(tmp_path)
    AnalysisCache(store=store).trace("NB", CACHE)
    (path,) = (tmp_path / "layer1").glob("*.npz")
    key = store.layer1_key("NB", CACHE.levels)
    np.savez_compressed(                      # valid envelope, no columns
        path, meta_store_key=np.frombuffer(key.encode(), dtype=np.uint8),
        meta_npz_format=np.asarray([NPZ_FORMAT], np.int64))

    fresh = AnalysisStore(tmp_path)
    assert fresh.load_layer1("NB", CACHE.levels) is None
    assert fresh.corrupt_drops == 1
    assert not path.exists()                  # dropped, so a rebuild heals it
    c = AnalysisCache(store=fresh)
    c.trace("NB", CACHE)
    assert c.trace_builds == 1
    assert AnalysisStore(tmp_path).load_layer1("NB", CACHE.levels) is not None


def test_foreign_payload_rejected(tmp_path):
    """A well-formed archive that isn't ours (wrong embedded key) is a miss."""
    import numpy as np
    from repro.dse.store import NPZ_FORMAT
    store = AnalysisStore(tmp_path)
    key = store.layer1_key("NB", CACHE.levels)
    path = tmp_path / "layer1" / f"cim-{key}.npz"
    np.savez_compressed(
        path, meta_store_key=np.frombuffer(b"somebody-else", dtype=np.uint8),
        meta_npz_format=np.asarray([NPZ_FORMAT], np.int64))
    assert store.load_layer1("NB", CACHE.levels) is None
    assert store.corrupt_drops == 1

    # ...and a well-formed *pickle* under the npz name is dropped, too
    path.write_bytes(pickle.dumps({"format": STORE_FORMAT,
                                   "key": "somebody-else", "payload": {}}))
    assert store.load_layer1("NB", CACHE.levels) is None
    assert store.corrupt_drops == 2


# ----------------------------------------------------------- two engines
def test_two_engines_share_store_zero_rebuilds(tmp_path):
    space = SweepSpace(workloads=("NB",), cim_levels=("L1_only", "both"),
                       techs=("sram", "fefet"))
    r1 = DSEEngine(store=tmp_path).run(space)
    assert r1.stats["trace_builds"] == 1
    assert r1.stats["offload_builds"] == 2
    assert r1.stats["store_writes"] >= 3            # 1x layer1(+flow) + 2x layer2

    r2 = DSEEngine(store=tmp_path).run(space)       # fresh engine, warm disk
    assert r2.stats["trace_builds"] == 0
    assert r2.stats["offload_builds"] == 0
    assert r2.stats["store_l1_hits"] >= 1
    assert r2.stats["store_l2_hits"] == 2
    assert [r.energy_improvement for r in r2] == \
        [r.energy_improvement for r in r1]
    assert [r.speedup for r in r2] == [r.speedup for r in r1]


def test_engine_rejects_cache_plus_store(tmp_path):
    with pytest.raises(ValueError):
        DSEEngine(cache=AnalysisCache(), store=tmp_path)


def test_store_disk_usage_gauges(tmp_path):
    """stats() reports on-disk bytes per layer and per owning backend —
    absolute gauges, surfaced through SweepResults.stats as well."""
    res = DSEEngine(store=tmp_path).run(SweepSpace(workloads=("NB",)))
    store = AnalysisStore(tmp_path)
    usage = store.disk_usage()
    assert usage["store_bytes_layer1"] > 0
    assert usage["store_bytes_layer2"] > 0
    assert usage["store_bytes_cim"] == usage["store_bytes_total"] == \
        usage["store_bytes_layer1"] + usage["store_bytes_layer2"]
    # engine stats carry the gauges as absolutes (not deltas)
    assert res.stats["store_bytes_total"] == usage["store_bytes_total"]
    # gauges live in stats() alongside the counters
    assert store.stats()["store_bytes_layer1"] == usage["store_bytes_layer1"]


# ------------------------------------------------------------ concurrency
def test_concurrent_caches_race_same_key_one_blob(tmp_path):
    """Two threads — separate caches, separate store handles, one cache
    dir — race the same layer-1/layer-2 key.  Exactly one valid blob per
    layer must exist afterwards, both threads must price identically, and
    the counters must stay consistent (no phantom hits, no corrupt
    drops)."""
    barrier = threading.Barrier(2)
    outcomes, errors = [], []

    def worker():
        cache = AnalysisCache(store=AnalysisStore(tmp_path))
        barrier.wait()                      # collide as hard as possible
        try:
            an = cache.trace_analysis("NB", CACHE)
            res, rs = cache.offload("NB", CACHE, CFG)
            rep = profile_system(cache.trace("NB", CACHE),
                                 offload=res, reshaped=rs)
            outcomes.append((len(an.flow.reg_consumers),
                             rep.energy_improvement, rep.speedup,
                             cache.trace_builds, cache.offload_builds,
                             cache.store.corrupt_drops))
        except Exception as exc:  # noqa: BLE001 — surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors
    assert len(outcomes) == 2

    # both threads computed/loaded the *same* analysis and price
    assert outcomes[0][:3] == outcomes[1][:3]
    # each thread built at most once per layer, and nobody saw corruption
    for _, _, _, trace_builds, offload_builds, corrupt in outcomes:
        assert trace_builds <= 1 and offload_builds <= 1
        assert corrupt == 0

    # exactly one blob per artifact on disk (trace npz + flow npz under
    # layer1, one pickle under layer2), and they are valid: a fresh cache
    # rebuilds nothing
    layer1 = sorted(p.name for p in (tmp_path / "layer1").glob("*.npz"))
    assert len(layer1) == 2                       # <key>.npz + <key>.flow npz
    assert len({name.split(".")[0] for name in layer1}) == 1   # same key
    assert len(list((tmp_path / "layer2").glob("*"))) == 1
    fresh = AnalysisCache(store=AnalysisStore(tmp_path))
    fresh.trace_analysis("NB", CACHE)
    fresh.offload("NB", CACHE, CFG)
    assert fresh.trace_builds == 0 and fresh.offload_builds == 0
    assert fresh.store.corrupt_drops == 0


def test_corrupt_drops_surface_in_engine_stats(tmp_path):
    """The corrupt-drop counter rides SweepResults.stats, so CLI surfaces
    (examples/dse_cim.py --cache-dir) and /metrics can report it."""
    res = DSEEngine(store=tmp_path).run(SweepSpace(workloads=("NB",)))
    assert res.stats["store_corrupt_drops"] == 0

    (blob,) = (p for p in (tmp_path / "layer1").glob("*.npz")
               if ".flow" not in p.name)          # the trace artifact
    blob.write_bytes(b"bit rot")
    res2 = DSEEngine(store=tmp_path).run(SweepSpace(workloads=("NB",)))
    assert res2.stats["store_corrupt_drops"] == 1
    assert res2.stats["trace_builds"] == 1          # rebuilt through the rot
    assert [r.energy_improvement for r in res2] == \
        [r.energy_improvement for r in res]


# ------------------------------------------------- backend coexistence
CIM_SPACE = SweepSpace(workloads=("NB",))


def test_two_backends_share_cache_dir_roundtrip(tmp_path):
    """CiM and TPU artifacts coexist in one store directory: each backend's
    second (fresh-engine) run does zero analysis work and prices
    identically, and neither evicts or collides with the other."""
    cim1 = DSEEngine(store=tmp_path).run(CIM_SPACE)
    tpu1 = DSEEngine(store=tmp_path, backend=TpuBackend()).run(TPU_SPACE)
    assert cim1.stats["trace_builds"] == 1
    assert tpu1.stats["trace_builds"] == 1

    cim2 = DSEEngine(store=tmp_path).run(CIM_SPACE)
    tpu2 = DSEEngine(store=tmp_path, backend=TpuBackend()).run(TPU_SPACE)
    assert cim2.stats["trace_builds"] == 0
    assert tpu2.stats["trace_builds"] == 0
    assert tpu2.stats["store_l1_hits"] == 1
    assert [r.energy_improvement for r in cim2] == \
        [r.energy_improvement for r in cim1]
    assert [r.energy_improvement for r in tpu2] == \
        [r.energy_improvement for r in tpu1]
    assert {r.backend for r in tpu2} == {"tpu"}


def test_tpu_version_bump_misses_while_cim_stays_warm(tmp_path, monkeypatch):
    """Bumping a backend's version stamp must invalidate *that* backend's
    persisted artifacts and no one else's."""
    DSEEngine(store=tmp_path).run(CIM_SPACE)
    DSEEngine(store=tmp_path, backend=TpuBackend()).run(TPU_SPACE)

    import repro.dse.backends as backends_mod
    monkeypatch.setattr(backends_mod, "TPU_ANALYSIS_VERSION",
                        backends_mod.TPU_ANALYSIS_VERSION + 1)
    tpu = DSEEngine(store=tmp_path, backend=TpuBackend()).run(TPU_SPACE)
    assert tpu.stats["trace_builds"] == 1          # forced re-analysis
    cim = DSEEngine(store=tmp_path).run(CIM_SPACE)
    assert cim.stats["trace_builds"] == 0          # untouched, still warm


def test_trace_vm_bump_misses_while_tpu_stays_warm(tmp_path):
    """...and symmetrically: a trace-VM version bump (the CiM stamp, held
    by the store) rebuilds CiM analyses while TPU artifacts — keyed by the
    TPU backend's own stamp, not the store's — stay warm."""
    from repro.core.trace import TRACE_VM_VERSION
    DSEEngine(store=tmp_path).run(CIM_SPACE)
    DSEEngine(store=tmp_path, backend=TpuBackend()).run(TPU_SPACE)

    bumped = AnalysisStore(tmp_path, version=TRACE_VM_VERSION + 1)
    cim = DSEEngine(store=bumped).run(CIM_SPACE)
    assert cim.stats["trace_builds"] == 1          # unreachable under v+1
    bumped2 = AnalysisStore(tmp_path, version=TRACE_VM_VERSION + 1)
    tpu = DSEEngine(store=bumped2, backend=TpuBackend()).run(TPU_SPACE)
    assert tpu.stats["trace_builds"] == 0
    assert tpu.stats["store_l1_hits"] == 1


# ---------------------------------------- structural memo across store loads
_GEOMETRIES = ("32K+256K", "64K+256K", "64K+2M")
_SHARE_SPACE = SweepSpace(workloads=("NB",), caches=_GEOMETRIES,
                          cim_levels=("L1_only", "both"))


def _fill_layer1(root, caches=_GEOMETRIES):
    """Layer 1 (trace + flow) of NB under ``caches``, on the numpy path."""
    from repro.core import accel
    with accel.use_backend("numpy"):
        fill = AnalysisCache(store=AnalysisStore(root))
        for name in caches:
            fill.trace_analysis("NB", CacheOption.of(name))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_store_loads_share_one_structural_memo(tmp_path, backend):
    """Every geometry of a workload loaded from the store shares the first
    load's ``_struct`` memo, so Algorithm 1's partition is built once per
    workload, and the records equal those of a sweep without a store."""
    from repro.core import accel
    _fill_layer1(tmp_path)
    with accel.use_backend(backend):
        cache = AnalysisCache(store=AnalysisStore(tmp_path))
        stored = DSEEngine(cache=cache, executor="serial").run(_SHARE_SPACE)
        fresh = DSEEngine(executor="serial").run(_SHARE_SPACE)
    assert stored.stats["trace_builds"] == 0
    structs = [cache._traces[("NB", CacheOption.of(n).levels)].trace._struct
               for n in _GEOMETRIES]
    assert all(s is structs[0] for s in structs)
    assert len(structs[0]["partitions"]) == 1
    assert cache.stats()["struct_shared"] == 2
    assert stored.stats["struct_shared"] == 2
    assert [dataclasses.astuple(r) for r in stored] == \
        [dataclasses.astuple(r) for r in fresh]


def test_store_load_with_other_structure_keeps_its_own_memo(tmp_path):
    """A stored trace whose addresses differ from the workload's known
    structural trace in one row keeps its own memo, and its selections are
    those of a fresh analysis of that very trace."""
    from repro.core.columnar import ColumnarTrace
    from repro.core.offload import analyze_trace
    from repro.core.trace import TraceResult
    first, other = (CacheOption.of(n) for n in _GEOMETRIES[:2])
    _fill_layer1(tmp_path, _GEOMETRIES[:1])
    built = AnalysisCache().trace("NB", other)
    arrays = built.trace.to_arrays()
    addr = arrays["col_addr"].copy()
    row = int(np.flatnonzero(built.trace.mem_mask)[0])
    addr[row] += 64                                  # one line further
    arrays["col_addr"] = addr
    planted = TraceResult(ColumnarTrace.from_arrays(arrays), built.cache,
                          built.outputs)
    store = AnalysisStore(tmp_path)
    store.save_layer1("NB", other.levels, planted)

    cache = AnalysisCache(store=AnalysisStore(tmp_path))
    known = cache.trace("NB", first)
    loaded = cache.trace("NB", other)
    assert cache.trace_builds == 0
    assert loaded.trace._struct is not known.trace._struct
    assert np.array_equal(loaded.trace.addr, addr)
    assert cache.stats()["struct_shared"] == 0
    got, _ = cache.offload("NB", other, CFG)
    want = analyze_trace(planted).select(CFG)
    assert [dataclasses.astuple(c) for c in got.candidates] == \
        [dataclasses.astuple(c) for c in want.candidates]
    assert got.claimed == want.claimed
    assert cache.stats()["struct_shared"] == 0


def test_daemon_reports_struct_shared_per_cache(tmp_path):
    """The daemon's stats document carries ``struct_shared`` under every
    backend cache; a store-backed sweep of two geometries shares once."""
    from repro.dse.service import ServiceClient, running_server
    _fill_layer1(tmp_path, _GEOMETRIES[:2])
    with running_server(cache_dir=str(tmp_path)) as (url, _service):
        client = ServiceClient(url)
        reply = client.sweep(["NB"], caches=list(_GEOMETRIES[:2]),
                             techs=["sram"])
        assert len(reply.records) == 2
        doc = client.metrics()
    for backend in ("cim", "tpu"):
        assert "struct_shared" in doc["cache"][backend]
    assert doc["cache"]["cim"]["struct_shared"] == 1
    assert doc["cache"]["tpu"]["struct_shared"] == 0
    assert doc["cache"]["cim"]["layer1"]["builds"] == 0
