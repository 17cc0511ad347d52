"""repro.obs: span tracer semantics (nesting, null-span off path,
bounded buffers), trace-context propagation across thread and process
executors, Chrome trace-event export validity, per-stage attribution,
the traced example CLI end to end, the daemon's per-request tracing
surface, and the metrics-registry fixes that rode along (leaf/branch
nest clashes, histogram quantile dedup)."""
import dataclasses
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from repro import obs
from repro.dse import AdaptiveDSE, DSEEngine, SweepSpace
from repro.dse.service import (MetricsRegistry, ServiceClient, ServiceError,
                               running_server)
from repro.dse.service.metrics import Histogram

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _no_tracer_leaks():
    """Every test starts and ends with tracing off — a leaked global
    tracer would silently change other tests' hot paths."""
    obs.disable()
    yield
    obs.disable()


def _space():
    return SweepSpace(workloads=("NB",), caches=("32K+256K", "64K+2M"),
                      cim_levels=("L1_only", "both"))


# ---------------------------------------------------------- tracer basics
def test_nested_spans_record_parentage_and_attrs():
    t = obs.enable(obs.Tracer())
    with obs.span("outer", cat="a", k=1):
        with obs.span("inner", cat="b") as inner:
            inner.set(hit=True)
    inner_rec, outer_rec = t.spans()          # finish order: inner first
    assert (inner_rec["name"], outer_rec["name"]) == ("inner", "outer")
    assert inner_rec["parent_id"] == outer_rec["span_id"]
    assert inner_rec["trace_id"] == outer_rec["trace_id"]
    assert outer_rec["parent_id"] is None
    assert outer_rec["attrs"] == {"k": 1}
    assert inner_rec["attrs"] == {"hit": True}
    assert 0 <= inner_rec["dur_ns"] <= outer_rec["dur_ns"]
    assert inner_rec["ts_ns"] >= outer_rec["ts_ns"]


def test_span_records_exception_and_propagates_it():
    t = obs.enable(obs.Tracer())
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    (rec,) = t.spans()
    assert rec["attrs"]["error"] == "ValueError"


def test_separate_roots_get_distinct_trace_ids():
    t = obs.enable(obs.Tracer())
    with obs.span("a"):
        pass
    with obs.span("b"):
        pass
    assert len({s["trace_id"] for s in t.spans()}) == 2


def test_off_hands_out_the_shared_null_span_and_records_nothing():
    assert obs.tracer() is None and not obs.active()
    s = obs.span("x", cat="y", k=1)
    assert s is obs.NULL_SPAN and s is obs.span("z")
    with s as entered:
        assert entered.set(a=1) is entered
    obs.ingest([{"name": "w"}])                # all no-ops
    assert obs.current() is None
    with obs.attach(None):                     # no-op attach
        assert obs.current() is None


def test_max_spans_bounds_memory_and_counts_drops():
    t = obs.enable(obs.Tracer(max_spans=3))
    for i in range(5):
        with obs.span(f"s{i}"):
            pass
    assert len(t.spans()) == 3
    assert t.dropped == 2


def test_take_removes_one_trace_and_drain_empties():
    t = obs.enable(obs.Tracer())
    with obs.span("a") as sa:
        pass
    with obs.span("b"):
        pass
    taken = t.take(sa.trace_id)
    assert [s["name"] for s in taken] == ["a"]
    assert [s["name"] for s in t.spans()] == ["b"]
    spans = t.drain()
    assert [s["name"] for s in spans] == ["b"]
    assert t.spans() == []


def test_enable_keeps_installed_tracer_unless_given_one():
    t1 = obs.enable()
    assert obs.enable() is t1                  # idempotent
    t2 = obs.enable(obs.Tracer())
    assert obs.tracer() is t2 and t2 is not t1


# -------------------------------------------- engine instrumentation
def test_engine_records_identical_tracing_on_vs_off():
    space = _space()
    base = DSEEngine(executor="serial").run(space)
    assert obs.tracer() is None                # untraced run installs nothing
    t = obs.enable(obs.Tracer())
    traced = DSEEngine(executor="serial").run(space)
    assert [dataclasses.astuple(r) for r in traced] == \
        [dataclasses.astuple(r) for r in base]
    names = {s["name"] for s in t.spans()}
    assert {"dse.run", "cache.trace", "cache.select",
            "backend.evaluate"} <= names


# the phase spans inside selection and pricing: name -> its parent
_PHASES = {"select.partition": "cache.select",
           "select.reshape": "cache.select",
           "accel.place.pack": "accel.place",
           "accel.place.device": "accel.place",
           "accel.place.unpack": "accel.place",
           "price.baseline": "backend.price",
           "price.cim": "backend.price",
           "price.macr": "backend.price"}


def test_accelerated_sweep_emits_phase_spans_under_their_layers():
    from repro.core import accel
    space = _space()
    with accel.use_backend("jax"):
        base = DSEEngine(executor="serial").run(space)
        t = obs.enable(obs.Tracer())
        traced = DSEEngine(executor="serial").run(space)
    assert [dataclasses.astuple(r) for r in traced] == \
        [dataclasses.astuple(r) for r in base]
    spans = t.spans()
    by_id = {s["span_id"]: s for s in spans}
    seen = {}
    for s in spans:
        if s["name"] in _PHASES:
            assert by_id[s["parent_id"]]["name"] == _PHASES[s["name"]]
            seen.setdefault(s["name"], []).append(s["attrs"])
    assert set(seen) == set(_PHASES)
    for attrs in seen["select.partition"]:
        assert attrs["source"] in ("memo", "build") and attrs["n_protos"] > 0
    for attrs in seen["accel.place.pack"]:
        assert attrs["n_leaf"] >= attrs["n_seg"] > 0 and attrs["n_acc"] > 0
    for attrs in seen["accel.place.unpack"]:
        assert attrs["n_candidates"] > 0
    for attrs in seen["select.reshape"]:
        assert attrs["n_host_seqs"] > 0
    # one pricing phase of each kind per point
    assert all(len(seen[n]) == len(traced)
               for n in ("price.baseline", "price.cim", "price.macr"))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_store_loads_share_the_partition_build(tmp_path, backend):
    """Every store load of a workload after its first takes the shared
    structural memo (``struct="shared"``), so a store-backed sweep builds
    Algorithm 1's partition once per (workload, partition key)."""
    from repro.core import accel
    from repro.dse import AnalysisCache
    space = SweepSpace(workloads=("NB", "LCS"),
                       caches=("32K+256K", "64K+256K", "64K+2M"),
                       cim_levels=("L1_only", "both"))
    with accel.use_backend("numpy"):                # layer 1 only
        fill = AnalysisCache(store=tmp_path)
        for p in space.points():
            fill.trace_analysis(p.workload, p.cache)
    with accel.use_backend(backend):
        t = obs.enable(obs.Tracer())
        res = DSEEngine(cache=AnalysisCache(store=tmp_path),
                        executor="thread", max_workers=1).run(space)
    assert res.stats["trace_builds"] == 0
    assert res.stats["struct_shared"] == 4
    spans = t.spans()
    by_id = {s["span_id"]: s for s in spans}
    if backend == "numpy":                  # one cache.trace span per load
        loads = {}
        for s in spans:
            if s["name"] == "cache.trace" \
                    and s["attrs"].get("source") == "store":
                loads.setdefault(s["attrs"]["workload"], []).append(
                    s["attrs"]["struct"])
        assert loads == {"NB": ["own", "shared", "shared"],
                         "LCS": ["own", "shared", "shared"]}
    else:                                   # one batch span per workload
        batches = {s["attrs"]["workload"]: s["attrs"] for s in spans
                   if s["name"] == "cache.replay_batch"}
        assert set(batches) == {"NB", "LCS"}
        for attrs in batches.values():
            assert attrs["struct"] == "shared" and attrs["n_shared"] == 2
    builds = {}
    for s in spans:
        if s["name"] == "select.partition" \
                and s["attrs"]["source"] == "build":
            wl = by_id[s["parent_id"]]["attrs"]["workload"]
            builds[wl] = builds.get(wl, 0) + 1
    assert builds == {"NB": 1, "LCS": 1}


def test_serial_attribution_telescopes_to_wall_clock():
    t = obs.enable(obs.Tracer())
    DSEEngine(executor="serial").run(_space())
    att = t.stage_attribution()
    assert att["n_spans"] > 0
    assert 0.95 <= att["coverage"] <= 1.05, att
    for cat in ("trace", "replay", "select", "price"):
        assert cat in att["stages"], att["stages"].keys()
    # second identical run: every cache layer answers from memo
    DSEEngine(executor="serial").run(_space())


def test_thread_executor_spans_share_one_trace_under_one_root():
    t = obs.enable(obs.Tracer())
    DSEEngine(executor="thread", max_workers=4).run(_space())
    spans = t.spans()
    assert len({s["trace_id"] for s in spans}) == 1
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if s["parent_id"] is None]
    assert [r["name"] for r in roots] == ["dse.run"]
    for s in spans:
        if s["parent_id"] is not None:
            assert s["parent_id"] in by_id, s["name"]


def test_process_executor_worker_spans_parent_into_coordinator(tmp_path):
    t = obs.enable(obs.Tracer())
    space = SweepSpace(workloads=("NB",), caches=("32K+256K", "64K+256K"),
                       cim_levels=("L1_only", "both"))
    DSEEngine(executor="process", max_workers=2, store=tmp_path).run(space)
    spans = t.spans()
    assert len({s["pid"] for s in spans}) >= 2       # workers shipped spans
    assert len({s["trace_id"] for s in spans}) == 1  # ...into one trace
    assert [s for s in spans if s["name"] == "worker.chunk"]
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if s["parent_id"] is None]
    assert [r["name"] for r in roots] == ["dse.run"]
    for s in spans:
        if s["parent_id"] is not None:
            assert s["parent_id"] in by_id, s["name"]


def test_adaptive_rounds_emit_spans():
    t = obs.enable(obs.Tracer())
    space = SweepSpace(workloads=("NB",),
                       caches=("32K+256K", "64K+256K", "64K+2M"),
                       cim_levels=("L1_only", "L2_only", "both"))
    AdaptiveDSE(space, engine=DSEEngine(executor="serial")).run()
    rounds = [s for s in t.spans() if s["name"] == "adaptive.round"]
    assert rounds
    assert [s["attrs"]["round"] for s in rounds] == list(range(len(rounds)))
    assert all("frontier_size" in s["attrs"] for s in rounds)
    assert rounds[-1]["attrs"]["stable"] is True


# ------------------------------------------------------- chrome export
def test_chrome_export_is_perfetto_valid(tmp_path):
    t = obs.enable(obs.Tracer())
    DSEEngine(executor="serial").run(_space())
    path = tmp_path / "trace.json"
    n = t.export_chrome(path)
    doc = json.loads(path.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == n == doc["otherData"]["spans"] > 0
    for e in xs:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                "args"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0
    # timestamps rebase to a zero origin
    assert min(e["ts"] for e in xs) == pytest.approx(0.0)
    # every child's [ts, ts+dur] nests inside its parent's interval
    by_id = {e["args"]["span_id"]: e for e in xs}
    for e in xs:
        ref = e["args"].get("parent_id")
        if ref:
            p = by_id[ref]
            assert e["ts"] >= p["ts"] - 1e-3
            assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-3
    assert {e["ph"] for e in events} == {"X", "M"}
    names = {e["name"] for e in events if e["ph"] == "M"}
    assert {"process_name", "thread_name"} <= names


def test_ndjson_export_round_trips(tmp_path):
    t = obs.enable(obs.Tracer())
    with obs.span("a", cat="x", k=1):
        pass
    path = tmp_path / "spans.ndjson"
    assert t.export_ndjson(path) == 1
    (line,) = path.read_text().splitlines()
    rec = json.loads(line)
    assert rec["name"] == "a" and rec["attrs"] == {"k": 1}


# ----------------------------------------------------------- attribution
def _synth(sid, parent, cat, ts, dur, **attrs):
    return {"name": sid, "cat": cat, "trace_id": "t", "span_id": sid,
            "parent_id": parent, "ts_ns": ts, "dur_ns": dur,
            "pid": 1, "tid": 1, "thread": "main", "attrs": attrs}


def test_stage_attribution_self_time_and_hit_rates():
    spans = [
        _synth("root", None, "engine", 0, 100),
        _synth("a", "root", "trace", 0, 60, source="build", workload="NB"),
        _synth("b", "root", "select", 60, 30, source="memo", workload="NB"),
    ]
    att = obs.stage_attribution(spans)
    assert att["wall_s"] == pytest.approx(100e-9)
    assert att["attributed_s"] == pytest.approx(100e-9)
    assert att["coverage"] == pytest.approx(1.0)
    assert att["stages"]["engine"]["self_s"] == pytest.approx(10e-9)
    assert att["stages"]["trace"]["hit_rate"] == 0.0
    assert att["stages"]["select"]["hit_rate"] == 1.0
    assert att["workloads"]["NB"]["trace"] == pytest.approx(60e-9)
    md = obs.attribution_markdown(att)
    assert "| stage |" in md and "| trace |" in md and "| NB |" in md


def test_stage_attribution_orphans_count_as_roots():
    # a span whose parent never reached this tracer (dropped, or a worker
    # chunk that died) must not vanish from wall-clock accounting
    att = obs.stage_attribution([_synth("x", "missing", "trace", 0, 50)])
    assert att["wall_s"] == pytest.approx(50e-9)
    assert att["coverage"] == pytest.approx(1.0)


def test_stage_attribution_unites_overlapping_children():
    # two pool threads price 10..60 and 40..90 under one 0..100 run: the
    # run did 20 ns itself (the union covers 80), not 0 (the sum is 100)
    spans = [_synth("run", None, "engine", 0, 100),
             _synth("a", "run", "price", 10, 50),
             _synth("b", "run", "price", 40, 50)]
    att = obs.stage_attribution(spans)
    assert att["stages"]["engine"]["self_s"] == pytest.approx(20e-9)
    assert att["stages"]["price"]["self_s"] == pytest.approx(100e-9)
    assert att["attributed_s"] == pytest.approx(120e-9)


def test_build_tree_nests_children_and_orphans():
    spans = [_synth("root", None, "engine", 0, 100),
             _synth("kid2", "root", "select", 60, 30),
             _synth("kid1", "root", "trace", 0, 60),
             _synth("lost", "missing", "price", 5, 1)]
    roots = obs.build_tree(spans)
    assert [r["span_id"] for r in roots] == ["root", "lost"]
    assert [c["span_id"] for c in roots[0]["children"]] == ["kid1", "kid2"]


# ----------------------------------------------- example CLI end to end
def test_example_cli_writes_valid_trace_and_report(tmp_path):
    """Acceptance: a cold --trace run produces a Perfetto-loadable file
    and --trace-report attribution sums to within 5% of wall-clock."""
    trace = tmp_path / "trace.json"
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run(
        [sys.executable, "examples/dse_cim.py", "--workload", "NB",
         "--trace", str(trace), "--trace-report"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(trace.read_text())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert xs and all(e["args"].get("trace_id") for e in xs)
    assert "| stage |" in proc.stdout
    footer = proc.stdout.strip().splitlines()[-1]
    m = re.search(r"\((\d+(?:\.\d+)?)%\)$", footer)
    assert m, footer
    assert 95.0 <= float(m.group(1)) <= 105.0


# -------------------------------------------------- daemon tracing plane
def test_service_requests_traced_and_queryable():
    with running_server(max_workers=4) as (url, _service):
        client = ServiceClient(url)
        r1 = client.sweep(["NB"], caches=["32K+256K"])
        r2 = client.sweep(["NB"], caches=["32K+256K", "64K+2M"])
        assert r1.trace_id and r2.trace_id
        assert r1.trace_id != r2.trace_id      # one root span per request
        tree = client.trace(r2.trace_id)
        assert tree["trace_id"] == r2.trace_id
        (root,) = tree["spans"]
        assert root["name"] == "http.sweep" and root["children"]
        assert tree["n_spans"] >= 2
        with pytest.raises(ServiceError) as exc:
            client.trace("0" * 16)
        assert exc.value.status == 404
        m = client.metrics()
        assert m["obs"]["tracing"] is True
        assert m["obs"]["buffered_traces"] == 2
        assert m["obs"]["dropped_spans"] == 0
        assert m["service"]["obs"]["spans"] >= tree["n_spans"]
        assert m["service"]["obs"]["stage_self_s"]
    # running_server owned the tracer, so exit restores tracing-off
    assert obs.tracer() is None


# ------------------------------------- metrics registry fixes (satellite)
def test_metrics_nest_leaf_then_branch_keeps_both():
    reg = MetricsRegistry()
    reg.counter("a")                 # leaf "a" registers first (counters
    reg.gauge_inc("a.b", 2)          # nest before gauges in snapshot())
    snap = reg.snapshot()
    assert snap["a"] == 1
    assert snap["a.b"] == 2          # literal dotted key, not dropped


def test_metrics_nest_branch_then_leaf_keeps_both():
    reg = MetricsRegistry()
    reg.counter("a.b")
    reg.gauge_inc("a", 5)
    snap = reg.snapshot()
    assert snap["a"]["b"] == 1
    assert snap["a."] == 5           # dotless name vs branch: "." suffix


def test_metrics_nest_same_kind_clash():
    reg = MetricsRegistry()
    reg.counter("x", 3)
    reg.counter("x.y", 7)
    snap = reg.snapshot()
    assert snap["x"] == 3 and snap["x.y"] == 7


def test_metrics_nest_plain_paths_untouched():
    reg = MetricsRegistry()
    reg.counter("requests.sweep", 2)
    reg.gauge_inc("inflight", 1)
    snap = reg.snapshot()
    assert snap["requests"]["sweep"] == 2 and snap["inflight"] == 1


def test_histogram_quantile_matches_snapshot():
    h = Histogram()
    for v in (5.0, 1.0, 3.0, 2.0, 4.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["p50"] == h.quantile(0.50) == 3.0
    assert snap["p90"] == h.quantile(0.90) == 5.0
    assert snap["p99"] == h.quantile(0.99) == 5.0
    assert snap["count"] == 5 and snap["max"] == 5.0
    empty = Histogram()
    assert empty.quantile(0.5) is None
    assert empty.snapshot()["p50"] is None
