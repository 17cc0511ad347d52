"""repro.dse.service: single-flight coalescing semantics, request codec
validation, the HTTP daemon end to end (sweep + adaptive over real
sockets), event-driven streaming (a round event must reach the client
while the server is still mid-run — no sleeps, gated on events), warm
repeats doing zero work, and the /metrics observability plane."""
import json
import threading
import time

import pytest

from repro.dse import SweepSpace
from repro.dse.service import (DSEService, MetricsRegistry, RequestError,
                               ServiceClient, ServiceError, SingleFlight,
                               parse_request, running_server)
from repro.dse.service.codec import records_json


# ------------------------------------------------------------ singleflight
def _spin_until(predicate, deadline_s=10.0):
    """Bounded spin on real shared state (not a sleep-based guess)."""
    deadline = time.monotonic() + deadline_s
    while not predicate():
        if time.monotonic() > deadline:
            raise AssertionError("condition never became true")


def test_singleflight_coalesces_concurrent_callers():
    """N concurrent callers of one key: the build runs once, every waiter
    receives the leader's value, counters account for all of them."""
    sf = SingleFlight()
    entered, release = threading.Event(), threading.Event()
    calls = []

    def build():
        calls.append(1)
        entered.set()
        assert release.wait(timeout=10)
        return "artifact"

    results = []

    def caller():
        results.append(sf.do("k", build))

    leader = threading.Thread(target=caller)
    leader.start()
    assert entered.wait(timeout=10)          # the build is now in flight
    waiters = [threading.Thread(target=caller) for _ in range(4)]
    for t in waiters:
        t.start()
    # all four must be *parked on the flight* before the leader finishes
    _spin_until(lambda: sf._flights["k"].waiters == 4)
    assert sf.inflight() == 1
    release.set()
    for t in [leader] + waiters:
        t.join(timeout=10)

    assert len(calls) == 1                   # one build for five callers
    assert [v for v, _ in results] == ["artifact"] * 5
    assert sorted(c for _, c in results) == [False] + [True] * 4
    assert sf.started == 1 and sf.coalesced == 4
    assert sf.inflight() == 0


def test_singleflight_error_propagates_but_is_not_cached():
    sf = SingleFlight()
    entered, release = threading.Event(), threading.Event()

    def boom():
        entered.set()
        assert release.wait(timeout=10)
        raise RuntimeError("build failed")

    errors = []

    def caller():
        try:
            sf.do("k", boom)
        except RuntimeError as exc:
            errors.append(str(exc))

    leader = threading.Thread(target=caller)
    leader.start()
    assert entered.wait(timeout=10)
    waiter = threading.Thread(target=caller)
    waiter.start()
    _spin_until(lambda: sf._flights["k"].waiters == 1)
    release.set()
    leader.join(timeout=10)
    waiter.join(timeout=10)
    assert errors == ["build failed"] * 2    # leader AND waiter both see it

    # the failure is not cached: the next call starts a fresh flight
    value, coalesced = sf.do("k", lambda: "recovered")
    assert (value, coalesced) == ("recovered", False)
    assert sf.started == 2


def test_singleflight_sequential_calls_each_run():
    """No caching across completed flights — that's the memo's job."""
    sf = SingleFlight()
    assert sf.do("k", lambda: 1) == (1, False)
    assert sf.do("k", lambda: 2) == (2, False)
    assert sf.started == 2 and sf.coalesced == 0


# ------------------------------------------------------------------- codec
def test_parse_request_defaults_and_space():
    req = parse_request({"workloads": ["NB"]})
    assert req["backend"] == "cim" and req["mode"] == "sweep"
    assert isinstance(req["space"], SweepSpace)
    assert len(req["space"]) == 1
    assert req["objectives"] == ("energy_improvement", "speedup")

    req = parse_request({"workloads": ["NB"], "techs": ["sram", "fefet"],
                         "cim_levels": ["L1_only", "both"]})
    assert len(req["space"]) == 4


@pytest.mark.parametrize("doc, fragment", [
    ({"workloads": ["nope"]}, "unknown workload"),
    ({}, "'workloads' is required"),
    ({"workloads": []}, "non-empty list"),
    ({"workloads": ["NB"], "backend": "quantum"}, "unknown backend"),
    ({"workloads": ["NB"], "mode": "exhaustive"}, "unknown mode"),
    ({"workloads": ["NB"], "backend": "tpu"}, "unknown arch"),
    ({"workloads": ["xlstm-125m"], "backend": "tpu",
      "techs": ["sram"]}, "CiM-only axes"),
    ({"workloads": ["NB"], "tpus": ["v5e"]}, "'tpus' is meaningless"),
    ({"workloads": ["xlstm-125m"], "backend": "tpu",
      "tpus": ["warp9"]}, "unknown TPU chip"),
    ({"workloads": ["NB"], "objectives": ["vibes"]}, "unknown objective"),
    ({"workloads": ["NB"], "max_rounds": -1}, "max_rounds"),
])
def test_parse_request_rejects(doc, fragment):
    with pytest.raises(RequestError, match=fragment):
        parse_request(doc)


def test_records_json_sanitizes_nonfinite():
    import dataclasses
    from repro.dse.results import SweepRecord
    fields = {f.name: (float("nan") if f.type == "float" else 0)
              for f in dataclasses.fields(SweepRecord)}
    fields.update(workload="NB", cache="32K+256K", cim_levels="L1",
                  tech="sram", cim_set="stt", host="default", backend="cim",
                  speedup=float("inf"), energy_improvement=2.0)
    (doc,) = records_json([SweepRecord(**fields)])
    assert doc["speedup"] is None                  # inf -> null
    assert doc["energy_improvement"] == 2.0
    json.dumps(doc, allow_nan=False)               # strict-JSON clean


# ----------------------------------------------------------------- metrics
def test_metrics_registry_snapshot():
    m = MetricsRegistry()
    m.counter("points.requested", by=3)
    m.counter("points.requested")
    m.gauge_inc("inflight")
    m.gauge_inc("inflight")
    m.gauge_dec("inflight")
    for v in (0.1, 0.2, 0.3):
        m.observe("latency_s.sweep", v)
    snap = m.snapshot()
    assert snap["points"]["requested"] == 4
    assert snap["inflight"] == 1
    hist = snap["latency_s"]["sweep"]
    assert hist["count"] == 3
    assert hist["max"] == pytest.approx(0.3)
    assert hist["p50"] == pytest.approx(0.2)
    assert m.counter_value("points.requested") == 4


# ------------------------------------------------------------- HTTP daemon
@pytest.fixture(scope="module")
def daemon():
    with running_server(max_workers=4) as (url, service):
        yield url, ServiceClient(url), service


def test_healthz_and_unknown_paths(daemon):
    url, client, _service = daemon
    health = client.healthz()
    assert health["status"] == "ok"
    assert health["backends"] == ["cim", "tpu"]
    with pytest.raises(ServiceError) as err:
        client._get_json("/nope")
    assert err.value.status == 404


def test_sweep_end_to_end(daemon):
    _url, client, _service = daemon
    events = list(client.stream({"workloads": ["NB"],
                                 "techs": ["sram", "fefet"]}))
    assert [e["event"] for e in events] == ["start", "result"]
    assert events[0]["n_points"] == 2
    reply = client.sweep(["NB"], techs=["sram", "fefet"])
    assert len(reply.records) == 2
    assert {r["tech"] for r in reply.records} == {"sram", "fefet"}
    assert all(r["energy_improvement"] > 0 for r in reply.records)
    assert 1 <= len(reply.frontier) <= 2


def test_bad_requests_are_400_not_streams(daemon):
    _url, client, _service = daemon
    for doc in ({"workloads": ["nope"]},
                {"workloads": ["NB"], "backend": "tpu",
                 "techs": ["sram"]},
                {"workloads": ["NB"], "objectives": ["vibes"]}):
        with pytest.raises(ServiceError) as err:
            list(client.stream(doc))
        assert err.value.status == 400

    # a body that isn't JSON at all is a 400 too, not a hung stream
    import http.client
    conn = http.client.HTTPConnection(client.host, client.port, timeout=30)
    try:
        conn.request("POST", "/v1/sweep", body=b"not json{",
                     headers={"Content-Type": "application/json"})
        assert conn.getresponse().status == 400
    finally:
        conn.close()


def test_warm_repeat_does_zero_work(daemon):
    """ISSUE 6 acceptance: a warm daemon answers a repeated exhaustive
    sweep with zero new trace builds (and zero new evaluations)."""
    _url, client, service = daemon
    req = dict(caches=["32K+256K", "64K+256K"], techs=["sram"])
    client.sweep(["NB"], **req)                        # warm it
    m1 = client.metrics()
    reply = client.sweep(["NB"], **req)                # repeat, warm
    m2 = client.metrics()
    assert len(reply.records) == 2
    assert (m2["service"]["points"]["evaluated"]
            == m1["service"]["points"]["evaluated"])
    assert (m2["cache"]["cim"]["layer1"]["builds"]
            == m1["cache"]["cim"]["layer1"]["builds"])
    assert (m2["service"]["points"]["memo_hits"]
            > m1["service"]["points"]["memo_hits"])


def test_concurrent_overlapping_sweeps_dedup(daemon):
    """Four concurrent identical requests on a cold workload: the daemon
    evaluates each unique SweepPoint.key exactly once."""
    url, client, _service = daemon
    m0 = client.metrics()["service"]["points"]
    barrier = threading.Barrier(4)
    failures = []

    def storm():
        local = ServiceClient(url)
        barrier.wait()
        try:
            reply = local.sweep(["LCS"], techs=["sram", "fefet"])
            assert len(reply.records) == 2
        except Exception as exc:  # noqa: BLE001 — surfaced below
            failures.append(exc)

    threads = [threading.Thread(target=storm) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not failures
    m1 = client.metrics()["service"]["points"]
    assert m1["requested"] - m0["requested"] == 8      # 4 clients x 2 points
    assert m1["evaluated"] - m0["evaluated"] == 2      # == unique keys
    saved = (m1["coalesced"] - m0["coalesced"]) + \
        (m1["memo_hits"] - m0["memo_hits"])
    assert saved == 6                                  # every duplicate


def test_metrics_snapshot_shape(daemon):
    _url, client, _service = daemon
    doc = client.metrics()
    assert doc["uptime_s"] >= 0
    assert doc["dedup_ratio"] is None or doc["dedup_ratio"] >= 1
    for backend in ("cim", "tpu"):
        for layer in ("layer1", "layer2"):
            stats = doc["cache"][backend][layer]
            assert set(stats) == {"builds", "hits", "hit_rate"}
    assert "store" not in doc                # no cache_dir on this daemon
    assert doc["service"]["requests"]["sweep"] >= 1
    assert doc["service"]["latency_s"]["sweep"]["count"] >= 1


def test_adaptive_end_to_end(daemon):
    _url, client, _service = daemon
    events = list(client.adaptive_events(
        ["NB"], caches=["32K+256K", "64K+256K"],
        cim_levels=["L1_only", "both"], max_rounds=4))
    kinds = [e["event"] for e in events]
    assert kinds[0] == "start" and kinds[-1] == "result"
    rounds = [e for e in events if e["event"] == "round"]
    assert rounds and [r["round"] for r in rounds] == list(range(len(rounds)))
    assert all("frontier" in r for r in rounds)
    assert events[-1]["n_records"] >= rounds[0]["n_priced"]


# -------------------------------------------------- event-driven streaming
def test_round_events_stream_while_server_still_running(monkeypatch):
    """The streaming guarantee, verified without sleeps: the client must
    receive round 0 while the server's generator is still *blocked* on an
    event only the client-side test releases.  If the server buffered the
    whole response, the first round could never arrive and the stub would
    time out into an in-band error instead."""
    import repro.dse.service.server as server_mod
    from repro.dse.adaptive import RoundEvent, RoundInfo
    from repro.dse.results import SweepResults

    gate = threading.Event()

    def make_info(n, stable):
        return RoundInfo(round=n, n_candidates=1, n_priced=1,
                         frontier_size=0, stable=stable, stats={},
                         elapsed_s=0.0)

    class StubAdaptive:
        def __init__(self, space, engine=None, objectives=None,
                     max_rounds=None):
            pass

        def run_iter(self):
            yield RoundEvent(info=make_info(0, False), frontier=[],
                             results=SweepResults(records=[]))
            if not gate.wait(timeout=30):
                raise RuntimeError("client never received round 0")
            yield RoundEvent(info=make_info(1, True), frontier=[],
                             results=SweepResults(records=[]))

    monkeypatch.setattr(server_mod, "AdaptiveDSE", StubAdaptive)
    with running_server() as (url, _service):
        events = ServiceClient(url).stream({"workloads": ["NB"],
                                            "mode": "adaptive"})
        assert next(events)["event"] == "start"
        first_round = next(events)           # server is parked on `gate`
        assert (first_round["event"], first_round["round"]) == ("round", 0)
        gate.set()                           # only now may round 1 exist
        rest = list(events)
        assert [(e["event"], e.get("round")) for e in rest] == \
            [("round", 1), ("result", None)]
        assert rest[-1]["n_rounds"] == 2


def test_midstream_failure_is_inband_error(monkeypatch):
    """Failures after the 200 commits travel as a terminal error event."""
    import repro.dse.service.server as server_mod

    class ExplodingAdaptive:
        def __init__(self, *a, **k):
            pass

        def run_iter(self):
            raise RuntimeError("pricing exploded")
            yield  # noqa: unreachable — makes this a generator

    monkeypatch.setattr(server_mod, "AdaptiveDSE", ExplodingAdaptive)
    with running_server() as (url, _service):
        events = ServiceClient(url).stream({"workloads": ["NB"],
                                            "mode": "adaptive"})
        assert next(events)["event"] == "start"
        with pytest.raises(ServiceError, match="pricing exploded"):
            list(events)


# ---------------------------------------------------- persistent store plane
def test_store_metrics_and_corrupt_drops_surface(tmp_path):
    """/metrics carries the store counters; a daemon restarted over a
    corrupted cache dir reports the drop (satellite: corrupt-drop counter
    surfaced end-to-end)."""
    with running_server(cache_dir=str(tmp_path)) as (url, _service):
        client = ServiceClient(url)
        client.sweep(["NB"])
        doc = client.metrics()
        assert doc["store"]["corrupt_drops"] == 0
        assert doc["store"]["store_writes"] >= 2

    (blob,) = (p for p in (tmp_path / "layer1").glob("*.npz")
               if ".flow" not in p.name)          # the trace artifact
    blob.write_bytes(b"bit rot")

    with running_server(cache_dir=str(tmp_path)) as (url, _service):
        client = ServiceClient(url)
        reply = client.sweep(["NB"])          # rebuilds through the rot
        assert len(reply.records) == 1
        doc = client.metrics()
        assert doc["store"]["corrupt_drops"] == 1
        assert doc["store"]["store_corrupt_drops"] == 1


def test_jax_backend_zero_recompiles(daemon):
    """ISSUE 7 satellite: under EVA_CIM_ACCEL=jax the daemon batches every
    geometry of a sweep into one replay launch, /metrics exposes the accel
    counters, and repeated sweeps — even through a COLD cache re-replaying
    the same shapes — add zero compiled specializations."""
    from repro.core import accel
    from repro.dse.engine import AnalysisCache
    from repro.dse.space import CacheOption

    _url, client, _service = daemon
    req = dict(caches=["32K+256K", "64K+256K", "64K+2M"], techs=["sram"])
    fallbacks = accel.fallbacks()
    with accel.use_backend("jax"):
        client.sweep(["KM"], **req)                    # cold: compiles
        m1 = client.metrics()
        assert m1["accel"]["backend"] == "jax"
        compiles = m1["accel"]["jit_compiles"]
        assert compiles > 0
        assert m1["cache"]["cim"]["replay_batches"] >= 1

        client.sweep(["KM"], **req)                    # warm repeat
        m2 = client.metrics()
        assert m2["accel"]["jit_compiles"] == compiles
        assert m2["accel"]["fallbacks"] == fallbacks   # stayed on jax
        assert (m2["cache"]["cim"]["replay_batches"]
                == m1["cache"]["cim"]["replay_batches"])

        # stronger than a memo hit: a fresh cache re-REPLAYS the sweep's
        # geometry batch and still reuses every compiled kernel
        fresh = AnalysisCache()
        fresh.replay_group("KM",
                           [CacheOption.of(n) for n in req["caches"]])
        assert fresh.replay_batches == 1
        assert accel.jit_compiles() == compiles
    m3 = client.metrics()
    assert m3["accel"]["backend"] == "numpy"           # override restored
