"""The reductions from spans and device traces to metrics, on hand-built
inputs whose answers are worked out by hand."""
from __future__ import annotations

import pytest

from bench import devtrace, kernels, measure, spans
from bench.peaks import peak


def _span(sid, name, ts, dur, parent=None):
    return {"span_id": sid, "parent_id": parent, "name": name,
            "ts_ns": ts, "dur_ns": dur, "attrs": {}}


def test_union_of_intervals():
    assert spans.union_ns([(0, 10), (5, 15), (20, 25), (24, 30)]) == 25
    assert spans.union_ns([]) == 0


def test_self_time_unites_overlapping_children():
    # run 0..100; two pool threads price 10..60 and 40..90 (union 80);
    # one child of the first prices 20..30
    recs = [_span("r", "dse.run", 0, 100),
            _span("a", "backend.evaluate", 10, 50, "r"),
            _span("b", "backend.evaluate", 40, 50, "r"),
            _span("c", "backend.price", 20, 10, "a")]
    got = spans.self_times(recs)
    assert got["dse.run"] == pytest.approx(20e-9)
    assert got["backend.evaluate"] == pytest.approx((40 + 50) * 1e-9)
    assert got["backend.price"] == pytest.approx(10e-9)


def _trace():
    # one chip; ops busy over 0-10, 5-20 (overlap), 30-40, 60-100 (cut
    # at the 90 ns window end); one replay module wholly inside the
    # window (0-20) and one placement module running past it (60-100)
    ops = [("fusion.1", 0, 10), ("fusion.2", 5, 15), ("while", 30, 10),
           ("sort", 60, 40)]
    modules = [("jit_geom(1)", 0, 20), ("jit_kernel(2)", 60, 40),
               ("jit_other", 30, 10)]
    host = [("engine.warm", 0, 50, 0),
            ("cache.replay_batch|KM", 0, 25, 0),
            ("accel.replay_batch", 1, 22, 0),
            ("cache.select|BFS", 26, 60, 1)]
    return {"chips": {"/device:TPU:0": {"XLA Ops": ops,
                                        "XLA Modules": modules}},
            "host": host}


def test_device_reduction_by_hand():
    got = devtrace.reduce(_trace(), (0, 90),
                          {"replay": "jit_geom", "place": "jit_kernel"})
    # busy: [0, 20] + [30, 40] + [60, 90] = 60 ns of a 90 ns window
    assert got["busy_s"] == pytest.approx(60e-9)
    assert got["window_s"] == pytest.approx(90e-9)
    # the replay launch is attributed to the workload span on its thread;
    # the placement launch ends after the window and is left out
    assert got["launches"]["replay"] == [("cache.replay_batch|KM",
                                          pytest.approx(20e-9))]
    assert got["launches"]["place"] == []
    assert got["device_ops"][0] == ["sort", pytest.approx(30e-9)]
    # gaps 20-30 and 40-60; the longest is labelled by the span open at
    # its midpoint (50: cache.select|BFS opened last)
    assert got["idle_gaps"] == [["cache.select|BFS", pytest.approx(20e-9)],
                                ["engine.warm", pytest.approx(10e-9)]]


def test_reduction_refuses_a_trace_without_a_device():
    with pytest.raises(ValueError):
        devtrace.reduce({"chips": {}, "host": []}, (0, 1), {})


def test_kernel_costs_by_hand():
    geo = [("L1", 32768, 4, 4, 8), ("L2", 262144, 8, 4, 8)]
    ops, nbytes = kernels.replay_cost(1000, [geo, geo])
    # per access per geometry: L1 2*(4*9+8*8)=200, L2 2*(8*9+8*8)=272,
    # outputs 9; inputs 5 per access once
    assert nbytes == 1000 * (2 * (200 + 272 + 9)) + 1000 * 5
    assert ops == 1000 * 2 * ((4 + 8) + (8 + 8))
    ops, nbytes = kernels.place_cost(n_leaf=100, n_access=8, n_seg=10)
    assert nbytes == 8 * 100 + 12 * 8 + 12 * 10
    assert ops == 3 * 100 + 8 * 3 + 8


def test_roofline_share_by_hand():
    p = peak("TPU v5 lite")
    # 819 kB at 819 GB/s is 1 us; measured 4 us -> 25 %, HBM-bound
    share, bound = kernels.roofline_share([(1.0, 819e3)], 4e-6, p)
    assert share == pytest.approx(25.0)
    assert bound == "hbm"
    share, bound = kernels.roofline_share([(197e6, 0.0)], 2e-6, p)
    assert share == pytest.approx(50.0)
    assert bound == "compute"


def test_measurements_feed_the_readers():
    dev = devtrace.reduce(_trace(), (0, 90),
                          {"replay": "jit_geom", "place": "jit_kernel"})
    geo = [("L1", 32768, 4, 4, 8)]
    replay = _span("a", "accel.replay_batch", 1_000, 20_000, parent="c")
    replay["attrs"] = {"n_accesses": 100}
    cache = _span("c", "cache.replay_batch", 0, 30_000, parent="r")
    cache["attrs"] = {"workload": "KM"}
    m = measure.Measurements(
        [_span("r", "dse.run", 0, 4_000_000), cache, replay], 2, dev,
        peak("TPU v5 lite"), {"KM": (100, [geo])}, {"KM": (1, 1, 1)})
    assert m.ms_per_point("dse.run") == pytest.approx(
        (4_000_000 - 30_000) / 1e6 / 2)
    assert m.ms_per_point("cache.idg") is None
    # the replay is timed by its span: 20 us over 128 padded steps
    assert m.replays == [("KM", pytest.approx(20e-6), 100)]
    assert measure.replay_steps(m) == 128
    ops, nbytes = kernels.replay_cost(100, [geo])
    assert m.roofline("replay") == pytest.approx(
        100 * (nbytes / 819e9) / 20e-6)
    assert m.roofline("place") is None


def test_profile_window_starts_when_the_device_is_traced(tmp_path):
    """The profiled window on the trace's clock opens when ``start_trace``
    returns, not at the trace's zero, and spans the profile's length."""
    from bench.run import Profile

    prof = Profile(tmp_path / "profile", lead_s=0.0, span_s=0.05)
    prof.start()
    prof.join()
    lo, hi = prof.window_ns
    assert 0 < lo < hi
    assert hi - lo >= 50_000_000
    assert list((tmp_path / "profile").rglob("*.xplane.pb"))
