"""The reductions from spans and device traces to metrics, on hand-built
inputs whose answers are worked out by hand."""
from __future__ import annotations

import pytest

from bench import devtrace, kernels, measure, spans, xspace
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


def _scoped_trace():
    # a while op (0-50) under "moe" holding two body ops under it (10-20,
    # 30-45); a backward op under "moe" (60-70); an attention op (50-60);
    # an op with no path (70-80); a "moe" op past the window end (95-120);
    # two custom calls, one wholly inside the window and one past its end
    ops = [("%while.3 = (s32[]) while(...)", 0, 50, "jit(step)/jvp(moe)/while"),
           ("%fusion.1 = f32[8]", 10, 10,
            "jit(step)/jvp(moe)/while/body/closed_call/dot_general"),
           ("%fusion.2 = f32[8]", 30, 15,
            "jit(step)/jvp(moe)/while/body/tanh"),
           ("%fusion.3 = f32[8]", 50, 10, "jit(step)/jvp(attn)/dot_general"),
           ("%fusion.4 = f32[8]", 60, 10,
            "jit(step)/transpose(jvp(moe))/dot_general"),
           ("%copy.5 = f32[8]", 70, 10, None),
           ("%fusion.6 = f32[8]", 95, 25, "jit(step)/jvp(moe)/add"),
           ("%flash.7 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p), "
            "custom_call_target=\"tpu_custom_call\"", 80, 5,
            "jit(step)/attn/flash"),
           ("%flash.8 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p), "
            "custom_call_target=\"tpu_custom_call\"", 92, 10,
            "jit(step)/attn/flash")]
    return {"chips": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": []}},
            "host": []}


def test_scope_time_is_the_union_of_its_ops_in_the_window():
    got = devtrace.reduce(_scoped_trace(), (0, 100), {},
                          scopes=("moe", "attn", "none"),
                          op_prefixes=("flash", "fusion"))
    # moe: the while and its body ops once (0-50), the backward op
    # (60-70) and the cut op past the window (95-100); attn: 50-60 and
    # the flash calls 80-85 and 92-100, cut
    assert got["scopes"]["moe"] == pytest.approx(65e-9)
    assert got["scopes"]["attn"] == pytest.approx(23e-9)
    # a scope with no op in the window reads nothing, not 0
    assert "none" not in got["scopes"]
    # busy 0-85 and 92-100: the op with no path counts there alone
    assert got["busy_s"] == pytest.approx(93e-9)
    # only the custom call wholly inside the window, without its
    # attributes; fusions are no custom calls
    assert got["ops"] == {"flash": [[pytest.approx(5e-9),
                                     "%flash.7 = f32[8,128]{1,0} "
                                     "custom-call(f32[8,128]{1,0} %p)"]],
                          "fusion": []}
    assert got["top_paths"][0] == ["%while.3 = (s32[]) while(...)",
                                   "jit(step)/jvp(moe)/while"]


def test_a_scope_is_a_whole_part_of_the_path():
    assert devtrace.in_scope("jit(f)/transpose(jvp(moe))/dot", "moe")
    assert devtrace.in_scope("jit(f)/eva_cim.place/sort", "eva_cim.place")
    assert not devtrace.in_scope("jit(f)/moe_router/dot", "moe")
    assert not devtrace.in_scope("jit(f)/jvp(attn)/dot", "jit")
    assert not devtrace.in_scope(None, "moe")


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


def test_traced_run_whose_profile_caught_no_device(tmp_path):
    """A profile with no device plane (the CPU's) gives no device numbers,
    and the readers of the program's spans still read: the replay's
    roofline takes its sizes from the observed traces, not the profile."""
    import numpy as np
    from bench import run
    from bench.kinds import sweep

    prof = run.Profile(tmp_path / "profile", lead_s=0.0, span_s=0.05)
    prof.start()
    prof.join()
    assert run.device_trace(prof, measure.SPAN_NAMES,
                            measure.KERNEL_MODULES) is None
    assert not (tmp_path / "profile").exists()

    geo = {"32K-256K": [("L1", 32768, 4, 4, 8)]}
    replay = _span("a", "accel.replay_batch", 1_000, 20_000, parent="c")
    replay["attrs"] = {"n_accesses": 100}
    cache = _span("c", "cache.replay_batch", 0, 30_000, parent="r")
    cache["attrs"] = {"workload": "KM"}
    observed = {"traces": {("KM", "32K-256K"): (np.zeros(100),)},
                "selections": {}}
    m = sweep.measurements([_span("r", "dse.run", 0, 4_000_000), cache,
                            replay], 2, None, observed, None, geo,
                           "TPU v5 lite")
    readers = run.metric_readers(("replay_roofline", "replay_us_per_step",
                                  "place_roofline", "device_idle_share"))
    ops, nbytes = kernels.replay_cost(100, list(geo.values()))
    assert readers["replay_roofline"](m) == pytest.approx(
        100 * (nbytes / 819e9) / 20e-6)
    assert readers["replay_us_per_step"](m) == pytest.approx(20e-6 / 128 * 1e6)
    assert readers["place_roofline"](m) is None
    assert readers["device_idle_share"](m) is None
    assert m.busy_s is None and m.breakdown is None


def test_roofline_of_a_workload_with_no_size_is_none():
    replay = _span("a", "accel.replay_batch", 1_000, 20_000, parent="c")
    replay["attrs"] = {"n_accesses": 100}
    cache = _span("c", "cache.replay_batch", 0, 30_000, parent="r")
    cache["attrs"] = {"workload": "KM"}
    m = measure.Measurements([_span("r", "dse.run", 0, 4_000_000), cache,
                              replay], 2, None, peak("TPU v5 lite"), {}, {})
    assert m.roofline("replay") is None


def test_device_trace_reads_a_device_plane_with_name_paths(tmp_path,
                                                           monkeypatch):
    """``run.device_trace`` over a profile that holds a device plane: its
    ops carry name paths beside their times, its modules none."""
    from bench import run

    prof = run.Profile(tmp_path / "profile", lead_s=0.0, span_s=0.0)
    prof.path.mkdir()
    (prof.path / "host.xplane.pb").write_bytes(b"")
    prof.window_ns = (0, 100)
    ev = _scoped_trace()
    ev["chips"]["/device:TPU:0"]["XLA Modules"] = [
        ("jit_step(1)", 0, 90)]
    ev["lines"] = {"/device:TPU:0": ["XLA Modules", "XLA Ops"]}
    monkeypatch.setattr(devtrace, "events_of", lambda path, names: ev)
    got = run.device_trace(prof, (), {}, ("moe",), ("flash",))
    assert got["scopes"]["moe"] == pytest.approx(65e-9)
    assert got["busy_s"] == pytest.approx(93e-9)
    assert not prof.path.exists()


def test_device_trace_reads_the_events_of_a_real_profile(tmp_path,
                                                         monkeypatch):
    """``run.device_trace`` over a profile the profiler wrote, its ops
    read as the CPU client's thread line stands in for a chip's ``XLA
    Ops``: each op finds its name path in its module's HLO, which the
    profile keeps, and a named scope's ops count under it."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from bench import run

    @jax.jit
    def f(x):
        with jax.named_scope("probe_scope"):
            x = jnp.sin(x) @ x
        return jnp.cos(x)

    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()
    prof = run.Profile(tmp_path / "profile", lead_s=0.0, span_s=0.3)
    prof.start()
    while prof.window_ns is None:
        f(x).block_until_ready()
    prof.join()
    pd = ProfileData.from_file(str(next(prof.path.rglob("*.xplane.pb"))))
    client = [line.name for plane in pd.planes
              if plane.name == devtrace.HOST_PLANE for line in plane.lines
              if line.name.startswith("tf_XLAPjRtCpuClient")]
    if not client:
        pytest.skip("this jaxlib's CPU profile has no client thread line")
    monkeypatch.setattr(devtrace, "DEVICE_PREFIX", devtrace.HOST_PLANE)
    monkeypatch.setattr(devtrace, "OPS_LINE", client[0])
    got = run.device_trace(prof, (), {}, ("probe_scope",))
    paths = [p for _, p in got["top_paths"] if p is not None]
    assert got["busy_s"] > 0 and paths
    assert any(devtrace.in_scope(p, "probe_scope") for p in paths)
    assert 0 < got["scopes"]["probe_scope"] <= got["busy_s"]
    assert not prof.path.exists()


def _pb(field, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(value)) + value


def _hlo(instructions):
    comp = b"".join(_pb(2, _pb(1, name) + (_pb(7, _pb(2, path)) if path
                                          else b""))
                    for name, path in instructions)
    return _pb(1, _pb(1, "m") + _pb(3, _pb(1, "entry") + comp))


def _xspace(modules):
    """An XSpace whose metadata plane holds each module's HloProto under
    the stat ``Hlo Proto`` (stat metadata id 7), after a device plane."""
    entries = b"".join(
        _pb(4, _pb(1, i + 1) + _pb(2, _pb(1, i + 1) + _pb(2, name)
                                   + _pb(5, _pb(1, 7) + _pb(6, _hlo(ins)))))
        for i, (name, ins) in enumerate(modules.items()))
    meta = (_pb(1, 9) + _pb(2, xspace.METADATA_PLANE) + entries
            + _pb(5, _pb(1, 7) + _pb(2, _pb(1, 7) + _pb(2, "Hlo Proto"))))
    device = _pb(2, "/device:TPU:0") + _pb(3, _pb(2, "XLA Ops"))
    return _pb(1, device) + _pb(1, meta)


def test_op_paths_come_from_the_module_that_ran_the_op():
    data = _xspace({"jit_step(3)": [("fusion.1", "jit(step)/jvp(moe)/dot"),
                                    ("copy.2", None),
                                    ("while.4", "jit(step)/while")],
                    "jit_init(5)": [("fusion.1", "jit(init)/attn/add")]})
    assert set(xspace.hlo_modules(data)) == {"jit_step(3)", "jit_init(5)"}
    find = xspace.path_finder(data)
    event = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    assert find("jit_step(3)", event) == "jit(step)/jvp(moe)/dot"
    assert find("jit_init(5)", event) == "jit(init)/attn/add"
    # with no module known, only an instruction of one module finds it
    assert find(None, event) is None
    assert find(None, "%while.4 = (s32[]) while(...)") == "jit(step)/while"
    assert find("jit_step(3)", "%copy.2 = f32[8]{0} copy(...)") is None
    # a module left out is not read
    only = xspace.path_finder(data, lambda m: m.startswith("jit_init"))
    assert only(None, event) == "jit(init)/attn/add"
