"""repro.core.sampling: spec codecs, plan construction, the windowed
trace machinery's byte-identity against the exact VM, estimator
unbiasedness (hypothesis property tests),
the degenerate full-coverage plan reproducing exact metrics bit-for-bit,
sampled sweep records through the engine/backend, and request-codec
validation of the ``sampling`` field."""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cache import L1_32K, L2_256K
from repro.core.offload import OffloadConfig, analyze_trace
from repro.core.profiler import profile_system
from repro.core.reshape import reshape
from repro.core.sampling import (SamplePlan, SampledStructural, SamplingSpec,
                                 build_plan, build_workload, estimate,
                                 sampled_report, sampled_structural,
                                 skim_program, trace_windows)
from repro.core.sampling.estimate import COMPONENTS
from repro.core.sampling.machines import SkimResult
from repro.core.trace import TraceLimits, attach_cache_results, \
    trace_structural
from repro.dse import CimBackend, DSEEngine, SweepSpace
from repro.dse.results import SweepRecord
from repro.dse.service import RequestError, parse_request

LEVELS = (L1_32K, L2_256K)
LIMITS = TraceLimits(max_instructions=1 << 62)
WL = "hmmer"                     # smallest/fastest registry kernel


def _exact_report(workload):
    fn, args = build_workload(workload)
    st_ = trace_structural(fn, *args, limits=LIMITS)
    tr = attach_cache_results(st_, LEVELS)
    analysis = analyze_trace(tr)
    result = analysis.select(OffloadConfig())
    return profile_system(tr, offload=result,
                          reshaped=reshape(analysis.trace, result))


# ----------------------------------------------------------------- spec
def test_spec_key_parse_dict_roundtrip():
    spec = SamplingSpec(mode="phase", interval=1024, budget=16, seed=3,
                        warmup=4096, target_ci=0.05, n_boot=50)
    assert spec.key() == "phase:i1024:b16:s3:w4096:t0.05:r50"
    assert SamplingSpec.parse(
        "phase:interval=1024,budget=16,seed=3,warmup=4096,"
        "target_ci=0.05,n_boot=50") == spec
    assert SamplingSpec.from_dict(spec.to_dict()) == spec
    # exact is the identity: no knobs in the key, default parse
    assert SamplingSpec().key() == "exact"
    assert SamplingSpec.parse("exact") == SamplingSpec()
    # defaults stay out of the key (cache identity must not churn)
    assert SamplingSpec(mode="stratified").key() == "stratified:i2048:b32:s0"


@pytest.mark.parametrize("bad", [
    dict(mode="simpoint"), dict(interval=32), dict(budget=0),
    dict(warmup=-1), dict(target_ci=1.0), dict(confidence=0.3),
    dict(n_boot=5)])
def test_spec_validation(bad):
    with pytest.raises(ValueError):
        SamplingSpec(**{"mode": "stratified", **bad})


def test_spec_parse_rejects_unknown_knob():
    with pytest.raises(ValueError):
        SamplingSpec.parse("phase:windows=4")
    with pytest.raises(ValueError):
        SamplingSpec.from_dict({"mode": "phase", "windows": 4})


# ----------------------------------------------------------------- plans
def _fake_skim(n_int, interval=64, rng=None):
    rng = rng or np.random.default_rng(0)
    feats = rng.uniform(0.0, 5.0, size=(n_int, 6))
    return SkimResult(features=feats, total_virtual=n_int * interval,
                      interval=interval)


def test_plan_full_coverage_degenerates():
    plan = build_plan(_fake_skim(8), SamplingSpec(mode="stratified",
                                                  budget=32))
    assert plan.full and plan.n_windows == 1
    assert plan.windows() == [(0, 8 * 64)]
    assert plan.weights().tolist() == [1.0]


@pytest.mark.parametrize("mode", ["stratified", "phase"])
def test_plan_weights_expand_to_population(mode):
    """Sum of expansion weights == interval count, picks are unique and
    sorted, every cluster is represented."""
    for seed in range(4):
        spec = SamplingSpec(mode=mode, budget=8, seed=seed)
        plan = build_plan(_fake_skim(40), spec)
        assert not plan.full
        assert plan.n_windows == 8
        assert plan.weights().sum() == pytest.approx(plan.n_intervals)
        idx = [p for p, _ in plan.picks]
        assert idx == sorted(idx) and len(set(idx)) == len(idx)
        sampled_clusters = {c for _, c in plan.picks}
        assert sampled_clusters == set(np.unique(plan.cluster_of))


# ------------------------------------------------------------- estimator
def test_estimator_identity_when_every_interval_sampled():
    """Weights of 1 over a full enumeration: totals are exact sums."""
    rng = np.random.default_rng(1)
    n = 12
    Y = rng.uniform(1.0, 2.0, size=(n, len(COMPONENTS)))
    plan = SamplePlan(interval=64, total_virtual=n * 64, mode="stratified",
                      cluster_of=np.arange(n), picks=tuple((i, i)
                                                           for i in range(n)))
    est = estimate(Y, plan, SamplingSpec(mode="stratified", n_boot=10))
    np.testing.assert_allclose(
        [est.totals[c] for c in COMPONENTS], Y.sum(0), rtol=1e-12)
    assert est.ci["energy_improvement"] == 0.0   # singletons: no variance


@settings(max_examples=8, deadline=None)
@given(st.integers(16, 48), st.integers(4, 10))
def test_estimator_unbiased_over_seeds(n_int, budget):
    """Property: the stratified expansion estimator's totals are unbiased —
    the seed-averaged estimate converges on the exact population total."""
    rng = np.random.default_rng(n_int * 101 + budget)
    Y = rng.uniform(1.0, 2.0, size=(n_int, len(COMPONENTS)))
    truth = Y.sum(0)
    acc = np.zeros(len(COMPONENTS))
    seeds = 48
    for seed in range(seeds):
        spec = SamplingSpec(mode="stratified", budget=budget, seed=seed,
                            n_boot=10)
        plan = build_plan(_fake_skim(n_int, rng=np.random.default_rng(7)),
                          spec)
        picked = Y[[p for p, _ in plan.picks]]
        est = estimate(picked, plan, spec)
        acc += [est.totals[c] for c in COMPONENTS]
    # MC error of the mean, not estimator bias: values in [1,2] keep the
    # per-seed relative spread small, so 48 seeds pin the mean to a few %
    np.testing.assert_allclose(acc / seeds, truth, rtol=0.04)


def test_estimator_rejects_shape_mismatch():
    plan = build_plan(_fake_skim(40), SamplingSpec(mode="stratified",
                                                   budget=8))
    with pytest.raises(ValueError):
        estimate(np.ones((3, len(COMPONENTS))), plan,
                 SamplingSpec(mode="stratified"))


# ----------------------------------------------- windowed-trace machinery
def test_full_window_trace_is_byte_identical():
    """One window covering the whole virtual stream must emit exactly the
    exact VM's rows — the foundation of exact-mode byte-identity."""
    fn, args = build_workload(WL)
    st_ = trace_structural(fn, *args, limits=LIMITS)
    skim = skim_program(fn, *args, interval=2048)
    wt = trace_windows(fn, *args, windows=[(0, skim.total_virtual)],
                       limits=LIMITS, expect_total=skim.total_virtual)
    assert wt.marks == [(0, 0, st_.columns.n)]
    a, b = st_.columns.to_arrays(), wt.structural.columns.to_arrays()
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_degenerate_plan_reproduces_exact_metrics():
    """budget >= n_intervals: the sampled pipeline is the identity."""
    rep = _exact_report(WL)
    est = sampled_report(WL, SamplingSpec(mode="stratified"), LEVELS,
                         OffloadConfig())
    assert est.n_windows == 1
    assert est.metrics["energy_improvement"] == pytest.approx(
        rep.energy_improvement, rel=1e-12)
    assert est.metrics["macr"] == pytest.approx(rep.macr, rel=1e-12)
    assert est.metrics["speedup"] == pytest.approx(rep.speedup, rel=1e-12)
    assert est.ci["energy_improvement"] == 0.0


def test_sampled_structural_interleaves_warmup():
    """Genuine sampling: warmup prefixes are traced but only measured
    windows are priced, and measured_marks() indexes the right rows."""
    spec = SamplingSpec(mode="stratified", interval=256, budget=4,
                        warmup=256, seed=1)
    ss = sampled_structural(WL, spec)
    assert not ss.plan.full and len(ss.plan.picks) == 4
    assert len(ss.measured) == 4 and len(ss.marks) > 4
    measured = ss.measured_marks()
    assert [m[0] for m in measured] == sorted(m[0] for m in measured)
    # genuine estimate lands in the exact report's neighborhood (cold
    # cache state bounds accuracy; the benchmark records the exact error)
    rep = _exact_report(WL)
    est = sampled_report(WL, spec, LEVELS, OffloadConfig())
    assert est.n_windows == 4
    assert est.metrics["energy_improvement"] == pytest.approx(
        rep.energy_improvement, rel=0.35)
    assert est.ci["energy_improvement"] >= 0.0


def test_sampled_structural_no_warmup_marks_all_measured():
    spec = SamplingSpec(mode="stratified", interval=256, budget=4,
                        warmup=0, seed=1)
    ss = sampled_structural(WL, spec)
    assert ss.measured == () and len(ss.marks) == 4
    assert ss.measured_marks() == ss.marks


# -------------------------------------------------------- records/backend
def _record(**over):
    base = dict(index=0, workload=WL, cache="32K+256K", cim_levels="L1+L2",
                tech="sram", cim_set="stt", host="A9-1GHz",
                energy_improvement=1.5, speedup=1.1, macr=0.4, macr_l1=0.3,
                base_energy_pj=10.0, cim_energy_pj=6.7, base_cycles=100.0,
                cim_cycles=90.0, base_runtime_ms=0.1, cim_runtime_ms=0.09,
                processor_ratio=0.5, cache_ratio=0.5, n_instructions=1000,
                n_mem_accesses=200, n_candidates=50, n_cim_ops=10)
    base.update(over)
    return SweepRecord(**base)


def test_sweep_record_to_dict_drops_sampling_when_exact():
    rec = _record()
    doc = rec.to_dict()
    assert "sampling" not in doc and "energy_improvement_ci" not in doc
    sampled = dataclasses.replace(rec, sampling="stratified:i64:b4:s0",
                                  energy_improvement_ci=0.01)
    doc = sampled.to_dict()
    assert doc["sampling"] == "stratified:i64:b4:s0"
    assert doc["energy_improvement_ci"] == 0.01


def test_backend_exact_spec_is_byte_identical_to_default():
    """SamplingSpec(mode='exact') through the engine: records equal the
    pre-sampling backend's field for field, with no sampling columns."""
    space = SweepSpace(workloads=(WL,), techs=("sram", "fefet"))
    base = DSEEngine(executor="serial").run(space).records
    exact = DSEEngine(executor="serial",
                      backend=CimBackend(sampling=SamplingSpec())
                      ).run(space).records
    assert [r.to_dict() for r in base] == [r.to_dict() for r in exact]
    assert all(r.sampling == "exact" for r in exact)


def test_backend_sampled_records_carry_key_and_ci():
    spec = SamplingSpec(mode="stratified", interval=256, budget=4,
                        warmup=256, seed=1)
    eng = DSEEngine(executor="serial", backend=CimBackend(sampling=spec))
    (rec,) = eng.run(SweepSpace(workloads=(WL,))).records
    assert rec.sampling == spec.key()
    doc = rec.to_dict()
    assert {"sampling", "energy_improvement_ci", "speedup_ci",
            "macr_ci"} <= doc.keys()
    assert rec.energy_improvement > 0 and rec.energy_improvement_ci >= 0
    # warm repeat prices from the memoized sampled artifacts
    (rec2,) = eng.run(SweepSpace(workloads=(WL,))).records
    assert rec2.to_dict() == doc


# ------------------------------------------------------------------ codec
def test_codec_accepts_sampling_string_and_dict():
    req = parse_request({"workloads": [WL],
                         "sampling": "stratified:interval=256,budget=4"})
    assert req["sampling"] == SamplingSpec(mode="stratified", interval=256,
                                           budget=4)
    req = parse_request({"workloads": [WL],
                         "sampling": {"mode": "phase", "seed": 2}})
    assert req["sampling"] == SamplingSpec(mode="phase", seed=2)
    # absent -> exact
    assert parse_request({"workloads": [WL]})["sampling"].is_exact


@pytest.mark.parametrize("doc,fragment", [
    ({"workloads": ["qwen1.5-0.5b"], "backend": "tpu",
      "sampling": "stratified"}, "tpu"),
    ({"workloads": [WL], "sampling": "simpoint"}, "sampling"),
    ({"workloads": [WL], "sampling": {"mode": "phase", "windows": 4}},
     "sampling"),
    ({"workloads": ["KM@64"]}, "sampling"),
    ({"workloads": ["KM@zero"], "sampling": "stratified"}, "scale"),
])
def test_codec_rejects_bad_sampling(doc, fragment):
    with pytest.raises(RequestError) as err:
        parse_request(doc)
    assert fragment in str(err.value)


def test_codec_scaled_workload_with_sampling_ok():
    req = parse_request({"workloads": ["KM@64"], "sampling": "stratified"})
    assert req["space"].workloads == ("KM@64",)
    assert req["sampling"].mode == "stratified"
