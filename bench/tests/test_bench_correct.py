"""``correct`` holds for a sound run and fails for each fault the cells can
have, and for the control (the reference in float32).

The runs drive the harness end to end on the CPU (the look for a chip is
skipped) over a small cell of the dse5 grid at ``build(1)``: NB (whose
addresses depend on its inputs) and KM (whose addresses the configuration
pins) under one cache geometry.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil
import types

import numpy as np
import pytest

from bench import check, reference, run

ROOT = pathlib.Path(__file__).resolve().parents[2]
WORKLOADS = ("NB", "KM")
# the build(1) streams' structure, and KM's addresses
STREAMS = {"NB": "db28fae99376948526647f9d9e8644b3b9137bfca80348f9df8d25836c2544e8",
           "KM": "86c2731f55f5dd18ba748d0f7228e0463e3f0ca4cc1869e040a9e2743d1dc570"}
KM_ADDRESSES = "40062cbcad86e6b438cb429e7660c4b7ca785d28dbcc8dd39edfad8657ea9d74"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A benchmark root holding one small cell, ``tiny.cold``."""
    root = tmp_path_factory.mktemp("bench_root")
    grid = json.loads((ROOT / "bench/configs/dse5-grid.json").read_text())
    grid.update(name="tiny", workloads=list(WORKLOADS), scales={},
                caches=grid["caches"][:1], cim_levels=[["L1", "L2"]],
                techs=["sram"], hosts=["A9-1GHz"],
                inputs={w: grid["inputs"][w] for w in WORKLOADS},
                streams=STREAMS, addresses={"KM": KM_ADDRESSES})
    (root / "bench/configs").mkdir(parents=True)
    (root / "bench/configs/tiny.json").write_text(json.dumps(grid))
    shutil.copytree(ROOT / "bench/traffic", root / "bench/traffic")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.cold", "config": "tiny",
                       "traffic": "cold", "chips": 1}],
        "per_layer": []}))
    return root


@pytest.fixture
def drive(tiny_root, monkeypatch):
    """Runs the cell once and hands back its result; the run's process
    settings (backend switch, compile cache) are restored afterwards."""
    import jax
    monkeypatch.setenv("EVA_CIM_ACCEL", "numpy")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_compilation_cache_max_size")}

    def go(seed=5):
        args = types.SimpleNamespace(workload="tiny.cold", seed=seed,
                                     seconds=0.0, trace=0)
        return run.run(args, require_tpu=False, root=tiny_root)

    yield go
    from jax.experimental.compilation_cache import compilation_cache
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _values(out):
    return {k: v["value"] for k, v in out["checks"].items()}


def test_sound_run_is_correct(drive):
    out = drive()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["attempted"] == len(WORKLOADS)
    assert list(out["checks"]) == list(check.NUMBERS)
    assert list(out)[-1] == "checks"


def test_stream_address_altered_fails(drive, monkeypatch):
    from repro.dse import engine
    real = engine.trace_structural

    def altered(fn, *args, **kwargs):
        st = real(fn, *args, **kwargs)
        addr = st.columns.addr
        first = int(np.flatnonzero(addr >= 0)[0])
        addr[first] += reference.LINE
        return st

    monkeypatch.setattr(engine, "trace_structural", altered)
    out = drive()
    assert not out["correct"]
    assert _values(out)["stream_mismatch"] == 1          # KM's; NB's is free


def test_replay_answer_altered_fails(drive, monkeypatch):
    from repro.core.accel import replay
    real = replay.replay_columns_batch

    def altered(addrs, writes, geometries):
        out = real(addrs, writes, geometries)
        level, hit, bank, mshr, counters = out[0]
        level = level.copy()
        level[0] = 3 if level[0] != 3 else 1
        return [(level, hit, bank, mshr, counters)] + list(out[1:])

    monkeypatch.setattr(replay, "replay_columns_batch", altered)
    out = drive()
    assert not out["correct"]
    assert _values(out)["replay_mismatch"] > 0


def test_selection_answer_altered_fails(drive, monkeypatch):
    from repro.core.accel import place
    real = place.place_candidates_jax

    def altered(part, ct, cfg):
        out = real(part, ct, cfg)
        out[0] = dataclasses.replace(out[0], moves=out[0].moves + 1)
        return out

    monkeypatch.setattr(place, "place_candidates_jax", altered)
    out = drive()
    assert not out["correct"]
    assert _values(out)["select_mismatch"] > 0


def test_priced_answer_altered_fails(drive, monkeypatch):
    from repro.dse.backends import CimBackend
    real = CimBackend.price

    def altered(self, point, analysis, selection, host):
        rec = real(self, point, analysis, selection, host)
        return dataclasses.replace(rec, speedup=rec.speedup * (1 + 1e-3))

    monkeypatch.setattr(CimBackend, "price", altered)
    out = drive()
    assert not out["correct"]
    assert _values(out)["price_gap"] > out["checks"]["price_gap"]["limit"]
    assert out["failed"] == out["attempted"]


def test_half_the_points_left_out_fails(drive, monkeypatch):
    from repro.dse import DSEEngine
    real = DSEEngine.run

    def half(self, space):
        res = real(self, space)
        return dataclasses.replace(res, records=res.records[::2])

    monkeypatch.setattr(DSEEngine, "run", half)
    out = drive()
    assert not out["correct"]
    assert _values(out)["points_missing"] > 0


def _tiny_reference(tiny_root, dtype=float):
    from bench import cell
    from bench.inputs import seeded_workloads
    from repro.core.trace import trace_structural
    from repro.workloads import build

    _, config, _ = cell.load_cell("tiny.cold", tiny_root)
    space, geometries = cell.build_space(config)
    with seeded_workloads(config["inputs"], 5, config.get("scales")):
        streams = {}
        for w in WORKLOADS:
            fn, args = build(w)
            streams[w] = reference.stream_of(
                trace_structural(fn, *args).columns)
    return config, space, streams, geometries


def test_control_float32_reference_fails(tiny_root):
    """The reference computed one precision below the configuration's,
    put in the program's place, does not pass."""
    config, space, streams, geometries = _tiny_reference(tiny_root)
    ref64 = check.Reference(streams, geometries, config["cim_set"])
    ref32 = check.Reference(streams, geometries, config["cim_set"],
                            dtype=np.float32)
    sweep = check.records_only(check.reference_records(space, ref32))
    numbers = check.compare([sweep], space, config, ref64)
    assert numbers["price_gap"] > config["limits"]["price_gap"]


def test_records_of_every_sweep_are_compared(tiny_root):
    """A sweep that keeps only its records is still held to the
    reference: one priced answer altered in the last sweep fails."""
    config, space, streams, geometries = _tiny_reference(tiny_root)
    ref = check.Reference(streams, geometries, config["cim_set"])
    sound = check.reference_records(space, ref)
    altered = list(sound)
    altered[-1] = types.SimpleNamespace(**dict(
        vars(sound[-1]), cim_cycles=sound[-1].cim_cycles * (1 + 1e-3)))
    sweeps = [check.records_only(sound), check.records_only(altered)]
    numbers = check.compare(sweeps, space, config, ref)
    assert numbers["price_gap"] > config["limits"]["price_gap"]
    assert numbers["failed_points"] == 1
    assert check.compare(sweeps[:1], space, config, ref)["price_gap"] == 0
