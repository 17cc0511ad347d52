"""The benchmark harness finds its pieces by name and refuses to measure
off the chip."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import cell, peaks, run

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cells_find_their_config_and_traffic(name):
    entry, config, traffic = cell.load_cell(name)
    assert entry["name"] == name
    assert config["name"] == entry["config"]
    assert traffic["layer1"] in ("build", "store")
    space, geometries = cell.build_space(config)
    assert len(space) == (len(config["workloads"]) * len(config["caches"])
                          * len(config["cim_levels"]) * len(config["techs"])
                          * len(config["hosts"]))
    assert sorted(geometries) == sorted(c["name"] for c in config["caches"])
    assert set(config["inputs"]) == set(config["workloads"])


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        cell.load_cell("no.such.cell")


def test_every_per_layer_metric_has_a_reader():
    names = [m["name"] for m in BENCH["per_layer"]]
    readers = run.metric_readers(names)
    assert sorted(readers) == sorted(names)
    assert all(callable(r) for r in readers.values())


def test_config_files_are_the_benchmarks():
    for c in BENCH["configs"]:
        doc = json.loads((ROOT / c["file"]).read_text())
        assert doc["name"] == c["name"]
        assert doc["reduced"] == c["reduced"]


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.peak("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_device_check_refuses_the_cpu():
    with pytest.raises(SystemExit) as e:
        run.devices_or_exit(1)
    assert e.value.code == 2


def _bench(cwd: pathlib.Path, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_off_the_chip_prints_no_result():
    proc = _bench(ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "not a TPU" in proc.stderr


def test_run_from_the_benchmark_files_alone_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bench(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
