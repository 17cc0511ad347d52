"""A benchmark cell, found by name: its configuration, its traffic mix and
the sweeps that the mix drives.

A traffic mix (``bench/traffic/<mix>.json``) says what is cold and what is
warm before each sweep of the window:

``layer1``          ``"build"``: every sweep starts from an empty analysis
                    cache, so the trace VM, the replay, the IDG, the
                    selection and the pricing all run; ``"store"``: set-up
                    fills an on-disk analysis store with layer 1 (trace +
                    IDG flow) and every sweep loads it from there, with
                    layer 2 (selections) never stored, so each sweep still
                    selects and prices every point.  The store is filled
                    on the numpy path (``EVA_CIM_ACCEL=numpy``, whose
                    layer 1 is the jax path's, value for value): the
                    window never replays, and the device replay of the
                    fill would only lengthen set-up;
``profile``         ``lead_s`` / ``span_s``: when the traced run's profiler
                    starts after the window opens, and for how long.
"""
from __future__ import annotations

import json
import pathlib
import shutil
from typing import Dict, Tuple

ROOT = pathlib.Path(__file__).resolve().parents[1]
WORK = ROOT / ".bench_work"


def load_cell(name: str, root: pathlib.Path = ROOT) -> Tuple[Dict, Dict, Dict]:
    """``(cell entry, configuration, traffic mix)`` of the cell ``name``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def build_space(config: Dict):
    """The configuration's design space as the engine's ``SweepSpace``,
    and each cache geometry by name as plain level tuples."""
    from repro.core.cache import CacheConfig
    from repro.dse import SweepSpace
    from repro.dse.space import CacheOption

    geometries = {}
    for c in config["caches"]:
        opt = CacheOption.of(tuple(CacheConfig(*lv) for lv in c["levels"]))
        if opt.name != c["name"]:
            raise ValueError(f"cache {c['name']!r} is named {opt.name!r} "
                             f"by the engine")
        geometries[opt.name] = [tuple(lv) for lv in c["levels"]]
    space = SweepSpace(
        workloads=tuple(config["workloads"]),
        caches=tuple(tuple(CacheConfig(*lv) for lv in c["levels"])
                     for c in config["caches"]),
        cim_levels=tuple(tuple(lv) for lv in config["cim_levels"]),
        techs=tuple(config["techs"]), cim_sets=(config["cim_set"],),
        hosts=tuple(config["hosts"]))
    return space, geometries


class Sweeps:
    """Runs one sweep of the cell's space at a time, as the mix says."""

    def __init__(self, traffic: Dict, space, work: pathlib.Path = WORK):
        from repro.dse.store import AnalysisStore

        class Layer1Store(AnalysisStore):
            """Serves layer 1; never keeps a selection."""

            def load_layer2(self, *args, **kwargs):
                return None

            def save_layer2(self, *args, **kwargs):
                return None

        self.space = space
        self.store = None
        if traffic["layer1"] == "store":
            root = work / "store"
            shutil.rmtree(root, ignore_errors=True)
            self.store = Layer1Store(root)
        elif traffic["layer1"] != "build":
            raise ValueError(f"unknown layer1 mode {traffic['layer1']!r}")

    def fill(self) -> None:
        """Layer 1 of every (workload, geometry) of the space into the
        store, on the numpy path; nothing where the mix keeps no store."""
        import os
        from repro.dse import AnalysisCache

        if self.store is None:
            return
        cache = AnalysisCache(store=self.store)
        saved = os.environ.get("EVA_CIM_ACCEL")
        os.environ["EVA_CIM_ACCEL"] = "numpy"
        try:
            for key in dict.fromkeys((p.workload, p.cache)
                                     for p in self.space.points()):
                cache.trace_analysis(*key)
        finally:
            if saved is None:
                os.environ.pop("EVA_CIM_ACCEL")
            else:
                os.environ["EVA_CIM_ACCEL"] = saved

    def run(self):
        """One sweep on a fresh analysis cache: ``(cache, results)``."""
        from repro.dse import AnalysisCache, DSEEngine

        cache = AnalysisCache(store=self.store)
        # one pricing thread: the engine's default pool of 8 contends for
        # the GIL, adding run-to-run spread and no throughput
        engine = DSEEngine(cache=cache, executor="thread", max_workers=1)
        return cache, engine.run(self.space)

    def close(self) -> None:
        if self.store is not None:
            shutil.rmtree(self.store.root, ignore_errors=True)
