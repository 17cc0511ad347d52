"""The comparison that decides ``correct``.

After the window, every sweep it ran is compared with the plain reference
(:mod:`bench.reference`): the records of every sweep, and the stream, the
replay and the selections of one sweep drawn from the seed.  There are
five numbers, each against its limit from the configuration file:

``points_missing``   points of the space that a sweep did not answer, or
                     answered out of order or twice (exact: limit 0);
``stream_mismatch``  workloads whose committed instruction stream is not
                     the one the configuration pins: its structure for
                     every workload but those whose length depends on their
                     data, and its addresses too for the workloads whose
                     addresses do not depend on their data (exact: limit 0);
``replay_mismatch``  accesses whose level, first-level hit, bank or MSHR
                     merge differs, plus differing hit/miss/writeback/DRAM
                     counters, over every geometry (exact: limit 0);
``select_mismatch``  offloading candidates that differ in any field, plus
                     removed host instructions that differ, over every
                     (workload, cache, CiM levels) selection (exact:
                     limit 0);
``price_gap``        the largest relative gap of any numeric field of any
                     record from the reference's value.
"""
from __future__ import annotations

import hashlib
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench import reference as ref

CAND_FIELDS = ("root_seq", "op_seqs", "op_classes", "load_seqs", "store_seqs",
               "level", "bank", "moves", "internal_edges", "added_loads",
               "memval_leaves", "dram_fills")
PRICE_FIELDS = ("energy_improvement", "speedup", "macr", "macr_l1",
                "base_energy_pj", "cim_energy_pj", "base_cycles",
                "cim_cycles", "base_runtime_ms", "cim_runtime_ms",
                "processor_ratio", "cache_ratio", "n_instructions",
                "n_mem_accesses", "n_candidates", "n_cim_ops")
NUMBERS = ("points_missing", "stream_mismatch", "replay_mismatch",
           "select_mismatch", "price_gap")


def stream_fingerprint(columns) -> str:
    """Hash of the stream's data-independent structure: ops, units,
    destination registers and the register operands of every
    instruction (addresses and immediates are left out)."""
    h = hashlib.sha256()
    reg = columns.src_tag == ref.SRC_REG
    for a in (columns.op, columns.unit, columns.dst, columns.src_off,
              columns.src_tag, np.where(reg, columns.src_val, 0)):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def address_fingerprint(columns) -> str:
    """Hash of the address of every instruction (-1 where it has none)."""
    return hashlib.sha256(np.ascontiguousarray(columns.addr).tobytes()
                          ).hexdigest()


def observe(cache, space, records, with_streams: bool = False) -> Dict:
    """What one sweep produced, read back from its analysis cache after
    the window: replay columns and counters per (workload, geometry),
    selections per (workload, geometry, CiM levels), each workload's
    stream and address fingerprints (and, ``with_streams``, the stream),
    and its records."""
    traces, selections, streams = {}, {}, {}
    for p in space.points():
        tkey = (p.workload, p.cache.name)
        if tkey not in traces:
            tr = cache.trace(p.workload, p.cache)
            ct = tr.trace
            mem = np.flatnonzero(ct.mem_mask)
            traces[tkey] = (ct.level[mem].copy(), ct.hit[mem].copy(),
                            ct.bank[mem].copy(), ct.mshr[mem].copy(),
                            dict(tr.cache.counters()))
            if p.workload not in streams:
                streams[p.workload] = (
                    (stream_fingerprint(ct), address_fingerprint(ct)),
                    ref.stream_of(ct) if with_streams else None)
        skey = (p.workload, p.cache.name, p.cim_levels)
        if skey not in selections:
            result, _ = cache.offload(p.workload, p.cache,
                                      p.offload_config())
            cands = [tuple(_plain(getattr(c, f)) for f in CAND_FIELDS)
                     for c in result.candidates]
            selections[skey] = (cands, set(result.claimed))
    return {"traces": traces, "selections": selections,
            "streams": streams, "records": list(records)}


def records_only(records) -> Dict:
    """A sweep whose records alone are compared."""
    return {"traces": {}, "selections": {}, "streams": {},
            "records": list(records)}


def _plain(v):
    return tuple(v) if isinstance(v, list) else v


class Reference:
    """The reference's answers for one cell, computed once per key."""

    def __init__(self, streams: Dict[str, Dict], caches: Dict[str, list],
                 cim_set: str, dtype=float):
        self.streams = streams
        self.caches = caches
        self.cim_set = ref.CIM_SETS[cim_set]
        self.F = dtype
        self._replay, self._flow, self._select, self._price = {}, {}, {}, {}
        self._prep: Dict[Tuple, dict] = {}
        self._counts: Dict[Tuple, dict] = {}
        self._base: Dict[Tuple, dict] = {}

    def replay(self, workload: str, cache: str):
        key = (workload, cache)
        if key not in self._replay:
            st = self.streams[workload]
            mem = [i for i, o in enumerate(st["op"]) if o in ("load", "store")]
            rows, counters = ref.replay([st["addr"][i] for i in mem],
                                        [st["op"][i] == "store" for i in mem],
                                        self.caches[cache])
            level = {s: r[0] for s, r in zip(mem, rows)}
            bank = {s: r[2] for s, r in zip(mem, rows)}
            columns = (np.asarray([ref.LEVELS.index(r[0]) for r in rows]),
                       np.asarray([int(r[1]) for r in rows]),
                       np.asarray([r[2] for r in rows]),
                       np.asarray([bool(r[3]) for r in rows]))
            self._replay[key] = (columns, counters, level, bank)
        return self._replay[key]

    def select(self, workload: str, cache: str, levels: Tuple[str, ...]):
        key = (workload, cache, levels)
        if key not in self._select:
            if workload not in self._flow:
                self._flow[workload] = ref.Flow(self.streams[workload])
            _, _, level, bank = self.replay(workload, cache)
            self._select[key] = ref.select(self.streams[workload],
                                           self._flow[workload], level, bank,
                                           self.cim_set, levels)
        return self._select[key]

    def price(self, workload: str, cache: str, levels: Tuple[str, ...],
              tech: str, host: str):
        key = (workload, cache, levels, tech, host)
        if key not in self._price:
            cands, claimed = self.select(workload, cache, levels)
            _, _, level, _ = self.replay(workload, cache)
            memo = self._base.setdefault((workload, cache, tech, host), {})
            if (workload, cache) not in self._prep:
                self._prep[workload, cache] = ref.prepare(
                    self.streams[workload], level)
            if (workload, cache, levels) not in self._counts:
                self._counts[workload, cache, levels] = ref.tally(cands,
                                                                  level)
            self._price[key] = ref.price(
                self.streams[workload], level, cands, claimed,
                self.caches[cache], tech, host, self.F, memo,
                self._prep[workload, cache],
                self._counts[workload, cache, levels])
        return self._price[key]


def _gap(a: float, b: float) -> float:
    a, b = float(a), float(b)
    if a == b:
        return 0.0
    if not (np.isfinite(a) and np.isfinite(b)):
        return float("inf")
    return abs(a - b) / max(abs(a), abs(b))


def compare(sweeps: Sequence[Dict], space, config: Dict,
            reference: Reference) -> Dict[str, float]:
    """The five numbers over every sweep of the window, and
    ``failed_points``: the points missing from a sweep or answered with a
    replay, a selection or a price that does not hold.  ``config`` gives
    the stream pins and the price limit."""
    pins, addr_pins = config["streams"], config["addresses"]
    price_limit = config["limits"]["price_gap"]
    want = [(p.index, p.workload, p.cache.name, "+".join(p.cim_levels),
             p.tech, p.cim_set, p.host.name) for p in space.points()]
    known = set(want)
    levels_of = {"+".join(p.cim_levels): p.cim_levels
                 for p in space.points()}
    out = dict.fromkeys(NUMBERS + ("failed_points",), 0)
    out["price_gap"] = 0.0
    for sw in sweeps:
        got = [(r.index, r.workload, r.cache, r.cim_levels, r.tech,
                r.cim_set, r.host) for r in sw["records"]]
        if got != want:
            wrong = len(set(want) ^ set(got)) + len(got) - len(set(got))
            out["points_missing"] += max(1, wrong)     # 1: out of order
            out["failed_points"] += len(set(want) - set(got))
        bad_keys = set()
        for w, ((fp, addr_fp), _) in sw["streams"].items():
            out["stream_mismatch"] += int(pins.get(w, fp) != fp
                                          or addr_pins.get(w, addr_fp)
                                          != addr_fp)
        for (w, c), (lv, hit, bank, mshr, counters) in sw["traces"].items():
            (r_lv, r_hit, r_bank, r_mshr), ref_counters, _, _ = \
                reference.replay(w, c)
            if len(r_lv) != len(lv):
                wrong = max(len(r_lv), len(lv))
            else:
                wrong = int(np.count_nonzero(
                    (r_lv != lv) | (r_hit != hit) | (r_bank != bank)
                    | (r_mshr != mshr)))
            wrong += sum(1 for k in set(ref_counters) | set(counters)
                         if ref_counters.get(k) != counters.get(k))
            out["replay_mismatch"] += wrong
            if wrong:
                bad_keys.add((w, c))
        for (w, c, levels), (cands, claimed) in sw["selections"].items():
            r_cands, r_claimed = reference.select(w, c, levels)
            r_rows = [tuple(_plain(rc[f]) for f in CAND_FIELDS)
                      for rc in r_cands]
            wrong = sum(1 for a, b in zip(cands, r_rows) if a != b) \
                + abs(len(cands) - len(r_rows)) + len(claimed ^ r_claimed)
            out["select_mismatch"] += wrong
            if wrong:
                bad_keys.add((w, c, levels))
        for r, key in zip(sw["records"], got):
            if key not in known:
                continue                       # counted in points_missing
            levels = levels_of[r.cim_levels]
            exp = reference.price(r.workload, r.cache, levels, r.tech,
                                  r.host)
            gap = max(_gap(getattr(r, f), exp[f]) for f in PRICE_FIELDS)
            out["price_gap"] = max(out["price_gap"], gap)
            out["failed_points"] += int(
                gap > price_limit or (r.workload, r.cache) in bad_keys
                or (r.workload, r.cache, levels) in bad_keys)
    return out


def reference_records(space, reference: Reference) -> List:
    """The reference's own records, shaped like the program's (the control
    puts these in the program's place)."""
    from types import SimpleNamespace
    recs = []
    for p in space.points():
        vals = reference.price(p.workload, p.cache.name, p.cim_levels,
                               p.tech, p.host.name)
        recs.append(SimpleNamespace(
            index=p.index, workload=p.workload, cache=p.cache.name,
            cim_levels="+".join(p.cim_levels), tech=p.tech,
            cim_set=p.cim_set, host=p.host.name, **vals))
    return recs
