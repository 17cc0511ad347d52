"""Plain reference of the Eva-CiM analysis, for deciding ``correct``.

A straightforward, one-instruction-at-a-time implementation of what the
sweep computes after the trace VM: the cache replay of each geometry
(LRU, write-back + write-allocate, banks, MSHR file), the producer/flow
tables, Algorithm 1 with its placement rule, the reshaping of §IV-C and
the McPAT/DESTINY-style pricing of both runs.  It imports nothing of the
program under test: the model constants below are copied from the
paper's Table III / Fig. 11 surrogates and the host presets, and the one
input it takes is the committed instruction stream (the paper's CIQ,
which Eva-CiM takes from GEM5 as an input), in :func:`stream_of`'s plain
form.  The benchmark pins each workload's stream in its configuration
file, so a stream that changed is refused before this reference runs.

``dtype`` selects the float type of the pricing arithmetic: ``float``
(float64, what the configuration states) or ``numpy.float32`` (the
control, one precision below).
"""
from __future__ import annotations

import math
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

LINE = 64

# Encoding of the instruction stream's integer columns (op, unit and
# level codes index these tuples).
OPS = (
    "load", "store", "branch", "agen", "mov",
    "add", "sub", "mul", "div", "rem", "pow",
    "max", "min", "cmp", "sel",
    "and", "or", "xor", "not", "shl", "shr",
    "abs", "neg", "sign", "floor", "round",
    "exp", "log", "tanh", "sqrt", "rsqrt", "sigmoid",
)
UNITS = ("IntAlu", "IntMult", "IntDiv", "FloatAdd", "FloatMult", "FloatDiv",
         "FloatSqrt", "MemRead", "MemWrite", "Branch", "SimdAlu")
LEVELS = (None, "L1", "L2", "MEM")
SRC_REG = 0

CIM_SETS = {
    "logic": frozenset({"and", "or", "xor"}),
    "stt": frozenset({"and", "or", "xor", "add", "sub", "max", "min", "cmp"}),
    "full": frozenset({"and", "or", "xor", "add", "sub", "max", "min", "cmp",
                       "mul"}),
}
CIM_OP_CLASS = {
    "or": "CiM-OR", "and": "CiM-AND", "xor": "CiM-XOR", "not": "CiM-OR",
    "add": "CiM-ADD", "sub": "CiM-ADD",
    "max": "CiM-XOR", "min": "CiM-XOR", "cmp": "CiM-XOR",
    "mul": "CiM-MUL",
}
MAX_TREE_OPS = 64
DEPTH = {"L1": 0, "L2": 1, "MEM": 2}

# Table III energies (pJ) at the (64 KiB, 4-way) and (256 KiB, 8-way)
# anchors, Fig. 11 latencies (cycles) at L1 / L2.
TABLE3 = {
    "sram": {"read": (61.0, 314.0), "CiM-OR": (71.0, 341.0),
             "CiM-AND": (72.0, 344.0), "CiM-XOR": (79.0, 365.0),
             "CiM-ADD": (79.0, 365.0)},
    "fefet": {"read": (34.0, 70.0), "CiM-OR": (35.0, 72.0),
              "CiM-AND": (88.0, 146.0), "CiM-XOR": (105.0, 205.0),
              "CiM-ADD": (105.0, 205.0)},
}
LATENCY = {
    "sram": {"read": (2, 8), "CiM-OR": (2, 8), "CiM-AND": (2, 8),
             "CiM-XOR": (2, 8), "CiM-ADD": (6, 12)},
    "fefet": {"read": (2, 6), "CiM-OR": (2, 6), "CiM-AND": (2, 6),
              "CiM-XOR": (2, 6), "CiM-ADD": (4, 9)},
}
ANCHOR_L1 = (64 * 1024, 4)
ANCHOR_L2 = (256 * 1024, 8)
BETA = 0.20
WRITE_FACTOR = 1.15
MUL_FACTOR = 4.0
DRAM_PJ = 15_000.0

_UNIT_PJ = {"IntAlu": 15.0, "IntMult": 40.0, "IntDiv": 90.0,
            "FloatAdd": 40.0, "FloatMult": 60.0, "FloatDiv": 140.0,
            "FloatSqrt": 160.0, "MemRead": 20.0, "MemWrite": 20.0,
            "Branch": 12.0, "SimdAlu": 30.0}
_A9 = dict(pipeline_pj=180.0, static_pj_per_cycle=150.0, base_cpi=0.65,
           l2_stall=8.0, mem_stall=60.0, overlap=0.4, cim_occupancy=0.35,
           cim_overlap=0.2, freq_ghz=1.0)
HOSTS = {
    "A9-1GHz": _A9,
    "inorder-1GHz": dict(_A9, pipeline_pj=80.0, static_pj_per_cycle=60.0,
                         base_cpi=1.15, overlap=0.9, cim_occupancy=0.5,
                         cim_overlap=0.65),
    "A9-2GHz": dict(_A9, static_pj_per_cycle=75.0, l2_stall=16.0,
                    mem_stall=120.0, freq_ghz=2.0),
    "big-OoO-2GHz": dict(_A9, pipeline_pj=300.0, static_pj_per_cycle=260.0,
                         base_cpi=0.4, l2_stall=16.0, mem_stall=120.0,
                         overlap=0.2, cim_occupancy=0.3, cim_overlap=0.08,
                         freq_ghz=2.0),
}

# A cache level: (name, size bytes, ways, banks, MSHRs).
Level = Tuple[str, int, int, int, int]


# --------------------------------------------------------------- stream
def stream_of(columns) -> Dict[str, list]:
    """The committed instruction stream as plain per-instruction lists,
    read from a columnar trace's structural columns."""
    off = columns.src_off.tolist()
    tag = columns.src_tag.tolist()
    val = columns.src_val.tolist()
    return {
        "op": [OPS[c] for c in columns.op.tolist()],
        "unit": [UNITS[c] for c in columns.unit.tolist()],
        "dst": columns.dst.tolist(),
        "srcs": [[(tag[j], val[j]) for j in range(off[i], off[i + 1])]
                 for i in range(len(off) - 1)],
        "addr": columns.addr.tolist(),
    }


# --------------------------------------------------------------- replay
class _Cache:
    def __init__(self, level: Level):
        self.name, size, self.assoc, self.banks, self.n_mshr = level
        self.n_sets = max(1, size // (LINE * self.assoc))
        self.sets = [OrderedDict() for _ in range(self.n_sets)]
        self.mshr: OrderedDict = OrderedDict()
        self.hits = self.misses = self.writebacks = 0

    def lookup(self, line: int) -> bool:
        s = self.sets[line % self.n_sets]
        if line in s:
            s.move_to_end(line)
            self.hits += 1
            return True
        self.misses += 1
        return False

    def fill(self, line: int, dirty: bool) -> Optional[int]:
        s = self.sets[line % self.n_sets]
        if line in s:
            s[line] = s[line] or dirty
            s.move_to_end(line)
            return None
        victim = None
        if len(s) >= self.assoc:
            old, old_dirty = s.popitem(last=False)
            if old_dirty:
                self.writebacks += 1
                victim = old
        s[line] = dirty
        return victim

    def in_flight(self, line: int) -> bool:
        if line in self.mshr:
            return True
        if len(self.mshr) >= self.n_mshr:
            self.mshr.popitem(last=False)
        self.mshr[line] = True
        return False


def replay(addrs: Sequence[int], writes: Sequence[bool],
           levels: Sequence[Level]):
    """Per access: (level name, first-level hit, bank, MSHR merge); plus
    the counters of every level and of DRAM."""
    caches = [_Cache(lv) for lv in levels]
    mem = {"reads": 0, "writes": 0}

    def write_back(line: int, from_index: int) -> None:
        if from_index + 1 < len(caches):
            victim = caches[from_index + 1].fill(line, True)
            if victim is not None:
                write_back(victim, from_index + 1)
        else:
            mem["writes"] += 1

    out = []
    for addr, is_write in zip(addrs, writes):
        line = addr // LINE
        served = len(caches)               # index of DRAM
        merged = False
        for i, c in enumerate(caches):
            if c.lookup(line):
                served = i
                break
            merged = c.in_flight(line) or merged
        else:
            mem["reads"] += 1
        for i in range(served):
            victim = caches[i].fill(line, False)
            if victim is not None:
                write_back(victim, i)
        if is_write:
            s = caches[0].sets[line % caches[0].n_sets]
            if line in s:
                s[line] = True
        bank_cache = caches[min(served, len(caches) - 1)]
        name = caches[served].name if served < len(caches) else "MEM"
        out.append((name, served == 0, line % bank_cache.banks, merged))
    counters = {"mem_reads": mem["reads"], "mem_writes": mem["writes"]}
    for c in caches:
        counters[f"{c.name}_hits"] = c.hits
        counters[f"{c.name}_misses"] = c.misses
        counters[f"{c.name}_writebacks"] = c.writebacks
    return out, counters


# ------------------------------------------------------------ flow tables
class Flow:
    """Register producers, register consumers, spilling stores and the
    producer behind each load, from one pass over the stream."""

    def __init__(self, stream: Dict[str, list]):
        ops, dst, srcs, addr = (stream["op"], stream["dst"], stream["srcs"],
                                stream["addr"])
        n = len(ops)
        self.producers: List[List[Optional[int]]] = []  # None: immediate
        self.reg_only_imm = [True] * n
        self.consumers: List[List[int]] = [[] for _ in range(n)]
        self.stores: List[List[int]] = [[] for _ in range(n)]
        self.load_source = [-1] * n
        last_writer: Dict[int, int] = {}
        value_at: Dict[int, int] = {}
        for seq in range(n):
            prods: List[Optional[int]] = []
            for tag, v in srcs[seq]:
                if tag != SRC_REG:
                    prods.append(None)
                    continue
                self.reg_only_imm[seq] = False
                p = last_writer.get(int(v))
                prods.append(p)
                if p is not None:
                    self.consumers[p].append(seq)
                    if ops[seq] == "store":
                        self.stores[p].append(seq)
            self.producers.append(prods)
            if ops[seq] == "store":
                resolved = [p for p in prods if p is not None]
                if resolved:
                    value_at[addr[seq]] = resolved[0]
            elif ops[seq] == "load":
                self.load_source[seq] = value_at.get(addr[seq], -1)
            if dst[seq] >= 0:
                last_writer[dst[seq]] = seq


# ------------------------------------------------------------ Algorithm 1
def select(stream: Dict[str, list], flow: Flow, level: Dict[int, str],
           bank: Dict[int, int], cim_set: frozenset,
           cim_levels: Sequence[str]) -> Tuple[List[dict], set]:
    """Offloading candidates in program order, and the removed host
    instructions (the generic, placement-aware form of Algorithm 1)."""
    ops, addr = stream["op"], stream["addr"]
    claimed: set = set()
    found: List[dict] = []
    cap = max(DEPTH[lv] for lv in cim_levels)
    enabled = sorted(DEPTH[lv] for lv in cim_levels)
    depth_name = {v: k for k, v in DEPTH.items()}

    def tree(root: int):
        budget = [MAX_TREE_OPS]

        def build(seq: int):
            if budget[0] <= 0:
                return None
            budget[0] -= 1
            children = []
            for p in flow.producers[seq]:
                if p is None:
                    continue                               # immediate leaf
                if ops[p] == "load":
                    children.append(("load", p))
                elif ops[p] == "mov" and flow.reg_only_imm[p]:
                    continue                               # mov #imm leaf
                elif ops[p] in cim_set and p not in claimed:
                    sub = build(p)
                    children.append(("memval", p) if sub is None
                                    else ("node", sub))
                else:
                    children.append(("memval", p))
            return (seq, children)
        return build(root)

    def preorder(node) -> List[int]:
        out = [node[0]]
        for kind, payload in node[1]:
            if kind == "node":
                out += preorder(payload)
        return out

    def leaves(node):
        out = []
        for kind, payload in node[1]:
            if kind == "load":
                out.append(("load", payload, level[payload]))
            elif kind == "memval":
                if not flow.stores[payload]:
                    return None
                last = flow.stores[payload][-1]
                out.append(("memval", payload, level[last]))
            else:
                sub = leaves(payload)
                if sub is None:
                    return None
                out += sub
        return out

    def accept(node) -> Optional[dict]:
        op_seqs = preorder(node)
        if any(s in claimed for s in op_seqs):
            return None
        lv = leaves(node)
        if lv is None or len(lv) < 1:
            return None
        if sum(1 for k, _, _ in lv if k == "load") < 1:
            return None
        deepest = max(min(DEPTH[l], cap) for _, _, l in lv)
        target = next((d for d in enabled if d >= deepest), enabled[-1])
        moves = sum(1 for _, _, l in lv if DEPTH[l] < target)
        op_set = set(op_seqs)
        loads = sorted({s for k, s, _ in lv if k == "load"} - claimed)
        internal = sum(1 for s in loads if flow.load_source[s] in op_set)
        stores: set = set()
        added = 0
        for p in op_seqs:
            stores.update(s for s in flow.stores[p] if s not in claimed)
            if p == node[0]:
                continue
            for c in flow.consumers[p]:
                if c not in op_set and c not in claimed \
                        and ops[c] != "store":
                    added += 1
        stores_l = sorted(stores)
        fills = {addr[s] // LINE for s in loads + stores_l
                 if level[s] == "MEM"}
        return {"root_seq": node[0], "op_seqs": op_seqs,
                "op_classes": [CIM_OP_CLASS.get(ops[s], "CiM-ADD")
                               for s in op_seqs],
                "load_seqs": loads, "store_seqs": stores_l,
                "level": depth_name[target],
                "bank": bank[loads[0]] if loads else None,
                "moves": moves, "internal_edges": internal,
                "added_loads": added,
                "memval_leaves": sum(1 for k, _, _ in lv if k == "memval"),
                "dram_fills": len(fills), "leaves": len(lv)}

    def take(cand: dict) -> None:
        found.append(cand)
        claimed.update(cand["op_seqs"], cand["load_seqs"],
                       cand["store_seqs"])

    for seq in range(len(ops) - 1, -1, -1):
        if ops[seq] not in cim_set or seq in claimed:
            continue
        t = tree(seq)
        whole = accept(t)
        if whole is not None:
            take(whole)
            continue
        for kind, payload in t[1]:
            if kind == "node":
                sub = accept(payload)
                if sub is not None:
                    take(sub)
    found.reverse()
    return found, claimed


# ---------------------------------------------------------------- pricing
def _energy(tech: str, op: str, size: int, assoc: int, F) -> object:
    if op == "write":
        return _energy(tech, "read", size, assoc, F) * F(WRITE_FACTOR)
    if op == "CiM-MUL":
        return _energy(tech, "CiM-ADD", size, assoc, F) * F(MUL_FACTOR)
    e1, e2 = (F(x) for x in TABLE3[tech][op])
    s1, a1 = F(ANCHOR_L1[0]), F(ANCHOR_L1[1])
    s2, a2 = F(ANCHOR_L2[0]), F(ANCHOR_L2[1])
    log = math.log if F is float else (lambda x: F(math.log(x)))
    alpha = (log(e2 / e1) - F(BETA) * log(a2 / a1)) / log(s2 / s1)
    return e1 * (F(size) / s1) ** alpha * (F(assoc) / a1) ** F(BETA)


def _latency(tech: str, op: str, level: str) -> int:
    if op == "write":
        op = "read"
    if op == "CiM-MUL":
        base = LATENCY[tech]["CiM-ADD"]
        return (base[0] if level == "L1" else base[1]) + 2
    row = LATENCY[tech].get(op, LATENCY[tech]["read"])
    return row[0] if level == "L1" else row[1]


def _running_sum(values, zero):
    """The values added one at a time, left to right, from ``zero``: a
    cumulative sum adds in that order, in the values' own precision."""
    return np.cumsum(values)[-1] if len(values) else zero


def prepare(stream: Dict[str, list], level: Dict[int, str]) -> Dict:
    """Per-instruction tables of the pricing that do not depend on the
    geometry's sizes, the tech or the host: each instruction's unit energy
    and its access kind (0: none; else 1 + 2 * level index + is-store)."""
    ops, units = stream["op"], stream["unit"]
    kind = np.zeros(len(ops), np.int64)
    for s, lv in level.items():
        if ops[s] in ("load", "store"):
            kind[s] = 1 + 2 * ("L1", "L2", "MEM").index(lv) \
                + (ops[s] == "store")
    return {"unit_pj": np.array([_UNIT_PJ.get(u, 15.0) for u in units]),
            "kind": kind, "n_mem": int(np.count_nonzero(kind))}


def tally(candidates: List[dict], level: Dict[int, str]) -> Dict:
    """What the pricing reads of a selection, whatever the tech and host:
    every CiM op in candidate order, as an index into the distinct
    (op class, level) pairs; the moves, internal edges and added loads
    per level, in order of first use; the DRAM fills; and the converted
    accesses, with those served by L1."""
    pairs: Dict[Tuple[str, str], int] = {}
    op_pair = [pairs.setdefault((cls, c["level"]), len(pairs))
               for c in candidates for cls in c["op_classes"]]
    moves: Dict[str, int] = {}
    internal: Dict[str, int] = {}
    added: Dict[str, int] = {}
    fills = 0
    for c in candidates:
        lv = c["level"]
        moves[lv] = moves.get(lv, 0) + c["moves"]
        internal[lv] = internal.get(lv, 0) + c["internal_edges"]
        added[lv] = added.get(lv, 0) + c["added_loads"]
        fills += c["dram_fills"]
    converted = [s for c in candidates for s in c["load_seqs"]
                 + c["store_seqs"]]
    return {"pairs": list(pairs), "op_pair": np.array(op_pair, np.int64),
            "moves": moves, "internal": internal, "added": added,
            "fills": fills, "converted": len(converted),
            "l1": sum(1 for s in converted if level[s] == "L1")}


def price(stream: Dict[str, list], level: Dict[int, str],
          candidates: List[dict], claimed: set, levels: Sequence[Level],
          tech: str, host_name: str, F=float,
          memo: Optional[dict] = None, prep: Optional[dict] = None,
          counts: Optional[dict] = None) -> Dict[str, object]:
    """Both runs priced instruction by instruction; the record's numeric
    fields.  ``memo`` keeps the baseline run, which does not depend on the
    candidates, for the next call with the same stream, geometry, tech
    and host; ``prep`` is :func:`prepare` of the stream and its levels,
    ``counts`` :func:`tally` of the candidates."""
    ops = stream["op"]
    host = {k: F(v) for k, v in HOSTS[host_name].items()}
    geo = {lv[0]: lv for lv in levels}
    has_l2 = "L2" in geo
    zero = F(0)
    dt = np.float64 if F is float else np.dtype(F)
    prep = prep or prepare(stream, level)
    counts = counts or tally(candidates, level)

    energies: Dict[Tuple[str, str], object] = {}

    def energy(op: str, lvl: str):
        if (op, lvl) not in energies:
            energies[op, lvl] = _energy(tech, op, geo[lvl][1], geo[lvl][2],
                                        F)
        return energies[op, lvl]

    def access(lvl: str, is_write: bool):
        op = "write" if is_write else "read"
        e = energy(op, "L1")
        if lvl in ("L2", "MEM") and has_l2:
            e = e + energy(op, "L2")
        if lvl == "MEM":
            e = e + F(DRAM_PJ)
        return e

    # per access kind: the cycles, the cache energy and the level it is
    # booked to (a DRAM access books its cache part to the last level)
    cycles_of = [host["base_cpi"]] * 3 \
        + [host["base_cpi"] + host["l2_stall"] * host["overlap"]] * 2 \
        + [host["base_cpi"] + host["mem_stall"] * host["overlap"]] * 2
    energy_of, book = [zero], [None]
    for lv in ("L1", "L2", "MEM"):
        for is_write in (False, True):
            e = access(lv, is_write)
            energy_of.append(e - F(DRAM_PJ) if lv == "MEM" else e)
            book.append(lv if lv != "MEM" else ("L2" if has_l2 else "L1"))
    cycles_of = np.array(cycles_of, dt)
    energy_of = np.array(energy_of, dt)

    def host_run(seqs):
        """One host run over ``seqs`` (in program order), each sum taken
        instruction by instruction."""
        kind = prep["kind"][seqs]
        pipe = _running_sum(np.full(len(seqs), host["pipeline_pj"], dt),
                            zero)
        units_e = _running_sum(prep["unit_pj"][seqs].astype(dt), zero)
        cycles = _running_sum(cycles_of[kind], zero)
        acc = kind[kind > 0]
        dram = _running_sum(np.full(np.count_nonzero(acc >= 5),
                                    F(DRAM_PJ), dt), zero)
        to_l2 = np.array([b == "L2" for b in book])[acc]
        cache: Dict[str, object] = {}         # levels in order of first use
        for key in sorted({book[k] for k in np.unique(acc)},
                          key=lambda b: int(np.argmax(to_l2 == (b == "L2")))):
            cache[key] = _running_sum(energy_of[acc[to_l2 == (key == "L2")]],
                                      zero)
        return pipe, units_e, dram, cycles, cache

    n = len(ops)
    if memo is None or "base" not in memo:
        pipe, units_e, dram, base_cycles, cache = host_run(np.arange(n))
        static = host["static_pj_per_cycle"] * base_cycles
        base = (pipe + units_e + static, sum(cache.values(), zero), dram,
                base_cycles)
        if memo is not None:
            memo["base"] = base
    base_proc, base_caches, base_dram, base_cycles = \
        memo["base"] if memo is not None else base
    base_total = base_proc + base_caches

    host_seqs = np.ones(n, bool)
    host_seqs[list(claimed)] = False
    pipe, units_e, dram, cycles, cache = host_run(np.flatnonzero(host_seqs))
    cim: Dict[str, object] = {}
    l1_read = F(_latency(tech, "read", "L1"))
    pipe = pipe + F(len(candidates)) * host["pipeline_pj"]
    cycles = cycles + F(len(candidates)) * host["base_cpi"]
    # each CiM op's energy is booked to its level, and it adds its
    # occupancy, then its overlapped extra latency, to the cycles
    pairs, op_pair = counts["pairs"], counts["op_pair"]
    if len(op_pair):
        cim_e = np.array([energy(cls, lv) for cls, lv in pairs], dt)
        extra = []
        for cls, lv in pairs:
            lat = F(_latency(tech, cls, lv))
            extra.append(host["cim_overlap"]
                         * (lat - l1_read if lat > l1_read else zero))
        steps = np.empty(2 * len(op_pair) + 1, dt)
        steps[0] = cycles
        steps[1::2] = host["cim_occupancy"]
        steps[2::2] = np.array(extra, dt)[op_pair]
        cycles = _running_sum(steps, zero)
        op_lv = np.array([lv for _, lv in pairs])[op_pair]
        for lv in dict.fromkeys(op_lv.tolist()):
            cim[lv] = _running_sum(cim_e[op_pair[op_lv == lv]], zero)
    fills = counts["fills"]
    for lv, k in counts["moves"].items():
        if k:
            cim[lv] = cim.get(lv, zero) + F(k) * energy("write", lv)
            cycles = cycles + F(k) * host["overlap"] \
                * F(_latency(tech, "write", lv))
    for lv, k in counts["internal"].items():
        if k:
            cim[lv] = cim.get(lv, zero) + F(k) * energy("CiM-OR", lv)
            cycles = cycles + F(k) * host["overlap"]
    if fills:
        dram = dram + F(fills) * F(DRAM_PJ)
        fill_lv = "L2" if has_l2 else "L1"
        cache[fill_lv] = cache.get(fill_lv, zero) \
            + F(fills) * energy("write", fill_lv)
        cycles = cycles + F(fills) * host["mem_stall"] * host["overlap"]
    for lv, k in counts["added"].items():
        if k:
            pipe = pipe + F(k) * host["pipeline_pj"]
            units_e = units_e + F(k) * F(_UNIT_PJ["MemRead"])
            cache[lv] = cache.get(lv, zero) + F(k) * access(lv, False)
            stall = host["l2_stall"] * host["overlap"] if lv == "L2" \
                else zero
            cycles = cycles + F(k) * (host["base_cpi"] + stall)
    static = host["static_pj_per_cycle"] * cycles
    cim_proc = pipe + units_e + static
    cim_caches = sum(cache.values(), zero) + sum(cim.values(), zero)
    cim_total = cim_proc + cim_caches

    total = max(1, prep["n_mem"])
    delta = base_total - cim_total
    small = abs(delta) < F(1e-12)
    freq = host["freq_ghz"] * F(1e9)
    return {
        "energy_improvement": base_total / max(cim_total, F(1e-9)),
        "speedup": base_cycles / max(cycles, F(1e-9)),
        "macr": F(counts["converted"]) / F(total),
        "macr_l1": F(counts["l1"]) / F(total),
        "base_energy_pj": base_total,
        "cim_energy_pj": cim_total,
        "base_cycles": base_cycles,
        "cim_cycles": cycles,
        "base_runtime_ms": base_cycles / freq * F(1e3),
        "cim_runtime_ms": cycles / freq * F(1e3),
        "processor_ratio": zero if small else (base_proc - cim_proc) / delta,
        "cache_ratio": zero if small else
        ((base_caches + base_dram) - (cim_caches + dram)) / delta,
        "n_instructions": n,
        "n_mem_accesses": total,
        "n_candidates": len(candidates),
        "n_cim_ops": len(op_pair),
    }
