"""Trace VM: lower any JAX program to a committed pseudo-RISC instruction queue.

This is the repo's stand-in for the paper's modified GEM5 + probes
(Fig. 2): ``trace_program(fn, *args)`` traces ``fn`` to a jaxpr, interprets
it with concrete numpy values, and *scalarizes* every array equation into a
stream of committed instructions — loads / stores with real addresses from a
buffer arena, ALU ops over a finite register file, immediates for literals.

The register allocator is what makes the paper's Fig. 4 pattern variants
appear naturally:

  (a) Load-Load-OP-Store    — both operands fetched from memory;
  (b) Load-Imm-OP-Store     — jaxpr literals / iota lower to immediates;
  (c) OP-(reg)-OP-Store     — a recently produced value is still live in a
                              register, so the consumer's load is elided and
                              the IDG edge points at the producing OP.

Every load/store goes through the :mod:`repro.core.cache` hierarchy, which
fills the I-state's "memory access" / "response from slave" fields (level,
hit, bank, MSHR) — the data-locality ground truth the offload selector needs.

RUT (register usage table) and IHT (index hash table) — the paper's O(N)
IDG construction aids (Fig. 6 / Algorithm 2) — are built incrementally here
while the trace is emitted, exactly as the probes would.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as jex_core
import numpy as np

from repro.core.cache import CacheConfig, CacheHierarchy, L1_32K, L2_256K
from repro.core.columnar import ColumnarBuilder, ColumnarTrace, _imm_kind
from repro.core.isa import (DTYPE_CODE, IMM_FLOAT, IMM_INT, OP_CODE, OP_LOAD,
                            OP_STORE, SRC_IMM, SRC_REG, U_BRANCH, UNIT_CODE,
                            Inst, Trace, unit_for)

# Version of the trace VM's *observable lowering semantics or artifact
# encoding*.  Bump whenever a change alters the committed instruction
# stream for an unchanged program (new lowering rules, register-allocator
# or arena-layout changes, cache model fixes...) OR the persisted layer-1
# representation (v2: columnar .npz columns replaced pickled Inst lists).
# The on-disk analysis store (repro.dse.store) keys every persisted
# artifact by this number, so stale traces from an older VM are
# invalidated instead of silently re-priced.
TRACE_VM_VERSION = 2

# pre-resolved emission codes: op -> (unit code for int, unit code for float)
_UNIT_CODES = {op: (UNIT_CODE[unit_for(op, False)],
                    UNIT_CODE[unit_for(op, True)]) for op in OP_CODE}
_MEM_RD_CODE = UNIT_CODE[unit_for("load", False)]
_MEM_WR_CODE = UNIT_CODE[unit_for("store", False)]
_BRANCH_CODE = UNIT_CODE[U_BRANCH]

# pre-packed ColumnarBuilder meta fragments for the inlined scalar emitter
# (see Machine.emit_scalar); the encodings mirror ColumnarBuilder.add
_LOAD_META = OP_LOAD | _MEM_RD_CODE << 5
_STORE_META = OP_STORE | _MEM_WR_CODE << 5
_IMM_INT_SMETA = SRC_IMM | IMM_INT << 1


# jit-compiled gather/scatter oracles, cached per static config: the eager
# lax dispatch costs tens of microseconds per call, which dominates kernels
# that hit these primitives once per loop iteration (mcf, astar); the jit
# cache re-traces per operand shape and replays the compiled computation
# after that — same XLA kernel the eager path runs, so values are bit-exact
@functools.lru_cache(maxsize=None)
def _jitted_gather(dnums, slice_sizes, mode):
    return jax.jit(functools.partial(jax.lax.gather,
                                     dimension_numbers=dnums,
                                     slice_sizes=slice_sizes, mode=mode))


@functools.lru_cache(maxsize=None)
def _jitted_scatter(is_add: bool, dnums, mode):
    op = jax.lax.scatter_add if is_add else jax.lax.scatter
    return jax.jit(functools.partial(op, dimension_numbers=dnums, mode=mode))


def on_host():
    """Context that runs the VM's jax value oracles (gather/scatter,
    dot_general, pad, sort, select_n) on jax's CPU device.

    The VM simulates a host CPU, and its workloads branch on data, so an
    oracle's value can change the committed instruction stream.  On a TPU
    the default device would compute them with its own defaults (matmul
    precision among them); pinning them to the CPU keeps the stream the
    same on every platform.  Every interpreter run enters this context."""
    return jax.default_device(jax.devices("cpu")[0])

# ======================================================================
# Values: concrete data + an address map (None => immediate / generated)
# ======================================================================
class Value:
    __slots__ = ("data", "addr")

    def __init__(self, data: np.ndarray, addr: Optional[np.ndarray]):
        self.data = data
        self.addr = addr                    # int64 addresses, same shape, or None

    @property
    def in_memory(self) -> bool:
        return self.addr is not None


# dtype -> tag/itemsize are pure and the dtype universe is tiny; the
# issubdtype/np.dtype machinery is measurably hot in scalar-heavy traces
_TAG_CACHE: Dict[Any, str] = {}
_SIZE_CACHE: Dict[Any, int] = {}


def _dtype_tag(dt: np.dtype) -> str:
    tag = _TAG_CACHE.get(dt)
    if tag is None:
        tag = "f" if np.issubdtype(dt, np.floating) else "i"
        _TAG_CACHE[dt] = tag
    return tag


def _itemsize(dt: np.dtype) -> int:
    size = _SIZE_CACHE.get(dt)
    if size is None:
        size = int(np.dtype(dt).itemsize)
        _SIZE_CACHE[dt] = size
    return size


# ======================================================================
# The machine
# ======================================================================
@dataclasses.dataclass
class TraceLimits:
    max_instructions: int = 4_000_000


class Machine:
    """Arena + register file + the emitted CIQ (columnar).

    The machine emits *structural* columns only — opcode, registers,
    addresses — one scalar append per field per committed instruction
    (:class:`~repro.core.columnar.ColumnarBuilder`), never an
    :class:`~repro.core.isa.Inst` object.  The memory-response fields
    (level/hit/bank/MSHR) are geometry-dependent and are attached
    afterwards by replaying the access stream through a
    :class:`~repro.core.cache.CacheHierarchy`
    (:func:`attach_cache_results`), which is what lets one structural
    trace serve every cache configuration of a sweep.  RUT/IHT are no
    longer built at commit time either: they are derived tables,
    reconstructed vectorized from the source-operand columns
    (:func:`repro.core.idg.build_rut_iht`).
    """

    # compiled inner loops carry induction/address-gen + branch overhead;
    # -O2 typically unrolls ~4x, so: one agen per element, one branch per 4.
    UNROLL = 4

    def __init__(self, n_regs: int = 24, limits: TraceLimits = TraceLimits(),
                 loop_overhead: bool = True):
        from repro.core.columnar import MAX_REG_ID
        if not 1 <= n_regs <= MAX_REG_ID - 1:     # +1 induction register
            raise ValueError(f"n_regs must be in [1, {MAX_REG_ID - 1}] "
                             "(columnar dst packing)")
        self.b = ColumnarBuilder()
        self.limits = limits
        self.loop_overhead = loop_overhead
        self._arena_top = 0x1000
        self._ov_count = 0
        # register file (single class; dtype tag recorded per instruction)
        self.n_regs = n_regs
        self._free_regs = list(range(n_regs + 1))       # +1: induction reg
        self._ov_reg = self._free_regs.pop()            # reserved induction var
        self._reg_of_addr: "OrderedDict[int, int]" = OrderedDict()  # LRU
        self._addr_of_reg: Dict[int, int] = {}
        # pre-built argument tuple for the (constant) loop-overhead agen op
        self._ov_args = (OP_CODE["agen"], _UNIT_CODES["agen"][False], False,
                         self._ov_reg, -1, 4,
                         ((SRC_REG, self._ov_reg), (SRC_IMM, 4)))
        # pre-packed meta words for the inlined scalar emitter
        self._ov_meta = (OP_CODE["agen"] | _UNIT_CODES["agen"][False] << 5
                         | (self._ov_reg + 1) << 10 | 4 << 18)
        self._branch_meta = OP_CODE["branch"] | _BRANCH_CODE << 5 | 4 << 18
        self._loops: List[dict] = []
        self._scope_cache: Dict[Any, dict] = {}

    # ------------------------------------------------------------ arena
    # Loop-scoped buffer reuse: compiled loops keep their temporaries on the
    # stack / in fixed buffers rather than allocating fresh memory per
    # iteration.  Inside a scan/while body, the i-th allocation of iteration
    # t reuses the i-th allocation of iteration t-3 (triple buffering keeps
    # carries from t-1 and freshly stacked outputs intact).  Without this,
    # every temporary is a compulsory DRAM miss and the whole analysis
    # drowns in DRAM traffic no real binary would produce.
    LOOP_REUSE_DEPTH = 3

    def alloc(self, shape: Tuple[int, ...], dt: np.dtype) -> np.ndarray:
        n = 1
        for s in shape:
            n *= int(s)
        # temporaries pack like stack slots (8 B granularity); standalone
        # buffers outside loops stay line-aligned like heap allocations
        in_loop = bool(self._loops)
        align = 7 if in_loop else 63
        size = (n * _itemsize(dt) + align) & ~align
        base = None
        if in_loop:
            scope = self._loops[-1]
            idx = len(scope["cur"])
            hist = scope["hist"]
            if len(hist) == self.LOOP_REUSE_DEPTH and idx < len(hist[0]) \
                    and hist[0][idx][1] == size:
                base = hist[0][idx][0]                   # recycle old temp
            scope["cur"].append((base if base is not None else self._arena_top,
                                 size))
        if base is None:
            base = self._arena_top
            self._arena_top += size
        if n == 1:
            a = np.array(base, dtype=np.int64)
            return a if not shape else a.reshape(shape)
        return (base + np.arange(n, dtype=np.int64) * _itemsize(dt)).reshape(shape)

    def push_loop(self, key=None) -> None:
        """Enter a loop body scope.  ``key`` (the loop jaxpr's id) resumes
        the scope across re-entry — an inner loop reuses the same stack
        slots on every run, exactly like a compiled loop nest."""
        if key is not None and key in self._scope_cache:
            scope = self._scope_cache[key]
            scope["cur"] = []
        else:
            scope = {"hist": [], "cur": []}
            if key is not None:
                self._scope_cache[key] = scope
        self._loops.append(scope)

    def next_iteration(self) -> None:
        scope = self._loops[-1]
        scope["hist"].append(scope["cur"])
        if len(scope["hist"]) > self.LOOP_REUSE_DEPTH:
            scope["hist"].pop(0)
        scope["cur"] = []

    def pop_loop(self) -> None:
        self._loops.pop()

    # ---------------------------------------------------------- registers
    def _alloc_reg(self) -> int:
        if self._free_regs:
            return self._free_regs.pop()
        if self._reg_of_addr:
            # evict LRU mapping; its value now lives only in memory
            addr, reg = self._reg_of_addr.popitem(last=False)
            del self._addr_of_reg[reg]
            return reg
        # nothing evictable (all regs hold in-flight temporaries): round-robin
        self._rr = (getattr(self, "_rr", -1) + 1) % self.n_regs
        return self._rr

    def _bind(self, addr: int, reg: int) -> None:
        old = self._addr_of_reg.get(reg)
        if old is not None:
            self._reg_of_addr.pop(old, None)
        self._reg_of_addr[addr] = reg
        self._addr_of_reg[reg] = addr

    def reg_holding(self, addr: int) -> Optional[int]:
        reg = self._reg_of_addr.get(addr)
        if reg is not None:
            self._reg_of_addr.move_to_end(addr)
        return reg

    # ----------------------------------------------------------- emission
    def _check_limit(self) -> None:
        if self.b.n > self.limits.max_instructions:
            raise RuntimeError(
                f"trace exceeded {self.limits.max_instructions} instructions; "
                "shrink the workload size")

    def emit_load(self, addr: int, tag: str, size: int) -> int:
        hit_reg = self.reg_holding(addr)
        if hit_reg is not None:
            return hit_reg                                # load elided (Fig.4c)
        reg = self._alloc_reg()
        self.b.add(OP_LOAD, _MEM_RD_CODE, tag == "f", reg, addr, size,
                   ((SRC_IMM, addr),))
        self._check_limit()
        self._bind(addr, reg)
        return reg

    def emit_op(self, op: str, tag: str, srcs: Sequence[Tuple[int, Any]],
                dst: Optional[int] = None) -> int:
        """``dst``: reuse a register (reduction accumulators, like a compiler)."""
        reg = self._alloc_reg() if dst is None else dst
        if dst is not None:
            old = self._addr_of_reg.pop(dst, None)
            if old is not None:
                self._reg_of_addr.pop(old, None)
        is_f = tag == "f"
        self.b.add(OP_CODE[op], _UNIT_CODES[op][is_f], is_f, reg, -1, 4,
                   tuple(srcs))
        self._check_limit()
        return reg

    def emit_store(self, addr: int, reg: int, tag: str, size: int) -> None:
        self.b.add(OP_STORE, _MEM_WR_CODE, tag == "f", -1, addr, size,
                   ((SRC_REG, reg),))
        self._check_limit()
        self._bind(addr, reg)                            # value is in reg + mem

    def emit_branch(self) -> None:
        self.b.add(OP_CODE["branch"], _BRANCH_CODE, False, -1, -1, 4, ())
        self._check_limit()

    def emit_loop_overhead(self) -> None:
        """Per-element induction/addr-gen + amortized loop branch (UNROLL)."""
        if not self.loop_overhead:
            return
        self.b.add(*self._ov_args)
        self._check_limit()
        self._ov_count += 1
        if self._ov_count % self.UNROLL == 0:
            self.emit_branch()

    def emit_scalar(self, op: str, tag: str, invals: Sequence["Value"],
                    out_addr: int, osize: int) -> None:
        """One whole scalar equation — loop overhead, operand loads, the op,
        the store — emitted straight-line.

        Byte-identical to ``emit_loop_overhead`` + ``emit_load``* +
        ``emit_op`` + ``emit_store`` called in sequence; exists because
        scalar-heavy kernels (LCS, mcf) lower ~1 committed instruction per
        jaxpr equation and spend most of their trace time on the CPython
        call overhead of that sequence.
        """
        b = self.b
        meta_l, addr_l, srcn_l = b.meta, b.addr, b.src_n
        smeta_l, sval_l = b.src_meta, b.src_val
        n_new = 0
        if self.loop_overhead:
            meta_l.append(self._ov_meta)
            addr_l.append(-1)
            srcn_l.append(2)
            smeta_l.append(SRC_REG)
            sval_l.append(self._ov_reg)
            smeta_l.append(_IMM_INT_SMETA)
            sval_l.append(4.0)
            n_new = 1
            self._ov_count += 1
            if self._ov_count % self.UNROLL == 0:
                meta_l.append(self._branch_meta)
                addr_l.append(-1)
                srcn_l.append(0)
                n_new = 2
        reg_of_addr = self._reg_of_addr
        op_smeta: List[int] = []
        op_sval: List[float] = []
        for v in invals:
            if v.addr is None:
                d = v.data.item()
                t = type(d)
                kind = (IMM_INT if t is int else
                        IMM_FLOAT if t is float else _imm_kind(d))
                op_smeta.append(SRC_IMM | kind << 1)
                op_sval.append(float(d))
            else:
                a = v.addr.item()
                reg = reg_of_addr.get(a)
                if reg is not None:
                    reg_of_addr.move_to_end(a)      # load elided (Fig.4c)
                else:
                    dt = v.data.dtype
                    reg = self._alloc_reg()
                    meta_l.append(_LOAD_META | (_dtype_tag(dt) == "f") << 9
                                  | (reg + 1) << 10 | _itemsize(dt) << 18)
                    addr_l.append(a)
                    srcn_l.append(1)
                    smeta_l.append(_IMM_INT_SMETA)
                    sval_l.append(float(a))
                    n_new += 1
                    self._bind(a, reg)
                op_smeta.append(SRC_REG)
                op_sval.append(reg)
        is_f = tag == "f"
        rd = self._alloc_reg()
        meta_l.append(OP_CODE[op] | _UNIT_CODES[op][is_f] << 5 | is_f << 9
                      | (rd + 1) << 10 | 4 << 18)
        addr_l.append(-1)
        srcn_l.append(len(op_smeta))
        smeta_l.extend(op_smeta)
        sval_l.extend(op_sval)
        meta_l.append(_STORE_META | is_f << 9 | osize << 18)
        addr_l.append(out_addr)
        srcn_l.append(1)
        smeta_l.append(SRC_REG)
        sval_l.append(rd)
        b.n += n_new + 2
        self._bind(out_addr, rd)
        self._check_limit()

    # ------------------------------------------------- value-level helpers
    def materialize(self, val: Value) -> Value:
        """Give an immediate-only value a memory buffer (mov+store each elem)."""
        if val.in_memory:
            return val
        data = np.asarray(val.data)
        addr = self.alloc(data.shape, data.dtype)
        tag = _dtype_tag(data.dtype)
        size = _itemsize(data.dtype)
        flat_d = data.ravel().tolist()
        flat_a = addr.ravel().tolist()
        for d, a in zip(flat_d, flat_a):
            r = self.emit_op("mov", tag, ((SRC_IMM, d),))
            self.emit_store(a, r, tag, size)
        return Value(data, addr)

    def store_const(self, arr: np.ndarray) -> Value:
        """Program constants live in memory but cost no trace instructions
        (they were written by the loader, not the program)."""
        arr = np.asarray(arr)
        addr = self.alloc(arr.shape, arr.dtype)
        # pre-touch DRAM residency without recording instructions
        return Value(arr, addr)


# ======================================================================
# jaxpr interpretation + scalarization
# ======================================================================
_ELEMENTWISE = {
    "add": "add", "sub": "sub", "mul": "mul", "div": "div",
    "max": "max", "min": "min", "and": "and", "or": "or", "xor": "xor",
    "not": "not", "neg": "neg", "abs": "abs", "sign": "sign",
    "exp": "exp", "log": "log", "tanh": "tanh", "logistic": "sigmoid",
    "sqrt": "sqrt", "rsqrt": "rsqrt", "floor": "floor", "ceil": "floor",
    "round": "round", "rem": "rem", "pow": "pow",
    "shift_left": "shl", "shift_right_logical": "shr",
    "shift_right_arithmetic": "shr", "erf": "exp", "exp2": "exp", "log1p": "log",
    "expm1": "exp", "cos": "exp", "sin": "exp", "is_finite": "cmp",
    "square": "mul", "cbrt": "sqrt", "tan": "exp",
}
_COMPARE = {"lt": "cmp", "le": "cmp", "gt": "cmp", "ge": "cmp",
            "eq": "cmp", "ne": "cmp"}
_NP_BINOP = {
    "add": np.add, "sub": np.subtract, "mul": np.multiply,
    "div": lambda a, b: np.divide(a, b) if np.issubdtype(np.result_type(a, b), np.floating)
           else np.floor_divide(a, b),
    "max": np.maximum, "min": np.minimum,
    "and": np.bitwise_and, "or": np.bitwise_or, "xor": np.bitwise_xor,
    "rem": np.remainder, "pow": np.power,
    "shift_left": np.left_shift, "shift_right_logical": np.right_shift,
    "shift_right_arithmetic": np.right_shift,
    "lt": np.less, "le": np.less_equal, "gt": np.greater,
    "ge": np.greater_equal, "eq": np.equal, "ne": np.not_equal,
}
_NP_UNOP = {
    "not": np.logical_not, "neg": np.negative, "abs": np.abs, "sign": np.sign,
    "exp": np.exp, "log": np.log, "tanh": np.tanh,
    "logistic": lambda x: 1.0 / (1.0 + np.exp(-x)),
    "sqrt": np.sqrt, "rsqrt": lambda x: 1.0 / np.sqrt(x),
    "floor": np.floor, "ceil": np.ceil, "round": np.round,
    "erf": lambda x: np.vectorize(float)(x),  # unused in workloads
    "exp2": np.exp2, "log1p": np.log1p, "expm1": np.expm1,
    "cos": np.cos, "sin": np.sin, "tan": np.tan,
    "is_finite": np.isfinite, "square": np.square, "cbrt": np.cbrt,
}

# pre-joined dispatch tables: prim -> (vm op, numpy oracle).  The dict
# unions used to be rebuilt on every equation, which dominated dispatch
# for scalar-heavy traces where each eqn emits only a couple instructions.
_EW_OPS = {**_ELEMENTWISE, **_COMPARE}
_EW_BINOP = {p: (_EW_OPS[p], _NP_BINOP[p]) for p in _NP_BINOP if p in _EW_OPS}
_EW_UNOP = {p: (_ELEMENTWISE[p], _NP_UNOP[p])
            for p in _NP_UNOP if p in _ELEMENTWISE}
_CALL_PRIMS = frozenset((
    "pjit", "jit", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "checkpoint", "remat", "custom_vjp_call_jaxpr"))


class TraceInterpreter:
    def __init__(self, machine: Machine):
        self.m = machine

    # ---------------------------------------------------------------- API
    def run(self, jaxpr, consts, args: List[Value]) -> List[Value]:
        env: Dict[Any, Value] = {}

        def read(atom) -> Value:
            if isinstance(atom, jex_core.Literal):
                return Value(np.asarray(atom.val), None)
            return env[atom]

        def write(var, val: Value) -> None:
            env[var] = val

        for var, const in zip(jaxpr.constvars, consts):
            arr = np.asarray(const)
            write(var, Value(arr, None) if arr.ndim == 0 else self.m.store_const(arr))
        for var, arg in zip(jaxpr.invars, args):
            write(var, arg)

        for eqn in jaxpr.eqns:
            invals = [read(a) for a in eqn.invars]
            outvals = self.eqn(eqn, invals)
            for var, val in zip(eqn.outvars, outvals):
                write(var, val)

        return [read(v) for v in jaxpr.outvars]

    # ------------------------------------------------------------- fetch
    def _fetch_srcs(self, vals: List[Value], idx_lists: List[List[int]],
                    i: int, tags: List[str], sizes: List[int]):
        srcs = []
        for v, idxs, tag, size in zip(vals, idx_lists, tags, sizes):
            if v.addr is None:
                d = v.data if v.data.ndim == 0 else v.data.ravel()[idxs[i]]
                srcs.append((SRC_IMM, d.item() if hasattr(d, "item") else d))
            else:
                r = self.m.emit_load(int(v.addr.ravel()[idxs[i]]), tag, size)
                srcs.append((SRC_REG, r))
        return srcs

    # ------------------------------------------------- elementwise family
    def _elementwise(self, op: str, invals: List[Value], out_data: np.ndarray
                     ) -> Value:
        m = self.m
        out_data = np.asarray(out_data)
        out_addr = m.alloc(out_data.shape, out_data.dtype)
        tag = _dtype_tag(out_data.dtype)
        osize = _itemsize(out_data.dtype)
        n = out_data.size
        if n == 1:
            # scalar fast path: pointer-heavy kernels (LCS, mcf) lower almost
            # every jaxpr equation to one committed instruction, so the
            # broadcast/ravel/tolist mirrors below dominate their trace time
            m.emit_scalar(op, tag, invals, out_addr.item(), osize)
            return Value(out_data, out_addr)
        # broadcast source addr/data maps to the output shape; plain-list
        # mirrors make the per-element emission loop scalar-cheap.  Sources
        # already output-shaped (the common case) skip the broadcast;
        # size-1 sources splat without touching numpy per element.
        srcs_flat = []
        for v in invals:
            data = np.asarray(v.data)
            if data.shape == out_data.shape:
                flat_d = data.ravel().tolist()
            elif data.size == 1:
                flat_d = [data.ravel()[0].item()] * n
            else:
                flat_d = np.broadcast_to(data, out_data.shape).ravel().tolist()
            if v.addr is None:
                flat_a = None
            elif v.addr.shape == out_data.shape:
                flat_a = v.addr.ravel().tolist()
            elif v.addr.size == 1:
                flat_a = [int(v.addr.ravel()[0])] * n
            else:
                flat_a = np.broadcast_to(v.addr,
                                         out_data.shape).ravel().tolist()
            srcs_flat.append((flat_d, flat_a, _dtype_tag(data.dtype),
                              _itemsize(data.dtype)))
        oaddr_flat = out_addr.ravel().tolist()
        emit_overhead = m.emit_loop_overhead
        emit_load, emit_op, emit_store = m.emit_load, m.emit_op, m.emit_store
        for i in range(n):
            emit_overhead()
            srcs = []
            for data, addr, stag, ssize in srcs_flat:
                if addr is None:
                    srcs.append((SRC_IMM, data[i]))
                else:
                    srcs.append((SRC_REG, emit_load(addr[i], stag, ssize)))
            rd = emit_op(op, tag, srcs)
            emit_store(oaddr_flat[i], rd, tag, osize)
        return Value(out_data, out_addr)

    # ----------------------------------------------------------- reduction
    def _reduce(self, op: str, inval: Value, axes: Tuple[int, ...],
                out_data: np.ndarray, init_imm) -> Value:
        """Sequential accumulation — acc stays in a register (Fig. 4c chains)."""
        m = self.m
        out_data = np.asarray(out_data)
        x = np.asarray(inval.data)
        tag = _dtype_tag(out_data.dtype)
        osize = _itemsize(out_data.dtype)
        ssize = _itemsize(x.dtype)
        keep = [a for a in range(x.ndim) if a not in axes]
        perm = keep + list(axes)
        red_n = int(np.prod([x.shape[a] for a in axes])) if axes else 1
        xa = (np.transpose(inval.addr, perm).reshape(-1, red_n).tolist()
              if inval.addr is not None else None)
        xd = np.transpose(x, perm).reshape(-1, red_n)
        xd_l = xd.tolist()
        out_addr = m.alloc(out_data.shape, out_data.dtype)
        oaddr_flat = out_addr.ravel().tolist()
        emit_overhead = m.emit_loop_overhead
        emit_load, emit_op, emit_store = m.emit_load, m.emit_op, m.emit_store
        for i in range(xd.shape[0]):
            acc = emit_op("mov", tag, ((SRC_IMM, init_imm),))
            row_a = xa[i] if xa is not None else None
            row_d = xd_l[i]
            for j in range(red_n):
                emit_overhead()
                if row_a is None:
                    src = (SRC_IMM, row_d[j])
                else:
                    src = (SRC_REG, emit_load(row_a[j], tag, ssize))
                acc = emit_op(op, tag, ((SRC_REG, acc), src), dst=acc)
            emit_store(oaddr_flat[i], acc, tag, osize)
        return Value(out_data, out_addr)

    def _argreduce(self, cmp_np, inval: Value, axis: int, out_data: np.ndarray
                   ) -> Value:
        m = self.m
        x = np.asarray(inval.data)
        perm = [a for a in range(x.ndim) if a != axis] + [axis]
        red_n = x.shape[axis]
        xa = (np.transpose(inval.addr, perm).reshape(-1, red_n)
              if inval.addr is not None else None)
        xd = np.transpose(x, perm).reshape(-1, red_n)
        out_data = np.asarray(out_data)
        out_addr = m.alloc(out_data.shape, out_data.dtype)
        oaddr_flat = out_addr.ravel()
        tag = _dtype_tag(x.dtype)
        ssize = _itemsize(x.dtype)
        for i in range(xd.shape[0]):
            best = m.emit_op("mov", tag, ((SRC_IMM, xd[i, 0].item()),)) \
                if xa is None else m.emit_load(int(xa[i, 0]), tag, ssize)
            bidx = m.emit_op("mov", "i", ((SRC_IMM, 0),))
            for j in range(1, red_n):
                m.emit_loop_overhead()
                if xa is None:
                    src = (SRC_IMM, xd[i, j].item())
                    cur = m.emit_op("mov", tag, (src,))
                else:
                    cur = m.emit_load(int(xa[i, j]), tag, ssize)
                c = m.emit_op("cmp", tag, ((SRC_REG, cur), (SRC_REG, best)))
                best = m.emit_op("sel", tag, ((SRC_REG, c), (SRC_REG, cur),
                                              (SRC_REG, best)), dst=best)
                bidx = m.emit_op("sel", "i", ((SRC_REG, c), (SRC_IMM, j),
                                              (SRC_REG, bidx)), dst=bidx)
            m.emit_store(int(oaddr_flat[i]), bidx, "i",
                         _itemsize(out_data.dtype))
        return Value(out_data, out_addr)

    # -------------------------------------------------------- dot_general
    def _dot_general(self, a: Value, b: Value, dnums, out_data: np.ndarray
                     ) -> Value:
        m = self.m
        (lc, rc), (lb, rb) = dnums
        A, B = np.asarray(a.data), np.asarray(b.data)

        def order(x, batch, contract):
            keep = [i for i in range(x.ndim) if i not in batch + contract]
            return list(batch) + keep + list(contract)

        pa, pb = order(A, tuple(lb), tuple(lc)), order(B, tuple(rb), tuple(rc))
        nb = int(np.prod([A.shape[i] for i in lb])) if lb else 1
        K = int(np.prod([A.shape[i] for i in lc])) if lc else 1
        Mm = A.size // (nb * K)
        Nn = B.size // (nb * K)
        Ad = np.transpose(A, pa).reshape(nb, Mm, K)
        Bd = np.transpose(B, pb).reshape(nb, Nn, K)
        Aa = (np.transpose(a.addr, pa).reshape(nb, Mm, K)
              if a.addr is not None else None)
        Ba = (np.transpose(b.addr, pb).reshape(nb, Nn, K)
              if b.addr is not None else None)
        out_data = np.asarray(out_data)
        out_addr = m.alloc(out_data.shape, out_data.dtype)
        oaddr = out_addr.reshape(nb, Mm, Nn)
        tag = _dtype_tag(out_data.dtype)
        asz, bsz = _itemsize(A.dtype), _itemsize(B.dtype)
        osize = _itemsize(out_data.dtype)
        Ad_l, Bd_l = Ad.tolist(), Bd.tolist()
        Aa_l = Aa.tolist() if Aa is not None else None
        Ba_l = Ba.tolist() if Ba is not None else None
        oaddr_l = oaddr.tolist()
        emit_overhead = m.emit_loop_overhead
        emit_load, emit_op, emit_store = m.emit_load, m.emit_op, m.emit_store
        for bi in range(nb):
            for i in range(Mm):
                a_row = Aa_l[bi][i] if Aa_l is not None else None
                ad_row = Ad_l[bi][i]
                for j in range(Nn):
                    b_row = Ba_l[bi][j] if Ba_l is not None else None
                    bd_row = Bd_l[bi][j]
                    acc = emit_op("mov", tag, ((SRC_IMM, 0),))
                    for k in range(K):
                        emit_overhead()
                        sa = ((SRC_REG, emit_load(a_row[k], tag, asz))
                              if a_row is not None else (SRC_IMM, ad_row[k]))
                        sb = ((SRC_REG, emit_load(b_row[k], tag, bsz))
                              if b_row is not None else (SRC_IMM, bd_row[k]))
                        prod = emit_op("mul", tag, (sa, sb))
                        acc = emit_op("add", tag,
                                      ((SRC_REG, acc), (SRC_REG, prod)),
                                      dst=acc)
                    emit_store(oaddr_l[bi][i][j], acc, tag, osize)
        return Value(out_data, out_addr)

    # ------------------------------------------------------- copy helpers
    def _copy_to_new_buffer(self, src: Value, out_data: np.ndarray) -> Value:
        """Materializing copy (concat / pad / dynamic slices): load+store."""
        m = self.m
        out_data = np.asarray(out_data)
        out_addr = m.alloc(out_data.shape, out_data.dtype)
        tag = _dtype_tag(out_data.dtype)
        size = _itemsize(out_data.dtype)
        sa = src.addr.ravel() if src.addr is not None else None
        sd = np.asarray(src.data).ravel()
        oa = out_addr.ravel()
        for i in range(out_data.size):
            m.emit_loop_overhead()
            if sa is None:
                r = m.emit_op("mov", tag, ((SRC_IMM, sd[i].item()),))
            else:
                r = m.emit_load(int(sa[i]), tag, size)
            m.emit_store(int(oa[i]), r, tag, size)
        return Value(out_data, out_addr)

    # ------------------------------------------------------------- gather
    def _gather_pointer_chase(self, operand: Value, out_data: np.ndarray,
                              gathered_addrs: np.ndarray,
                              index_srcs: Optional[Value]) -> Value:
        """Emit idx-load + address-arith + data-load per gathered element."""
        m = self.m
        out_data = np.asarray(out_data)
        out_addr = m.alloc(out_data.shape, out_data.dtype)
        tag = _dtype_tag(out_data.dtype)
        size = _itemsize(out_data.dtype)
        ia = (index_srcs.addr.ravel() if index_srcs is not None
              and index_srcs.addr is not None else None)
        id_flat = (np.asarray(index_srcs.data).ravel()
                   if index_srcs is not None else None)
        ga = gathered_addrs.ravel()
        oa = out_addr.ravel()
        n_idx = len(id_flat) if id_flat is not None else 0
        for i in range(out_data.size):
            m.emit_loop_overhead()
            # the index value itself is loaded (pointer chasing), then one
            # address-arith op, then the dependent data load
            if ia is not None:
                ri = m.emit_load(int(ia[i % n_idx]), "i", 4)
                m.emit_op("agen", "i", ((SRC_REG, ri), (SRC_IMM, 0)))
            r = m.emit_load(int(ga[i]), tag, size)
            m.emit_store(int(oa[i]), r, tag, size)
        return Value(out_data, out_addr)

    # ================================================================ eqns
    def eqn(self, eqn, invals: List[Value]) -> List[Value]:
        prim = eqn.primitive.name
        params = eqn.params

        # ---- elementwise binaries / unaries (hottest dispatch first: every
        # branch below keys on disjoint prim names, so order is free) -------
        ew = _EW_BINOP.get(prim)
        if ew is not None:
            op, np_fn = ew
            out = np_fn(np.asarray(invals[0].data), np.asarray(invals[1].data))
            out = np.asarray(out, dtype=eqn.outvars[0].aval.dtype)
            return [self._elementwise(op, invals, out)]
        ew = _EW_UNOP.get(prim)
        if ew is not None:
            op, np_fn = ew
            out = np_fn(np.asarray(invals[0].data))
            out = np.asarray(out, dtype=eqn.outvars[0].aval.dtype)
            return [self._elementwise(op, invals, out)]

        # ---- views: no instructions --------------------------------------
        if prim in ("reshape", "squeeze", "expand_dims"):
            shape = params.get("new_sizes") or params.get("shape") or \
                eqn.outvars[0].aval.shape
            v = invals[0]
            return [Value(np.asarray(v.data).reshape(shape),
                          v.addr.reshape(shape) if v.addr is not None else None)]
        if prim == "dynamic_slice":
            operand, *starts = invals
            sizes = params["slice_sizes"]
            st = [int(s.data) for s in starts]
            st = [max(0, min(s, operand.data.shape[i] - sizes[i]))
                  for i, s in enumerate(st)]
            sl = tuple(slice(s, s + z) for s, z in zip(st, sizes))
            v = invals[0]
            # runtime offset: the slice is a view, address-arith is implicit
            return [Value(np.asarray(v.data)[sl],
                          v.addr[sl] if v.addr is not None else None)]
        if prim == "select_n":
            # pure element selection — numpy is bit-exact with XLA here, and
            # skipping the per-eqn dispatch matters inside scan/while bodies
            pred, *cases = invals
            pd = np.asarray(pred.data)
            cds = [np.asarray(c.data) for c in cases]
            if pd.dtype == bool and len(cds) == 2:
                out = np.where(pd, cds[1], cds[0])
            elif len(cds) < 32:                    # np.choose's arity limit
                out = np.choose(pd.astype(np.int64), cds)
            else:
                out = jax.lax.select_n(pd, *cds)
            return [self._elementwise("sel", [pred] + list(cases),
                                      np.asarray(out))]
        if prim == "broadcast_in_dim":
            shape = params["shape"]
            bdims = params["broadcast_dimensions"]
            v = invals[0]
            src = np.asarray(v.data)
            expand = [1] * len(shape)
            for i, d in enumerate(bdims):
                expand[d] = src.shape[i]
            data = np.broadcast_to(src.reshape(expand), shape)
            addr = (np.broadcast_to(v.addr.reshape(expand), shape)
                    if v.addr is not None else None)
            return [Value(data, addr)]
        if prim == "convert_element_type":
            new_dt = params["new_dtype"]
            v = invals[0]
            out = np.asarray(v.data).astype(new_dt)
            if v.addr is None:
                return [Value(out, None)]
            # conversion happens in-register per element (mov)
            return [self._elementwise("mov", [v], out)]

        # ---- call-like: inline ------------------------------------------
        if prim in _CALL_PRIMS:
            sub = params.get("jaxpr") or params.get("call_jaxpr")
            if hasattr(sub, "jaxpr"):
                return self.run(sub.jaxpr, sub.consts, list(invals))
            return self.run(sub, (), list(invals))

        # ---- control flow ------------------------------------------------
        if prim == "while":
            return self._while(eqn, invals)
        if prim == "scan":
            return self._scan(eqn, invals)
        if prim == "cond":
            return self._cond(eqn, invals)

        if prim == "transpose":
            perm = params["permutation"]
            v = invals[0]
            return [Value(np.transpose(v.data, perm),
                          np.transpose(v.addr, perm) if v.addr is not None else None)]
        if prim == "rev":
            dims = params["dimensions"]
            v = invals[0]
            sl = tuple(slice(None, None, -1) if i in dims else slice(None)
                       for i in range(np.asarray(v.data).ndim))
            return [Value(np.asarray(v.data)[sl],
                          v.addr[sl] if v.addr is not None else None)]
        if prim == "slice":
            v = invals[0]
            sl = tuple(slice(b, e, s) for b, e, s in
                       zip(params["start_indices"], params["limit_indices"],
                           params["strides"] or [1] * len(params["start_indices"])))
            return [Value(np.asarray(v.data)[sl],
                          v.addr[sl] if v.addr is not None else None)]
        if prim in ("stop_gradient", "copy"):
            return [invals[0]]

        if prim == "iota":
            shape = eqn.outvars[0].aval.shape
            dt = eqn.outvars[0].aval.dtype
            dim = params.get("dimension", 0)
            n = shape[dim] if shape else 0
            base = np.arange(n, dtype=dt)
            expand = [1] * len(shape)
            expand[dim] = n
            data = np.broadcast_to(base.reshape(expand), shape)
            return [Value(data, None)]                  # generated: immediates

        # ---- select / clamp ----------------------------------------------
        if prim == "clamp":
            lo, x, hi = invals
            out = np.clip(np.asarray(x.data), np.asarray(lo.data),
                          np.asarray(hi.data))
            return [self._elementwise("sel", [lo, x, hi], np.asarray(out))]

        if prim == "integer_pow":
            y = params["y"]
            out = np.power(np.asarray(invals[0].data), y)
            return [self._elementwise("mul", invals, out)]

        # ---- reductions -----------------------------------------------------
        if prim in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                    "reduce_and", "reduce_or"):
            axes = tuple(params["axes"])
            x = np.asarray(invals[0].data)
            np_fn = {"reduce_sum": np.sum, "reduce_max": np.max,
                     "reduce_min": np.min, "reduce_prod": np.prod,
                     "reduce_and": np.all, "reduce_or": np.any}[prim]
            out = np.asarray(np_fn(x, axis=axes),
                             dtype=eqn.outvars[0].aval.dtype)
            op = {"reduce_sum": "add", "reduce_max": "max", "reduce_min": "min",
                  "reduce_prod": "mul", "reduce_and": "and",
                  "reduce_or": "or"}[prim]
            init = {"add": 0, "max": float("-inf") if x.dtype.kind == "f" else np.iinfo(x.dtype).min,
                    "min": float("inf") if x.dtype.kind == "f" else np.iinfo(x.dtype).max,
                    "mul": 1, "and": True, "or": False}[op]
            return [self._reduce(op, invals[0], axes, out, init)]
        if prim in ("argmax", "argmin"):
            axis = params["axes"][0]
            np_fn = np.argmax if prim == "argmax" else np.argmin
            out = np.asarray(np_fn(np.asarray(invals[0].data), axis=axis),
                             dtype=eqn.outvars[0].aval.dtype)
            cmp = np.greater if prim == "argmax" else np.less
            return [self._argreduce(cmp, invals[0], axis, out)]
        if prim == "cumsum":
            # sequential scan along axis: acc chains (variant c)
            axis = params["axis"]
            x = np.asarray(invals[0].data)
            out = np.cumsum(x, axis=axis).astype(eqn.outvars[0].aval.dtype)
            return [self._elementwise("add", [invals[0]], out)]
        if prim in ("cummax", "cummin"):
            axis = params["axis"]
            fn = np.maximum.accumulate if prim == "cummax" else np.minimum.accumulate
            out = fn(np.asarray(invals[0].data), axis=axis)
            return [self._elementwise("max", [invals[0]], out)]

        # ---- matmul ---------------------------------------------------------
        if prim == "dot_general":
            dnums = params["dimension_numbers"]
            A, B = np.asarray(invals[0].data), np.asarray(invals[1].data)
            out = jax.lax.dot_general(A, B, dnums)  # value oracle (on_host)
            out = np.asarray(out, dtype=eqn.outvars[0].aval.dtype)
            return [self._dot_general(invals[0], invals[1], dnums, out)]

        # ---- data movement --------------------------------------------------
        if prim == "concatenate":
            dim = params["dimension"]
            datas = [np.asarray(v.data) for v in invals]
            out = np.concatenate(datas, axis=dim)
            # one materializing copy; source addresses stacked as views
            srcs_addr = []
            for v, d in zip(invals, datas):
                srcs_addr.append(v.addr if v.addr is not None
                                 else np.full(d.shape, -1, np.int64))
            src_addr = np.concatenate(srcs_addr, axis=dim)
            merged = Value(out, None)
            if all(v.addr is None for v in invals):
                return [merged]
            fake = Value(out, src_addr)
            # elements with addr -1 come from immediates: emit mov+store
            return [self._concat_copy(fake, out)]
        if prim == "pad":
            v, pv = invals
            cfgp = params["padding_config"]
            out = np.asarray(jax.lax.pad(np.asarray(v.data),
                                         np.asarray(pv.data), cfgp))
            fake = self._pad_addr_view(v, pv, cfgp, out)
            return [self._concat_copy(fake, out)]

        if prim == "gather":
            operand, indices = invals
            out = np.asarray(_jitted_gather(
                params["dimension_numbers"], params["slice_sizes"],
                params.get("mode"))(np.asarray(operand.data),
                                    np.asarray(indices.data)))
            if operand.addr is None:
                return [self._copy_to_new_buffer(Value(out, None), out)]
            # gather flat element ids (int32, x64-safe), then map to addresses
            ids = np.arange(np.asarray(operand.data).size,
                            dtype=np.int32).reshape(np.asarray(operand.data).shape)
            gids = np.asarray(_jitted_gather(
                params["dimension_numbers"], params["slice_sizes"],
                jax.lax.GatherScatterMode.CLIP)(ids,
                                                np.asarray(indices.data)))
            gaddr = operand.addr.ravel()[gids.ravel()].reshape(out.shape)
            return [self._gather_pointer_chase(operand, out, gaddr, indices)]
        if prim in ("scatter", "scatter-add", "scatter_add"):
            return [self._scatter(eqn, invals)]

        if prim == "dynamic_update_slice":
            operand, update, *starts = invals
            st = [int(np.asarray(s.data)) for s in starts]
            od = np.asarray(operand.data)
            ud = np.asarray(update.data)
            st = [max(0, min(s, od.shape[i] - ud.shape[i]))
                  for i, s in enumerate(st)]
            out = od.copy()
            sl = tuple(slice(s, s + z) for s, z in zip(st, ud.shape))
            out[sl] = ud
            if operand.addr is None:
                base = self.m.materialize(Value(od, None))
            else:
                base = operand
            # in-place update: store the update elements into the base buffer
            self._store_region(base, update, sl)
            new = Value(out, base.addr)
            return [new]

        if prim in ("sort",):
            # small sorts appear in argsort-based code; price as n log n cmp+sel
            xs = [np.asarray(v.data) for v in invals]
            outs = jax.lax.sort(xs, dimension=params.get("dimension", -1),
                                num_keys=params.get("num_keys", 1))
            outs = outs if isinstance(outs, (list, tuple)) else [outs]
            res = []
            for v, o in zip(invals, outs):
                res.append(self._copy_to_new_buffer(v, np.asarray(o)))
            return res

        if prim in ("random_seed", "random_wrap", "random_bits", "random_unwrap"):
            # PRNG lowering: price as elementwise int ops on the output
            out_aval = eqn.outvars[0].aval
            out = np.zeros(out_aval.shape, dtype=np.uint32)
            return [Value(out, None)]

        raise NotImplementedError(
            f"trace VM: unsupported primitive '{prim}' "
            f"(params={list(params)}) — extend core/trace.py or rewrite the workload")

    # ------------------------------------------------------- concat helper
    def _concat_copy(self, fake: Value, out: np.ndarray) -> Value:
        m = self.m
        out_addr = m.alloc(out.shape, out.dtype)
        tag = _dtype_tag(out.dtype)
        size = _itemsize(out.dtype)
        sa = fake.addr.ravel()
        sd = out.ravel()
        oa = out_addr.ravel()
        for i in range(out.size):
            m.emit_loop_overhead()
            if sa[i] < 0:
                r = m.emit_op("mov", tag, ((SRC_IMM, sd[i].item()),))
            else:
                r = m.emit_load(int(sa[i]), tag, size)
            m.emit_store(int(oa[i]), r, tag, size)
        return Value(out, out_addr)

    def _pad_addr_view(self, v: Value, pv: Value, cfgp, out: np.ndarray) -> Value:
        addr = np.full(out.shape, -1, np.int64)
        sl = tuple(slice(lo, lo + (s - 1) * (st + 1) + 1, st + 1)
                   for (lo, hi, st), s in zip(cfgp, np.asarray(v.data).shape))
        if v.addr is not None:
            addr[sl] = v.addr
        return Value(out, addr)

    def _store_region(self, base: Value, update: Value, sl) -> None:
        m = self.m
        tgt_addr = base.addr[sl]
        ud = np.asarray(update.data)
        tag = _dtype_tag(ud.dtype)
        size = _itemsize(ud.dtype)
        ua = update.addr.ravel() if update.addr is not None else None
        udf = ud.ravel()
        ta = tgt_addr.ravel()
        for i in range(ud.size):
            m.emit_loop_overhead()
            if ua is None:
                r = m.emit_op("mov", tag, ((SRC_IMM, udf[i].item()),))
            else:
                r = m.emit_load(int(ua[i]), tag, size)
            m.emit_store(int(ta[i]), r, tag, size)

    def _scatter(self, eqn, invals: List[Value]) -> Value:
        operand, indices, updates = invals
        dnums = eqn.params["dimension_numbers"]
        is_add = eqn.primitive.name in ("scatter-add", "scatter_add")
        od = np.asarray(operand.data)
        idx = np.asarray(indices.data)
        ud = np.asarray(updates.data)
        base = operand if operand.addr is not None else self.m.materialize(operand)
        # destination flat ids via a marker scatter (x64-safe int32 trick);
        # duplicate destinations keep the last writer — pricing approximation.
        marker = np.asarray(_jitted_scatter(
            False, dnums, jax.lax.GatherScatterMode.CLIP)(
            np.full(od.shape, -1, np.int32), idx,
            np.arange(ud.size, dtype=np.int32).reshape(ud.shape)))
        dest_flat = np.full(ud.size, -1, np.int64)
        mk = marker.ravel()
        sel = mk >= 0
        dest_flat[mk[sel]] = np.nonzero(sel)[0]
        if is_add:
            res = np.asarray(_jitted_scatter(
                True, dnums, jax.lax.GatherScatterMode.CLIP)(od, idx, ud))
        else:
            # plain scatter: the marker already resolved the written cells
            # (and their last writer), so the result is one fancy-index
            # assignment — element movement only, bit-exact with the lax
            # scatter the marker came from
            res = od.copy()
            res.ravel()[np.nonzero(sel)[0]] = ud.ravel()[mk[sel]]
            res = np.asarray(res)
        m = self.m
        tag = _dtype_tag(ud.dtype)
        size = _itemsize(ud.dtype)
        ua = updates.addr.ravel() if updates.addr is not None else None
        udf = ud.ravel()
        ia = indices.addr.ravel() if indices.addr is not None else None
        baddr = base.addr.ravel()
        for i in range(ud.size):
            if dest_flat[i] < 0:
                continue
            m.emit_loop_overhead()
            if ia is not None:
                m.emit_load(int(ia[i % ia.size]), "i", 4)
                m.emit_op("agen", "i", ((SRC_IMM, 0),))
            if ua is None:
                r = m.emit_op("mov", tag, ((SRC_IMM, udf[i].item()),))
            else:
                r = m.emit_load(int(ua[i]), tag, size)
            tgt = int(baddr[dest_flat[i]])
            if is_add:
                rold = m.emit_load(tgt, tag, size)
                r = m.emit_op("add", tag, ((SRC_REG, rold), (SRC_REG, r)))
            m.emit_store(tgt, r, tag, size)
        return Value(res, base.addr)

    # ------------------------------------------------------- control flow
    def _while(self, eqn, invals: List[Value]) -> List[Value]:
        params = eqn.params
        cond_j, body_j = params["cond_jaxpr"], params["body_jaxpr"]
        nc, nb = params["cond_nconsts"], params["body_nconsts"]
        cconsts = invals[:nc]
        bconsts = invals[nc:nc + nb]
        carry = list(invals[nc + nb:])
        it = 0
        self.m.push_loop(key=("while", id(body_j.jaxpr)))
        try:
            while True:
                pred = self.run(cond_j.jaxpr, cond_j.consts, cconsts + carry)[0]
                self.m.emit_branch()
                if not bool(np.asarray(pred.data)):
                    break
                carry = self.run(body_j.jaxpr, body_j.consts, bconsts + carry)
                self.m.next_iteration()
                it += 1
                if it > 1_000_000:
                    raise RuntimeError("while loop runaway in trace VM")
        finally:
            self.m.pop_loop()
        return carry

    def _scan(self, eqn, invals: List[Value]) -> List[Value]:
        params = eqn.params
        j = params["jaxpr"]
        n_consts, n_carry = params["num_consts"], params["num_carry"]
        length = params["length"]
        consts = invals[:n_consts]
        carry = list(invals[n_consts:n_consts + n_carry])
        xs = invals[n_consts + n_carry:]
        ys_acc: List[List[Value]] = None
        order = range(length - 1, -1, -1) if params.get("reverse") else range(length)
        self.m.push_loop(key=("scan", id(j.jaxpr)))
        try:
            for t in order:
                x_t = []
                for x in xs:
                    d = np.asarray(x.data)[t]
                    a = x.addr[t] if x.addr is not None else None
                    x_t.append(Value(d, a))
                self.m.emit_branch()
                outs = self.run(j.jaxpr, j.consts, consts + carry + x_t)
                carry = outs[:n_carry]
                ys = outs[n_carry:]
                if ys_acc is None:
                    ys_acc = [[] for _ in ys]
                for acc, y in zip(ys_acc, ys):
                    acc.append(y)
                self.m.next_iteration()
        finally:
            self.m.pop_loop()
        ys_out: List[Value] = []
        for acc in (ys_acc or []):
            if params.get("reverse"):
                acc = acc[::-1]
            data = np.stack([np.asarray(v.data) for v in acc])
            if all(v.addr is not None for v in acc):
                addr = np.stack([v.addr for v in acc])
            else:
                addr = None
            ys_out.append(Value(data, addr))
        return carry + ys_out

    def _cond(self, eqn, invals: List[Value]) -> List[Value]:
        branches = eqn.params["branches"]
        idx = int(np.asarray(invals[0].data))
        idx = max(0, min(idx, len(branches) - 1))
        self.m.emit_branch()
        br = branches[idx]
        return self.run(br.jaxpr, br.consts, list(invals[1:]))


# ======================================================================
# Public API
# ======================================================================
@dataclasses.dataclass
class StructuralTrace:
    """Geometry-independent half of a traced program: the structural
    columns plus the interpreter's concrete outputs.  One of these is
    built per workload; :func:`attach_cache_results` replays its memory
    stream through a cache hierarchy to produce the (much cheaper)
    per-geometry :class:`TraceResult`."""
    columns: ColumnarTrace
    outputs: List[np.ndarray]

    @property
    def n_instructions(self) -> int:
        return len(self.columns)


class TraceResult:
    """One traced (program, cache geometry) pair: the columnar CIQ with
    memory-response columns filled, the replayed cache hierarchy (for its
    statistics), and the program outputs.  ``rut`` / ``iht`` are derived
    views, reconstructed vectorized on first access."""

    __slots__ = ("trace", "cache", "outputs", "structural")

    def __init__(self, trace: ColumnarTrace, cache: CacheHierarchy,
                 outputs: List[np.ndarray],
                 structural: Optional[StructuralTrace] = None):
        self.trace = trace
        self.cache = cache
        self.outputs = outputs
        self.structural = structural

    @property
    def rut(self) -> Dict[int, List[int]]:
        return self.trace.rut

    @property
    def iht(self) -> Dict[int, List[Tuple[int, int]]]:
        return self.trace.iht

    @property
    def n_instructions(self) -> int:
        return len(self.trace)

    def mem_accesses(self) -> int:
        return self.trace.mem_accesses()


def trace_structural(fn: Callable, *args, n_regs: int = 24,
                     limits: TraceLimits = TraceLimits()) -> StructuralTrace:
    """Lower ``fn(*args)`` to the structural instruction columns (no cache
    model involved — the stream is identical under every geometry)."""
    closed = jax.make_jaxpr(fn)(*args)
    machine = Machine(n_regs=n_regs, limits=limits)
    interp = TraceInterpreter(machine)
    arg_vals = [machine.store_const(np.asarray(a))
                for a in jax.tree_util.tree_leaves(args)]
    with on_host():
        outs = interp.run(closed.jaxpr, closed.consts, arg_vals)
    return StructuralTrace(machine.b.finish(machine.n_regs),
                           [np.asarray(v.data) for v in outs])


def attach_cache_results(st: StructuralTrace,
                         cache_levels: Tuple[CacheConfig, ...] = (L1_32K,
                                                                  L2_256K)
                         ) -> TraceResult:
    """Replay the structural trace's memory stream through a fresh cache
    hierarchy, producing the per-geometry level/hit/bank/MSHR columns —
    byte-identical to recording the accesses at emission time, at a
    fraction of the cost of re-interpreting the program."""
    return attach_cache_results_batch(st, [cache_levels])[0]


def attach_cache_results_batch(st: StructuralTrace,
                               geometries: Sequence[Tuple[CacheConfig, ...]]
                               ) -> List[TraceResult]:
    """Replay one structural trace under many cache geometries.

    The structural columns are shared; each geometry only needs its own
    level/hit/bank/MSHR columns.  Under ``EVA_CIM_ACCEL=jax`` every
    geometry comes out of one batched accelerator replay
    (:func:`repro.core.accel.replay_columns`, differentially tested
    bit-exact against :meth:`CacheHierarchy.replay`); the numpy path —
    and any batch the accelerator declines — replays per geometry."""
    from repro.core import accel

    ct = st.columns
    mem_idx = np.flatnonzero(ct.mem_mask)
    addrs = ct.addr[mem_idx]
    is_writes = ct.op[mem_idx] == OP_STORE
    batched = accel.replay_columns(addrs, is_writes, list(geometries))
    out = []
    for gi, cache_levels in enumerate(geometries):
        hier = CacheHierarchy(cache_levels)
        if batched is not None and batched[gi] is not None:
            lvl, hit, bank, mshr, counters = batched[gi]
            hier.restore_counters(counters)   # sets stay cold, like the
        else:                                 # store's rehydration path
            lvl, hit, bank, mshr = hier.replay(addrs, is_writes)
        level_col = np.zeros(ct.n, np.int8)
        hit_col = np.full(ct.n, -1, np.int8)
        bank_col = np.full(ct.n, -1, np.int16)
        mshr_col = np.zeros(ct.n, bool)
        level_col[mem_idx] = lvl
        hit_col[mem_idx] = hit
        bank_col[mem_idx] = bank
        mshr_col[mem_idx] = mshr
        out.append(TraceResult(ct.with_mem_results(level_col, hit_col,
                                                   bank_col, mshr_col),
                               hier, st.outputs, structural=st))
    return out


def trace_program(fn: Callable, *args,
                  cache_levels: Tuple[CacheConfig, ...] = (L1_32K, L2_256K),
                  n_regs: int = 24,
                  limits: TraceLimits = TraceLimits()) -> TraceResult:
    """Run ``fn(*args)`` on the trace VM; returns the CIQ + probe tables.

    ``args`` are treated as memory-resident program inputs (like benchmark
    data loaded before the region of interest); jaxpr literals and iota
    lower to immediates.
    """
    return attach_cache_results(trace_structural(fn, *args, n_regs=n_regs,
                                                 limits=limits),
                                cache_levels)
