"""Columnar-vs-row path equivalence (the PR-5 tentpole's safety net).

The columnar trace core re-derives everything the object-based pipeline
used to build incrementally — RUT/IHT, the producer index, the flow maps,
the IDG forest, the candidate partition — vectorized from the columns.
These tests drive random small jaxpr programs (hypothesis) plus the three Fig. 4 pattern variants through BOTH
paths and require identical results:

  * the ``Inst`` row views are faithful to the columns, and reconstructing
    RUT/IHT with the original incremental commit-time algorithm from those
    rows matches the vectorized tables;
  * the flow index (reg consumers / stores / load sources) matches the
    original object-at-a-time construction;
  * IDG forests have identical shapes, node seqs, and leaf payloads;
  * Algorithm 1 returns identical candidate sets, claimed sets, reshapes,
    and (approx-equal) priced reports through both paths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import trace_program
from repro.core.columnar import ColumnarTrace
from repro.core.idg import IDGBuilder, _build_flow_rows, build_flow_index
from repro.core.isa import CIM_SET_STT, SRC_IMM, SRC_REG
from repro.core.offload import OffloadConfig, select_candidates
from repro.core.profiler import profile_system
from repro.core.reshape import reshape

# ----------------------------------------------------------------------
# the three Fig. 4 pattern variants as explicit programs
# ----------------------------------------------------------------------
def _variant_a(n):          # Load-Load-OP-Store: both operands from memory
    a = jnp.arange(n, dtype=jnp.int32)
    b = jnp.arange(n, dtype=jnp.int32) * 2
    return (lambda a, b: (a + b) ^ a), (a, b)


def _variant_b(n):          # Load-Imm-OP-Store: literal lowers to immediate
    a = jnp.arange(n, dtype=jnp.int32)
    return (lambda a: (a & 7) + 3), (a,)


def _variant_c(n):          # OP-(reg)-OP chains: reduction accumulators
    a = jnp.asarray(np.random.default_rng(0).integers(0, 50, n), jnp.int32)
    return (lambda a: jnp.sum((a + 1) ^ a)), (a,)


FIG4_VARIANTS = (_variant_a, _variant_b, _variant_c)


def _rebuild_rut_iht_incremental(rows, n_regs):
    """The original probe algorithm: RUT/IHT built at commit time."""
    rut = {r: [] for r in range(n_regs + 1)}
    iht = {}
    for inst in rows:
        srcs_regs = [v for t, v in inst.srcs if t == SRC_REG]
        iht[inst.seq] = [(r, len(rut[r]) - 1) for r in srcs_regs]
        if inst.dst is not None:
            rut[inst.dst].append(inst.seq)
    return rut, iht


def _forest_shape(forest):
    """Comparable structure of an IDG forest: node seqs + leaf payloads."""
    def node_shape(node):
        out = [("op", node.inst.seq)]
        for kind, payload in node.children:
            if kind == "node":
                out.append(("sub", node_shape(payload)))
            elif kind in ("load", "memval"):
                out.append((kind, payload.seq))
            else:
                out.append((kind, payload))
        return out

    return [node_shape(t) for t in forest]


def _cand_tuple(c):
    return (c.root_seq, tuple(c.op_seqs), tuple(c.op_classes),
            tuple(c.load_seqs), tuple(c.store_seqs), c.level, c.bank,
            c.moves, c.internal_edges, c.added_loads, c.memval_leaves,
            c.dram_fills)


def _check_equivalence(fn, args, cfg=OffloadConfig()):
    tr = trace_program(fn, *args)
    ct = tr.trace
    assert isinstance(ct, ColumnarTrace)
    rows = list(ct)                                    # materialized row path

    # --- row views faithful to the columns ------------------------------
    for seq, inst in enumerate(rows):
        assert inst.seq == seq
        assert inst.op == ct.op[seq] or True           # decoded below
    from repro.core.isa import LEVELS, OPS, UNITS
    for seq in (0, len(rows) // 2, len(rows) - 1):
        inst = rows[seq]
        assert inst.op == OPS[ct.op[seq]]
        assert inst.unit == UNITS[ct.unit[seq]]
        assert inst.level == LEVELS[ct.level[seq]]
        assert (inst.dst if inst.dst is not None else -1) == ct.dst[seq]

    # --- RUT/IHT: vectorized == incremental over the same stream --------
    ref_rut, ref_iht = _rebuild_rut_iht_incremental(rows, ct.n_regs)
    assert tr.rut == ref_rut
    assert tr.iht == ref_iht

    # --- flow maps: vectorized == object-at-a-time ----------------------
    fast = build_flow_index(ct)
    slow = _build_flow_rows(rows, ref_rut, ref_iht)
    assert fast.reg_consumers == slow.reg_consumers
    assert fast.store_of == slow.store_of
    assert fast.load_source == slow.load_source
    assert fast.value_loads == slow.value_loads

    # --- IDG forests ----------------------------------------------------
    fast_forest = IDGBuilder(ct).build_forest(cfg.cim_set)
    slow_forest = IDGBuilder(rows, ref_rut, ref_iht).build_forest(cfg.cim_set)
    assert _forest_shape(fast_forest) == _forest_shape(slow_forest)

    # --- Algorithm 1: candidates, claimed, reshape, pricing -------------
    fast_res = select_candidates(ct, cfg=cfg)
    slow_res = select_candidates(rows, ref_rut, ref_iht, cfg)
    assert [_cand_tuple(c) for c in fast_res.candidates] == \
        [_cand_tuple(c) for c in slow_res.candidates]
    assert fast_res.claimed == slow_res.claimed
    fast_rs = reshape(ct, fast_res)
    slow_rs = reshape(rows, slow_res)
    assert fast_rs.host_seqs == slow_rs.host_seqs
    assert fast_rs.cim_groups == slow_rs.cim_groups
    assert fast_rs.moves == slow_rs.moves
    assert fast_rs.added_loads == slow_rs.added_loads
    assert fast_rs.dram_fills == slow_rs.dram_fills

    rep_fast = profile_system(tr, cfg, offload=fast_res, reshaped=fast_rs)
    rep_slow = profile_system(tr, cfg, offload=slow_res, reshaped=slow_rs)
    assert rep_fast.energy_improvement == \
        pytest.approx(rep_slow.energy_improvement)
    assert rep_fast.speedup == pytest.approx(rep_slow.speedup)
    assert rep_fast.macr == rep_slow.macr
    return tr


# ---------------------------------------------------------------- fig. 4
@pytest.mark.parametrize("variant", FIG4_VARIANTS,
                         ids=["load_load_op", "load_imm_op", "reg_chain"])
def test_fig4_variants_equivalent(variant):
    fn, args = variant(24)
    tr = _check_equivalence(fn, args)
    kinds = set()
    for inst in tr.trace:
        if inst.op in ("add", "xor", "and"):
            tags = tuple(t for t, _ in inst.srcs)
            if tags == (SRC_REG, SRC_REG):
                kinds.add("reg_reg")
            if SRC_IMM in tags:
                kinds.add("imm")
    assert kinds                                   # the pattern is present


def test_same_bank_config_equivalent():
    """Placement-constrained configs run the generic single-pass path on
    columns — still identical to the row path."""
    fn, args = _variant_a(32)
    _check_equivalence(fn, args, OffloadConfig(require_same_bank=True))
    _check_equivalence(fn, args, OffloadConfig(allow_cross_level=False,
                                               cim_levels=("L1",)))


# ------------------------------------------------------- random programs
_OPS = ("add", "xor", "and", "or", "sub", "max")


@settings(max_examples=12, deadline=None)
@given(st.integers(4, 40), st.integers(0, 6), st.sampled_from(_OPS),
       st.sampled_from(_OPS))
def test_property_random_programs_equivalent(n, seed, op1, op2):
    r = np.random.default_rng(seed)
    a = jnp.asarray(r.integers(0, 100, (n,)), jnp.int32)
    b = jnp.asarray(r.integers(1, 100, (n,)), jnp.int32)
    f1 = getattr(jnp, {"add": "add", "xor": "bitwise_xor",
                       "and": "bitwise_and", "or": "bitwise_or",
                       "sub": "subtract", "max": "maximum"}[op1])
    f2 = getattr(jnp, {"add": "add", "xor": "bitwise_xor",
                       "and": "bitwise_and", "or": "bitwise_or",
                       "sub": "subtract", "max": "maximum"}[op2])

    def prog(a, b):
        c = f1(a, b)
        d = f2(c, a)
        return jnp.sum(d) + jnp.max(c)

    _check_equivalence(prog, (a, b))


@settings(max_examples=6, deadline=None)
@given(st.integers(3, 12), st.integers(0, 4))
def test_property_scan_programs_equivalent(n, seed):
    r = np.random.default_rng(seed + 100)
    x = jnp.asarray(r.integers(0, 20, (n,)), jnp.int32)

    def prog(x):
        def body(c, t):
            c = c + (t ^ c)
            return c, c
        return jax.lax.scan(body, jnp.int32(1), x)

    _check_equivalence(prog, (x,))


# ----------------------------------------------------- key-lock pruning
def test_analysis_cache_key_locks_pruned():
    """Satellite: completed layers release their build locks — long
    adaptive runs must not leak one threading.Lock per analysis key."""
    from repro.dse import AnalysisCache
    from repro.dse.space import CacheOption
    cache = AnalysisCache()
    cache.trace("NB", CacheOption.of("32K+256K"))
    cache.offload("NB", CacheOption.of("32K+256K"), OffloadConfig())
    cache.artifact(1, ("blob", "x"), lambda: 42)
    assert cache._key_locks == {}
    # and the artifacts really are memoized (hits, not rebuilds)
    cache.trace("NB", CacheOption.of("32K+256K"))
    assert cache.trace_hits >= 1 and cache._key_locks == {}
