"""Differential harness for the accelerated analysis path (PR-7 tentpole).

``repro.core.accel`` re-implements the two numpy hot loops — the cache
replay state machine and Algorithm 1's vectorized placement — as jitted
jax kernels.  The numpy implementations stay in the tree as the
reference oracle, and these tests are the contract that keeps the two
backends interchangeable:

  * random access streams x random geometry *batches* through
    :func:`replay_columns_batch` vs element-exact
    :meth:`CacheHierarchy.replay` — bit-equal level/hit/bank/MSHR
    columns and equal counter dicts (LRU order, MSHR FIFO/merge,
    writeback cascades and all);
  * random programs x geometries x offload configs through the full
    ``trace -> select -> price`` pipeline under ``use_backend("jax")``
    vs numpy — identical candidate tuples, claimed sets, and *exactly*
    equal priced energy/speedup/MACR (the figure artifacts must stay
    byte-identical under ``EVA_CIM_ACCEL=jax``);
  * the Pallas segment-reduce kernels (interpret mode on CPU) vs the
    XLA ``jax.ops`` segment ops they substitute for;
  * the batched ``attach_cache_results_batch`` vs geometry-at-a-time
    numpy attachment.

Geometry parameters are drawn from a small fixed pool so the jit cache
stays bounded (shapes are padded to powers of two — see
``repro.core.accel.replay``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import accel, trace_program
from repro.core.accel.replay import replay_columns_batch
from repro.core.cache import (CacheConfig, CacheHierarchy, L1_32K, L1_64K,
                              L2_256K, L2_2M, SPM_1M)
from repro.core.offload import OffloadConfig, select_candidates
from repro.core.profiler import profile_system
from repro.core.trace import (attach_cache_results,
                              attach_cache_results_batch, trace_structural)


def _g(sets, assoc, banks, mshrs, name="L1"):
    return CacheConfig(name, sets * 64 * assoc, assoc,
                       banks=banks, mshrs=mshrs)


# small geometries exercise every replacement/merge corner (direct-mapped,
# single-set, one-entry MSHR files) while keeping padded state tiny
GEOMETRIES = (
    (_g(1, 1, 1, 1),),
    (_g(4, 4, 4, 2),),
    (_g(1, 4, 2, 1),),
    (_g(4, 1, 1, 2), _g(4, 4, 4, 2, "L2")),
    (_g(1, 1, 1, 1), _g(4, 1, 2, 1, "L2")),
    (_g(4, 4, 4, 2), _g(4, 4, 1, 2, "L2")),
    (_g(1, 2, 2, 2), _g(1, 4, 4, 1, "L2")),
)
PRESETS = ((L1_32K, L2_256K), (L1_64K, L2_256K), (L1_64K, L2_2M), (SPM_1M,))

_OPS = ("add", "xor", "and", "or", "sub", "max")
_JNP_OP = {"add": "add", "xor": "bitwise_xor", "and": "bitwise_and",
           "or": "bitwise_or", "sub": "subtract", "max": "maximum"}
CFGS = (OffloadConfig(),
        OffloadConfig(cim_levels=("L1",)),
        OffloadConfig(cim_levels=("L2",)))


def _decode_stream(encoded):
    """One int per access: bit 0 is the store flag, the rest the address
    (single-list encoding keeps the strategies stub-compatible)."""
    addrs = np.asarray([v >> 1 for v in encoded], np.int64)
    wr = np.asarray([v & 1 for v in encoded], bool)
    return addrs, wr


def _cand_tuple(c):
    return (c.root_seq, tuple(c.op_seqs), tuple(c.op_classes),
            tuple(c.load_seqs), tuple(c.store_seqs), c.level, c.bank,
            c.moves, c.internal_edges, c.added_loads, c.memval_leaves,
            c.dram_fills)


def _assert_columns_equal(ref_cols, jax_cols):
    for name, a, b in zip(("level", "hit", "bank", "mshr"),
                          ref_cols, jax_cols):
        assert np.array_equal(a, b), name


# ======================================================================
# replay: stream + counters vs the CacheHierarchy oracle  (110 examples)
# ======================================================================
@settings(max_examples=110, deadline=None)
@given(st.lists(st.integers(0, 2 * 26 * 64 - 1), min_size=0, max_size=60),
       st.lists(st.sampled_from(GEOMETRIES), min_size=1, max_size=3))
def test_replay_stream_differential(encoded, geos):
    addrs, wr = _decode_stream(encoded)
    out = replay_columns_batch(addrs, wr, geos)
    assert out is not None and len(out) == len(geos)
    for gi, levels in enumerate(geos):
        hier = CacheHierarchy(levels)
        ref = hier.replay(addrs, wr)
        lvl, hit, bank, mshr, counters = out[gi]
        _assert_columns_equal(ref, (lvl, hit, bank, mshr))
        assert (lvl.dtype, hit.dtype, bank.dtype, mshr.dtype) == \
            (np.int8, np.int8, np.int16, np.bool_)
        assert counters == hier.counters()


# ======================================================================
# end-to-end: select + price under use_backend("jax")  (40 examples)
# ======================================================================
@settings(max_examples=40, deadline=None)
@given(st.integers(4, 24), st.integers(0, 5), st.sampled_from(_OPS),
       st.sampled_from(_OPS), st.sampled_from(GEOMETRIES),
       st.sampled_from(CFGS))
def test_selection_differential(n, seed, op1, op2, geo, cfg):
    if len(geo) == 1 and "L2" in cfg.cim_levels:
        cfg = CFGS[1]          # single-level geometries price L1-CiM only
    r = np.random.default_rng(seed)
    a = jnp.asarray(r.integers(0, 100, (n,)), jnp.int32)
    b = jnp.asarray(r.integers(1, 100, (n,)), jnp.int32)
    f1, f2 = getattr(jnp, _JNP_OP[op1]), getattr(jnp, _JNP_OP[op2])

    def prog(a, b):
        c = f1(a, b)
        d = f2(c, a)
        return jnp.sum(d) + jnp.max(c)

    struct = trace_structural(prog, a, b)
    tr_np = attach_cache_results(struct, geo)
    res_np = select_candidates(tr_np.trace, cfg=cfg)
    rep_np = profile_system(tr_np, cfg, offload=res_np)
    with accel.use_backend("jax"):
        tr_j = attach_cache_results(struct, geo)
        res_j = select_candidates(tr_j.trace, cfg=cfg)
        rep_j = profile_system(tr_j, cfg, offload=res_j)

    for col in ("level", "hit", "bank", "mshr"):
        assert np.array_equal(getattr(tr_np.trace, col),
                              getattr(tr_j.trace, col)), col
    assert tr_np.cache.counters() == tr_j.cache.counters()
    assert [_cand_tuple(c) for c in res_np.candidates] == \
        [_cand_tuple(c) for c in res_j.candidates]
    assert res_np.claimed == res_j.claimed
    # pricing must be EXACTLY equal — the figure artifacts are compared
    # byte-for-byte across backends
    assert rep_np.energy_improvement == rep_j.energy_improvement
    assert rep_np.speedup == rep_j.speedup
    assert rep_np.macr == rep_j.macr


# ======================================================================
# pallas segment kernels vs the XLA ops they replace  (30 examples)
# ======================================================================
@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 70 * 41 - 1), min_size=0, max_size=300),
       st.integers(1, 40))
def test_pallas_segment_ops_match_xla(encoded, n_seg):
    from repro.core.accel import pallas_ops
    ids = jnp.asarray([v % n_seg for v in encoded], jnp.int32)
    vals = jnp.asarray([v // 41 - 10 for v in encoded], jnp.int32)
    s_ref = jax.ops.segment_sum(vals, ids, num_segments=n_seg)
    m_ref = jax.ops.segment_max(vals, ids, num_segments=n_seg)
    assert np.array_equal(pallas_ops.segment_sum(vals, ids, n_seg), s_ref)
    assert np.array_equal(pallas_ops.segment_max(vals, ids, n_seg), m_ref)


# ======================================================================
# batched attachment vs geometry-at-a-time numpy  (20 examples)
# ======================================================================
@settings(max_examples=20, deadline=None)
@given(st.integers(4, 24), st.integers(0, 5),
       st.lists(st.sampled_from(GEOMETRIES), min_size=1, max_size=3))
def test_attach_batch_differential(n, seed, geos):
    r = np.random.default_rng(seed + 7)
    a = jnp.asarray(r.integers(0, 64, (n,)), jnp.int32)

    def prog(a):
        return jnp.sum((a * 3) ^ a)

    struct = trace_structural(prog, a)
    with accel.use_backend("jax"):
        batch = attach_cache_results_batch(struct, geos)
    for gi, geo in enumerate(geos):
        ref = attach_cache_results(struct, geo)
        for col in ("level", "hit", "bank", "mshr"):
            assert np.array_equal(getattr(ref.trace, col),
                                  getattr(batch[gi].trace, col)), col
        assert ref.cache.counters() == batch[gi].cache.counters()


# ======================================================================
# deterministic cases
# ======================================================================
def test_nb_fig14_geometries_bit_exact():
    """The fig14 sweep's real workload x cache presets: full pipeline
    equality on the artifact-bearing path (trace columns, counters,
    candidates, and exactly-equal priced reports)."""
    from repro.workloads import build
    fn, args = build("NB")
    struct = trace_structural(fn, *args)
    for geo in PRESETS:
        cfg = OffloadConfig() if len(geo) > 1 \
            else OffloadConfig(cim_levels=("L1",))
        tr_np = attach_cache_results(struct, geo)
        res_np = select_candidates(tr_np.trace, cfg=cfg)
        rep_np = profile_system(tr_np, cfg, offload=res_np)
        with accel.use_backend("jax"):
            tr_j = attach_cache_results(struct, geo)
            res_j = select_candidates(tr_j.trace, cfg=cfg)
            rep_j = profile_system(tr_j, cfg, offload=res_j)
        for col in ("level", "hit", "bank", "mshr"):
            assert np.array_equal(getattr(tr_np.trace, col),
                                  getattr(tr_j.trace, col)), (geo, col)
        assert tr_np.cache.counters() == tr_j.cache.counters()
        assert [_cand_tuple(c) for c in res_np.candidates] == \
            [_cand_tuple(c) for c in res_j.candidates]
        assert rep_np.energy_improvement == rep_j.energy_improvement
        assert rep_np.speedup == rep_j.speedup
        assert rep_np.macr == rep_j.macr


def test_replay_fig14_shape_class_bit_exact():
    """One seeded stream under the three Fig. 14 presets in one batched
    call, at the fig14 sweep's padded shape class: 3 geometries padded to
    4, 4,096 sets, 8 ways, 8 MSHRs.  The stream runs in blocks of 50
    accesses of one of three kinds: spread over a 1 MB footprint; crowding
    24 lines into four sets of every level, so that L1's dirty victims
    cascade into L2 and L2's dirty victims fall to memory; cycling five
    lines through one 4-way L1 set, so that misses merge in the MSHRs."""
    from repro.core.accel import replay
    geos = PRESETS[:3]
    rng = np.random.default_rng(1814)
    n = 4000
    stride = max(c.n_sets for geo in geos for c in geo) * 64
    spread = rng.integers(0, 1 << 20, n)
    crowd = rng.integers(0, 4, n) * 64 + rng.integers(0, 24, n) * stride
    cycle = 7 * 64 + np.arange(n) % 5 * stride
    kind = rng.integers(0, 3, n // 50).repeat(50)
    addrs = np.choose(kind, [spread, crowd, cycle]).astype(np.int64)
    wr = rng.random(n) < 0.4

    (_, fn, args), = replay._launches(addrs, wr, geos)
    assert fn is replay._build(2, 4096, 8, 8) and args[0].shape == (4, 2)
    out = replay_columns_batch(addrs, wr, geos)
    for gi, levels in enumerate(geos):
        hier = CacheHierarchy(levels)
        ref = hier.replay(addrs, wr)
        lvl, hit, bank, mshr, counters = out[gi]
        _assert_columns_equal(ref, (lvl, hit, bank, mshr))
        assert counters == hier.counters()
        assert counters["L1_writebacks"] > 0, counters
        assert counters["L2_writebacks"] > 0 and counters["mem_writes"] > 0
        assert mshr.any()


@settings(max_examples=25, deadline=None)
@given(st.integers(4, 24), st.integers(0, 5), st.sampled_from(_OPS),
       st.sampled_from(GEOMETRIES), st.sampled_from(CFGS))
def test_place_candidates_jax_differential(n, seed, op1, geo, cfg):
    """place_candidates_jax vs its numpy twin ``offload._place``, called
    directly on the same structural partition (not through the backend
    switch) — identical candidate tuples in identical order."""
    from repro.core.accel.place import place_candidates_jax
    from repro.core.idg import IDGBuilder
    from repro.core.offload import _partition, _place, build_flow_index

    if len(geo) == 1 and "L2" in cfg.cim_levels:
        cfg = CFGS[1]
    r = np.random.default_rng(seed + 13)
    a = jnp.asarray(r.integers(0, 100, (n,)), jnp.int32)
    b = jnp.asarray(r.integers(1, 100, (n,)), jnp.int32)
    f1 = getattr(jnp, _JNP_OP[op1])

    def prog(a, b):
        c = f1(a, b)
        return jnp.sum(c ^ a) + jnp.max(c)

    struct = trace_structural(prog, a, b)
    ct = attach_cache_results(struct, geo).trace
    part = _partition(ct, IDGBuilder(ct), build_flow_index(ct), cfg)
    with accel.use_backend("numpy"):
        ref = _place(part, ct, cfg)
    got = place_candidates_jax(part, ct, cfg)
    assert got is not None
    assert [_cand_tuple(c) for c in got] == [_cand_tuple(c) for c in ref]


def test_backend_switch():
    """Env-var default, in-process override, and validation."""
    assert accel.backend() in ("numpy", "jax")
    with accel.use_backend("jax"):
        assert accel.enabled()
        with accel.use_backend("numpy"):
            assert not accel.enabled()
        assert accel.enabled()
    with pytest.raises(ValueError):
        accel.set_backend("cuda")
    # numpy backend: the batched entry points decline immediately
    with accel.use_backend("numpy"):
        assert accel.replay_columns(np.zeros(1, np.int64), np.zeros(1, bool),
                                    [PRESETS[0]]) is None


def test_jit_compile_accounting():
    """Replaying an already-compiled shape must not add specializations —
    the service's zero-recompile guarantee hangs off this counter."""
    addrs = (np.arange(40, dtype=np.int64) % 7) * 64
    wr = np.zeros(40, bool)
    geos = [GEOMETRIES[0], GEOMETRIES[3]]
    replay_columns_batch(addrs, wr, geos)
    before = accel.jit_compiles()
    assert before > 0
    out = replay_columns_batch(addrs + 64, ~wr, geos)
    assert out is not None
    assert accel.jit_compiles() == before


def test_fallback_counter_on_int32_overflow():
    """A stream beyond the int32 line budget is one counted fallback under
    the jax backend, and none under numpy (which never tries jax)."""
    addrs = np.asarray([0, 2 ** 40], np.int64)
    wr = np.zeros(2, bool)
    before = accel.fallbacks()
    with accel.use_backend("jax"):
        assert accel.replay_columns(addrs, wr, [GEOMETRIES[0]]) is None
    assert accel.fallbacks() == before + 1
    with accel.use_backend("numpy"):
        assert accel.replay_columns(addrs, wr, [GEOMETRIES[0]]) is None
    assert accel.fallbacks() == before + 1


def test_replay_overflow_falls_back():
    """Streams beyond the kernel's int32 line budget decline the batch;
    attachment then transparently uses the numpy oracle."""
    addrs = np.asarray([0, 2 ** 40], np.int64)
    assert replay_columns_batch(addrs, np.zeros(2, bool),
                                [GEOMETRIES[0]]) is None

    a = jnp.arange(16, dtype=jnp.int32)
    struct = trace_structural(lambda a: jnp.sum(a + a), a)
    ref = attach_cache_results(struct, PRESETS[0])
    with accel.use_backend("jax"):
        out = attach_cache_results(struct, PRESETS[0])
    for col in ("level", "hit", "bank", "mshr"):
        assert np.array_equal(getattr(ref.trace, col),
                              getattr(out.trace, col))


def test_kernel_names_are_stable_in_the_lowered_modules():
    """The device-trace reduction finds the placement kernel by its
    module, ``jit_kernel``, and a profile tells the replay's scan steps
    apart by their named scope: both survive in the lowered text."""
    import re
    from repro.core.accel import place, replay

    i32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)
    fn = place._build.__wrapped__(64, 64, 16, (0, 1), 1, False)
    lowered = fn.lower(i32(64), i32(64), i32(64), i32(64), i32(64),
                       jax.ShapeDtypeStruct((), jnp.int32))
    module = re.search(r"module @(\S+)", lowered.as_text()).group(1)
    assert module.startswith("jit_kernel")
    assert f"{place.SCOPE}/" in lowered.as_text(debug_info=True)

    fn = replay._build.__wrapped__(2, 64, 8, 8)
    params = [jax.ShapeDtypeStruct((4, 2), jnp.int32)] * 4
    stream = [jax.ShapeDtypeStruct((128,), dt)
              for dt in (jnp.int32, jnp.bool_, jnp.bool_)]
    text = fn.lower(*params, *stream).as_text(debug_info=True)
    assert f"{replay.STEP_SCOPE}/" in text
