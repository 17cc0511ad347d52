"""Batched cache replay: ``CacheHierarchy.replay`` as a jitted jax scan.

One call evaluates *all* cache geometries of a sweep against the shared
structural access stream: the per-geometry LRU/MSHR/writeback state
machine runs as a single ``lax.scan`` over the stream, ``vmap``-ped
across the geometry batch, so N geometries cost one kernel launch
instead of N python replays.

Bit-exactness with the :class:`~repro.core.cache.CacheHierarchy` oracle
is the contract (the differential suite in ``tests/test_accel.py``
fuzzes it).  The OrderedDict semantics map onto arrays as follows:

  * **LRU order** — each resident way carries a monotonically increasing
    stamp ``t * K + slot``; ``t`` is the access index, ``slot`` numbers
    the python-side touch points of one access in their exact execution
    order (probes first, then the demand-fill/cascade-writeback chain of
    :meth:`CacheHierarchy._access`).  ``move_to_end`` is a fresh stamp;
    the eviction victim is the min-stamp resident way.  Same-set
    collisions inside one access (a cascade landing in the set a demand
    fill is about to evict from) resolve exactly like the dict, because
    the cascade's slot precedes the next demand fill's slot.
  * **MSHR file** — a per-level ``(M,)`` line array with insertion
    stamps; FIFO retirement evicts the min-stamp entry.  A merge
    (line already outstanding) bumps the count in python — which is
    never read and does not reorder — so it is a pure membership test.
  * **mark_dirty** — a dict value assignment: dirty bit only, no stamp.

Counters (hits/misses/writebacks/mem traffic) are derived from the
service levels plus two scanned accumulators, matching
:meth:`CacheHierarchy.counters` key-for-key so a fresh hierarchy can be
rehydrated with :meth:`~CacheHierarchy.restore_counters` (the same
counters-only contract the on-disk store already relies on).

Shapes are padded to powers of two (stream length, sets, ways, MSHRs,
batch) so repeated sweeps and fuzzed geometry batches reuse jit cache
entries; every jitted entry point is registered with
:func:`repro.core.accel.register_jitted` for compile accounting.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.accel import register_jitted
from repro.core.cache import LINE, CacheConfig
from repro.core.isa import LEVEL_CODE, LEVEL_MEM

_I32_LIM = 2 ** 31 - 1
STEP_SCOPE = "eva_cim.replay.step"       # named scope of one scan step's ops


def _pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1)."""
    return 1 << (max(1, int(n)) - 1).bit_length()


def _slots(n_levels: int):
    """Stamp slot ids for the touch points of one access, in the exact
    python execution order of ``CacheHierarchy._access``: probes for each
    level, then per demand-filled level its fill followed by the cascade
    writeback chain into the deeper levels."""
    lookup = list(range(n_levels))
    slot = n_levels
    demand = [0] * n_levels
    cascade = [[0] * n_levels for _ in range(n_levels)]
    for i in range(n_levels):
        demand[i] = slot
        slot += 1
        for m in range(i + 1, n_levels):
            cascade[i][m] = slot
            slot += 1
    return lookup, demand, cascade, slot    # slot == stamps per access


@functools.lru_cache(maxsize=None)
def _build(L: int, S: int, A: int, M: int):
    """Jitted, geometry-vmapped replay for L-level hierarchies padded to
    (S sets, A ways, M MSHR entries).  Cached per padded shape so every
    sweep over same-depth geometries shares one compilation."""
    lookup_slot, demand_slot, cascade_slot, K = _slots(L)
    BIG = jnp.int32(_I32_LIM)

    def geom(n_sets, assoc, banks, mshrs, lines, is_w, valid):
        ways = jnp.arange(A, dtype=jnp.int32)
        mslots = jnp.arange(M, dtype=jnp.int32)

        def fill(tags, dirty, stamp, l, set_l, line, dirty_in, en, stamp_val):
            """``_Level.fill`` at level ``l``: present -> refresh stamp and
            OR the dirty bit; absent -> insert (LRU-evicting when full),
            returning the dirty-victim flag + line for the cascade."""
            row_t, row_d, row_s = tags[l, set_l], dirty[l, set_l], stamp[l, set_l]
            present_vec = row_t == line
            present = present_vec.any()
            occ = row_t >= 0
            full = occ.sum() >= assoc[l]
            free_way = jnp.argmax(~occ & (ways < assoc[l]))
            lru_way = jnp.argmin(jnp.where(occ, row_s, BIG))
            ins_way = jnp.where(full, lru_way, free_way)
            way = jnp.where(present, jnp.argmax(present_vec), ins_way)
            victim = en & ~present & full & row_d[ins_way]
            victim_line = jnp.where(victim, row_t[ins_way], 0)
            new_d = jnp.where(present, row_d[way] | dirty_in, dirty_in)
            tags = tags.at[l, set_l, way].set(
                jnp.where(en, line, row_t[way]))
            dirty = dirty.at[l, set_l, way].set(
                jnp.where(en, new_d, row_d[way]))
            stamp = stamp.at[l, set_l, way].set(
                jnp.where(en, stamp_val, row_s[way]))
            return victim, victim_line, tags, dirty, stamp

        def access(carry, x):
            tags, dirty, stamp, mlines, mstamp, wbs, memw, t = carry
            line, wr, ok = x
            base = t * K
            set_l = [line % n_sets[l] for l in range(L)]

            # probe phase: first hit breaks the walk; every missed level
            # also probes its MSHR file
            found = jnp.bool_(False)
            merged = jnp.bool_(False)
            service = jnp.int32(L + 1)
            for l in range(L):
                row = tags[l, set_l[l]]
                probe = ok & ~found
                hit_vec = row == line
                hit = probe & hit_vec.any()
                way = jnp.argmax(hit_vec)
                stamp = stamp.at[l, set_l[l], way].set(          # move_to_end
                    jnp.where(hit, base + lookup_slot[l],
                              stamp[l, set_l[l], way]))
                miss = probe & ~hit
                mrow = mlines[l]
                in_flight = (mrow == line).any()
                merged = merged | (miss & in_flight)
                m_occ = mrow >= 0
                m_full = m_occ.sum() >= mshrs[l]
                m_free = jnp.argmax(~m_occ & (mslots < mshrs[l]))
                m_fifo = jnp.argmin(jnp.where(m_occ, mstamp[l], BIG))
                m_ins = jnp.where(m_full, m_fifo, m_free)
                insert = miss & ~in_flight
                mlines = mlines.at[l, m_ins].set(
                    jnp.where(insert, line, mrow[m_ins]))
                mstamp = mstamp.at[l, m_ins].set(
                    jnp.where(insert, t, mstamp[l, m_ins]))
                service = jnp.where(hit, jnp.int32(l + 1), service)
                found = found | hit

            # fill phase: allocate in every level above the service point;
            # each fill's dirty victim cascades into the next level down,
            # falling off the last level as a DRAM write
            for i in range(L):
                en = ok & (service >= jnp.int32(i + 2))
                flag, vline, tags, dirty, stamp = fill(
                    tags, dirty, stamp, i, set_l[i], line,
                    jnp.bool_(False), en, base + demand_slot[i])
                wbs = wbs.at[i].add(flag.astype(jnp.int32))
                for m in range(i + 1, L):
                    flag, vline, tags, dirty, stamp = fill(
                        tags, dirty, stamp, m, vline % n_sets[m], vline,
                        jnp.bool_(True), flag, base + cascade_slot[i][m])
                    wbs = wbs.at[m].add(flag.astype(jnp.int32))
                memw = memw + flag.astype(jnp.int32)

            # write-allocate: dirty the line in L1 (no LRU reorder)
            row0 = tags[0, set_l[0]]
            dirty = dirty.at[0, set_l[0]].set(
                dirty[0, set_l[0]] | ((row0 == line) & ok & wr))

            bank = line % banks[jnp.minimum(service, jnp.int32(L)) - 1]
            return ((tags, dirty, stamp, mlines, mstamp, wbs, memw, t + 1),
                    (service, merged, bank))

        def step(carry, x):
            with jax.named_scope(STEP_SCOPE):
                return access(carry, x)

        init = (jnp.full((L, S, A), -1, jnp.int32),
                jnp.zeros((L, S, A), jnp.bool_),
                jnp.zeros((L, S, A), jnp.int32),
                jnp.full((L, M), -1, jnp.int32),
                jnp.zeros((L, M), jnp.int32),
                jnp.zeros((L,), jnp.int32),
                jnp.int32(0), jnp.int32(0))
        carry, (service, merged, bank) = lax.scan(
            step, init, (lines, is_w, valid))
        wbs, memw = carry[5], carry[6]
        lvl = jnp.arange(1, L + 1, dtype=jnp.int32)
        hits = (valid[None, :] & (service[None, :] == lvl[:, None])).sum(1)
        misses = (valid[None, :] & (service[None, :] > lvl[:, None])).sum(1)
        mem_reads = (valid & (service == L + 1)).sum()
        return service, merged, bank, hits, misses, wbs, mem_reads, memw

    fn = jax.jit(jax.vmap(geom, in_axes=(0, 0, 0, 0, None, None, None)))
    return register_jitted(fn)


def _launches(addrs, is_writes,
              geometries: Sequence[Tuple[CacheConfig, ...]]
              ) -> Optional[List[Tuple[List[int], object, tuple]]]:
    """The kernel launches of one batched replay: per hierarchy depth,
    ``(geometry indices, jitted kernel, argument tuple)``.  ``None`` when
    the stream exceeds the kernel's int32 budget."""
    addrs = np.asarray(addrs, np.int64)
    n = addrs.shape[0]
    lines = addrs // LINE
    n_pad = _pow2(max(n, 64))
    if n and (lines.min() < 0 or lines.max() >= _I32_LIM):
        return None
    if n_pad * _slots(max(len(g) for g in geometries))[3] >= _I32_LIM:
        return None                        # LRU stamps would overflow int32

    lines_p = np.zeros(n_pad, np.int32)
    lines_p[:n] = lines
    wr_p = np.zeros(n_pad, bool)
    wr_p[:n] = np.asarray(is_writes, bool)
    valid = np.zeros(n_pad, bool)
    valid[:n] = True

    by_depth: Dict[int, List[int]] = {}
    for gi, levels in enumerate(geometries):
        by_depth.setdefault(len(levels), []).append(gi)

    out = []
    for L, idxs in sorted(by_depth.items()):
        g_pad = _pow2(len(idxs))
        rows = idxs + [idxs[-1]] * (g_pad - len(idxs))   # pad with a repeat
        params = np.empty((4, g_pad, L), np.int32)
        for r, gi in enumerate(rows):
            for li, cfg in enumerate(geometries[gi]):
                params[:, r, li] = (cfg.n_sets, cfg.assoc, cfg.banks,
                                    cfg.mshrs)
        fn = _build(L, _pow2(params[0].max()), _pow2(params[1].max()),
                    _pow2(params[3].max()))
        out.append((idxs, fn, (params[0], params[1], params[2], params[3],
                               lines_p, wr_p, valid)))
    return out


# lint: numpy-twin(repro.core.cache:CacheHierarchy.replay, batched)
def replay_columns_batch(addrs, is_writes,
                         geometries: Sequence[Tuple[CacheConfig, ...]]
                         ) -> Optional[List[tuple]]:
    """Replay one access stream under every geometry in one batched call.

    Returns, per geometry, ``(level, hit, bank, mshr, counters)`` — the
    four columns of :meth:`CacheHierarchy.replay` (same dtypes) plus the
    :meth:`CacheHierarchy.counters` dict.  Returns ``None`` when the
    stream exceeds the int32 budget of the kernel (the caller falls back
    to the numpy oracle and counts the fallback)."""
    if not geometries:
        return []
    launches = _launches(addrs, is_writes, geometries)
    if launches is None:
        return None
    n = len(addrs)
    results: List[Optional[tuple]] = [None] * len(geometries)
    for idxs, fn, args in launches:
        service, merged, bank, hits, misses, wbs, memr, memw = \
            [np.asarray(o) for o in fn(*args)]
        for r, gi in enumerate(idxs):
            levels = geometries[gi]
            codes = np.asarray([LEVEL_CODE[c.name] for c in levels]
                               + [LEVEL_MEM], np.int8)
            sv = service[r, :n]
            counters = {"mem_reads": int(memr[r]), "mem_writes": int(memw[r])}
            for li, c in enumerate(levels):
                counters[f"{c.name}_hits"] = int(hits[r, li])
                counters[f"{c.name}_misses"] = int(misses[r, li])
                counters[f"{c.name}_writebacks"] = int(wbs[r, li])
            results[gi] = (codes[sv - 1], (sv == 1).astype(np.int8),
                           bank[r, :n].astype(np.int16),
                           merged[r, :n].astype(bool), counters)
    return results
