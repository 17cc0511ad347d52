"""Batched cache replay: ``CacheHierarchy.replay`` as a jitted jax scan.

One call evaluates *all* cache geometries of a sweep against the shared
structural access stream: the per-geometry LRU/MSHR/writeback state
machine runs as a single ``lax.scan`` over the stream, ``vmap``-ped
across the geometry batch, so N geometries cost one kernel launch
instead of N python replays.

Bit-exactness with the :class:`~repro.core.cache.CacheHierarchy` oracle
is the contract (the differential suite in ``tests/test_accel.py``
fuzzes it).  The OrderedDict semantics map onto arrays as follows:

  * **State layout** — each level is one ``(S, 3A)`` int32 buffer of set
    rows: a set's ``A`` tags (``-1`` is an empty way), its ``A`` dirty
    bits and its ``A`` LRU stamps side by side.
  * **Update rule** — a step reads the row of the access's own set at
    every level once, applies every touch of that set to the row in
    registers (probe, demand fill, write-allocate), and writes the row
    back once.  A cascade writeback whose victim line maps to another
    set reads and writes that set's row; when it maps to the access's
    own set it is folded into the held row, which the final write then
    carries.  Rows are read before they are written and never read
    again from the old buffer, so each write updates the carried buffer
    in place.
  * **Why in place on the TPU** — a row is the minor dimension of its
    buffer, so the batched row reads (gathers) and row writes (scatters)
    want the same layout and the compiler keeps them as single ops on
    the loop carry.  Written one element at a time into ``(L, S, A)``
    arrays, the reads wanted the ways minor and the scatters the sets
    minor, and every touch cost a layout copy of the whole state
    (``tests/test_tpu_compile.py`` pins the copy-free loop body).
  * **LRU order** — each resident way carries a monotonically increasing
    stamp ``t * K + slot``; ``t`` is the access index, ``slot`` numbers
    the python-side touch points of one access in their exact execution
    order (probes first, then the demand-fill/cascade-writeback chain of
    :meth:`CacheHierarchy._access`).  ``move_to_end`` is a fresh stamp;
    the eviction victim is the min-stamp resident way (the first free
    way while the set has one).  Same-set collisions inside one access
    (a cascade landing in the set a demand fill is about to evict from)
    resolve exactly like the dict, because the touches apply to the row
    in slot order.
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

        def read(level, s):
            """Set ``s`` of one level as its (tags, dirty, stamp) ways."""
            row = level[s]
            return row[:A], row[A:2 * A] != 0, row[2 * A:]

        def write(level, s, row):
            tags, dirty, stamp = row
            return level.at[s].set(
                jnp.concatenate([tags, dirty.astype(jnp.int32), stamp]))

        def pick(cond, a, b):
            return tuple(jnp.where(cond, x, y) for x, y in zip(a, b))

        def fill(row, l, line, dirty_in, en, stamp_val):
            """``_Level.fill`` at level ``l`` on one set's row: present ->
            refresh stamp and OR the dirty bit; absent -> insert
            (LRU-evicting when full), returning the dirty-victim flag +
            line for the cascade."""
            row_t, row_d, row_s = row
            present_vec = row_t == line
            present = present_vec.any()
            occ = row_t >= 0
            full = occ.sum() >= assoc[l]
            free_way = jnp.argmax(~occ & (ways < assoc[l]))
            lru_way = jnp.argmin(jnp.where(occ, row_s, BIG))
            ins = ways == jnp.where(full, lru_way, free_way)
            at = en & jnp.where(present, present_vec, ins)
            victim = en & ~present & full & (ins & row_d).any()
            victim_line = jnp.where(victim, jnp.where(ins, row_t, 0).sum(), 0)
            row = (jnp.where(at, line, row_t),
                   jnp.where(at, dirty_in | (present_vec & row_d), row_d),
                   jnp.where(at, stamp_val, row_s))
            return victim, victim_line, row

        def access(carry, x):
            cache, mlines, mstamp, wbs, memw, t = carry
            cache, mlines, mstamp, wbs = (list(cache), list(mlines),
                                          list(mstamp), list(wbs))
            line, wr, ok = x
            base = t * K
            set_l = [line % n_sets[l] for l in range(L)]
            # every touch of an access's own set at level l works on this
            # row; it is written back once, after the access
            home = [read(cache[l], set_l[l]) for l in range(L)]

            # probe phase: first hit breaks the walk; every missed level
            # also probes its MSHR file
            found = jnp.bool_(False)
            merged = jnp.bool_(False)
            service = jnp.int32(L + 1)
            for l in range(L):
                row_t, row_d, row_s = home[l]
                probe = ok & ~found
                hit_vec = row_t == line          # a set holds a line once
                hit = probe & hit_vec.any()
                home[l] = (row_t, row_d,                        # move_to_end
                           jnp.where(hit & hit_vec, base + lookup_slot[l],
                                     row_s))
                miss = probe & ~hit
                mrow, msrow = mlines[l], mstamp[l]
                in_flight = (mrow == line).any()
                merged = merged | (miss & in_flight)
                m_occ = mrow >= 0
                m_full = m_occ.sum() >= mshrs[l]
                m_free = jnp.argmax(~m_occ & (mslots < mshrs[l]))
                m_fifo = jnp.argmin(jnp.where(m_occ, msrow, BIG))
                m_at = (miss & ~in_flight
                        & (mslots == jnp.where(m_full, m_fifo, m_free)))
                mlines[l] = jnp.where(m_at, line, mrow)
                mstamp[l] = jnp.where(m_at, t, msrow)
                service = jnp.where(hit, jnp.int32(l + 1), service)
                found = found | hit

            # fill phase: allocate in every level above the service point;
            # each fill's dirty victim cascades into the next level down,
            # falling off the last level as a DRAM write.  A cascade may
            # land in another set than the access's own: it reads and
            # writes that set's row in place, and folds into ``home`` when
            # the sets coincide (the later home write then supersedes it).
            for i in range(L):
                en = ok & (service >= jnp.int32(i + 2))
                flag, vline, home[i] = fill(home[i], i, line, jnp.bool_(False),
                                            en, base + demand_slot[i])
                wbs[i] = wbs[i] + flag.astype(jnp.int32)
                for m in range(i + 1, L):
                    s = vline % n_sets[m]
                    same = s == set_l[m]
                    row = pick(same, home[m], read(cache[m], s))
                    flag, vline, row = fill(row, m, vline, jnp.bool_(True),
                                            flag, base + cascade_slot[i][m])
                    cache[m] = write(cache[m], s, row)
                    home[m] = pick(same, row, home[m])
                    wbs[m] = wbs[m] + flag.astype(jnp.int32)
                memw = memw + flag.astype(jnp.int32)

            # write-allocate: dirty the line in L1 (no LRU reorder)
            row_t, row_d, row_s = home[0]
            home[0] = (row_t, row_d | ((row_t == line) & ok & wr), row_s)
            cache = [write(cache[l], set_l[l], home[l]) for l in range(L)]

            bank = line % banks[jnp.minimum(service, jnp.int32(L)) - 1]
            return ((tuple(cache), tuple(mlines), tuple(mstamp), tuple(wbs),
                     memw, t + 1), (service, merged, bank))

        def step(carry, x):
            with jax.named_scope(STEP_SCOPE):
                return access(carry, x)

        empty_set = jnp.concatenate([jnp.full((A,), -1, jnp.int32),
                                     jnp.zeros((2 * A,), jnp.int32)])
        init = ((jnp.tile(empty_set, (S, 1)),) * L,
                (jnp.full((M,), -1, jnp.int32),) * L,
                (jnp.zeros((M,), jnp.int32),) * L,
                (jnp.int32(0),) * L,
                jnp.int32(0), jnp.int32(0))
        carry, (service, merged, bank) = lax.scan(
            step, init, (lines, is_w, valid))
        wbs, memw = jnp.stack(carry[3]), carry[4]
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
