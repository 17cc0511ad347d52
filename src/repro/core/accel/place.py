"""Jax placement: the vectorized half of Algorithm 1 as segment reductions.

The numpy ``_place`` (``repro.core.offload``) computes, per structural
proto-candidate, four placement quantities against one geometry's
level/bank columns: the target CiM level (a segment-max over leaf
depths, lifted to the shallowest enabled level), the operand move count
(a segment-sum of leaves shallower than the target), the DRAM fill
count (unique ``(proto, line)`` pairs among MEM-served accesses), and
the home bank.  This module runs the same math as one jitted kernel:

  * the *structural* flat arrays (leaf/access sequence ids + proto ids,
    padded to powers of two with a sentinel segment) are built once per
    (structural trace, partition key) and memoized on the trace's shared
    ``_struct`` dict — geometry variants reuse them;
  * per geometry only the gathered ``level``/``addr`` values change, so
    repeated sweep points hit one compiled specialization (the proto
    count rides along as a traced scalar);
  * the numpy ``pid * 2**40 + line`` unique-key trick needs 64-bit ints
    the accelerator path doesn't have — uniqueness is counted instead
    via ``lexsort`` + adjacent-difference, which is exact in int32;
  * the segment reductions run through :mod:`jax.ops` by default, or the
    Pallas kernels of :mod:`repro.core.accel.pallas_ops` on TPU (or when
    ``EVA_CIM_PALLAS=1`` forces them — interpret mode on CPU).

``place_candidates_jax`` returns ``None`` whenever the trace exceeds the
int32 budget; the caller then falls back to the numpy oracle and counts
the fallback (:func:`repro.core.accel.fallbacks`).
"""
from __future__ import annotations

import functools
import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.accel import register_jitted
from repro.core.isa import LEVEL_MEM

_I32_LIM = 2 ** 31 - 1
SCOPE = "eva_cim.place"            # named scope of the kernel's ops


def _pow2(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


def _use_pallas() -> bool:
    return (jax.default_backend() == "tpu"
            or os.environ.get("EVA_CIM_PALLAS") == "1")


@functools.lru_cache(maxsize=None)
def _build(n_leaf: int, n_acc: int, n_seg_pad: int,
           enabled: Tuple[int, ...], depth_cap: int, use_pallas: bool):
    """Jitted placement kernel for one padded problem shape."""
    enabled_arr = jnp.asarray(enabled, jnp.int32)

    if use_pallas:
        from repro.core.accel import pallas_ops

        def seg_sum(v, i):
            return pallas_ops.segment_sum(v, i, n_seg_pad)

        def seg_max(v, i):
            return pallas_ops.segment_max(v, i, n_seg_pad)
    else:
        def seg_sum(v, i):
            return jax.ops.segment_sum(v, i, num_segments=n_seg_pad)

        def seg_max(v, i):
            return jax.ops.segment_max(v, i, num_segments=n_seg_pad)

    # the jitted function's name gives the device trace its module,
    # ``jit_kernel``; the named scope marks every op of the body
    def kernel(leaf_level, leaf_pid, acc_level, acc_line, acc_pid, n_seg):
        with jax.named_scope(SCOPE):
            # target level: deepest leaf (DRAM clamped to the cap), lifted
            # to the shallowest enabled depth; empty segments place at
            # depth 0, exactly like the numpy path's zero-filled max_depth
            depth = jnp.minimum(leaf_level - 1, depth_cap)
            max_depth = jnp.maximum(seg_max(depth, leaf_pid), 0)
            tpos = jnp.minimum(jnp.searchsorted(enabled_arr, max_depth),
                               len(enabled) - 1)
            target = enabled_arr[tpos]

            # moves: leaves resident shallower than the target level
            shallower = (depth < target[leaf_pid]).astype(jnp.int32)
            moves = seg_sum(shallower, leaf_pid)

            # DRAM fills: unique (proto, line) pairs among MEM-served
            # accesses; sort by (proto, line) and count group heads
            # (sentinel-segment entries — non-MEM accesses and padding —
            # are masked out)
            pid_k = jnp.where(acc_level == LEVEL_MEM, acc_pid, n_seg)
            order = jnp.lexsort((acc_line, pid_k))
            sp = pid_k[order]
            sl = acc_line[order]
            head = jnp.concatenate([jnp.ones(1, bool),
                                    (sp[1:] != sp[:-1])
                                    | (sl[1:] != sl[:-1])])
            fills = seg_sum((head & (sp < n_seg)).astype(jnp.int32), sp)
            return target, moves, fills

    return register_jitted(jax.jit(kernel))


def _flat_arrays(part, ct, cfg):
    """Structural flat views of the partition, memoized per partition key
    on the trace's shared ``_struct`` dict (one build serves every
    geometry of a sweep)."""
    memo = ct._struct.setdefault("place_flat", {})
    key = cfg.partition_key()
    flat = memo.get(key)
    if flat is not None:
        return flat
    protos = part.protos
    n_seg = len(protos)
    leaf_counts = np.asarray([len(p.leaf_src) for p in protos], np.int64)
    acc_counts = np.asarray([len(p.load_seqs) + len(p.store_seqs)
                             for p in protos], np.int64)
    all_leaf = np.concatenate([np.asarray(p.leaf_src, np.int64)
                               for p in protos]) if leaf_counts.sum() \
        else np.empty(0, np.int64)
    acc_seqs = np.concatenate([np.asarray(p.load_seqs + p.store_seqs,
                                          np.int64)
                               for p in protos]) if acc_counts.sum() \
        else np.empty(0, np.int64)

    def pad(seqs, counts):
        n_pad = _pow2(len(seqs))
        seq_p = np.zeros(n_pad, np.int64)
        pid_p = np.full(n_pad, n_seg, np.int32)      # sentinel segment
        seq_p[:len(seqs)] = seqs
        pid_p[:len(seqs)] = np.repeat(np.arange(n_seg, dtype=np.int32),
                                      counts)
        return seq_p, pid_p

    flat = pad(all_leaf, leaf_counts) + pad(acc_seqs, acc_counts)
    memo[key] = flat
    return flat


# lint: numpy-twin(repro.core.offload:_place)
def place_candidates_jax(part, ct, cfg) -> Optional[List]:
    """``_place`` on the jax backend; ``None`` -> use the numpy oracle."""
    from repro.core.offload import _DEPTH_LEVEL, _LEVEL_DEPTH, Candidate

    protos = part.protos
    if not protos:
        return []
    n_seg = len(protos)
    with obs.span("accel.place.pack", cat="jit") as sp:
        leaf_seq, leaf_pid, acc_seq, acc_pid = _flat_arrays(part, ct, cfg)
        sp.set(n_leaf=len(leaf_seq), n_acc=len(acc_seq), n_seg=n_seg)
        acc_addr = ct.addr[acc_seq]
        # int32 budget guard over the *real* access rows only: padding
        # rows carry the sentinel pid and gather ct.addr[0], which is -1
        # whenever seq 0 is not a memory access — the kernel masks them
        # out, so they must not veto the jax path
        real_addr = acc_addr[acc_pid < n_seg]
        if len(real_addr) and (real_addr.min() < 0
                               or real_addr.max() // 64 >= _I32_LIM):
            return None
        args = (ct.level[leaf_seq].astype(np.int32), leaf_pid,
                ct.level[acc_seq].astype(np.int32),
                (acc_addr // 64).astype(np.int32), acc_pid, np.int32(n_seg))
        depth_cap = max(_LEVEL_DEPTH[l] for l in cfg.cim_levels)
        enabled = tuple(sorted(_LEVEL_DEPTH[l] for l in cfg.cim_levels))
        fn = _build(len(leaf_seq), len(acc_seq), _pow2(n_seg + 1),
                    enabled, depth_cap, _use_pallas())
    # dispatch, the device run and the copy back
    with obs.span("accel.place.device", cat="jit"):
        target, moves, fills = fn(*args)
        target = np.asarray(target)[:n_seg]
        moves = np.asarray(moves)[:n_seg]
        fills = np.asarray(fills)[:n_seg]

    with obs.span("accel.place.unpack", cat="jit", n_candidates=n_seg):
        bank_col = ct.bank
        level_of = [_DEPTH_LEVEL[int(d)] for d in target]
        out = []
        for i, p in enumerate(protos):
            out.append(Candidate(
                root_seq=p.root_seq, op_seqs=p.op_seqs,
                op_classes=p.op_classes, load_seqs=p.load_seqs,
                store_seqs=p.store_seqs, level=level_of[i],
                bank=int(bank_col[p.load_seqs[0]]) if p.load_seqs else None,
                moves=int(moves[i]), internal_edges=p.internal_edges,
                added_loads=p.added_loads, memval_leaves=p.memval_leaves,
                dram_fills=int(fills[i])))
    return out
