"""Ahead-of-time compiles of the analysis kernels for a described TPU v5e.

Nothing here runs on a chip: each test lowers a kernel of the main path
for one device of a described ``v5e:2x2`` topology and compiles it with
the TPU compiler, which refuses what the chip would refuse (tile
alignment, scoped memory, programs that do not fit HBM).  The shapes are
the fig14 sweep's and the ``KM@256`` stream's.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Code that asks ``jax.default_backend()`` still sees the CPU here,
so the tests steer the Pallas switches themselves.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.accel import pallas_ops, place, replay
from repro.core.cache import L1_32K, L1_64K, L2_256K, L2_2M

V5E_HBM_BYTES = 16 * 1024 ** 3
FIG14 = ((L1_32K, L2_256K), (L1_64K, L2_256K), (L1_64K, L2_2M))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_pallas(monkeypatch):
    """Pallas kernels lowered for the chip, not the interpreter."""
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n,n_seg", [(1 << 14, 1 << 12), (1 << 20, 1 << 16)],
                         ids=["fig14", "KM@256"])
@pytest.mark.parametrize("op", ["segment_sum", "segment_max"])
def test_segment_kernels_compile(one_chip, compiled_pallas, op, n, n_seg):
    fn = jax.jit(functools.partial(getattr(pallas_ops, op),
                                   n_segments=n_seg))
    compiled = fn.lower(_spec((n,), jnp.int32, one_chip),
                        _spec((n,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_pad", [1 << 16, 1 << 22], ids=["fig14", "KM@256"])
def test_replay_scan_compiles_and_fits(one_chip, n_pad):
    """The fig14 geometry batch (3 geometries padded to 4) over a fig14-size
    stream and over the whole ``KM@256`` stream (3.1M accesses)."""
    g = 4
    sets = max(c.n_sets for geo in FIG14 for c in geo)
    assoc = max(c.assoc for geo in FIG14 for c in geo)
    mshrs = max(c.mshrs for geo in FIG14 for c in geo)
    fn = replay._build.__wrapped__(2, replay._pow2(sets), replay._pow2(assoc),
                                   replay._pow2(mshrs))
    params = [_spec((g, 2), jnp.int32, one_chip)] * 4
    stream = [_spec((n_pad,), dt, one_chip)
              for dt in (jnp.int32, jnp.bool_, jnp.bool_)]
    mem = fn.lower(*params, *stream).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, mem


def _loop_copies(hlo_text, min_elems):
    """Shapes of the ``copy`` ops inside while-loop bodies of an optimized
    HLO module whose result holds at least ``min_elems`` elements."""
    bodies = set(re.findall(r" while\(.*?body=%([\w.\-]+)", hlo_text))
    copies, comp = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"^%([\w.\-]+) \(", line)
        if head:
            comp = head.group(1)
        op = re.match(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* copy\(", line)
        if comp in bodies and op:
            dims = [int(d) for d in op.group(2).split(",") if d]
            if math.prod(dims) >= min_elems:
                copies.append(f"{op.group(1)}{dims}")
    return copies


def test_replay_step_copies_no_state(one_chip):
    """The replay's scan step writes the rows it touches in place: at the
    fig14 sweep's shape the compiled loop body copies no buffer of the
    carried cache state's size.  With the state as ``(L, S, A)`` arrays
    of tags, dirty bits and stamps, written one element at a time, the
    batched row reads wanted the ways minor and the batched scatters the
    sets minor, so on a TPU v5e the body held 12 copies, 11 of them
    layout copies of a whole ``[4,2,4096,8]`` state array, and a scan
    step took 157.5 us.  With each level one ``(S, 3A)`` buffer of set
    rows it holds no state-sized copy (one copy of a ``pred[1,4]``) and a
    step takes 21.9 us."""
    sets, assoc = 4096, 8
    fn = replay._build.__wrapped__(2, sets, assoc, 8)
    params = [_spec((4, 2), jnp.int32, one_chip)] * 4
    stream = [_spec((1 << 16,), dt, one_chip)
              for dt in (jnp.int32, jnp.bool_, jnp.bool_)]
    text = fn.lower(*params, *stream).compile().as_text()
    assert text.count(" while(") == 1      # row reads and writes: no inner loop
    assert _loop_copies(text, sets * assoc) == []


def test_placement_kernel_compiles_with_pallas(one_chip, compiled_pallas):
    """Placement at the fig14 sweep's size: leaves/accesses padded to 2^14,
    2^12 proto-candidate segments, L1+L2 CiM levels enabled."""
    n_leaf, n_acc, n_seg_pad = 1 << 14, 1 << 14, 1 << 12
    fn = place._build.__wrapped__(n_leaf, n_acc, n_seg_pad, (0, 1), 1,
                                  use_pallas=True)
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    compiled = fn.lower(i32((n_leaf,)), i32((n_leaf,)), i32((n_acc,)),
                        i32((n_acc,)), i32((n_acc,)), i32(())).compile()
    assert "tpu_custom_call" in compiled.as_text()
