"""The name path of each compiled op, read from a profile's HLO modules.

A device op's event in the trace names its HLO instruction
(``%fusion.12 = ...``) but, on the TPU v5e, carries no name path: its
stats are its offset, its duration and a time scale.  The profile keeps
each compiled module's ``HloProto`` in the ``/host:metadata`` plane
instead, and an instruction's ``metadata.op_name`` there is the path of
named scopes and transformations it was traced under
(``jit(step)/transpose(jvp(moe))/dot_general``; a fusion has its root's).

``jax.profiler.ProfileData`` does not expose event metadata, so this
reads the few fields it needs straight off the protobuf wire format:

* ``XSpace.planes`` (1); ``XPlane.name`` (2), ``.event_metadata`` (4, a
  map entry: key 1, ``XEventMetadata`` 2), ``.stat_metadata`` (5, key 1,
  ``XStatMetadata`` 2 with ``name`` 2);
* ``XEventMetadata.name`` (2), ``.stats`` (5); ``XStat.metadata_id`` (1),
  ``.bytes_value`` (6);
* ``HloProto.hlo_module`` (1); ``HloModuleProto.computations`` (3);
  ``HloComputationProto.instructions`` (2); ``HloInstructionProto.name``
  (1), ``.metadata`` (7); ``OpMetadata.op_name`` (2).
"""
from __future__ import annotations

from typing import Callable, Dict, Iterator, Optional, Tuple

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _varint(b: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def fields(b: memoryview) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of each field of one message: an int for
    a varint, a view of the bytes for the others."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(b, i)
        elif kind == 1:
            value, i = b[i:i + 8], i + 8
        elif kind == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        elif kind == 5:
            value, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, value


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def hlo_modules(data: bytes) -> Dict[str, bytes]:
    """The ``HloProto`` of each module in the metadata plane, by the
    module's name as the trace gives it (``jit_step(12)``)."""
    out: Dict[str, bytes] = {}
    for f, plane in fields(memoryview(data)):
        if f != 1:
            continue
        name, entries, stat_names = None, [], {}
        for g, v in fields(plane):
            if g == 2:
                name = _text(v)
            elif g == 4:
                entries.append(v)
            elif g == 5:
                key = stat = None
                for h, w in fields(v):
                    if h == 1:
                        key = w
                    elif h == 2:
                        stat = next((_text(x) for q, x in fields(w)
                                     if q == 2), None)
                stat_names[key] = stat
        if name != METADATA_PLANE:
            continue
        for entry in entries:
            for h, meta in fields(entry):
                if h != 2:
                    continue
                module, proto = None, None
                for q, x in fields(meta):
                    if q == 2:
                        module = _text(x)
                    elif q == 5:
                        stat = dict(fields(x))
                        if stat_names.get(stat.get(1)) == HLO_PROTO_STAT \
                                and 6 in stat:
                            proto = bytes(stat[6])
                if module is not None and proto is not None:
                    out[module] = proto
    return out


def op_paths(hlo_proto: bytes) -> Dict[str, Optional[str]]:
    """``{instruction name: its op_name}`` over every computation of one
    module; an instruction with no op_name maps to None."""
    out: Dict[str, Optional[str]] = {}
    for f, module in fields(memoryview(hlo_proto)):
        if f != 1:
            continue
        for g, comp in fields(module):
            if g != 3:
                continue
            for h, ins in fields(comp):
                if h != 2:
                    continue
                name = path = None
                for q, x in fields(ins):
                    if q == 1:
                        name = _text(x)
                    elif q == 7:
                        path = next((_text(y) for r, y in fields(x)
                                     if r == 2), None) or None
                out[name] = path
    return out


def instruction_of(event_name: str) -> str:
    """The instruction an ``XLA Ops`` event names: ``fusion.12`` of
    ``%fusion.12 = f32[8] fusion(...)``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def path_finder(data: bytes, wanted: Callable[[str], bool] = lambda m: True
                ) -> Callable[[Optional[str], str], Optional[str]]:
    """A function of (module name or None, event name) that gives the op's
    name path from the modules of the profile ``data`` that ``wanted``
    keeps.  With no module, or one the profile holds no HLO for, an
    instruction name that only one module has still finds its path."""
    modules = {m: op_paths(p) for m, p in hlo_modules(data).items()
               if wanted(m)}
    only: Dict[str, Optional[str]] = {}
    seen: Dict[str, int] = {}
    for paths in modules.values():
        for ins, path in paths.items():
            seen[ins] = seen.get(ins, 0) + 1
            only[ins] = path
    only = {ins: p for ins, p in only.items() if seen[ins] == 1}

    def find(module: Optional[str], event_name: str) -> Optional[str]:
        ins = instruction_of(event_name)
        if module in modules:
            return modules[module].get(ins)
        return only.get(ins)
    return find
