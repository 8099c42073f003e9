"""Device self time by the program's named scopes, from a profiler trace.

A device op of the trace is an HLO instruction of one compiled program.
Its ``jax.named_scope``\\ s are in the instruction's ``op_name`` metadata
(``jit(f)/vmap()/while/body/engine.score/vmap(sroa.alg4)/...``), which the
op's event does not carry.  The trace does carry each program's optimized
HLO (plane ``/host:metadata``, stat ``Hlo Proto``) and, on each op's
metadata, its ``program_id``.  So an op is looked up in its own program by
instruction name; a fusion takes the scopes of its fused computation's
root, and an instruction the compiler added without an ``op_name`` those of
the instruction that calls its computation.  An op's *scope path* is the program's scopes in its ``op_name``,
outermost first (``engine.score/sroa.alg4/sroa.alg3/sroa.alg2``); its
device self time (``devtrace.self_times``) goes to that path, and to
``UNSCOPED`` where it has none.

``jax.profiler.ProfileData`` does not expose event metadata, so the
``.xplane.pb`` file is read here at the protobuf wire level, and only the
fields numbered below (from ``xplane.proto`` and ``xla/service/hlo.proto``).
"""
from __future__ import annotations

import re

from bench import devtrace

# The program's scopes (src/repro/fleet/engine.py, src/repro/core/sroa.py,
# src/repro/fleet/batch.py).
SCOPES = frozenset({"engine.nominate", "engine.score", "engine.select",
                    "engine.final", "sroa.bounds", "sroa.alg4", "sroa.alg3",
                    "sroa.alg2", "reprice"})
UNSCOPED = "(unscoped)"

# xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_META, PLANE_STAT_META = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
EVMETA_NAME, EVMETA_STATS = 2, 5
STATMETA_NAME = 2
STAT_META_ID, STAT_UINT, STAT_INT, STAT_STR, STAT_BYTES = 1, 3, 4, 5, 6
# hlo.proto
HLO_MODULE = 1
MODULE_COMPUTATIONS = 3
COMP_INSTRUCTIONS, COMP_ID, COMP_ROOT_ID = 2, 5, 6
INSTR_NAME, INSTR_METADATA, INSTR_ID, INSTR_CALLED = 1, 7, 35, 38
METADATA_OP_NAME = 2


# ------------------------------------------------------------ wire format
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> dict[int, list]:
    """Field number -> values of one serialized message (varints as ints,
    length-delimited fields as bytes)."""
    out: dict[int, list] = {}
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        out.setdefault(key >> 3, []).append(v)
    return out


def _one(msg: dict, num: int, default=None):
    return msg[num][0] if num in msg else default


def _packed(values: list) -> list[int]:
    """A repeated int64 field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            x, i = _varint(v, i)
            out.append(x)
    return out


# ------------------------------------------------------------ scopes
def scope_path(op_name: str, scopes=SCOPES) -> str:
    """The scopes of ``scopes`` in ``op_name``, outermost first, joined by
    ``/``; ``UNSCOPED`` where there are none."""
    found = [w for w in re.findall(r"[\w.]+", op_name) if w in scopes]
    return "/".join(found) or UNSCOPED


def hlo_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` of one serialized ``HloProto``.

    A fusion (an instruction that calls a computation) takes its callee's
    root's ``op_name``; an instruction the compiler added without one (a
    copy, a tuple) takes that of the instruction calling its computation.
    """
    module = _fields(_one(_fields(hlo_proto), HLO_MODULE, b""))
    roots, caller, instrs = {}, {}, {}
    for cb in module.get(MODULE_COMPUTATIONS, ()):
        c = _fields(cb)
        cid = _one(c, COMP_ID)
        roots[cid] = _one(c, COMP_ROOT_ID)
        for ib in c.get(COMP_INSTRUCTIONS, ()):
            ins = _fields(ib)
            md = _fields(_one(ins, INSTR_METADATA, b""))
            called = _packed(ins.get(INSTR_CALLED, []))
            iid = _one(ins, INSTR_ID)
            instrs[iid] = [bytes(_one(ins, INSTR_NAME, b"")).decode(),
                           bytes(_one(md, METADATA_OP_NAME, b"")).decode(),
                           called, cid]
            for callee in called:
                caller.setdefault(callee, iid)
    for ins in instrs.values():                  # fusions: the root's scope
        root = instrs.get(roots.get(ins[2][0])) if ins[2] else None
        if root is not None and root[1]:
            ins[1] = root[1]
    out = {}
    for name, op_name, _, cid in instrs.values():
        seen = set()
        while not op_name and cid in caller and cid not in seen:
            seen.add(cid)
            up = instrs[caller[cid]]
            op_name, cid = up[1], up[3]
        out[name] = op_name
    return out


# ------------------------------------------------------------ the trace
def op_names(path: str) -> dict[str, str]:
    """Device op event name (its HLO text) -> ``op_name``, from the
    programs' HLO and the ops' ``program_id``s kept in one ``.xplane.pb``.

    Only the planes' metadata is parsed here; event names are unique in a
    plane, so the name identifies the op's program.
    """
    with open(path, "rb") as fh:
        space = _fields(memoryview(fh.read()))
    programs, ops = {}, {}
    for pb in space.get(SPACE_PLANES, ()):
        plane = _fields(pb)
        pname = bytes(_one(plane, PLANE_NAME, b"")).decode()
        if pname != "/host:metadata" and not pname.startswith("/device:"):
            continue
        stat_names = {}
        for e in plane.get(PLANE_STAT_META, ()):
            m = _fields(e)
            stat_names[_one(m, MAP_KEY, 0)] = bytes(_one(
                _fields(_one(m, MAP_VALUE, b"")), STATMETA_NAME,
                b"")).decode()
        for e in plane.get(PLANE_EVENT_META, ()):
            m = _fields(e)
            em = _fields(_one(m, MAP_VALUE, b""))
            stats = {}
            for sb in em.get(EVMETA_STATS, ()):
                st = _fields(sb)
                for num in (STAT_UINT, STAT_INT, STAT_STR, STAT_BYTES):
                    if num in st:
                        stats[stat_names.get(_one(st, STAT_META_ID, 0))] = \
                            st[num][0]
            if pname == "/host:metadata":
                if "Hlo Proto" in stats:
                    programs[_one(m, MAP_KEY, 0)] = stats["Hlo Proto"]
            elif "program_id" in stats:
                name = bytes(_one(em, EVMETA_NAME, b"")).decode(
                    errors="replace")
                ops[name] = stats["program_id"]
    per_program = {pid: hlo_op_names(hlo) for pid, hlo in programs.items()}
    out = {}
    for name, pid in ops.items():
        instr = name.split(" = ", 1)[0].lstrip("%")
        out[name] = per_program.get(pid, {}).get(instr, "")
    return out


def read_xplane(path: str, host_names) -> tuple[dict, list]:
    """Device ops, under their full event names, and the named host spans
    of one ``.xplane.pb`` (``devtrace.read_xplane`` keeps only the
    instruction's name, which two programs may share)."""
    from jax.profiler import ProfileData

    device: dict = {}
    host: list = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            device[plane.name] = [(ev.name, ev.start_ns * 1e-9,
                                   ev.end_ns * 1e-9)
                                  for ln in plane.lines if ln.name == "XLA Ops"
                                  for ev in ln.events]
        elif plane.name.startswith("/host:"):
            host += [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                     for ln in plane.lines for ev in ln.events
                     if ev.name in host_names]
    return device, host


def device_scopes(device: dict, host: list, names: dict,
                  scopes=SCOPES) -> list:
    """[scope path, device self seconds] inside the window span of
    ``host`` (``devtrace.WINDOW_SPAN``), most time first; ``names`` maps
    an op's event name to its ``op_name`` (:func:`op_names`)."""
    windows = [(a, b) for n, a, b in host if n == devtrace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {devtrace.WINDOW_SPAN!r} span in the trace")
    lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    out: dict[str, float] = {}
    for events in device.values():
        for name, d in devtrace.self_times(events, lo, hi):
            path = scope_path(names.get(name, ""), scopes)
            out[path] = out.get(path, 0.0) + d
    return [[p, s] for p, s in sorted(out.items(), key=lambda kv: -kv[1])]


def reduce_file(path: str, host_names=()) -> dict:
    """``device_scopes`` of one trace file, with its busy time."""
    device, host = read_xplane(path, set(host_names) | {devtrace.WINDOW_SPAN})
    scoped = device_scopes(device, host, op_names(path))
    return {"device_scopes": scoped,
            "busy_s": devtrace.reduce(device, host)["busy_s"]}
