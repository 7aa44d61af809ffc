"""Device time of the decode step by the program's named scopes.

The program names each layer of its step with ``jax.named_scope``:

* ``weights``: ``parallel/shardings.gather_group``, the store view cast to
  its logical shape and dtype, for every parameter group;
* ``attention``: ``models/attention.self_attention`` and
  ``cross_attention``;
* ``mlp``: ``models/layers.mlp_apply``;
* ``tp_site``: ``models/layers.tp_psum``, the whole tensor-parallel
  all-reduce site (the codec's glue and kernels, any collective);
* ``embed``: the embedding gather and lookup in ``models/model.forward``;
* ``layers``: the block loops in ``forward`` (the scan's slicing of the
  stacked store and caches, norms, residual adds);
* ``head``: the final norm and the greedy head (``train/serve_step._head``).

A scope reaches the compiled program as a component of each operation's
``op_name`` metadata (``jit(step)/layers/while/body/closed_call/checkpoint/
weights/convert_element_type``). An operation belongs to the innermost of
the seven scopes in its ``op_name``. The compiler makes some operations
with no ``op_name``; one of those inside the body of a loop or another
called computation belongs to the scope of the operation that runs that
body (the gather that attention expands into 16 loops, say), and one at
the top level of the program to no scope.

The profiler trace carries the program: its ``/host:metadata`` plane holds
each executed module's ``HloProto`` under the module's name
(``jit_step(<program id>)``). The map from operation name to scope is
read from there, by a small reader of the protobuf wire format over the
published ``XSpace`` and ``HloProto`` schemas (the field numbers below):
``jax.profiler.ProfileData`` does not expose that plane's contents. Each
operation's own time is the trace reduction's (``Reduced.op_s``: mean over
devices, a ``while`` less the operations inside it), so the scopes'
seconds sum to the step program's own time.
"""
from __future__ import annotations

import os
import re
import time
from pathlib import Path
from typing import Dict, Iterator, Optional, Tuple

SCOPES = ("weights", "attention", "mlp", "tp_site", "embed", "layers",
          "head")
UNSCOPED = "unscoped"
STEP = "jit_step"                      # the decode step's module

# XSpace (tsl/profiler/protobuf/xplane.proto)
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
ENTRY_KEY, ENTRY_VALUE = 1, 2          # a map<int64, message> entry
EVENT_METADATA_NAME, EVENT_METADATA_STATS = 2, 5
STAT_METADATA_ID, STAT_METADATA_NAME = 1, 2
STAT_METADATA_ID_REF, STAT_BYTES = 1, 6
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
# HloProto (xla/service/hlo.proto)
HLO_MODULE = 1
MODULE_COMPUTATIONS = 3
COMPUTATION_INSTRUCTIONS, COMPUTATION_ID = 2, 5
INSTR_NAME, INSTR_OPCODE, INSTR_METADATA, INSTR_CALLED = 1, 2, 7, 38
OP_NAME = 2

Program = Dict[str, Tuple[str, Optional[str]]]    # op -> (opcode, scope)


def innermost(op_name: str) -> Optional[str]:
    """The last of the seven scopes among the components of an
    ``op_name`` (``jit(step)/layers/.../tp_site/reduce_min``), or None."""
    found = [c for c in re.split(r"[/:]", op_name) if c in SCOPES]
    return found[-1] if found else None


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a serialized protobuf message: an int for
    a varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _ints(value) -> list:
    """A repeated integer field's element: packed or one varint."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def program_scopes(hlo_module: bytes) -> Program:
    """Each instruction of a serialized ``HloModuleProto``: its opcode and
    its scope (the innermost in its ``op_name``, else that of the
    instruction whose called computation holds it, else None)."""
    comps: Dict[int, list] = {}
    for f, comp in fields(memoryview(hlo_module)):
        if f != MODULE_COMPUTATIONS:
            continue
        cid, instrs = 0, []
        for g, v in fields(comp):
            if g == COMPUTATION_ID:
                cid = v
            elif g == COMPUTATION_INSTRUCTIONS:
                name = opcode = op_name = ""
                called: list = []
                for h, w in fields(v):
                    if h == INSTR_NAME:
                        name = _text(w)
                    elif h == INSTR_OPCODE:
                        opcode = _text(w)
                    elif h == INSTR_METADATA:
                        op_name = next((_text(x) for k, x in fields(w)
                                        if k == OP_NAME), "")
                    elif h == INSTR_CALLED:
                        called += _ints(w)
                instrs.append((name, opcode, innermost(op_name), called))
        comps[cid] = instrs
    caller: Dict[int, Tuple[int, Optional[str]]] = {}
    for cid, instrs in comps.items():
        for _, _, own, called in instrs:
            for c in called:
                caller.setdefault(c, (cid, own))

    def inherited(cid: int) -> Optional[str]:
        if cid not in caller:
            return None
        up, own = caller[cid]
        return own or inherited(up)

    out: Program = {}
    for cid, instrs in comps.items():
        up = inherited(cid)
        for name, opcode, own, _ in instrs:
            out[name] = (opcode, own or up)
    return out


def trace_programs(xspace: bytes) -> Dict[str, Program]:
    """The programs a serialized ``XSpace`` carries in its metadata plane,
    by module name as the trace gives it (``jit_step(1669...)``)."""
    out: Dict[str, Program] = {}
    for f, plane in fields(memoryview(xspace)):
        if f != SPACE_PLANES:
            continue
        items = list(fields(plane))
        if not any(g == PLANE_NAME and _text(v) == METADATA_PLANE
                   for g, v in items):
            continue
        stat_names = {}
        for g, v in items:
            if g == PLANE_STAT_METADATA:
                md = dict(fields(dict(fields(v)).get(ENTRY_VALUE, b"")))
                stat_names[md.get(STAT_METADATA_ID, 0)] = _text(
                    md.get(STAT_METADATA_NAME, b""))
        for g, v in items:
            if g != PLANE_EVENT_METADATA:
                continue
            name, protos = "", []
            for h, w in fields(dict(fields(v)).get(ENTRY_VALUE, b"")):
                if h == EVENT_METADATA_NAME:
                    name = _text(w)
                elif h == EVENT_METADATA_STATS:
                    st = dict(fields(w))
                    if stat_names.get(st.get(STAT_METADATA_ID_REF)) == \
                            HLO_PROTO_STAT and STAT_BYTES in st:
                        protos.append(st[STAT_BYTES])
            for hlo in protos:
                module = next((m for k, m in fields(hlo) if k == HLO_MODULE),
                              None)
                if module is not None:
                    out[name] = program_scopes(bytes(module))
    return out


def module_key(module: str) -> str:
    """A module's name as the trace reduction prefixes its operations:
    ``jit_step(16699843...)`` -> ``jit_step(166998)``."""
    name, _, pid = module.partition("(")
    return f"{name}({pid[:6]})" if pid else name


def scope_seconds(op_s: Dict[str, float], programs: Dict[str, Program]
                  ) -> Tuple[Dict[str, float], float]:
    """Own seconds of the step program's operations by scope,
    ``unscoped`` for those with none or not in the program, from the
    reduction's ``op_s``; and the seconds of the operations found in the
    program."""
    maps = {module_key(m): p for m, p in programs.items()
            if m.partition("(")[0] == STEP}
    out = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    found = 0.0
    for key, s in op_s.items():
        prog, _, op = key.partition("/")
        if prog not in maps:
            continue
        entry = maps[prog].get(op)
        if entry is not None:
            found += s
        out[(entry and entry[1]) or UNSCOPED] += s
    return out, found


def step_seconds(m, root: Path) -> Optional[Dict[str, float]]:
    """The decode step's own seconds by scope in the traced window of a
    run whose trace the harness left under ``root``; None where no time
    lies under any of the scopes (a program that names none, or a trace
    that carries no program).
    Read once for the run's ``Measures``, kept on it as ``scope_s``; the
    first call says what it found."""
    from chipbench import harness, trace
    if "scope_s" in vars(m):
        return vars(m)["scope_s"]
    try:
        path = trace.find_xplane(str(root / harness.TRACE_DIR))
    except FileNotFoundError:
        return None
    t0 = time.perf_counter()
    with open(path, "rb") as f:
        raw = f.read()
    try:
        programs = trace_programs(raw)
    except (ValueError, IndexError) as e:
        harness.say(f"scopes: the trace's programs cannot be read: {e}")
        programs = {}
    secs, found = scope_seconds(m.trace.op_s, programs)
    total = sum(secs.values())
    named = any(secs[s] > 0 for s in SCOPES)
    vars(m)["scope_s"] = secs if named else None
    steps = max(m.work.get("steps", 0), 1)
    per = ", ".join(f"{k} {1e3 * v / steps:.4f}" for k, v in secs.items())
    harness.say(
        f"scopes, ms a step over {steps} steps: {per}; ops found in the "
        f"program {100 * found / total if total else 0:.3f}% of {STEP}'s "
        f"{total:.6f} s; trace {os.path.getsize(path)} bytes read in "
        f"{time.perf_counter() - t0:.2f} s"
        + ("" if named else "; the program names no scope"))
    return vars(m)["scope_s"]


def ms_per_step(m, root: Path, scope: str) -> Optional[float]:
    """Device ms per decode step under ``scope`` (own time), mean over
    devices; None outside decode or where the program names no scope."""
    if m.work["mode"] != "decode" or not m.work["steps"]:
        return None
    secs = step_seconds(m, root)
    if secs is None:
        return None
    return 1e3 * secs[scope] / m.work["steps"]
