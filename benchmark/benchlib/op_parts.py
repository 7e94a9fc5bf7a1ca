"""The part of the MODEL every device operation of a traced slice belongs to.

The step programs open ``jax.named_scope(part)`` where the work is done
(the program's ``models/parts.py``: a closed set — ``attn.proj``, ``mlp``,
``head``, ``experts``, ``recur``, ``select``, ...), so every HLO
instruction's ``op_name`` reads ``jit(decode_steps)/while/body/.../attn.proj/
dot_general``. The profiler keeps the optimised HLO module of every program
it saw in the file's ``/host:metadata`` plane (one ``Hlo Proto`` stat an
event metadata, named like the ``XLA Modules`` events); an ``XLA Ops`` event
is named by its HLO line (``%fusion.12 = ...``). So:

    part of an event = the innermost name of the closed set in the
                       ``op_name`` of the instruction it is named after, in
                       the module whose execution holds it (by time, as
                       ``trace_reduce.ops_inside``);
    a fusion whose own ``op_name`` names no part (the compiler rooted it at
    a bitcast or a copy of its own making) takes the part most of its fused
    instructions carry; an operation with no part anywhere is ``-``.

``jax.profiler.ProfileData`` shows planes, lines and events but not a
plane's event metadata, so the metadata plane is read from the raw
``XSpace`` bytes by the few lines of protobuf wire format below (no
dependency; the fields' numbers are xplane.proto's and hlo.proto's).

A trace of a program without scopes (the parent of the PR that added them),
or one cut without its metadata plane (tests/data), gives ``-`` everywhere
and ``events`` returns None: every reader built on it leaves its metric out.

    python3 benchmark/benchlib/op_parts.py <trace dir or .xplane.pb>

prints device seconds by program x part — the table that replaces
``decode_steps:fusion 1.591``.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

# the program's package, found as run.py finds it (the command line below
# starts from this directory)
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.append(_ROOT)
try:        # the closed set is the program's; the parent has none
    from aws_k8s_ansible_provisioner_tpu.models.parts import PARTS
except ImportError:
    PARTS = ()

NONE = "-"
# the dense matmuls' parts: the weight stream A3 is about
DENSE = ("attn.proj", "attn.out", "mlp", "head")
METADATA_PLANE = "/host:metadata"

# (program, part, start ns, duration ns) of one XLA Ops event
PartEvent = Tuple[str, str, int, int]

_cache: dict = {}


def part_of(op_name: str, parts=PARTS) -> Optional[str]:
    """The innermost name of the closed set among ``op_name``'s scopes."""
    for scope in reversed(op_name.split("/")):
        if scope in parts:
            return scope
    return None


# -- protobuf wire format, as much as the two messages need -------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a
    varint, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, wire, val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def hlo_modules(xspace) -> Dict[str, memoryview]:
    """{event metadata name: serialized HloProto} of the metadata plane
    (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4, a map whose
    value = 2; XEventMetadata.name = 2, .stats = 5; XStat.bytes_value = 6)."""
    out: Dict[str, memoryview] = {}
    for f, _, plane in _fields(xspace):
        if f != 1:
            continue
        entries, name = [], ""
        for f2, _, v in _fields(plane):
            if f2 == 2:
                name = _text(v)
            elif f2 == 4:
                entries.append(v)
        if name != METADATA_PLANE:
            continue
        for entry in entries:
            for f3, _, meta in _fields(entry):
                if f3 != 2:
                    continue
                mname, blob = "", None
                for f4, _, v in _fields(meta):
                    if f4 == 2:
                        mname = _text(v)
                    elif f4 == 5:
                        for f5, w5, sv in _fields(v):
                            if f5 == 6 and w5 == 2:
                                blob = sv
                if blob is not None:
                    out[mname] = blob
    return out


def instruction_parts(hlo_proto, parts=PARTS) -> Dict[str, str]:
    """{instruction name: part} of one module, every computation's
    instructions (HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2, .id = 5; HloInstructionProto.name
    = 1, .opcode = 2, .metadata = 7 whose op_name = 2,
    .called_computation_ids = 38). An instruction with no part of its own
    that calls computations (a fusion) takes the part most of THEIR
    instructions carry; one with none anywhere is left out."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, List[int]] = {}
    body: Dict[int, List[str]] = {}         # computation id -> instructions
    for f, _, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f2, _, comp in _fields(module):
            if f2 != 3:
                continue
            cid, names = None, []
            for f3, w3, v in _fields(comp):
                if f3 == 5 and w3 == 0:
                    cid = v
                elif f3 == 2:
                    name, op_name, called = "", "", []
                    for f4, w4, iv in _fields(v):
                        if f4 == 1:
                            name = _text(iv)
                        elif f4 == 7:
                            for f5, _, mv in _fields(iv):
                                if f5 == 2:
                                    op_name = _text(mv)
                        elif f4 == 38:
                            if w4 == 0:
                                called.append(iv)
                            else:               # packed
                                j = 0
                                while j < len(iv):
                                    c, j = _varint(iv, j)
                                    called.append(c)
                    names.append(name)
                    own[name] = part_of(op_name, parts)
                    if called:
                        calls[name] = called
            body[cid] = names

    def inherited(name: str, depth: int = 0) -> Dict[str, int]:
        votes: Dict[str, int] = {}
        for cid in calls.get(name, ()):
            for inner in body.get(cid, ()):
                p = own.get(inner)
                if p is not None:
                    votes[p] = votes.get(p, 0) + 1
                elif depth < 4:
                    for q, n in inherited(inner, depth + 1).items():
                        votes[q] = votes.get(q, 0) + n
        return votes

    out = {}
    for name, p in own.items():
        if p is None and name in calls:
            votes = inherited(name)
            if votes:
                p = max(sorted(votes), key=votes.get)
        if p is not None:
            out[name] = p
    return out


def load_parts(path: str) -> Dict[str, Dict[str, str]]:
    """{module name as the metadata plane gives it: {instruction: part}} of
    a ``.xplane.pb`` (read once a file); {} where it holds no HLO module or
    the program names no part."""
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        with open(path, "rb") as f:
            data = memoryview(f.read())
        _cache[key] = {name: instruction_parts(blob)
                       for name, blob in hlo_modules(data).items()} \
            if PARTS else {}
    return _cache[key]


# -- joining the slice's events ----------------------------------------------


def instruction_of(event_name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(...)`` -> ``fusion.12``."""
    m = re.match(r"^%?([^\s=]+)", event_name)
    return m.group(1) if m else event_name


def _module_parts(module_name: str, ops: List[str],
                  modules: Dict[str, Dict[str, str]]) -> Dict[str, str]:
    """The metadata plane's module for an ``XLA Modules`` event name: the
    one of that name, else — a program compiled in several variants keeps
    one name a variant — the one of that FUNCTION that knows most of the
    instructions ``ops`` the execution ran."""
    if module_name in modules:
        return modules[module_name]
    from benchlib import trace_reduce as tr

    prog = tr.program_of(module_name)
    same = [m for name, m in modules.items() if tr.program_of(name) == prog]
    if not same:
        return {}
    return max(same, key=lambda m: sum(1 for o in ops if o in m))


def events(trace, modules: Dict[str, Dict[str, str]]
           ) -> Optional[List[PartEvent]]:
    """Every operation of device 0 (containers left out, as ``trace_reduce
    .load`` leaves them) with the program whose execution holds it and its
    part; None where no operation carries a part (no scopes, no module)."""
    from benchlib import trace_reduce as tr

    if trace is None or not trace.devices or not modules:
        return None
    dev = trace.devices[0]
    mods = sorted(dev.modules, key=lambda e: e[1])
    ops = sorted(dev.ops, key=lambda e: e[1])
    held: List[Tuple[tuple, str]] = []       # (op event, module name or "")
    i = 0
    for ev in ops:
        while i < len(mods) and mods[i][1] + mods[i][2] <= ev[1]:
            i += 1
        inside = i < len(mods) and mods[i][1] <= ev[1]
        held.append((ev, mods[i][0] if inside else ""))
    by_module: Dict[str, List[str]] = {}
    for ev, mod in held:
        by_module.setdefault(mod, []).append(instruction_of(ev[0]))
    table = {mod: _module_parts(mod, names[:2000], modules)
             for mod, names in by_module.items() if mod}
    out = [(tr.program_of(mod) if mod else NONE,
            table.get(mod, {}).get(instruction_of(ev[0]), NONE),
            ev[1], ev[2]) for ev, mod in held]
    return out if any(e[1] != NONE for e in out) else None


def of_context(ctx) -> Optional[List[PartEvent]]:
    """``events`` of a traced run's slice (``LayerContext``), or None."""
    if ctx.trace is None:
        return None
    from benchlib import session, trace_reduce

    path = trace_reduce.find_xplane(session.TRACE_DIR)
    return events(ctx.trace, load_parts(path)) if path else None


def seconds(evs: List[PartEvent], program: str, parts=None) -> float:
    """Device seconds of ``program``'s operations whose part is in ``parts``
    (None: every operation, named or not)."""
    return sum(e[3] for e in evs if e[0] == program
               and (parts is None or e[1] in parts)) / 1e9


def by_execution(evs: List[PartEvent], program: str,
                 execs: List[Tuple[int, int]]) -> List[Dict[str, float]]:
    """One ``{part: device seconds}`` an execution ``(start ns, end ns)`` of
    ``program`` (in order of start): the operations that start inside it."""
    mine = sorted((e for e in evs if e[0] == program), key=lambda e: e[2])
    out, i = [], 0
    for start, end in execs:
        acc: Dict[str, float] = {}
        while i < len(mine) and mine[i][2] < start:
            i += 1
        while i < len(mine) and mine[i][2] < end:
            acc[mine[i][1]] = acc.get(mine[i][1], 0.0) + mine[i][3] / 1e9
            i += 1
        out.append(acc)
    return out


def dense_need_and_time(ctx, program: str, need_of) -> Tuple[float, float]:
    """(need seconds, device seconds) of the dense matmuls' parts over the
    executions of ``program`` in the traced slice that join a dispatch
    record (benchlib/engine_loop.join_executions). ``need_of(record,
    {part: (bytes, elements)})`` gives the (flops, bytes) the execution
    needs of the parts it is handed: those of ``DENSE`` the program's gauge
    weighs AND whose operations this execution shows — a part the trace
    does not show drops out of both sides, so a missing name cannot push a
    share over 100."""
    from benchlib import engine_loop, opsbytes

    evs, weights = of_context(ctx), param_weights()
    if not evs or not weights:
        return 0.0, 0.0
    joined = [(ev, rec) for ev, rec in engine_loop.join_executions(
        ctx.trace, engine_loop.dispatch_records(ctx.spans),
        engine_loop.phases_of(ctx), program) if rec is not None]
    shown = by_execution(evs, program,
                         [(ev[1], ev[1] + ev[2]) for ev, _ in joined])
    need = secs = 0.0
    for (_, rec), by in zip(joined, shown):
        mine = {p: weights[p] for p in DENSE if by.get(p) and p in weights}
        if not mine:
            continue
        flops, byts = need_of(rec, mine)
        need += opsbytes.roofline_seconds(flops, byts, ctx.peaks)[0]
        secs += sum(by[p] for p in mine)
    return need, secs


def table(evs: List[PartEvent]) -> Dict[str, Dict[str, float]]:
    """{program: {part: device seconds}}, largest first."""
    acc: Dict[str, Dict[str, float]] = {}
    for prog, part, _, d in evs:
        row = acc.setdefault(prog, {})
        row[part] = row.get(part, 0.0) + d / 1e9
    return {prog: dict(sorted(row.items(), key=lambda kv: -kv[1]))
            for prog, row in sorted(acc.items(),
                                    key=lambda kv: -sum(kv[1].values()))}


def param_weights() -> Dict[str, Tuple[float, float]]:
    """{part: (bytes, matmul elements)} of the tree the server in this
    process serves (``tpu_serve_param_bytes`` / ``..._elements``); {} where
    the program has no such gauge."""
    try:
        from aws_k8s_ansible_provisioner_tpu.serving import metrics

        return metrics.params_by_part.by_part()
    except (ImportError, AttributeError):
        return {}


if __name__ == "__main__":
    import json

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchlib import trace_reduce

    arg = sys.argv[1]
    path = arg if os.path.isfile(arg) else trace_reduce.find_xplane(arg)
    evs = events(trace_reduce.load(path, host_lines=False), load_parts(path))
    print(json.dumps(table(evs) if evs else None, indent=1))
