"""The program's own names in a traced window: phases, scopes and spans.

The train step names its work (``src/repro/obs/device.py``): ``model``
around the forward and loss, ``grad_accum`` around the f32 gradient sum,
``optimizer`` around the update, and a scope per kernel op (``attention``
among them).  Differentiation wraps them in its own markers.  Every HLO op
keeps that path as its ``op_name``; the trace names each device operation
by its HLO instruction, and carries each program's optimized HLO in its
``/host:metadata`` plane, from which this module maps one to the other.

A device op falls in one phase by its ``op_name``:

- ``optimizer``: under ``optimizer``;
- ``recompute``: under ``model`` and ``rematted_computation``;
- ``backward``: under ``model`` and a ``transpose(...)``, or under
  ``grad_accum``;
- ``forward``: under ``model`` otherwise;
- none: anything else (the unscoped share).

The program's host spans (``repro/...``) come with their stats, such as
``repro/train_step``'s ``compiled``.  A program without these names reads
nothing: every reader then returns None.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import devtrace

SPAN_PREFIX = "repro/"
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
HLO_PROTO_STAT = "Hlo Proto"
PHASES = ("forward", "recompute", "backward", "optimizer")


@dataclass
class Scoped:
    """Device operations per chip, each with its ``op_name``, and the
    program's host spans with their stats, in ns on the trace's clock."""
    ops: Dict[int, List[dict]] = field(default_factory=dict)
    spans: List[dict] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Scoped":
        return cls(ops={int(k): v for k, v in d["ops"].items()},
                   spans=d["spans"])


# ------------------------------------------------------------ the file --

def _message_classes():
    """The few fields of ``XSpace`` and ``HloProto`` read here, declared
    by their published field numbers (xplane.proto, hlo.proto); a field
    named in the plural is repeated."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    T = descriptor_pb2.FieldDescriptorProto
    S, B, I = T.TYPE_STRING, T.TYPE_BYTES, T.TYPE_INT64
    f = descriptor_pb2.FileDescriptorProto(name="chipbench_trace.proto",
                                           package="cb", syntax="proto2")

    def msg(name, *fields, within=None):
        m = (within.nested_type if within else f.message_type).add(name=name)
        for fname, num, typ in fields:
            fd = m.field.add(name=fname, number=num, label=(
                T.LABEL_REPEATED if fname.endswith("s") else T.LABEL_OPTIONAL))
            if isinstance(typ, str):
                fd.type, fd.type_name = T.TYPE_MESSAGE, typ
            else:
                fd.type = typ
        return m

    msg("XStat", ("metadata_id", 1, I), ("bytes_value", 6, B))
    msg("XEventMetadata", ("name", 2, S), ("stats", 5, ".cb.XStat"))
    msg("XStatMetadata", ("name", 2, S))
    plane = msg("XPlane", ("name", 2, S))
    for fname, num, value in (("event_metadata", 4, "XEventMetadata"),
                              ("stat_metadata", 5, "XStatMetadata")):
        entry = msg(fname.title().replace("_", "") + "Entry",
                    ("key", 1, I), ("value", 2, ".cb." + value), within=plane)
        entry.options.map_entry = True
        plane.field.add(name=fname, number=num, label=T.LABEL_REPEATED,
                        type=T.TYPE_MESSAGE,
                        type_name=".cb.XPlane." + entry.name)
    msg("XSpace", ("planes", 1, ".cb.XPlane"))
    msg("OpMetadata", ("op_name", 2, S))
    msg("HloInstruction", ("name", 1, S), ("metadata", 7, ".cb.OpMetadata"),
        ("id", 35, I), ("called_computation_ids", 38, I))
    msg("HloComputation", ("instructions", 2, ".cb.HloInstruction"),
        ("id", 5, I), ("root_id", 6, I))
    msg("HloModule", ("computations", 3, ".cb.HloComputation"))
    msg("HloProto", ("hlo_module", 1, ".cb.HloModule"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return tuple(message_factory.GetMessageClass(
        pool.FindMessageTypeByName("cb." + n)) for n in ("XSpace", "HloProto"))


def _base(module: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return module.split("(", 1)[0]


def hlo_op_names(data: bytes) -> Dict[str, Dict[str, str]]:
    """For each program in a serialized ``XSpace``: its instructions'
    ``op_name``, by instruction name.  A fusion without one takes the
    first its fused computation has, root first."""
    XSpace, HloProto = _message_classes()
    out: Dict[str, Dict[str, str]] = {}
    for plane in XSpace.FromString(data).planes:
        if plane.name != METADATA_PLANE:
            continue
        proto_id = next((k for k, v in plane.stat_metadata.items()
                         if v.name == HLO_PROTO_STAT), None)
        for md in plane.event_metadata.values():
            for st in md.stats:
                if st.metadata_id != proto_id:
                    continue
                comps = HloProto.FromString(
                    st.bytes_value).hlo_module.computations
                first = {}
                for c in comps:
                    ordered = sorted(c.instructions,
                                     key=lambda i: i.id != c.root_id)
                    first[c.id] = next((i.metadata.op_name for i in ordered
                                        if i.metadata.op_name), "")
                names = out.setdefault(_base(md.name), {})
                for c in comps:
                    for i in c.instructions:
                        names[i.name] = i.metadata.op_name or next(
                            (first[k] for k in i.called_computation_ids
                             if first.get(k)), "")
    return out


def load(path: str) -> Scoped:
    """Device operations with their ``op_name`` and the ``repro/`` host
    spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    names = hlo_op_names(data)
    sc = Scoped()
    for plane in ProfileData.from_serialized_xspace(data).planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if not m:
            sc.spans += [{"name": e.name, "start": e.start_ns,
                          "dur": e.duration_ns,
                          "stats": {k: str(v) for k, v in e.stats}}
                         for line in plane.lines for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                          _base(e.name))
                         for e in lines.get(MODULES_LINE, []))
        ops, j = [], 0
        for e in sorted(lines.get(devtrace.OPS_LINE, []),
                        key=lambda e: e.start_ns):
            while j + 1 < len(modules) and modules[j + 1][0] <= e.start_ns:
                j += 1
            module = modules[j][2] if modules else ""
            instr = e.name.partition(" = ")[0].lstrip("%")
            op_name = names.get(module, {}).get(instr, "")
            ops.append({"start": e.start_ns, "dur": e.duration_ns,
                        "op_name": op_name.split(";", 1)[0]})
        sc.ops[int(m.group(1))] = ops
    return sc


_CACHE: Dict[str, Scoped] = {}


def of(run) -> Scoped:
    """The newest trace under the harness's trace directory, read once."""
    import harness
    files = sorted(glob.glob(os.path.join(harness.TRACE_DIR, "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        return Scoped()
    key = f"{files[-1]}:{os.path.getmtime(files[-1])}"
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = load(files[-1])
        _report(_CACHE[key], run)
    return _CACHE[key]


# ------------------------------------------------------------ reduction --

@functools.lru_cache(maxsize=1 << 16)
def under(scope: str, op_name: str) -> bool:
    """``op_name`` lies under ``scope``, bare or inside a transform's
    marker (``jvp(scope)``, ``transpose(jvp(scope))``)."""
    pat = re.compile(r"(^|\()" + re.escape(scope) + r"\)*$")
    return any(pat.search(p) for p in op_name.split("/"))


@functools.lru_cache(maxsize=1 << 16)
def phase(op_name: str) -> Optional[str]:
    """The phase an ``op_name`` belongs to, or None."""
    if under("optimizer", op_name):
        return "optimizer"
    if under("grad_accum", op_name):
        return "backward"
    if not under("model", op_name):
        return None
    parts = op_name.split("/")
    if "rematted_computation" in parts:
        return "recompute"
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    return "forward"


def _self_ns(sc: Scoped, window, pick) -> Optional[float]:
    """Device self time in the window of the ops ``pick(op_name)`` keeps,
    averaged over chips; None if no op carries a scope of the program."""
    if not any(phase(e["op_name"]) for ev in sc.ops.values() for e in ev):
        return None
    tr = devtrace.Trace(ops=sc.ops, window=tuple(window))
    per = [sum(t for e, t, _ in ev if t > 0 and pick(e["op_name"]))
           for ev in devtrace.self_times(tr).values()]
    return sum(per) / len(per)


def phase_ms(sc: Scoped, window, steps: int, name: str) -> Optional[float]:
    """Device self time per step of one phase, in ms."""
    ns = _self_ns(sc, window, lambda o: phase(o) == name)
    return None if ns is None or steps <= 0 else ns / steps / 1e6


def scope_ms(sc: Scoped, window, steps: int, scope: str) -> Optional[float]:
    """Device self time per step of the ops under ``scope`` in any phase,
    in ms; None if no op is under it."""
    if not any(under(scope, e["op_name"])
               for ev in sc.ops.values() for e in ev):
        return None
    ns = _self_ns(sc, window, lambda o: under(scope, o))
    return None if ns is None or steps <= 0 else ns / steps / 1e6


def compiles_in_window(sc: Scoped, window) -> Optional[int]:
    """``repro/train_step`` spans inside the window that compiled or
    loaded a program; None if the window holds no such span."""
    lo, hi = window
    steps = [s for s in sc.spans if s["name"] == SPAN_PREFIX + "train_step"
             and s["start"] >= lo and s["start"] + s["dur"] <= hi]
    if not steps:
        return None
    return sum(s["stats"].get("compiled") == "1" for s in steps)


def _report(sc: Scoped, run) -> None:
    """One line on standard error: each phase and the unscoped share of
    busy time, with the unscoped ops that took most."""
    window, steps = run.trace.window, run.steps
    ms = {p: phase_ms(sc, window, steps, p) for p in PHASES}
    if steps <= 0 or any(v is None for v in ms.values()):
        return
    busy = devtrace.busy_s(run.trace) * 1e3 / steps
    left: Dict[str, float] = {}
    tr = devtrace.Trace(ops=sc.ops, window=tuple(window))
    for ev in devtrace.self_times(tr).values():
        for e, t, _ in ev:
            if t > 0 and phase(e["op_name"]) is None:
                k = e["op_name"] or "(no op_name)"
                left[k] = left.get(k, 0.0) + t / steps / 1e6
    top = sorted(left.items(), key=lambda kv: -kv[1])[:5]
    print("scopes: ms a step " + ", ".join(f"{k} {v:.3f}"
                                           for k, v in ms.items())
          + f"; busy {busy:.3f}; unscoped share"
          f" {1 - sum(ms.values()) / busy:.5f}; unscoped ops {top}",
          file=sys.stderr, flush=True)
