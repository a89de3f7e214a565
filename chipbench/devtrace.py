"""From a JAX profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` the profiler writes into plain events:
the operations of each device's ``XLA Ops`` line, and the host spans the
benchmark itself opens (``chipbench/...``).  Everything after that works
on those events alone, so a small recorded trace checks it on any machine.

- busy time of a device: the union of its operation intervals;
- idle share: one minus busy time over the traced window;
- a kernel's time: the summed durations of the events its selector picks.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "chipbench/"
OPCODE = re.compile(r"(?:\}|\)|\]) ([a-z][a-z0-9_-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclass
class Trace:
    """Device operations per chip and the benchmark's host spans, in ns
    on one clock, with the traced window's bounds."""
    ops: Dict[int, List[dict]] = field(default_factory=dict)
    host: List[dict] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "host": self.host, "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops={int(k): v for k, v in d["ops"].items()},
                   host=d["host"], window=tuple(d["window"]))


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                tr.ops[int(m.group(1))] = [
                    {"name": e.name, "start": e.start_ns,
                     "dur": e.duration_ns} for e in line.events]
            elif not m:
                tr.host += [{"name": e.name, "start": e.start_ns,
                             "dur": e.duration_ns} for e in line.events
                            if e.name.startswith(HOST_PREFIX)]
    spans = [h for h in tr.host if h["name"] == HOST_PREFIX + "traced"]
    if spans:
        s = spans[0]
        tr.window = (s["start"], s["start"] + s["dur"])
    return tr


def save(tr: Trace, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tr.to_json(), f)


def read_saved(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


# ------------------------------------------------------------ intervals --

def _clip(events: Iterable[dict], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out = []
    for e in events:
        a, b = max(e["start"], lo), min(e["start"] + e["dur"], hi)
        if b > a:
            out.append((a, b))
    return out


def union(iv: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(iv: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in iv)


def subtract(iv: Sequence[Tuple[float, float]],
             cover: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Parts of ``iv`` (merged) not covered by ``cover`` (merged)."""
    out, j = [], 0
    for a, b in iv:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def busy_s(tr: Trace) -> float:
    """Busy seconds inside the window, averaged over the chips traced."""
    lo, hi = tr.window
    per = [length(union(_clip(ev, lo, hi))) for ev in tr.ops.values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def idle_share(tr: Trace) -> Optional[float]:
    if not tr.ops or tr.window_s <= 0:
        return None
    return 1.0 - busy_s(tr) / tr.window_s


# -------------------------------------------------------------- kernels --

def select(tr: Trace, selector: dict) -> List[dict]:
    """Events inside the window that ``selector`` picks: every
    ``{"field": regex}`` pair in its ``match`` has to match."""
    pats = {k: re.compile(v) for k, v in selector["match"].items()}
    lo, hi = tr.window
    return [e for ev in tr.ops.values() for e in ev
            if e["start"] >= lo and e["start"] + e["dur"] <= hi
            and all(p.search(str(e.get(k, ""))) for k, p in pats.items())]


def label(e: dict) -> str:
    """A short name for an operation: the HLO instruction's name and
    opcode (a custom call with its target), without the operand list."""
    text = e["name"]
    head, _, rest = text.partition(" = ")
    if not rest:
        return text[:80]
    m = OPCODE.search(rest)
    op = m.group(1) if m else "?"
    if op == "custom-call":
        t = TARGET.search(rest)
        op += "/" + (t.group(1) if t else "?")
    return f"{head.lstrip('%')} {op}"


def self_times(tr: Trace) -> Dict[int, List[list]]:
    """Each operation of each chip as ``[event, self time, leaf]``: its
    duration in the window less what the operations nested in it (the
    bodies of ``while`` loops, calls) cover, and whether any is."""
    lo, hi = tr.window
    out = {}
    for chip, ev in tr.ops.items():
        res, stack = [], []
        for e in sorted(ev, key=lambda e: (e["start"], -e["dur"])):
            end = e["start"] + e["dur"]
            while stack and (stack[-1][1] <= e["start"] or end > stack[-1][1]):
                stack.pop()
            own = length(_clip([e], lo, hi))
            if stack:
                parent = res[stack[-1][0]]
                parent[1] -= own
                parent[2] = False
            res.append([e, own, True])
            stack.append((len(res) - 1, end))
        out[chip] = res
    return out


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The operations that took most device self time, summed by
    ``label`` and averaged over chips."""
    tot: Dict[str, float] = {}
    for ev in self_times(tr).values():
        for e, t, _ in ev:
            if t > 0:
                k = label(e)
                tot[k] = tot.get(k, 0.0) + t
    k = max(len(tr.ops), 1)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / k / 1e9] for name, t in top]


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """The longest idle gaps of chip 0 in the window, each named by the
    benchmark's host span open at its start."""
    lo, hi = tr.window
    if not tr.ops:
        return []
    busy = union(_clip(tr.ops[min(tr.ops)], lo, hi))
    gaps = subtract([(lo, hi)], busy)
    spans = sorted((h for h in tr.host if h["name"] != HOST_PREFIX + "traced"),
                   key=lambda h: -h["start"])
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        host = next((h["name"][len(HOST_PREFIX):] for h in spans
                     if h["start"] <= a < h["start"] + h["dur"]), "none")
        out.append([host, (b - a) / 1e9])
    return out
