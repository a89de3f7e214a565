"""The exchanges between chips in a device trace.

An operation is a collective when the HLO opcode in its event's text
(``devtrace.OPCODE``) is one of ``KINDS``, bare or as the ``-start`` and
``-done`` halves of its asynchronous form.  Two readings:

- ``self_ms``: device self time a step of the collective operations,
  averaged over the chips: the start and done events of an async pair
  count with their own durations, as every other operation does;
- ``exposed_share``: the share of the traced window in which a chip runs
  a collective and no other operation, averaged over the chips.  A
  synchronous collective covers its own event; an async pair covers the
  time from its start's begin to its done's end, since the transfer runs
  between the two.  Other operations are the leaves of the trace (an
  operation no other nests in): a ``while`` loop or a call covers its
  body's time and would hide every collective inside it.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import devtrace

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
         "all-to-all")
#: the start an async done waits on: its operand
_DONE_OF = re.compile(r"-done\(.*?%([\w.-]+)")


def kind(e: dict) -> Tuple[Optional[str], str]:
    """``(collective, half)``: the collective's kind, or None, and
    ``"start"``, ``"done"`` or ``""`` for a synchronous one."""
    op = devtrace.label(e).rsplit(" ", 1)[-1]
    for half in ("start", "done"):
        if op.endswith("-" + half) and op[:-len(half) - 1] in KINDS:
            return op[:-len(half) - 1], half
    return (op, "") if op in KINDS else (None, "")


def self_ms(tr: devtrace.Trace, steps: int) -> Optional[float]:
    """Device self time a step of the collectives, in ms, averaged over
    the chips; None when the trace holds no collective or no step."""
    if steps <= 0:
        return None
    per, seen = [], False
    for ev in devtrace.self_times(tr).values():
        picked = [t for e, t, _ in ev if kind(e)[0]]
        seen |= bool(picked)
        per.append(sum(t for t in picked if t > 0))
    if not seen:
        return None
    return sum(per) / len(per) / steps / 1e6


def spans(ev: List[dict]) -> List[Tuple[float, float]]:
    """The time each collective of one chip covers: a synchronous one its
    event, an async pair from its start's begin to its done's end.  A
    done pairs with the latest open start of the instruction its text
    names; a half left unpaired covers its own event."""
    out: List[Tuple[float, float]] = []
    open_: Dict[str, List[dict]] = {}
    for e in sorted(ev, key=lambda e: e["start"]):
        k, half = kind(e)
        if k is None:
            continue
        if half == "start":
            name = e["name"].partition(" = ")[0].strip().lstrip("%")
            open_.setdefault(name, []).append(e)
            continue
        m = _DONE_OF.search(e["name"]) if half == "done" else None
        starts = open_.get(m.group(1), []) if m else []
        begin = starts.pop()["start"] if starts else e["start"]
        out.append((begin, e["start"] + e["dur"]))
    out += [(e["start"], e["start"] + e["dur"])
            for starts in open_.values() for e in starts]
    return out


def exposed_share(tr: devtrace.Trace) -> Optional[float]:
    """Share of the window, in %, in which a chip runs a collective and no
    other leaf operation, averaged over the chips; None when the trace
    holds no collective."""
    lo, hi = tr.window
    if hi <= lo:
        return None
    per, seen = [], False
    for chip, ev in devtrace.self_times(tr).items():
        coll = spans(tr.ops[chip])
        seen |= bool(coll)
        comm = devtrace.union(devtrace._clip(
            [{"start": a, "dur": b - a} for a, b in coll], lo, hi))
        work = devtrace.union(devtrace._clip(
            [e for e, _, leaf in ev if leaf and kind(e)[0] is None], lo, hi))
        per.append(devtrace.length(devtrace.subtract(comm, work)))
    if not seen:
        return None
    return 100.0 * sum(per) / len(per) / (hi - lo)
