"""A kernel's share of its roofline, from its trace events and its count.

share = max(operations / peak FLOP/s, bytes / peak bandwidth) / the summed
device time of the events ``events/<kernel>.json`` selects.  No event, no
number: a kernel that did not run reports nothing, never 0.
"""
import spec
import devtrace
import work


def share(run, kernel: str):
    events = devtrace.select(run.trace, spec.kernel_events(kernel,
                                                        base=run.base))
    busy_s = sum(e["dur"] for e in events) / 1e9
    if not events or busy_s <= 0:
        return None
    w = spec.kernel_count(kernel, base=run.base).work_of(run, len(events))
    return 100.0 * work.roofline_s(w, run.peaks) / busy_s
