"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell is one entry of the benchmark's ``workloads``: a configuration, a
traffic mix and a chip count.  Each part is a file of its own under this
directory, found by name and never listed in code:

    configs/<config>.json    model sizes as run, source, cuts, deployment
    references/<family>.py   the plain reference's layer equations and loss
                             for the configuration's model family, and
                             ``INIT``, initialisers of leaves the default
                             rules do not cover (``weights.py``)
    traffic/<traffic>.json   sequence, batch, microbatch, ZeRO, mesh, data
    cells/<workload>.json    the limits of the numbers ``correct`` compares
    metrics/<metric>.py      one per-layer metric's reader
    counts/<kernel>.py       the work one call of a kernel needs
    events/<kernel>.json     which trace events are that kernel
    peaks.json               published chip peaks keyed by ``device_kind``

Adding a configuration, a traffic mix, a cell or a metric adds files and
an entry in ``BENCHMARK.json``; nothing here changes.  A configuration of
a family the benchmark has not met brings ``references/<family>.py`` with
it, and nothing else changes: ``loss(model, ein, params, tokens)``, the
mean loss of a block of rows in float32, and ``INIT`` where its leaves
need one.  ``ein`` is the reference's matrix product
(``reference.make_ein``); a product whose output holds the rows names
them first, as ``b``, and the reference keeps them split over the cell's
chips.  The placement of the reference's state over the chips is the
same for every family.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(RuntimeError):
    """A name in ``BENCHMARK.json`` that no file answers, or a bad file."""


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing benchmark file {path}") from e


@dataclass
class Cell:
    """Everything one run of one workload reads from the benchmark's files."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    family: Any
    base: str = HERE


def load_cell(workload: str, *, root: str = ROOT, base: str = HERE) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``, with its
    configuration, traffic and limits read from ``base``."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    config = _load_json(os.path.join(base, "configs",
                                     entry["config"] + ".json"))
    traffic = _load_json(os.path.join(base, "traffic",
                                      entry["traffic"] + ".json"))
    cell = _load_json(os.path.join(base, "cells", workload + ".json"))
    family = reference_family(config["model"]["family"], base=base)

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=traffic, limits=dict(cell["limits"]),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                family=family, base=base)


def peaks_for(device_kind: str, *, base: str = HERE) -> Dict[str, Any]:
    """Published peaks of ``device_kind``; an unknown chip is an error."""
    table = _load_json(os.path.join(base, "peaks.json"))
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise SpecError(f"device kind {device_kind!r} is not in peaks.json"
                        f" ({sorted(table['devices'])})") from None


def kernel_count(kernel: str, *, base: str = HERE):
    """``counts/<kernel>.py``: the work one call of the kernel needs, from
    the problem's shapes."""
    return _load_module(os.path.join(base, "counts", kernel + ".py"),
                        "chipbench_count_" + kernel)


def kernel_events(kernel: str, *, base: str = HERE) -> Dict[str, Any]:
    """``events/<kernel>.json``: which trace events are the kernel's."""
    return _load_json(os.path.join(base, "events", kernel + ".json"))


def metric_reader(name: str, *, base: str = HERE) -> Callable:
    """``read(run) -> float | None`` from ``metrics/<name>.py``."""
    return _load_module(os.path.join(base, "metrics", name + ".py"),
                        "chipbench_metric_" + name).read


def reference_family(family: str, *, base: str = HERE):
    """``references/<family>.py``: the family's ``loss`` for the plain
    reference, and its ``INIT`` where it has one."""
    return _load_module(os.path.join(base, "references", family + ".py"),
                        "chipbench_reference_" + family)


def _load_module(path: str, modname: str):
    if not os.path.exists(path):
        raise SpecError(f"no benchmark file {path}")
    spec = importlib.util.spec_from_file_location(
        modname.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
