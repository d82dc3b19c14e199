"""Find a cell's files by the names ``BENCHMARK.json`` gives.

Everything that belongs to one configuration, traffic mix, per-layer
metric or kernel lane is a file of its own under one of the benchmark's
``paths``, so a later change adds a cell, a mix, a metric or a lane by
adding files and entries, never by editing the harness:

    <path>/traffic/<traffic>.json    the mix's parameters (generator.py)
    <path>/limits/<cell>.json        the limits of the comparison
    <path>/metrics/<metric>.py       ``read(readings) -> float | None``;
                                     ``<metric>.<kind>`` is read by the
                                     same file: one quantity in cells
                                     whose end-to-end metrics differ
    <path>/lanes/<lane>.py           ``count(shape) -> {"flops", "bytes"}``
                                     and ``TRACE_NAMES`` of its kernel
    <path>/peaks.json                peaks by device kind, with source
    <path>/kinds/topology/<kind>.py  a fabric kind (``topology.kind``)
    <path>/kinds/controller/<kind>.py  a controller kind
    <path>/kinds/event/<kind>.py     an event kind of a mix's ``events``

A configuration's file is the one its ``BENCHMARK.json`` entry names.

A kind file has two halves.  Its reference half is NumPy and imports
nothing of the program; ``chipbench/reference.py`` runs on it.  Its
program half, ``program(...)``, imports ``repro`` inside the function
and builds the program's object from the repo's own builders; only
``chipbench/program.py`` calls it.  The reference, the comparison and
the generator import nothing of the program:

    topology    ``nodes(t) -> int``, ``pairs(t) -> (E, 2)`` directed
                (src, dst) pairs, ``TRANSITIVE`` (every node maps to
                every other by a relabelling that keeps the fabric);
                ``program(t) -> Topology``
    controller  ``reference(c, deg, dtype)`` -> an object with
                ``readout`` ("continuous", or "integer": each edge's
                occupancy rounded to a whole frame before the node sum),
                ``init(shape) -> state`` and ``step(net, nu_u, state) ->
                (nu', state')``, the per-period update in ``dtype``; a
                FINC/FDEC kind, which decides in pulses and reads whole
                frames, also states its rule: ``kp``, ``fs``, ``budget``
                (pulses a period) and ``want(net, state)``, with state
                ``{"c_est": ...}``, which ``compare.PulseReplay`` holds
                (``kinds/controller/discrete.py``); ``program(c) ->
                (ControllerConfig, {SimConfig field: value})``
    event       ``period(ev, config) -> int`` and ``apply(ev, config,
                live)`` (the reference's mutation of ``reference.Live``
                at the start of that period); ``program(ev, topo,
                config)`` -> the program's event
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List


class SpecError(ValueError):
    """A name in ``BENCHMARK.json`` that resolves to no file."""


@dataclasses.dataclass(frozen=True)
class Kinds:
    """The kind files a cell's configuration and mix name."""

    topology: object     # module of kinds/topology/<kind>.py
    controller: object   # module of kinds/controller/<kind>.py
    events: Dict[str, object]   # event kind -> module of kinds/event/<kind>.py

    def event(self, ev: dict):
        return self.events[ev["kind"]]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    kinds: Kinds


class Spec:
    def __init__(self, root: Path):
        self.root = Path(root)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise SpecError(f"no BENCHMARK.json in {self.root}")
        self.data = json.loads(path.read_text())
        self.paths = [self.root / p for p in self.data["paths"]]

    def find(self, kind: str, name: str) -> Path:
        """The file ``<path>/<kind>/<name>`` under the first path that
        holds it (``kind`` empty: ``<path>/<name>``)."""
        for p in self.paths:
            f = p / kind / name if kind else p / name
            if f.is_file():
                return f
        raise SpecError(f"no {kind or 'file'} {name!r} under "
                        f"{[str(p) for p in self.paths]}")

    def load_json(self, kind: str, name: str) -> dict:
        return json.loads(self.find(kind, name).read_text())

    def module(self, kind: str, name: str):
        """Import ``<path>/<kind>/<name>.py`` as a module of its own."""
        f = self.find(kind, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind.replace('/', '_')}_{name.replace('.', '_')}",
            f)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reader(self, metric: str):
        """The module that reads a per-layer metric: ``metrics/<r>.py``
        for a metric named ``<r>`` or ``<r>.<kind>``."""
        return self.module("metrics", metric.split(".")[0])

    def kinds(self, config: dict, traffic: dict) -> Kinds:
        """The kind files of a configuration and a mix."""
        return Kinds(
            topology=self.module("kinds/topology",
                                 config["topology"]["kind"]),
            controller=self.module("kinds/controller",
                                   config["controller"]["kind"]),
            events={ev["kind"]: self.module("kinds/event", ev["kind"])
                    for ev in traffic.get("events", [])})

    def lanes(self) -> dict:
        """{lane: module} of every ``lanes/<lane>.py``."""
        names = sorted({f.stem for p in self.paths
                        for f in (p / "lanes").glob("*.py")})
        return {n: self.module("lanes", n) for n in names}

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.data["workloads"]}
        if name not in cells:
            raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in self.data["configs"]}
        if w["config"] not in configs:
            raise SpecError(f"workload {name!r} names no known config")
        config = json.loads((self.root / configs[w["config"]]["file"])
                            .read_text())

        def mine(m):
            return name in m.get("workloads", [name])

        # A per-layer metric with no ``workloads`` is reported in every
        # cell that reports the end-to-end metric it moves.
        e2e = [m for m in self.data["end_to_end"] if mine(m)]
        moved = {m["name"] for m in e2e}
        traffic = self.load_json("traffic", w["traffic"] + ".json")
        return Cell(
            name=name, chips=int(w["chips"]), config=config,
            traffic=traffic,
            limits=self.load_json("limits", name + ".json"),
            end_to_end=e2e,
            per_layer=[m for m in self.data["per_layer"]
                       if name in m.get("workloads", [name])
                       and m["moves"] in moved],
            kinds=self.kinds(config, traffic))
