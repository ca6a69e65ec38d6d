"""Loads BENCHMARK.json and the data files it names, and refuses what the
contract refuses: a cell, a configuration, a traffic mix, a process and a
per-layer metric are each a file found by its name. Imports nothing but
the standard library, so the child processes can use it."""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    pass


def check_name(name: str, what: str = "name") -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise SpecError(f"{what} {name!r}: 1-64 of letters, digits, '_', '.', '-'")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not _UNIT.match(unit):
        raise SpecError(f"unit {unit!r}: 1-16 of letters, digits, '_/%.-', no space")
    return unit


def check_metric(entry: dict) -> dict:
    check_name(entry.get("name"), "metric name")
    check_unit(entry.get("unit"))
    if entry.get("better") not in ("lower", "higher"):
        raise SpecError(f"metric {entry['name']}: better is 'lower' or 'higher'")
    if entry.get("source") not in SOURCES:
        raise SpecError(f"metric {entry['name']}: source one of {SOURCES}")
    return entry


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def peak_for(device_kind: str) -> dict:
    """The chip's published peaks; a device that is not in the table is an
    error, never a default."""
    table = _read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SpecError(
            f"no published peak for device_kind {device_kind!r} in zbench/peaks.json"
        )
    return table["devices"][device_kind]


def documents() -> list:
    """``BENCHMARK.json``, then ``zbench/pending.json``: cells of the same
    shape that wait for a repair of the program and are not listed (the
    driver never runs them; the builder does, to show the fault and later
    its repair). A name that both hold is BENCHMARK.json's."""
    docs = [_read_json(os.path.join(CHECKOUT, "BENCHMARK.json"))]
    pending = os.path.join(HERE, "pending.json")
    if os.path.exists(pending):
        docs.append(_read_json(pending))
    return docs


class Cell:
    """One entry of ``workloads`` with everything it names."""

    def __init__(self, name: str):
        docs = documents()
        bench = next(
            (d for d in docs if any(w["name"] == name for w in d["workloads"])), None
        )
        if bench is None:
            known = ", ".join(w["name"] for d in docs for w in d["workloads"])
            raise SpecError(f"unknown workload {name!r}; there are: {known}")
        self.listed = bench is docs[0]
        self.run_seconds = docs[0]["run_seconds"]
        self.entry = next(w for w in bench["workloads"] if w["name"] == name)
        self.name = check_name(name, "workload")
        self.chips = int(self.entry["chips"])
        cfg_rows = [c for c in bench["configs"] if c["name"] == self.entry["config"]]
        if not cfg_rows:
            raise SpecError(f"workload {name}: no config {self.entry['config']!r}")
        self.config = _read_json(os.path.join(CHECKOUT, cfg_rows[0]["file"]))
        self.config_name = check_name(cfg_rows[0]["name"], "config")
        check_name(self.entry["traffic"], "traffic")
        self.traffic = _read_json(os.path.join(HERE, "workloads", f"{name}.json"))

        def reported_here(m: dict) -> bool:
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [
            check_metric(m) for m in bench["end_to_end"] if reported_here(m)
        ]
        e2e_names = {m["name"] for m in self.end_to_end}
        self.per_layer = []
        for m in bench["per_layer"]:
            check_metric(m)
            if reported_here(m) and m["moves"] in e2e_names:
                reader = _read_json(
                    os.path.join(HERE, "layer_metrics", f"{m['name']}.json")
                )
                self.per_layer.append({**m, "reader": reader})

    def processes(self) -> dict:
        """process id -> its module (``GRAPH`` for the reference, ``build``
        for the program's model), for the processes this cell's mix names."""
        return {
            pid: importlib.import_module(
                "zbench.processes." + check_name(pid).replace("-", "_")
            )
            for pid in self.traffic["mix"]
        }
