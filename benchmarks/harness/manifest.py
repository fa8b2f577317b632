"""BENCHMARK.json and the files it names, found by name and never by
an ``if`` on a name: a cell, a mix, a configuration, a generator, a job
kind and a per-layer metric are each a file of their own."""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    pass


def _load_module(path: str, label: str):
    if not os.path.isfile(path):
        raise ManifestError(f"{label}: no file {path}")
    # metric names carry dots, so the module name is made import-safe
    mod_name = "benchfile_" + re.sub(r"\W", "_", os.path.basename(path))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """``root`` is a checkout (or a temporary copy of one): the
    directory that holds BENCHMARK.json."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        path = os.path.join(self.root, "BENCHMARK.json")
        try:
            with open(path) as f:
                self.doc: Dict[str, Any] = json.load(f)
        except OSError as e:
            raise ManifestError(f"cannot read {path}: {e}") from e
        self.bench_dir = os.path.join(self.root, self.doc["paths"][0])

    # -- entries ---------------------------------------------------------
    def _entry(self, group: str, name: str) -> dict:
        for e in self.doc[group]:
            if e["name"] == name:
                return e
        known = ", ".join(e["name"] for e in self.doc[group])
        raise ManifestError(f"no {group} entry named {name!r} (known: {known})")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        with open(os.path.join(self.root, entry["file"])) as f:
            return json.load(f)

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.bench_dir, "traffic", name + ".json")) as f:
            return json.load(f)

    def metrics_for(self, cell: str, group: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports:
        all without a ``workloads`` key, and those that list the cell."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell in m["workloads"]]

    # -- code found by name ----------------------------------------------
    def job(self, kind: str):
        return _load_module(os.path.join(self.bench_dir, "jobs", kind + ".py"),
                            f"job kind {kind!r}")

    def generator(self, name: str):
        return _load_module(os.path.join(self.bench_dir, "data", name + ".py"),
                            f"generator {name!r}")

    def metric_reader(self, name: str):
        return _load_module(
            os.path.join(self.bench_dir, "metrics", name + ".py"),
            f"per-layer metric {name!r}")

    # -- the contract's limits, as far as a file can be checked here ------
    def problems(self) -> List[str]:
        out: List[str] = []
        doc = self.doc

        def name_ok(what, s):
            if not isinstance(s, str) or not NAME_RE.match(s):
                out.append(f"{what}: {s!r} is not an allowed name")

        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [e["name"] for e in doc[group]]
            for n in names:
                name_ok(group, n)
            if len(set(names)) != len(names):
                out.append(f"{group}: a name appears twice")
        e2e = {m["name"] for m in doc["end_to_end"]}
        if "setup_s" not in e2e:
            out.append("end_to_end: setup_s is missing")
        for m in doc["end_to_end"] + doc["per_layer"]:
            if not UNIT_RE.match(m["unit"]):
                out.append(f"{m['name']}: unit {m['unit']!r} not allowed")
            if m["better"] not in ("lower", "higher"):
                out.append(f"{m['name']}: better is {m['better']!r}")
            if m["source"] not in SOURCES:
                out.append(f"{m['name']}: source {m['source']!r}")
        for m in doc["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                out.append(f"{m['name']}: an end-to-end source is "
                           "host_clock or device_trace")
            if not 0 < m["bound"] <= 0.1:
                out.append(f"{m['name']}: bound {m['bound']}")
        for m in doc["per_layer"]:
            if m["moves"] not in e2e:
                out.append(f"{m['name']}: moves unknown {m['moves']!r}")
            if not os.path.isfile(os.path.join(
                    self.bench_dir, "metrics", m["name"] + ".py")):
                out.append(f"{m['name']}: no reader file")
        cfgs = {c["name"] for c in doc["configs"]}
        used = set()
        pairs = set()
        for w in doc["workloads"]:
            name_ok("traffic", w["traffic"])
            if w["config"] not in cfgs:
                out.append(f"{w['name']}: unknown config {w['config']!r}")
            used.add(w["config"])
            if (w["config"], w["traffic"]) in pairs:
                out.append(f"{w['name']}: pair appears twice")
            pairs.add((w["config"], w["traffic"]))
            if w["chips"] not in (1, 4):
                out.append(f"{w['name']}: chips {w['chips']}")
            if not 1 <= len(w["why"]) <= 200:
                out.append(f"{w['name']}: why has {len(w['why'])} characters")
            if not os.path.isfile(os.path.join(
                    self.bench_dir, "traffic", w["traffic"] + ".json")):
                out.append(f"{w['name']}: no traffic file")
        for c in doc["configs"]:
            if c["name"] not in used:
                out.append(f"config {c['name']}: no cell uses it")
            if not os.path.isfile(os.path.join(self.root, c["file"])):
                out.append(f"config {c['name']}: no file {c['file']}")
            for k in c["reduced"]:
                name_ok("reduced", k)
        if not 1 <= doc["run_seconds"] <= 51:
            out.append(f"run_seconds {doc['run_seconds']}")
        return out
