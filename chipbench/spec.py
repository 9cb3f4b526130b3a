"""Where the benchmark's data files are, found by the names in
``BENCHMARK.json``. Nothing here lists cells, configurations, traffic
mixes or metrics: a later PR adds files and entries, and edits none."""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


class Spec:
    """One benchmark file (``BENCHMARK.json``, or the self-test's tiny
    twin) and the files its names lead to."""

    def __init__(self, path: str):
        self.path = path
        self.doc = load_json(path)
        self.run_seconds = self.doc["run_seconds"]

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.doc["workloads"])
        raise SystemExit("chipbench: no workload %r (have: %s)" % (name, known))

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(ROOT, c["file"]))
        raise SystemExit("chipbench: no config %r" % name)

    def traffic(self, name: str) -> dict:
        return load_json(os.path.join(HERE, "traffic", name + ".json"))

    def metrics_for(self, section: str, cell: str) -> list:
        """Entries of ``end_to_end`` or ``per_layer`` that this cell
        reports: those that list it, and those that list no cells whose
        end-to-end metric (``moves``) the cell reports."""
        e2e_here = {
            m["name"]
            for m in self.doc["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]
        }
        out = []
        for m in self.doc[section]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif section == "end_to_end" or m["moves"] in e2e_here:
                out.append(m)
        return out


def generator(kind: str):
    return importlib.import_module("chipbench.generators." + kind)


def layer_metric(name: str) -> dict:
    return load_json(os.path.join(HERE, "layer_metrics", name + ".json"))


def reader(name: str):
    return importlib.import_module("chipbench.readers." + name)
