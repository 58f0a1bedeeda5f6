"""Finding a cell's files by name.

``BENCHMARK.json`` names cells, configurations and metrics; everything
that belongs to one of them sits in a file of its own under one of the
benchmark's directories (``paths``), found by that name:

    configs/<config>.json       traffic/<traffic>.json
    kinds/<kind>.py             references/<reference>.py
    metrics/<metric>.json       readers/<reader>.py

The harness holds no list of them.  A later PR adds files and entries
and edits nothing here.  Directories are searched in the order of
``paths``, then the harness's own directory (which holds the kinds and
readers when ``BENCHMARK.json`` is a test's own).
"""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


class CellError(RuntimeError):
    """A cell, or a file it names, cannot be found or is malformed."""


class Cells:
    def __init__(self, root):
        self.root = os.path.abspath(root)
        path = os.path.join(self.root, "BENCHMARK.json")
        try:
            with open(path) as f:
                self.bench = json.load(f)
        except OSError as exc:
            raise CellError(f"no BENCHMARK.json under {self.root}") from exc
        self.dirs = [os.path.join(self.root, p)
                     for p in self.bench["paths"]]
        if HERE not in self.dirs:
            self.dirs.append(HERE)

    def find(self, group, name, suffix):
        for d in self.dirs:
            path = os.path.join(d, group, name + suffix)
            if os.path.isfile(path):
                return path
        raise CellError(f"{group}/{name}{suffix} is in none of "
                        f"{self.dirs}")

    def data(self, group, name):
        with open(self.find(group, name, ".json")) as f:
            return json.load(f)

    def module(self, group, name):
        """The module ``<group>/<name>.py``, loaded from its file (names
        may hold dots and dashes, so not by import path)."""
        path = self.find(group, name, ".py")
        modname = "benchmark_%s_%s" % (
            group, "".join(c if c.isalnum() else "_" for c in name))
        cached = sys.modules.get(modname)
        if cached is not None and getattr(cached, "__file__", None) == path:
            return cached
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
        return mod

    def cell(self, workload):
        """Everything a run of ``workload`` needs, as one dict."""
        for w in self.bench["workloads"]:
            if w["name"] == workload:
                break
        else:
            raise CellError(
                f"no workload {workload!r} in BENCHMARK.json (it has "
                f"{[w['name'] for w in self.bench['workloads']]})")
        entry = next((c for c in self.bench["configs"]
                      if c["name"] == w["config"]), None)
        if entry is None:
            raise CellError(f"{workload}: no config {w['config']!r}")
        with open(os.path.join(self.root, entry["file"])) as f:
            config = json.load(f)
        traffic = self.data("traffic", w["traffic"])
        return {"name": workload, "chips": int(w["chips"]),
                "config_name": w["config"], "config": config,
                "traffic_name": w["traffic"], "traffic": traffic,
                "kind": self.module("kinds", traffic["kind"]),
                "reference": self.module("references",
                                         config["reference"])}

    def metrics(self, which, workload):
        """The ``end_to_end`` or ``per_layer`` entries that belong to
        ``workload``: those that list it, and those that list no cells."""
        return [m for m in self.bench[which]
                if "workloads" not in m or workload in m["workloads"]]

    def reader(self, metric_name):
        """(description, read function) of a per-layer metric."""
        desc = self.data("metrics", metric_name)
        return desc, self.module("readers", desc["reader"]).read
