"""Where the benchmark's data files are, found by the names in
`BENCHMARK.json`. No Python file holds a list of cells, configurations,
traffic mixes or metrics: a later PR adds a file and a manifest entry.

    BENCHMARK.json                         the manifest (root of the checkout)
    benchmark/configs/<config>.json        sizes, as the manifest's `file` says
    benchmark/traffic/<traffic>.json       the mode and its parameters
    benchmark/layer_metrics/<metric>.json  the reader and its arguments
"""

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchFiles:
    """The manifest and the data files under ``root`` (a checkout, or a
    copy of its data files that a test made)."""

    def __init__(self, root=ROOT):
        self.root = root
        self.manifest = self._json("BENCHMARK.json")

    def _json(self, *parts):
        with open(os.path.join(self.root, *parts)) as f:
            return json.load(f)

    def _named(self, section, name):
        for entry in self.manifest[section]:
            if entry["name"] == name:
                return entry
        known = sorted(e["name"] for e in self.manifest[section])
        raise KeyError(f"BENCHMARK.json {section} has no {name!r}; it has {known}")

    def cell(self, name):
        return self._named("workloads", name)

    def sizes(self, config):
        return self._json(self._named("configs", config)["file"])

    def traffic(self, name):
        return self._json("benchmark", "traffic", name + ".json")

    def metrics(self, section, cell):
        """The metrics of ``section`` that ``cell`` reports: those that
        list it under `workloads`, and those with no such key."""
        return [m for m in self.manifest[section]
                if cell in m.get("workloads", [cell])]

    def reader_spec(self, metric):
        return self._json("benchmark", "layer_metrics", metric + ".json")


def module(kind, name):
    """`benchmark/<kind>/<name>.py`: a mode, a configuration's adapter,
    its reference, a reader or a cost function."""
    return importlib.import_module(f"benchmark.{kind}.{name}")
