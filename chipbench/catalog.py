"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Everything that belongs to one configuration, one traffic mix, one runner
or one per-layer metric is a file of its own under one of the benchmark's
``paths``; this module is the only place that knows the layout. A later PR
adds files and entries and edits nothing here.
"""

import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCHMARK_FILE = ROOT / "BENCHMARK.json"


class Catalog:
    """One ``BENCHMARK.json`` and the directories its ``paths`` name."""

    def __init__(self, benchmark_file=BENCHMARK_FILE):
        self.file = pathlib.Path(benchmark_file)
        self.spec = json.loads(self.file.read_text())
        self.dirs = [ROOT / p for p in self.spec["paths"]]

    def find(self, relative):
        """The first ``<path>/<relative>`` that exists, in ``paths`` order."""
        for d in self.dirs:
            if (d / relative).is_file():
                return d / relative
        raise FileNotFoundError(
            f"{relative} is under none of {[str(d) for d in self.dirs]}")

    def json(self, relative):
        return json.loads(self.find(relative).read_text())

    def module(self, kind, name):
        """``<path>/<kind>/<name>.py`` loaded as a module of its own."""
        path = self.find(f"{kind}/{name}.py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def cell(self, workload):
        """(cell entry, configuration dict, traffic dict) of one workload."""
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in {self.file}; "
                           f"have {sorted(cells)}")
        cell = cells[workload]
        entry = {c["name"]: c for c in self.spec["configs"]}[cell["config"]]
        config = json.loads((ROOT / entry["file"]).read_text())
        traffic = self.json(f"traffic/{cell['traffic']}.json")
        return cell, config, traffic

    def metrics(self, group, workload):
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.spec[group]
                if "workloads" not in m or workload in m["workloads"]]
