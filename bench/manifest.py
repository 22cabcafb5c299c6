"""Find a cell's pieces by the names in ``BENCHMARK.json``.

A workload entry names a configuration and a traffic mix; the files are
found by those names, so a later change adds a cell with new files and
new entries and edits nothing here:

* ``configs/<config>.json`` — the configuration's sizes (the ``file`` of
  its entry), and ``configs/<config>.py`` beside it with ``generate``:
  the raw lists from the seed, as a ``graphref.PlainNetwork`` (the plain
  reference), from which ``netbuild.py`` builds the program's network;
* ``traffic/<traffic>.json`` — the mix's parameters, read by
  ``loadplan.py``;
* ``metrics/<metric>.py`` — the reader of a per-layer metric, with a
  ``read(ctx)`` that returns a number or None. A metric named
  ``<quantity>.<suffix>`` is read by ``metrics/<quantity>.py``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of the manifest, with everything it names resolved."""

    def __init__(self, name: str, root: Path = ROOT):
        self.root = Path(root)
        bench = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench = bench
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{sorted(by_name)}")
        self.workload = by_name[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config_path = self.root / self.config_entry["file"]
        self.config = json.loads(self.config_path.read_text())
        here = self.root / "bench"
        self.config_module_path = self.config_path.with_suffix(".py")
        self.traffic_path = here / "traffic" / f"{self.workload['traffic']}.json"
        self.traffic = json.loads(self.traffic_path.read_text())
        self.chips = int(self.workload["chips"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
        self.metrics_dir = here / "metrics"

    def config_module(self):
        return load_module(self.config_module_path)

    def reader(self, metric: str):
        path = self.metrics_dir / f"{metric}.py"
        if not path.exists():
            path = self.metrics_dir / f"{metric.split('.')[0]}.py"
        return load_module(path)

