"""Finds a cell's pieces by name under the benchmark's root.

``BENCHMARK.json`` names the cell; its configuration, traffic mix and
metrics each sit in a file of their own:

  <file of the config entry>         sizes, as a JSON object
  bench/configs/<reference>.py       the plain reference it names
  bench/traffic/<mix>.json           one traffic mix
  bench/metrics/<metric>.py          one reader per metric

so a later cell, configuration, mix or metric is added as new files and
new ``BENCHMARK.json`` entries, and no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

#: the benchmark's own directory under the root of the checkout
BENCH = "bench"


@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict          # the configuration file, as JSON
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def model(self) -> dict:
        return self.config["model"]

    def reference(self):
        """The plain reference module the configuration names."""
        return load_module(self.root / BENCH / "configs" /
                           f"{self.config['reference']}.py")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reports(metric: dict, cell: str, end_to_end: list[dict]) -> bool:
    """Whether ``cell`` reports ``metric`` (its ``workloads`` list, or, for
    a per-layer metric without one, every cell that reports its ``moves``)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = next(m for m in end_to_end if m["name"] == metric["moves"])
        return reports(moved, cell, end_to_end)
    return True


def load_cell(root: Path, name: str) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads((root / BENCH / "traffic" /
                      f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if reports(m, name, bench["end_to_end"])]
    layer = [m for m in bench["per_layer"]
             if reports(m, name, bench["end_to_end"])]
    return Cell(root, name, int(w["chips"]), config, mix, e2e, layer)


def reader(root: Path, metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    return load_module(Path(root) / BENCH / "metrics" / f"{metric}.py").read
