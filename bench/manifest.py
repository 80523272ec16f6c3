"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, named after it:

* ``bench/configs/<config>.json``   sizes and the deployment;
* ``bench/families/<name>.py``      the architecture a config names under
  ``reference``: spec, weights, bytes and FLOPs (``bench/families``);
* ``bench/reference/<name>.py``     its plain reference;
* ``bench/traffic/<traffic>.json``  the parameters of one mix;
* ``bench/limits/<cell>.json``      what ``correct`` holds a cell to;
* ``bench/metrics/<metric>.py``     a reader with ``read(rec)``; a
  metric named ``<quantity>.<suffix>`` (one quantity split by the
  end-to-end metric it moves) falls back to ``<quantity>.py``.

A new configuration, mix, cell or metric is a new file and a new
manifest entry; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent


@dataclass(frozen=True)
class Cell:
    """One workload of the manifest with everything it names loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]     # the manifest's metrics this cell reports
    per_layer: list[dict]
    limits: dict               # compared number -> its limit
    root: Path = CHECKOUT      # where its files were found


def load_manifest(root: Path = CHECKOUT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = CHECKOUT) -> Cell:
    """The workload ``name`` with its configuration and traffic files."""
    man = load_manifest(root)
    wl = next((w for w in man["workloads"] if w["name"] == name), None)
    if wl is None:
        known = ", ".join(w["name"] for w in man["workloads"])
        raise SystemExit(f"unknown workload {name!r}; the manifest has {known}")
    cfg_entry = next(c for c in man["configs"] if c["name"] == wl["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())
    limits = json.loads(
        (root / "bench" / "limits" / f"{name}.json").read_text())
    e2e = [m for m in man["end_to_end"] if _reports(m, name)]
    layer = [m for m in man["per_layer"] if _reports(m, name)]
    return Cell(name, wl["chips"], config, traffic, e2e, layer, limits,
                root)


def _load_module(path: Path, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = CHECKOUT):
    """``read(rec) -> float | None`` of the metric ``name``: its own file,
    else that of the quantity it splits (``mixed_step_ms.rate`` ->
    ``mixed_step_ms.py``)."""
    metrics = root / "bench" / "metrics"
    path = metrics / f"{name}.py"
    if not path.exists() and "." in name:
        path = metrics / f"{name.rsplit('.', 1)[0]}.py"
    return _load_module(path, f"bench_metric_{name}").read


def reference_module(name: str, root: Path = CHECKOUT):
    """The plain reference a configuration names (``config["reference"]``)."""
    return _load_module(root / "bench" / "reference" / f"{name}.py",
                        f"bench_reference_{name}")


def family_module(name: str, root: Path = CHECKOUT):
    """The architecture family a configuration names under ``reference``."""
    return _load_module(root / "bench" / "families" / f"{name}.py",
                        f"bench_family_{name}")
