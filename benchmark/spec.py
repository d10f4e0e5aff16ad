"""Where the benchmark's data lives and how a name becomes a file.

Everything that belongs to one configuration, one traffic mix, one task, one
optimizer reference, one mixing matrix or one per-layer metric sits in a file
of its own, found here by the name ``BENCHMARK.json`` (or the file that names
it) gives.  A later PR adds files and entries; it edits nothing that is here.

    BENCHMARK.json workloads[i].config   -> benchmark/configs/<config>.json
                                            benchmark/reference/<config>.py
    BENCHMARK.json workloads[i].traffic  -> benchmark/traffic/<traffic>.json
    config["task"]                       -> benchmark/tasks/<task>.py
    traffic["optimizer"]["base"]["name"] -> benchmark/reference/optim_<name>.py
    traffic["mixing"]                    -> benchmark/reference/mixing_<name>.py
                                            (the matrices and the eager op they describe)
    BENCHMARK.json per_layer[i].name     -> benchmark/layer_metrics/<name>.py
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SELFTEST_WORKLOADS = os.path.join(HERE, "selftest", "workloads.json")


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(relpath: str):
    """Import ``benchmark/<relpath>`` by file, whatever its name (a
    configuration's name may hold ``-`` and ``.``)."""
    path = os.path.join(HERE, relpath)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark: no file {path}")
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in relpath)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module      # dataclasses and pickling look it up
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    """One entry of ``workloads`` with the files its names point to."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)  # metric entries
    per_layer: list = field(default_factory=list)
    # None for a cell of BENCHMARK.json (a TPU, always); the tiny twins
    # under selftest/ name the platform they rehearse on.
    platform: str | None = None
    # A twin borrows a row of peaks.json so that the roofline arithmetic
    # runs too; a cell takes the row of the device it runs on.
    peaks_of: str | None = None

    @property
    def phases(self) -> list:
        """The timed phases: each a share of ``--seconds`` on the first
        ``devices`` chips.  Default: one phase on all of the cell's chips."""
        return self.traffic.get("phases") or [
            {"name": "all", "devices": self.chips, "share": 1.0}]


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``; failing that, a rehearsal
    twin of ``selftest/workloads.json`` (same schema plus ``platform``,
    ``peaks_of`` and ``stands_for``, the cell whose metric lists it borrows;
    its configuration and traffic files lie under ``selftest/``)."""
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    platform = peaks_of = None
    metrics_of, base = name, HERE
    if entry is None and os.path.isfile(SELFTEST_WORKLOADS):
        twins = read_json(SELFTEST_WORKLOADS)["workloads"]
        entry = next((w for w in twins if w["name"] == name), None)
        if entry is not None:
            platform, metrics_of = entry["platform"], entry["stands_for"]
            peaks_of = entry["peaks_of"]
            base = os.path.join(HERE, "selftest")
    if entry is None:
        known = [w["name"] for w in bench["workloads"]]
        raise SystemExit(f"benchmark: no workload {name!r}; "
                         f"BENCHMARK.json has {known}")
    return Cell(
        name=name, chips=int(entry["chips"]),
        config_name=entry["config"], traffic_name=entry["traffic"],
        config=read_json(os.path.join(
            base, "configs", entry["config"] + ".json")),
        traffic=read_json(os.path.join(
            base, "traffic", entry["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"]
                    if _applies(m, metrics_of)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, metrics_of)],
        platform=platform, peaks_of=peaks_of)


def peak_row(device_kind: str) -> dict:
    """The row of ``peaks.json`` for this device.  A device that is not in
    the table is an error, not a default."""
    table = read_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"benchmark: no peaks on record for device kind {device_kind!r} "
            f"(benchmark/peaks.json has {sorted(table)})")
    return table[device_kind]


def task_module(cell: Cell):
    return load_module(os.path.join("tasks", cell.config["task"] + ".py"))


def reference_module(cell: Cell):
    """The configuration's plain reference; a toy of the selftest names the
    configuration whose reference it shares."""
    name = cell.config.get("reference", cell.config_name)
    return load_module(os.path.join("reference", name + ".py"))


def optimizer_reference(cell: Cell):
    base = cell.traffic["optimizer"]["base"]["name"]
    return load_module(os.path.join("reference", f"optim_{base}.py"))


def mixing_reference(cell: Cell):
    return load_module(os.path.join(
        "reference", f"mixing_{cell.traffic['mixing']}.py"))


def layer_metric_reader(name: str):
    return load_module(os.path.join("layer_metrics", name + ".py")).read
