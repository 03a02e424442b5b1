"""The benchmark's data, each piece found by name.

``BENCHMARK.json`` at the checkout's root pairs a configuration with a
traffic mix in each cell. Everything that belongs to one configuration, one
mix or one per-layer metric lives in a file of its own beside this module:

* ``configs/<config>.json`` -- the model configuration as it is run,
* ``traffic/<mix>.json`` -- the parameters of one traffic mix,
* ``metrics/<metric>.py`` -- the reader of one per-layer metric,
* ``peaks.json`` -- the chip's published peaks, keyed by device kind.

Nothing here imports JAX, so a run can read its cell before it touches the
accelerator.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def config_file(name: str) -> dict:
    return load_json(HERE / "configs" / f"{name}.json")


def traffic_file(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def cell(name: str, bench: dict | None = None) -> dict:
    """One workload of ``BENCHMARK.json`` with its configuration, mix and
    the metrics it reports: ``end_to_end`` and ``per_layer`` entries that
    list it under ``workloads`` or list no workloads at all."""
    bench = bench if bench is not None else benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise ValueError(f"no workload named {name!r} in BENCHMARK.json")

    def reports(metric):
        return name in metric.get("workloads", [name])

    return {
        "workload": entry,
        "config": config_file(entry["config"]),
        "mix": traffic_file(entry["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise ValueError(f"device kind {device_kind!r} is not in peaks.json; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def reader(metric: str):
    """The module ``metrics/<metric>.py``; its ``read(ctx)`` returns the
    metric's value, or None where the run gave it nothing to read."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"chip_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
