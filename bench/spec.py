"""The benchmark's definition: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric lives in a file of its own, found by its name:

    bench/configs/<config>.json     sizes as run, source, cut, deployment
    bench/traffic/<traffic>.json    parameters the general generator reads
    bench/limits/<cell>.json        the limits ``correct`` is judged by
    bench/metrics/<metric>.py       a reader: ``read(ctx) -> float | None``
    bench/drivers/<kind>.py         the loop for one kind of traffic

So a later cell, configuration or metric is added by adding files and
entries, never by editing one that is there.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(RuntimeError):
    """The benchmark's files do not hold what a run needs."""


def _json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload named {name!r} in BENCHMARK.json")


def config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(ROOT / c["file"])
    raise SpecError(f"no configuration named {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def limits(cell: str) -> dict:
    return _json(BENCH / "limits" / f"{cell}.json")


def _module(path: Path) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"missing file {path.relative_to(ROOT)}")
    mod_name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str) -> ModuleType:
    return _module(BENCH / "drivers" / f"{kind}.py")


def reader(metric: str) -> ModuleType:
    return _module(BENCH / "metrics" / f"{metric}.py")


def metrics_of(bench: dict, cell: str, group: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    without a ``workloads`` key, and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]
