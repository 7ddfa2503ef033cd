"""Finds the benchmark's parts by name.

``BENCHMARK.json`` at the checkout root names the cells and metrics; each
configuration, model family, traffic mix, per-cell limit, per-layer
metric and kernel family is a file of its own under ``bench/``:

  bench/configs/<config>.json    sizes as run, the plain reference's spec;
                                 its optional ``"family"`` names the family
                                 file (``"dense"`` where it has none)
  bench/families/<family>.py     the family: ``program_config(c)``, the
                                 program's configuration object;
                                 ``Reference``, its plain float32
                                 reference (reference.py drives it);
                                 ``Shape``, what its work is counted from
  bench/traffic/<traffic>.json   parameters of the one general generator
  bench/limits/<workload>.json   the limit of each number that ``correct``
                                 compares, with the readings it came from
  bench/metrics/<metric>.py      ``read(record)`` -> number or None
  bench/kernels/<kernel>.json    the pattern that finds one kernel family
                                 in the trace and the function that counts
                                 its operations: ``"<module>:<function>"``,
                                 or a bare name in bench.harness.work

A new cell, configuration, model family, traffic mix, metric or kernel
family is a new file and a new ``BENCHMARK.json`` entry; nothing here
changes. So are a new host span (trace_reduce.py keeps every ``engine.``
and ``bench.`` span) and a new engine counter (each serving round carries
the change of every counter in the engine's registry).
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib
import sys
from typing import Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(RuntimeError):
    """A name that BENCHMARK.json or a data file refers to does not
    resolve."""


def _read_json(path: pathlib.Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing benchmark file {path}") from e


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in bench["workloads"])
    raise SpecError(f"no workload {name!r} in BENCHMARK.json ({known})")


def load_config(bench: dict, name: str,
                root: pathlib.Path = ROOT) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            return _read_json(root / entry["file"])
    raise SpecError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, base: pathlib.Path = BENCH_DIR) -> dict:
    return _read_json(base / "traffic" / f"{name}.json")


def load_limits(workload_name: str, base: pathlib.Path = BENCH_DIR) -> dict:
    return _read_json(base / "limits" / f"{workload_name}.json")


def end_to_end_metrics(bench: dict, workload_name: str) -> List[dict]:
    """The cell's end-to-end metrics: those listing it, and those that
    list no cells."""
    return [m for m in bench["end_to_end"]
            if workload_name in m.get("workloads", [workload_name])]


def per_layer_metrics(bench: dict, workload_name: str) -> List[dict]:
    """The cell's per-layer metrics: those listing it, and those without a
    list whose end-to-end metric the cell reports."""
    reported = {m["name"] for m in end_to_end_metrics(bench, workload_name)}
    out = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if (workload_name in cells) if cells is not None \
                else m["moves"] in reported:
            out.append(m)
    return out


def _load_file(path: pathlib.Path, prefix: str, name: str):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module   # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str, base: pathlib.Path = BENCH_DIR
                  ) -> Callable[[dict], Optional[float]]:
    """The ``read(record)`` function of bench/metrics/<name>.py."""
    path = base / "metrics" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no reader {path} for per-layer metric {name!r}")
    return _load_file(path, "bench_metric_", name).read


# one module object a family file, so the reference's jitted programs,
# keyed by its model object, are built once a process
_FAMILIES: Dict[pathlib.Path, object] = {}


def load_family(config: dict, base: pathlib.Path = BENCH_DIR):
    """The module bench/families/<family>.py of a configuration file's
    ``"family"`` (``"dense"`` where it has none)."""
    name = config.get("family", "dense")
    path = (base / "families" / f"{name}.py").resolve()
    if path not in _FAMILIES:
        if not path.exists():
            raise SpecError(f"no family file {path} for family {name!r} of "
                            f"configuration {config.get('name')!r}")
        _FAMILIES[path] = _load_file(path, "bench_family_", name)
    return _FAMILIES[path]


def work_function(name: str) -> Callable:
    """A kernel family's ``"work"``: ``"<module>:<function>"``, or a bare
    function name in bench.harness.work."""
    module, _, fn = name.rpartition(":")
    module = module or "bench.harness.work"
    try:
        found = getattr(importlib.import_module(module), fn, None)
    except ModuleNotFoundError as e:
        raise SpecError(f"no module {module!r} for work function "
                        f"{name!r}") from e
    if not callable(found):
        raise SpecError(f"no work function {fn!r} in {module}")
    return found


def kernel_families(base: pathlib.Path = BENCH_DIR) -> Dict[str, dict]:
    """{family: {"pattern": a regular expression over a device op's HLO
    text, "work": the function that counts one launch (``work_function``
    resolves it)}}, one file each."""
    return {p.stem: _read_json(p)
            for p in sorted((base / "kernels").glob("*.json"))}
