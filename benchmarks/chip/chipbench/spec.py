"""Finds a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic; their files are ``configs/<config>.json`` (as the config entry's
``file`` says) and ``traffic/<traffic>.json``. Each metric is read by
``metrics/<metric>.py``, a module with ``read(rec) -> float | None``. Each
tier of a configuration names its architecture's kind (its ``reference``
field), which is ``kinds/<kind>.py`` (see ``tier_kind``).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
KINDS_DIR = BENCH_DIR / "kinds"


@dataclasses.dataclass
class Cell:
    name: str
    config: dict            # the configuration file's contents
    mix: dict               # the traffic file's contents
    chips: int
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    for tier in config["tiers"]:
        tier_kind(tier["reference"])
    mix = load_mix(w["traffic"])
    return Cell(name=name, config=config, mix=mix, chips=int(w["chips"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def load_mix(name: str) -> dict:
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def load_config(name: str) -> dict:
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks. A device that is not in the table is an
    error, never a default."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in peaks.json (known: {sorted(table)})")
    return table[device_kind]


def tier_kind(name: str):
    """The module ``kinds/<name>.py``: everything the harness knows of one
    architecture. It provides

    * ``Reference(params, model, quant=None)`` with ``.gaps(seqs, rows,
      tokens)``: the plain float32 reference (``chipbench.reference``);
    * ``token_flops(m, keys) -> float``: one token through the layers, the
      LM head left out, attending over ``keys`` keys where it attends;
    * ``paged_layers(m) -> int``: how many layers keep K/V in the paged
      cache;
    * optionally ``stack_axes(names, shape) -> int``: how many leading axes
      a weight leaf stacks over (``chipbench.weights``).

    A kind with no file is an error that names the file."""
    return _load_kind(KINDS_DIR / f"{name}.py")


@functools.cache
def _load_kind(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"tier kind {path.stem!r} has no file at "
                                f"{path}")
    return _module(path, f"chipbench_kind_{path.stem}")


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    return _module(path, f"chipbench_metric_{name.replace('.', '_')}").read


def read_metrics(metrics: List[dict], rec: dict) -> Dict[str, dict]:
    """Each metric's reading as ``{"value", "unit"}``; a reader that finds
    nothing to read returns None and the metric is left out."""
    out: Dict[str, dict] = {}
    for m in metrics:
        value = metric_reader(m["name"])(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
