"""A cell and its files, found by name from ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, per-layer
metric or kernel lives in a file of its own, named after it:

    bench/configs/<config>.json      sizes, quantization, source; its
                                     CPU test cut (``tiny``)
    bench/traffic/<mix>.json         driver, lengths, rate,
                                     engine settings
    bench/checks/<cell>.json         the correctness comparison's limit
                                     (and the test cut's, ``tiny_limit``)
    bench/drivers/<driver>.py        a serve loop (``run(session, ...)``)
    bench/metrics/<metric>.py        ``read(run) -> float | None``
    bench/work/<kernel>.py           trace-name patterns, ops and bytes
    bench/references/<name>.py       a plain reference forward
    bench/peaks.json                 peaks keyed by ``device_kind``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: str = ""
    layer: str = ""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    check: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _applies(entry: Dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    moves = entry.get("moves")
    return moves is None or moves in e2e_names


def _metric(entry: Dict) -> Metric:
    return Metric(entry["name"], entry["unit"], entry["better"],
                  entry["source"], entry.get("moves", ""),
                  entry.get("layer", ""))


def load_cell(name: str, manifest_path: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``manifest_path`` (``ROOT/BENCHMARK.json``)."""
    manifest = load_json(manifest_path or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    config = load_json(ROOT / conf["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    check = load_json(BENCH / "checks" / f"{name}.json")
    e2e = [e for e in manifest["end_to_end"] if _applies(e, name, [])]
    names = [e["name"] for e in e2e]
    per = [e for e in manifest["per_layer"] if _applies(e, name, names)]
    return Cell(name, w["chips"], config, traffic, check,
                [_metric(e) for e in e2e], [_metric(e) for e in per])


def peaks(device_kind: str, path: Path = BENCH / "peaks.json") -> Dict:
    """The peaks of one device kind; an unknown kind is an error."""
    table = load_json(path)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]
