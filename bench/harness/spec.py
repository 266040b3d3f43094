"""Find a cell and everything it names: ``BENCHMARK.json`` at the
checkout root, ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/peaks.json`` and the
configuration's model family, ``bench/families/<family>.py``.  Nothing
here knows a cell by name: a new file is a new configuration, mix or
family."""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict[str, Any]        # bench/configs/<config>.json
    traffic: Dict[str, Any]       # bench/traffic/<traffic>.json
    chips: int
    end_to_end: List[Dict[str, Any]]   # metrics this cell reports
    per_layer: List[Dict[str, Any]]


def load_json(path: Path) -> Any:
    with open(path) as fh:
        return json.load(fh)


def bench_dir() -> Path:
    """Where cells' files are found (a test may point it elsewhere)."""
    return BENCH_DIR


def config_file(name: str) -> Path:
    return bench_dir() / "configs" / f"{name}.json"


def traffic_file(name: str) -> Path:
    return bench_dir() / "traffic" / f"{name}.json"


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, benchmark: Optional[Dict[str, Any]] = None
              ) -> Cell:
    bm = benchmark if benchmark is not None \
        else load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bm["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload named {workload!r} in "
                         "BENCHMARK.json")
    return Cell(
        name=workload,
        config=load_json(config_file(entry["config"])),
        traffic=load_json(traffic_file(entry["traffic"])),
        chips=int(entry["chips"]),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, workload)])


def peaks_for(device_kind: str) -> Dict[str, Any]:
    """The chip's published peaks; a device not in the table is an
    error, never a default."""
    table = load_json(bench_dir() / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"bench/peaks.json has no entry for device kind "
                         f"{device_kind!r}")
    return table[device_kind]


def family(cfg: Dict[str, Any]) -> ModuleType:
    """The module of the configuration's model family (its interface:
    ``bench/families/__init__.py``).  A family with no file, or a file
    without the whole interface, is an error, never a default."""
    name = cfg.get("family")
    if name is None:
        raise SystemExit("the configuration names no model family "
                         "(its \"family\" key)")
    path = bench_dir() / "families" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no model family file {path} for family "
                         f"{name!r}")
    return _load_family(path)


@functools.lru_cache(maxsize=None)
def _load_family(path: Path) -> ModuleType:
    from families import INTERFACE
    found = importlib.util.spec_from_file_location(
        "bench_family_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(found)
    found.loader.exec_module(mod)
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"model family file {path} lacks "
                         f"{', '.join(missing)}")
    return mod
