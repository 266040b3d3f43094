"""Shared by the benchmark's tests (a module of its own, not a
``conftest.py``: the repository's ``tests/conftest.py`` is imported by
that name).  Puts ``bench/`` and ``src/`` on the path, and offers a copy
of the benchmark's files in which a test may place cells of its own."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

DATA = Path(__file__).resolve().parent / "data"

# which real cell a test cell reports like
LIKE = {"tiny-chat": "internlm2-1.8b.longprompt",
        "tiny-cotrain": "qwen1.5-0.5b.chat-cotrain"}


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A copy of bench/ (metrics, peaks, configs, traffic, families)
    plus the tests' small configurations, mixes and families, found
    through ``spec.bench_dir``."""
    from harness import spec
    root = tmp_path / "bench"
    for sub in ("metrics", "configs", "traffic", "families"):
        shutil.copytree(BENCH / sub, root / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "peaks.json", root / "peaks.json")
    for sub in ("configs", "traffic", "families"):
        for f in (DATA / sub).glob("*.*"):
            shutil.copy(f, root / sub / f.name)
    monkeypatch.setattr(spec, "bench_dir", lambda: root)
    return root


def benchmark_with(mix: str, config: str = "tiny") -> dict:
    """BENCHMARK.json with a cell ``<config>.<mix>`` that reports the
    same metrics as the real cell it stands for."""
    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    name = f"{config}.{mix}"
    bm["workloads"].append({"name": name, "config": config, "traffic": mix,
                            "chips": 1, "why": "CPU test"})
    for m in bm["per_layer"] + bm["end_to_end"]:
        if "workloads" in m and LIKE[mix] in m["workloads"]:
            m["workloads"].append(name)
    return bm
