"""A checkout of the benchmark at CPU test size: the real manifest,
harness, drivers, metrics, references and limits, with the configuration
and traffic files of ``tests/bench/tiny`` in place of the real ones, and
the cells of ``tiny/workloads.json`` (left out of the benchmark for now,
their driver paths kept under test) added with their limits."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
TINY = pathlib.Path(__file__).resolve().parent / "tiny"

if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def make_tiny_checkout(root: pathlib.Path) -> pathlib.Path:
    """``root`` laid out as a checkout; returns its ``bench`` directory."""
    bench = root / "bench"
    bench.mkdir(parents=True)
    os.symlink(REPO / "src", root / "src")
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    manifest["workloads"] += json.loads(
        (TINY / "workloads.json").read_text())
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    for item in BENCH.iterdir():
        if item.name not in ("configs", "traffic", "limits", "__pycache__"):
            os.symlink(item, bench / item.name)
    (bench / "limits").mkdir()
    for item in [*(BENCH / "limits").iterdir(), *(TINY / "limits").iterdir()]:
        os.symlink(item, bench / "limits" / item.name)
    for sub in ("configs", "traffic"):
        (bench / sub).mkdir()
        for item in (BENCH / sub).iterdir():
            tiny = TINY / sub / item.name
            if item.suffix == ".json" and tiny.exists():
                shutil.copy(tiny, bench / sub / item.name)
            elif item.suffix in (".py", ".json"):
                os.symlink(item, bench / sub / item.name)
    return bench


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory) -> pathlib.Path:
    return make_tiny_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="session")
def manifest() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())
