"""What the benchmark's tests share: the harness's lookup seeing the tests'
own model class beside the accepted ones (``bench_cells.py``)."""

from __future__ import annotations

import os

import bench_cells
import pytest

from benchmarks import harness


@pytest.fixture
def lookup(monkeypatch):
    """``harness.benchmark()`` answers with the tests' class and cell
    entered, and a class whose files lie in the tests' directory is found
    there: nothing under ``benchmarks/`` names it."""
    accepted, load = harness.MODELS, harness.load_module

    def load_module(folder: str, name: str):
        if folder == accepted and os.path.exists(
                os.path.join(bench_cells.MODELS, name + ".py")):
            folder = bench_cells.MODELS
        return load(folder, name)

    monkeypatch.setattr(harness, "benchmark", lambda: bench_cells.BENCH)
    monkeypatch.setattr(harness, "load_module", load_module)
