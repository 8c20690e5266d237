"""The benchmark as its tests see it: ``BENCHMARK.json`` with the tests' own
model class entered beside the accepted configurations and cells.

Every test here that runs over ``BENCH["configs"]`` or ``BENCH["workloads"]``
takes ``BENCH`` from this file, so it also runs over ``twokind``: layers of
two kinds, a head width that is not ``hidden // heads``, a leaf of three
axes, a leaf with a gradient of exactly zero and a second objective
(``models/twokind.py``). A test that assumes Llama's shape of every
configuration then fails in the PR that writes it, not in the PR that brings
the first such model. ``BENCH`` is the only benchmark this file gives out,
and ``test_readers.py`` holds the test files to it.
"""

from __future__ import annotations

import os

from benchmarks import check, harness

HERE = os.path.dirname(os.path.abspath(__file__))
MODELS = os.path.join(HERE, "models")    # the tests' own class's two files
_ACCEPTED = harness.benchmark()
CONFIG_FILE = "tests/benchmarks/configs/twokind.json"
CELL = "twokind.steady"
OWN_CONFIG = {
    "name": "twokind",
    "source": harness.load_json(harness.ROOT, CONFIG_FILE)["source"],
    "file": CONFIG_FILE, "reduced": [],
    "why": "a second model class, as files"}
OWN_CELL = {
    "name": CELL, "config": "twokind", "traffic": "steady_b2_s2048",
    "chips": 1,
    "why": "the window, the comparison and the readers on a class the "
           "harness has never heard of"}
BENCH = dict(_ACCEPTED, configs=_ACCEPTED["configs"] + [OWN_CONFIG],
             workloads=_ACCEPTED["workloads"] + [OWN_CELL])
# the rehearsal's widths read other gaps than the chip's sizes; these are the
# tiny models' own limits (program on the CPU: 4e-5, 8e-4, 1.5e-3, 3e-4; a
# planted fault reads 0.2 or more, or counts rows), not the cells'
TINY_LIMITS = {"rows_wrong": 0, "loss_gap": 1e-3, "grad_gap": 1e-2,
               "change_gap": 1e-2, "grad_gap_whole": 1e-2,
               "compiles_in_window": 0, "saves_uncommitted": 0}


def limits_for(workload: str) -> dict:
    """An accepted cell's limits file; the rehearsal's for the tests' own."""
    return TINY_LIMITS if workload == CELL else check.limits_for(workload)
