"""The reader PR 36 added, ``step.remat_share``, on hand-made records of a
traced run's ``by_name``. Plain Python on dicts; nothing here starts JAX."""

from __future__ import annotations

import os

import pytest
from bench_cells import BENCH

from benchmarks import harness

NAME = "step.remat_share"


def _cell(name: str = "internlm2_1p8b.steady") -> dict:
    entry, cfg, traffic = harness.cell(BENCH, name)
    return {"workload": entry, "cfg": cfg, "traffic": traffic,
            "model": harness.model_class(cfg),
            "device": {"kind": "TPU v5 lite"}}


def _remat_run(names: dict) -> dict:
    run = _cell()
    run["traced"] = {"by_name": names,
                     "count_by_name": dict.fromkeys(names, 1)}
    return run


@pytest.mark.parametrize("names,expected", [
    # two recomputed instructions (XLA numbers a second copy) and one that
    # only READS a recomputed value: the mark counts in the name alone
    ({"%fusion.906.remat = bf16[2,2048,8192]{2,1,0} fusion(%bitcast.7)": 1.0,
      "%fusion.702.remat2 = bf16[2,2048,8192]{2,1,0} fusion(%bitcast.9)": 2.0,
      "%fusion.166 = (f32[], bf16[2048,8192,1]{1,0,2}) fusion(%bitcast.4139,"
      " %fusion.846.remat)": 5.0,
      "%flash_attn_fwd.3 = bf16[2,16,2048,128]{3,2,1,0} custom-call()": 2.0},
     30.0),
    # a traced program that recomputes nothing reads 0, which is a reading
    ({"%fusion.1 = f32[] fusion()": 2.0, "%copy-done.3 = f32[8] copy-done()":
      1.0}, 0.0),
    # no trace, or a trace without device events: nothing
    (None, None),
    ({}, None)])
def test_remat_share_counts_the_mark_in_the_instructions_own_name(
        names, expected):
    read = harness.load_module("metrics", NAME).read
    run = _cell() if names is None else _remat_run(names)
    assert read(run) == (None if expected is None
                         else pytest.approx(expected))


def test_remat_share_is_in_benchmark_json_with_its_reader():
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    assert listed[NAME]["moves"] == "tokens_per_s"
    assert listed[NAME]["layer"] == "step program"
    # every training cell's trace has device events, recomputed or not: the
    # metric reads (0 where nothing is recomputed), so it lists no cells
    assert "workloads" not in listed[NAME]
    assert os.path.exists(os.path.join(
        os.path.dirname(harness.__file__), "metrics", NAME + ".py"))
