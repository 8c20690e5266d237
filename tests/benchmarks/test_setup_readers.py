"""The set-up readers (``benchmarks/metrics/setup.*.py``) and their reduction
(``benchmarks/setup_reduce.py``): on hand-made span records from the three
processes, and on a run of the tiny cell in this process, whose worker spans
are the program's own. The readers have no entry in ``BENCHMARK.json`` yet:
the window and ``run.py`` do not collect their records (PERF.md section 7)."""

from __future__ import annotations

import json
import os
import time

import pytest
from bench_cells import BENCH

from benchmarks import harness, setup_reduce
from benchmarks import run as bench_run
from benchmarks import worker

SETUP_METRICS = ("setup.device_probe_s", "setup.backend_init_s",
                 "setup.relower_s", "setup.compile_s", "setup.state_init_s",
                 "setup.unattributed_s")
STARTED, OPENED = 1000.0, 1120.0


def _span(name, ts, end_ts, pid=20, span_id=None, parent_id="", **attrs):
    return {"kind": "span", "name": name, "ts": ts, "end_ts": end_ts,
            "duration_s": end_ts - ts, "trace_id": "t0",
            "span_id": span_id or f"{name}-{ts}", "parent_id": parent_id,
            "pid": pid, "attrs": attrs}


def _launcher():
    """The launcher's process: its own spans, the agent's, the master's,
    and a worker span its telemetry relayed (also in the worker's own
    record), one of the window's among them."""
    return [
        _span("master_prepare", 1001.0, 1001.5, pid=10),
        _span("device_probe", 1001.5, 1027.5, pid=10, devices=1,
              source="probe"),
        _span("rendezvous", 1028.0, 1028.1, pid=10, span_id="rdzv"),
        _span("rendezvous_round", 1028.0, 1028.1, pid=10),
        _span("restore_or_init", 1061.0, 1100.0, span_id="restore"),
        _span("train_window", 1121.0, 1122.0),
    ]


def _worker():
    """The worker's flight recorder as the window finds it after closing."""
    return [
        _span("backend_init", 1035.0, 1045.0, parent_id="rdzv",
              platform="tpu", devices=1),
        _span("recompile", 1046.0, 1060.0, phase="relower", devices=1,
              mesh={"data": 1}),
        _span("restore_or_init", 1061.0, 1100.0, span_id="restore"),
        # on the main thread and on the compile thread at once
        _span("state_init", 1061.5, 1066.5, parent_id="restore",
              bytes=6_000_000),
        _span("recompile", 1061.2, 1099.9, parent_id="restore", phase="aot",
              cache="hit", cache_hits=1, cache_misses=0),
        _span("train_window", 1101.0, 1102.0),     # the warm-up's
        _span("host_sync", 1102.0, 1103.0),
        _span("recompile", 1104.0, 1105.0, phase="relower"),   # a later one
        _span("train_window", 1119.0, 1125.0),     # ends inside the window
        {"kind": "event", "name": "compile_event", "ts": 1100.0},
    ]


def _run(worker_spans=None, launcher_spans=None) -> dict:
    snapshot = _worker() if worker_spans is None else worker_spans
    return {"started_wall": STARTED, "window": {"opened_wall": OPENED},
            "setup": setup_reduce.setup_record(snapshot, OPENED),
            "flight": _launcher() if launcher_spans is None
            else launcher_spans}


def _read(name: str, run: dict):
    return harness.load_module("metrics", name).read(run)


def test_setup_record_keeps_the_spans_that_ended_before_the_window():
    record = setup_reduce.setup_record(_worker(), OPENED)
    assert record["record"] == "setup"
    names = [s["name"] for s in record["spans"]]
    assert names == ["backend_init", "recompile", "restore_or_init",
                     "state_init", "recompile", "train_window", "host_sync",
                     "recompile"]
    relower = record["spans"][1]
    assert relower["attrs"] == {"phase": "relower", "devices": 1}   # no dict
    assert set(relower) == set(setup_reduce.FIELDS) | {"attrs"}


def test_read_flight_takes_the_spans_of_every_dump(tmp_path):
    (tmp_path / "flight-process-10.json").write_text(json.dumps(
        {"version": 1, "events": _launcher()[:3] + [
            {"kind": "event", "name": "worker_spawn", "ts": 1030.0}]}))
    (tmp_path / "flight-worker-20.json").write_text(json.dumps(
        {"version": 1, "events": _launcher()[3:]}))
    (tmp_path / "notes.json").write_text("{}")
    spans = setup_reduce.read_flight(str(tmp_path))
    assert [s["name"] for s in spans] == [s["name"] for s in _launcher()]


def test_a_span_in_both_sources_counts_once():
    records = setup_reduce.spans(_run())
    ids = [r["span_id"] for r in records]
    assert len(ids) == len(set(ids))
    assert ids.count("restore") == 1
    assert all(r["end_ts"] < OPENED for r in records)
    assert len(records) == 4 + 8        # the launcher's four, the worker's


@pytest.mark.parametrize("name,expected", [
    ("setup.device_probe_s", 26.0),
    ("setup.backend_init_s", 10.0),
    ("setup.relower_s", 14.0),          # the first, not the later one
    ("setup.compile_s", 38.7),
    ("setup.state_init_s", 5.0),
    # 120 s less 0.5 + 26 + 0.1 + 10 + 14 + 39 + 2 + 1 covered: the compile
    # and the weights inside restore_or_init count once
    ("setup.unattributed_s", 27.4)])
def test_each_setup_reader_on_hand_made_spans(name, expected):
    assert _read(name, _run()) == pytest.approx(expected)


def test_an_overlapped_compile_and_init_count_once():
    records = [_span("state_init", 1061.5, 1066.5),
               _span("recompile", 1061.2, 1099.9, phase="aot")]
    assert setup_reduce.covered(records, STARTED, OPENED) == pytest.approx(
        38.7)
    # clipped to the set-up
    assert setup_reduce.covered(records, 1062.0, 1063.0) == pytest.approx(1.0)


@pytest.mark.parametrize("name,span", [
    ("setup.device_probe_s", "device_probe"),
    ("setup.backend_init_s", "backend_init"),
    ("setup.relower_s", "recompile"),
    ("setup.compile_s", "recompile"),
    ("setup.state_init_s", "state_init"),
    ("setup.unattributed_s", "device_probe"),
    ("setup.unattributed_s", "backend_init")])
def test_a_setup_reader_gives_nothing_without_its_span(name, span):
    kept = [s for s in _worker() if s.get("name") != span]
    launcher = [s for s in _launcher() if s["name"] != span]
    assert _read(name, _run(kept, launcher)) is None
    # a run whose records the benchmark does not collect
    assert _read(name, {"started_wall": STARTED,
                        "window": {"opened_wall": OPENED}}) is None


def _value(name: str, run: dict):
    try:
        return _read(name, run)
    except Exception as e:  # noqa: BLE001 - off the TPU a peak is missing
        return type(e).__name__


def test_a_recorded_run_reads_alike_with_the_set_up_fields(tmp_path):
    """The tiny cell through the window in this process: every accepted
    reader gives what it gave before the ``setup`` and ``flight`` fields
    were there, and the new readers read the program's own spans (no
    launcher here: no ``device_probe``, so nothing unattributed)."""
    from dlrover_tpu import obs

    name = BENCH["workloads"][0]["name"]
    since = time.time()
    ctx = worker.context(name, seed=2_147_483_801, seconds=0.5, trace=False,
                         report_path=str(tmp_path / "report.jsonl"),
                         workdir=str(tmp_path), rehearse=True,
                         in_process=True)
    assert harness.load_module("windows", ctx.traffic["window"]).run(ctx) == 0
    run = bench_run.gather(ctx.report.read(), BENCH, name,
                           started_wall=since, seconds=0.5, trace=False)
    opened = run["window"]["opened_wall"]
    mine = [r for r in obs.get_flight_recorder().snapshot()
            if r.get("ts", 0.0) >= since and r.get("pid") == os.getpid()]
    setup = setup_reduce.setup_record(mine, opened)
    collected = dict(run, setup=setup, flight=setup["spans"][:3])
    accepted = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(accepted) == 24 and not set(accepted) & set(SETUP_METRICS)
    assert ({m: _value(m, run) for m in accepted}
            == {m: _value(m, collected) for m in accepted})

    values = {m: _read(m, collected) for m in SETUP_METRICS}
    assert all(values[m] > 0 for m in (
        "setup.backend_init_s", "setup.relower_s", "setup.compile_s",
        "setup.state_init_s")), values
    assert values["setup.device_probe_s"] is None
    assert values["setup.unattributed_s"] is None
    assert all(_read(m, run) is None for m in SETUP_METRICS)
    spans = setup_reduce.spans(collected)
    restore = setup_reduce.find(spans, "restore_or_init")
    aot = setup_reduce.find(spans, "recompile", phase="aot")
    assert aot["parent_id"] == restore["span_id"]
    assert aot["attrs"]["cache"] in ("hit", "miss")
    assert restore["ts"] <= aot["ts"] and aot["end_ts"] <= opened
