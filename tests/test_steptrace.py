"""Steptrace: clock-aligned per-step critical-path tracing.

The ISSUE 17 acceptance story: every fleet step is attributed to the
rank and phase that gated it. Worker records (obs/steptrace.py) carry
NTP-style clock offsets whose stamped uncertainty provably bounds the
true offset (property tests with injectable clocks); the master-side
assembler (master/steptrace.py) joins records by (generation, step),
solves the critical path across the cross-slice barrier, and feeds the
tsdb, the CriticalPathRule, and the tools/steptrace.py waterfall —
which renders byte-identically from the live RPC and a flight dump.
"""

import importlib.util
import json
import os
import random
import statistics
import sys
import threading
import time

import numpy as np
import pytest

from dlrover_tpu import obs
from dlrover_tpu.common.config import Context
from dlrover_tpu.master.steptrace import (
    StepTraceAssembler,
    solve_group,
    summarize_solved,
)
from dlrover_tpu.obs.steptrace import (
    TRACE_PHASES,
    ClockSync,
    StepTraceRecorder,
    phase_seconds,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_context():
    Context.reset()
    yield
    Context.reset()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"_steptrace_test_{name}", os.path.join(REPO, "tools",
                                                f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _rec(rank, step, phases, *, gen=0, slice_id=None, t0=1000.0,
         off=0.0, err=0.001, peers=None):
    entry = {"v": 1, "step": step, "gen": gen,
             "slice": rank if slice_id is None else slice_id,
             "rank": rank, "t0": t0, "off": off, "err": err,
             "phases": phases}
    if peers:
        entry["peers"] = peers
    return entry


# ---------------------------------------------------------------------------
# ClockSync: the midpoint estimator's uncertainty must BOUND the truth
# ---------------------------------------------------------------------------


class _SimLink:
    """Injectable wall clock + one-RTT probe function with a known true
    offset and arbitrary (asymmetric) request/response latency."""

    def __init__(self, true_offset, d_req, d_resp, drift=0.0):
        self.t = 0.0              # true (master) time
        self.true_offset = true_offset
        self.d_req, self.d_resp = d_req, d_resp
        self.drift = drift        # local oscillator rate error

    def local(self):
        # local wall = (true time) * (1+drift) - true_offset at t=0;
        # master - local = true_offset - drift*t (drifts apart)
        return (self.t * (1.0 + self.drift)) - self.true_offset

    def current_offset(self):
        return self.t - self.local()

    def probe(self):
        self.t += self.d_req
        server_ts = self.t
        self.t += self.d_resp
        return server_ts

    def advance(self, seconds):
        self.t += seconds


class TestClockSync:
    def test_no_probe_is_the_unaligned_sentinel(self):
        sync = ClockSync(probe_fn=None)
        assert sync.estimate() == (0.0, -1.0)
        assert not sync.probe()

    def test_midpoint_bound_holds_under_asymmetric_latency(self):
        # grossly asymmetric: 1 ms out, 30 ms back — the midpoint is
        # wrong by almost RTT/2, and the stamped bound must say so
        link = _SimLink(true_offset=3.7, d_req=0.001, d_resp=0.030)
        sync = ClockSync(probe_fn=link.probe, wall=link.local,
                         mono=link.local)
        assert sync.probe()
        offset, err = sync.estimate()
        assert err >= 0.0
        assert abs(offset - link.current_offset()) <= err + 1e-12

    def test_property_sweep_random_offset_latency(self):
        rng = random.Random(17)
        for _ in range(50):
            link = _SimLink(
                true_offset=rng.uniform(-120.0, 120.0),
                d_req=rng.uniform(1e-4, 0.05),
                d_resp=rng.uniform(1e-4, 0.05))
            sync = ClockSync(probe_fn=link.probe, wall=link.local,
                             mono=link.local)
            for _ in range(rng.randint(1, 5)):
                link.advance(rng.uniform(0.0, 2.0))
                assert sync.probe()
            offset, err = sync.estimate()
            assert abs(offset - link.current_offset()) <= err + 1e-12

    def test_drift_ages_the_bound_and_it_still_holds(self):
        # a 100 ppm-fast local oscillator, probed once, then 300 s of
        # silence: the true offset moved ~30 ms; the aged bound
        # (DRIFT_PPM=200 allowance) must still cover it
        link = _SimLink(true_offset=-5.0, d_req=0.002, d_resp=0.002,
                        drift=100e-6)
        sync = ClockSync(probe_fn=link.probe, wall=link.local,
                         mono=link.local)
        assert sync.probe()
        _, err_fresh = sync.estimate()
        link.advance(300.0)
        offset, err_aged = sync.estimate()
        assert err_aged > err_fresh
        assert abs(offset - link.current_offset()) <= err_aged

    def test_fresher_lower_uncertainty_sample_wins(self):
        link = _SimLink(true_offset=1.0, d_req=0.050, d_resp=0.050)
        sync = ClockSync(probe_fn=link.probe, wall=link.local,
                         mono=link.local)
        sync.probe()
        _, err_wide = sync.estimate()
        link.d_req = link.d_resp = 0.0005   # the network calmed down
        sync.probe()
        _, err_tight = sync.estimate()
        assert err_tight < err_wide

    def test_failed_and_declined_probes_keep_the_estimate(self):
        link = _SimLink(true_offset=2.0, d_req=0.001, d_resp=0.001)
        sync = ClockSync(probe_fn=link.probe, wall=link.local,
                         mono=link.local)
        assert sync.probe()
        before = sync.estimate()

        sync._probe_fn = lambda: (_ for _ in ()).throw(OSError("down"))
        assert not sync.probe()
        sync._probe_fn = lambda: -1.0   # old master: unsupported RPC
        assert not sync.probe()
        assert sync.estimate() == before
        assert sync.stats()["failures"] == 2

    def test_maybe_probe_rate_limits_even_on_failure(self):
        calls = []
        link = _SimLink(true_offset=0.0, d_req=0.001, d_resp=0.001)

        def probe():
            calls.append(1)
            return link.probe()

        sync = ClockSync(probe_fn=probe, wall=link.local,
                         mono=link.local)
        assert sync.maybe_probe(30.0)
        assert not sync.maybe_probe(30.0)     # not due yet
        link.advance(31.0)
        assert sync.maybe_probe(30.0)
        assert len(calls) == 2


# ---------------------------------------------------------------------------
# StepTraceRecorder: ring, stamping, droppable flush
# ---------------------------------------------------------------------------


class TestRecorder:
    def test_record_shape_and_clock_stamp(self):
        link = _SimLink(true_offset=4.2, d_req=0.001, d_resp=0.001)
        sync = ClockSync(probe_fn=link.probe, wall=link.local,
                         mono=link.local)
        sync.probe()
        recorder = StepTraceRecorder(capacity=8, rank=3, slice_id=1,
                                     clock_sync=sync)
        recorder.record(7, 2, 1234.5,
                        [("data_wait", 0.0, 0.01),
                         ("compute", 0.01, 0.2)],
                        peers={0: 0.19})
        (entry,) = recorder.drain()
        assert entry["step"] == 7 and entry["gen"] == 2
        assert entry["rank"] == 3 and entry["slice"] == 1
        assert entry["err"] >= 0.0
        assert abs(entry["off"] - 4.2) <= entry["err"] + 1e-3
        assert entry["phases"] == [["data_wait", 0.0, 0.01],
                                   ["compute", 0.01, 0.2]]
        assert entry["peers"] == {"0": 0.19}
        assert phase_seconds(entry) == {"data_wait": 0.01,
                                        "compute": 0.2}

    def test_ring_drops_oldest_and_counts(self):
        recorder = StepTraceRecorder(capacity=4)
        for step in range(10):
            recorder.record(step, 0, 0.0, [("compute", 0.0, 0.01)])
        assert recorder.dropped == 6
        batch = recorder.drain()
        assert [r["step"] for r in batch] == [6, 7, 8, 9]
        assert recorder.drain() == []

    def test_flush_swallows_transport_failure(self):
        class _DeadClient:
            def report_telemetry(self, **kwargs):
                raise ConnectionError("gone")

        recorder = StepTraceRecorder(capacity=4)
        recorder.record(1, 0, 0.0, [("compute", 0.0, 0.01)])
        recorder.flush_to(_DeadClient())   # must not raise
        assert recorder.drain() == []      # batch consumed (lost)

    def test_flush_ships_batch(self):
        shipped = {}

        class _Client:
            def report_telemetry(self, steptrace=None, **kwargs):
                shipped["batch"] = steptrace

        recorder = StepTraceRecorder(capacity=4)
        recorder.record(1, 0, 0.0, [("compute", 0.0, 0.01)])
        recorder.flush_to(_Client())
        assert len(shipped["batch"]) == 1

    def test_record_overhead_under_one_percent_of_10ms_step(self):
        """Acceptance: record + batching must cost < 1 % of a 10 ms
        CPU step — i.e. a median under 100 µs (it is single-digit µs:
        one dict build and a bounded append)."""
        link = _SimLink(true_offset=1.0, d_req=0.001, d_resp=0.001)
        sync = ClockSync(probe_fn=link.probe, wall=link.local,
                         mono=link.local)
        sync.probe()
        recorder = StepTraceRecorder(capacity=512, rank=0, slice_id=0,
                                     clock_sync=sync)
        phases = [("data_wait", 0.0, 0.001), ("h2d", 0.001, 0.0005),
                  ("compute", 0.0015, 0.008),
                  ("checkpoint", 0.0095, 0.0005)]
        samples = []
        for step in range(1000):
            t0 = time.perf_counter()
            recorder.record(step, 0, 1000.0 + step, phases,
                            peers={1: 0.009})
            samples.append(time.perf_counter() - t0)
        median = statistics.median(samples)
        assert median < 0.0001, f"median record cost {median*1e6:.1f}us"


# ---------------------------------------------------------------------------
# solve_group / summarize_solved: the critical-path walk
# ---------------------------------------------------------------------------


class TestSolve:
    def test_single_lane_attributes_its_dominant_phase(self):
        solved = solve_group(0, 5, {0: _rec(
            0, 5, [["data_wait", 0.0, 0.02], ["compute", 0.02, 0.3]])})
        assert solved["gating_rank"] == 0
        assert solved["gating_phase"] == "compute"
        assert not solved["hopped"]
        assert solved["cross_slice_wait_s"] == 0.0

    def test_tail_rank_wins(self):
        solved = solve_group(0, 5, {
            0: _rec(0, 5, [["compute", 0.0, 0.1]]),
            1: _rec(1, 5, [["compute", 0.0, 0.4]]),
        })
        assert solved["gating_rank"] == 1
        assert solved["span_s"] == pytest.approx(0.4)

    def test_clock_offset_moves_the_tail(self):
        # rank 0's record ENDS later in local time (1000.8 vs
        # 1000.35), but its clock runs 0.5 s ahead — aligned, rank 0
        # ends at 1000.3 and rank 1 at 1000.35: rank 1 is the tail
        solved = solve_group(0, 5, {
            0: _rec(0, 5, [["compute", 0.0, 0.3]], t0=1000.5, off=-0.5),
            1: _rec(1, 5, [["compute", 0.0, 0.35]], t0=1000.0, off=0.0),
        })
        assert solved["gating_rank"] == 1

    def test_barrier_hop_names_the_delayed_slice(self):
        # slice 0 waited on slice 1's header: the walk must hop the
        # barrier and attribute slice 1's compute, not slice 0's wait
        solved = solve_group(3, 9, {
            0: _rec(0, 9, [["compute", 0.0, 0.1],
                           ["local_post", 0.1, 0.002],
                           ["cross_slice_wait", 0.102, 0.3],
                           ["apply", 0.402, 0.01]],
                    peers={"1": 0.4}),
            1: _rec(1, 9, [["compute", 0.0, 0.39],
                           ["local_post", 0.39, 0.002],
                           ["apply", 0.402, 0.01]]),
        })
        assert solved["gating_rank"] == 1
        assert solved["gating_phase"] == "compute"
        assert solved["hopped"]
        assert solved["cross_slice_wait_s"] == pytest.approx(0.3)
        assert 0.0 < solved["cross_slice_wait_fraction"] <= 1.0

    def test_hop_never_reattributes_the_wait_itself(self):
        # degenerate: the hopped-to slice's record is ALSO mostly wait
        # (both stalled on a third party) — the hop excludes
        # cross_slice_wait so attribution falls to its real work
        solved = solve_group(0, 2, {
            0: _rec(0, 2, [["compute", 0.0, 0.01],
                           ["cross_slice_wait", 0.01, 0.5]],
                    peers={"1": 0.5}),
            1: _rec(1, 2, [["compute", 0.0, 0.02],
                           ["cross_slice_wait", 0.02, 0.4]]),
        })
        assert solved["gating_rank"] == 1
        assert solved["gating_phase"] == "compute"

    def test_payload_is_json_stable(self):
        solved = solve_group(0, 1, {0: _rec(
            0, 1, [["compute", 0.0, 0.123456789]])})
        assert solved == json.loads(json.dumps(solved))

    def test_summary_shape_and_dominants(self):
        groups = [solve_group(0, s, {
            0: _rec(0, s, [["compute", 0.0, 0.1]]),
            1: _rec(1, s, [["compute", 0.0, 0.3]]),
        }) for s in range(4)]
        summary = summarize_solved(groups)
        assert summary["steps"] == 4
        assert summary["dominant_gating_rank"] == 1
        assert summary["dominant_gating_phase"] == "compute"
        assert summary["by_rank"]["1"]["gating_steps"] == 4
        assert summary["by_rank"]["1"]["gating_s"] == pytest.approx(1.2)
        assert summarize_solved([])["cross_slice_wait_fraction"] == -1.0


# ---------------------------------------------------------------------------
# StepTraceAssembler: join, ring, publish watermark, eviction
# ---------------------------------------------------------------------------


class _FakeTsdb:
    def __init__(self):
        self.points = []

    def ingest(self, name, value, labels=None, **kwargs):
        self.points.append((name, value, labels or {}))


class TestAssembler:
    def test_ingest_validates_and_counts_drops(self):
        asm = StepTraceAssembler(ring_steps=8)
        good = _rec(0, 1, [["compute", 0.0, 0.1]])
        unranked = _rec(-1, 2, [["compute", 0.0, 0.1]])
        accepted = asm.ingest(
            [good, unranked, {"no": "step"}, "junk", 42],
            node_rank=5)
        assert accepted == 2
        stats = asm.stats()
        assert stats["records_total"] == 2 and stats["dropped"] == 3
        payload = asm.query_payload()
        # the rank-less record adopted the sender's node_rank
        assert payload["steps"][1]["gating_rank"] == 5

    def test_ring_evicts_oldest_groups(self):
        asm = StepTraceAssembler(ring_steps=4)
        for step in range(10):
            asm.ingest([_rec(0, step, [["compute", 0.0, 0.1]])])
        steps = [g["step"] for g in asm.query_payload()["steps"]]
        assert steps == [6, 7, 8, 9]

    def test_query_filters(self):
        asm = StepTraceAssembler(ring_steps=32)
        for step in range(10):
            asm.ingest([_rec(0, step, [["compute", 0.0, 0.1]])])
        got = asm.query_payload(start_step=3, end_step=5)["steps"]
        assert [g["step"] for g in got] == [3, 4, 5]
        got = asm.query_payload(last_n=2)["steps"]
        assert [g["step"] for g in got] == [8, 9]

    def test_tsdb_publish_watermark_once_per_group(self):
        tsdb = _FakeTsdb()
        asm = StepTraceAssembler(tsdb=tsdb, ring_steps=32)
        asm.ingest([_rec(0, 1, [["compute", 0.0, 0.1]])])
        assert tsdb.points == []        # newest group: not published
        asm.ingest([_rec(0, 2, [["compute", 0.0, 0.1]])])
        names = [p[0] for p in tsdb.points]
        assert names == [
            "dlrover_tpu_steptrace_gating_rank",
            "dlrover_tpu_steptrace_gating_seconds",
            "dlrover_tpu_steptrace_cross_slice_wait_fraction",
        ]
        assert tsdb.points[1][2] == {"phase": "compute"}
        before = len(tsdb.points)
        # a late record for step 1 must not re-publish it
        asm.ingest([_rec(1, 1, [["compute", 0.0, 0.05]])])
        assert len(tsdb.points) == before

    def test_eviction_sweep_drops_departed_ranks(self):
        asm = StepTraceAssembler(ring_steps=8)
        asm.ingest([_rec(0, 1, [["compute", 0.0, 0.1]]),
                    _rec(1, 1, [["compute", 0.0, 0.2]])])
        asm.evict_departed([0])
        (group,) = asm.query_payload()["steps"]
        assert [ln["rank"] for ln in group["lanes"]] == [0]

    def test_generation_separates_groups(self):
        asm = StepTraceAssembler(ring_steps=8)
        asm.ingest([_rec(0, 5, [["compute", 0.0, 0.1]], gen=1)])
        asm.ingest([_rec(0, 5, [["compute", 0.0, 0.2]], gen=2)])
        steps = asm.query_payload()["steps"]
        assert [(g["gen"], g["step"]) for g in steps] == [(1, 5), (2, 5)]


# ---------------------------------------------------------------------------
# CriticalPathRule: gating seconds with hysteresis, phase evidence
# ---------------------------------------------------------------------------


class TestCriticalPathRule:
    def _snapshot(self, summary):
        from dlrover_tpu.master.diagnosis.rules import DiagnosisSnapshot

        return DiagnosisSnapshot(ts=time.time(), worker_speeds={},
                                 steptrace=summary)

    def _summary(self, rank=3, gating=8, total=10, phase="compute",
                 seconds=4.0):
        return {
            "steps": total,
            "by_rank": {str(rank): {
                "gating_steps": gating, "gating_s": seconds,
                "phases": {phase: seconds}}},
            "dominant_gating_rank": rank,
            "dominant_gating_phase": phase,
            "cross_slice_wait_fraction": 0.1,
        }

    def test_flags_with_hysteresis_and_names_the_phase(self):
        from dlrover_tpu.master.diagnosis.rules import CriticalPathRule

        ctx = Context.singleton()
        ctx.update(straggler_trigger_windows=3,
                   diagnosis_min_worker_samples=2)
        rule = CriticalPathRule()
        snap = self._snapshot(self._summary())
        assert rule.evaluate(snap, ctx) == []
        assert rule.evaluate(snap, ctx) == []
        (report,) = rule.evaluate(snap, ctx)
        assert report.worker_id == 3
        assert report.severity == "warning"
        assert "compute" in report.summary
        assert "gated 8/10" in report.summary
        assert "4.00s gating" in report.summary
        assert report.details["gating_phase"] == "compute"
        assert "profile:3" in report.actions
        assert 3 in rule.flagged
        # flagged stays quiet while the evidence persists
        assert rule.evaluate(snap, ctx) == []

    def test_clears_after_clean_windows(self):
        from dlrover_tpu.master.diagnosis.rules import CriticalPathRule

        ctx = Context.singleton()
        ctx.update(straggler_trigger_windows=1,
                   diagnosis_min_worker_samples=2)
        rule = CriticalPathRule()
        rule.evaluate(self._snapshot(self._summary()), ctx)
        assert 3 in rule.flagged
        clean = self._snapshot(self._summary(gating=1))
        assert rule.evaluate(clean, ctx) == []
        (report,) = rule.evaluate(clean, ctx)
        assert report.severity == "info"
        assert 3 not in rule.flagged

    def test_disabled_and_undersampled_windows_are_skipped(self):
        from dlrover_tpu.master.diagnosis.rules import CriticalPathRule

        ctx = Context.singleton()
        ctx.update(straggler_trigger_windows=1,
                   diagnosis_min_worker_samples=5)
        rule = CriticalPathRule()
        assert rule.evaluate(self._snapshot(None), ctx) == []
        thin = self._summary(total=3, gating=3)
        assert rule.evaluate(self._snapshot(thin), ctx) == []
        ctx.update(critical_path_gating_fraction=0.0,
                   diagnosis_min_worker_samples=2)
        assert rule.evaluate(self._snapshot(self._summary()), ctx) == []

    def test_a_fleet_of_one_has_no_critical_path(self):
        """Its only rank trivially gates every step: no report, no
        profiler request (every single-worker run used to get one)."""
        from dlrover_tpu.master.diagnosis.rules import CriticalPathRule

        ctx = Context.singleton()
        ctx.update(straggler_trigger_windows=1,
                   diagnosis_min_worker_samples=2)
        alone = summarize_solved([
            solve_group(0, step, {0: _rec(
                0, step, [["compute", 0.0, 0.35]], t0=1000.0 + step)})
            for step in range(10)])
        assert alone["ranks"] == 1 and alone["steps"] == 10
        assert alone["by_rank"]["0"]["gating_steps"] == 10
        rule = CriticalPathRule()
        assert rule.evaluate(self._snapshot(alone), ctx) == []
        assert rule.flagged == set()
        # the same gating share with a second rank in the window flags
        pair = dict(alone, ranks=2)
        (report,) = rule.evaluate(self._snapshot(pair), ctx)
        assert "profile:0" in report.actions

    def test_departed_rank_evidence_evicted(self):
        from dlrover_tpu.master.diagnosis.rules import CriticalPathRule

        ctx = Context.singleton()
        ctx.update(straggler_trigger_windows=3,
                   diagnosis_min_worker_samples=2)
        rule = CriticalPathRule()
        rule.evaluate(self._snapshot(self._summary(rank=3)), ctx)
        rule.evaluate(self._snapshot(self._summary(rank=3)), ctx)
        # rank 3 departs; a different rank's window arrives
        rule.evaluate(self._snapshot(self._summary(rank=4)), ctx)
        assert 3 not in rule._over
        # rank 3 re-joins: its counter restarts from zero
        assert rule.evaluate(
            self._snapshot(self._summary(rank=3)), ctx) == []

    def test_in_default_chain(self):
        from dlrover_tpu.master.diagnosis.rules import default_rules

        assert "critical_path" in [r.name for r in default_rules()]

    def test_manager_folds_assembler_summary(self):
        from dlrover_tpu.master.diagnosis.manager import DiagnosisManager
        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        asm = StepTraceAssembler(ring_steps=8)
        asm.ingest([_rec(0, 1, [["compute", 0.0, 0.1]])])
        manager = DiagnosisManager(SpeedMonitor(), steptrace=asm)
        snap = manager.snapshot()
        assert snap.steptrace is not None
        assert snap.steptrace["steps"] == 1


# ---------------------------------------------------------------------------
# rendering: waterfall golden byte-identity + chrome trace schema
# ---------------------------------------------------------------------------


def _two_slice_assembler():
    asm = StepTraceAssembler(ring_steps=32)
    for step in (1, 2, 3):
        asm.ingest([_rec(0, step,
                         [["data_wait", 0.0, 0.01],
                          ["compute", 0.01, 0.1],
                          ["local_post", 0.11, 0.002],
                          ["cross_slice_wait", 0.112, 0.3],
                          ["apply", 0.412, 0.01]],
                         slice_id=0, peers={"1": 0.41})])
        asm.ingest([_rec(1, step,
                         [["data_wait", 0.0, 0.01],
                          ["compute", 0.01, 0.4],
                          ["local_post", 0.41, 0.002],
                          ["apply", 0.412, 0.01]],
                         slice_id=1)])
    return asm


class TestWaterfall:
    def test_live_and_flight_renders_are_byte_identical(self, tmp_path):
        tool = _load_tool("steptrace")
        asm = _two_slice_assembler()
        live = tool.render_waterfall(asm.query_payload(last_n=128))

        recorder = obs.flight_recorder.FlightRecorder(capacity=64)
        recorder.record_event("steptrace",
                              snapshot=asm.flight_snapshot())
        path = recorder.dump(str(tmp_path / "flight-master.json"))
        with open(path) as f:
            dump = json.load(f)
        payload = tool.payload_from_flight(dump)
        assert payload is not None
        postmortem = tool.render_waterfall(payload)
        assert postmortem.encode() == live.encode()

    def test_waterfall_names_the_gating_lane_and_phase(self):
        tool = _load_tool("steptrace")
        text = tool.render_waterfall(
            _two_slice_assembler().query_payload(), width=32)
        assert "gating: rank 1 (compute" in text
        assert "via barrier hop" in text
        assert "w" in text            # the wait is drawn on lane 0
        assert "*" in text            # the gating lane is marked
        assert "dominant rank 1" in text

    def test_cli_renders_from_flight_dump(self, tmp_path, capsys):
        tool = _load_tool("steptrace")
        asm = _two_slice_assembler()
        recorder = obs.flight_recorder.FlightRecorder(capacity=64)
        recorder.record_event("steptrace",
                              snapshot=asm.flight_snapshot())
        path = recorder.dump(str(tmp_path / "dump.json"))
        assert tool.main(["--flight", path]) == 0
        out = capsys.readouterr().out
        assert "gating: rank 1" in out
        # a dump with no steptrace event exits 2, loudly
        empty = obs.flight_recorder.FlightRecorder(capacity=8)
        empty_path = empty.dump(str(tmp_path / "empty.json"))
        assert tool.main(["--flight", empty_path]) == 2

    def test_step_filter(self, tmp_path, capsys):
        tool = _load_tool("steptrace")
        asm = _two_slice_assembler()
        recorder = obs.flight_recorder.FlightRecorder(capacity=64)
        recorder.record_event("steptrace",
                              snapshot=asm.flight_snapshot())
        path = recorder.dump(str(tmp_path / "dump.json"))
        assert tool.main(["--flight", path, "--step", "2"]) == 0
        out = capsys.readouterr().out
        assert "1 assembled steps" in out


class TestChromeTrace:
    def test_schema_flow_edges_and_no_negative_durations(self,
                                                         tmp_path):
        tool = _load_tool("steptrace")
        asm = _two_slice_assembler()
        out = tmp_path / "trace.json"
        recorder = obs.flight_recorder.FlightRecorder(capacity=64)
        recorder.record_event("steptrace",
                              snapshot=asm.flight_snapshot())
        dump_path = recorder.dump(str(tmp_path / "dump.json"))
        assert tool.main(["--flight", dump_path,
                          "--chrome-trace", str(out)]) == 0
        with open(out) as f:
            trace = json.load(f)

        events = trace["traceEvents"]
        assert trace["displayTimeUnit"] == "ms"
        phases_seen = set()
        by_ph = {}
        for event in events:
            assert event["ph"] in ("M", "X", "s", "f")
            by_ph.setdefault(event["ph"], []).append(event)
            if event["ph"] == "M":
                assert event["name"] == "process_name"
                continue
            # schema: every timed event is placed, non-negative,
            # integer pid/tid, step args carried
            assert isinstance(event["pid"], int)
            assert isinstance(event["tid"], int)
            assert event["ts"] >= 0.0
            if event["ph"] == "X":
                assert event["dur"] >= 0.0
                phases_seen.add(event["name"])
            assert event["args"]["step"] >= 0
        assert {"compute", "cross_slice_wait", "apply"} <= phases_seen
        assert {e["pid"] for e in by_ph["M"]} == {0, 1}

        # cross-process flow edges: every source pairs with a sink of
        # the same id, source on the gating rank, sink no earlier than
        # the source (clock-aligned, never a backwards arrow)
        sources = {e["id"]: e for e in by_ph["s"]}
        sinks = {e["id"]: e for e in by_ph["f"]}
        assert sources and set(sources) == set(sinks)
        for flow_id, source in sources.items():
            sink = sinks[flow_id]
            assert source["pid"] == 1      # the delayed (gating) slice
            assert sink["pid"] == 0        # the waiting slice
            assert sink["ts"] >= source["ts"]
            assert sink.get("bp") == "e"

    def test_clock_offsets_align_lanes(self):
        # rank 1's local clock is 100 s behind; aligned, its compute
        # must land INSIDE the step, not 100 s away
        tool = _load_tool("steptrace")
        asm = StepTraceAssembler(ring_steps=8)
        asm.ingest([
            _rec(0, 1, [["compute", 0.0, 0.1]], t0=1000.0, off=0.0),
            _rec(1, 1, [["compute", 0.0, 0.12]], t0=900.0, off=100.0),
        ])
        trace = tool.chrome_trace(asm.query_payload())
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        span = max(e["ts"] + e["dur"] for e in xs) - min(
            e["ts"] for e in xs)
        assert span < 1e6   # < 1 s, not ~100 s


# ---------------------------------------------------------------------------
# tools/top.py panel + tools/obs_dump.py filters (satellites)
# ---------------------------------------------------------------------------


class TestTopPanel:
    def test_panel_renders_attribution(self):
        top = _load_tool("top")
        data = {"steptrace": _two_slice_assembler().query_payload()}
        lines = top.render_critical_path(data)
        text = "\n".join(lines)
        assert "critical path" in text
        assert "dominant rank 1" in text
        assert "compute" in text

    def test_panel_handles_missing_evidence(self):
        top = _load_tool("top")
        lines = top.render_critical_path({"steptrace": {}})
        assert "(no traced steps)" in "\n".join(lines)

    def test_flight_collect_reads_the_snapshot_event(self, tmp_path):
        top = _load_tool("top")
        asm = _two_slice_assembler()
        recorder = obs.flight_recorder.FlightRecorder(capacity=64)
        recorder.record_event("steptrace",
                              snapshot=asm.flight_snapshot())
        path = recorder.dump(str(tmp_path / "dump.json"))
        with open(path) as f:
            dump = json.load(f)
        data = top.collect_from_flight(dump, path)
        assert data["steptrace"]["summary"]["steps"] == 3
        assert "dominant rank 1" in top.render(data)


class TestObsDumpFilters:
    def _payload(self):
        return {
            "role": "worker", "pid": 1, "host": "h", "reason": "test",
            "dumped_at": 1000.0,
            "events": [
                {"kind": "event", "name": "replan_applied",
                 "ts": 900.0, "attrs": {"step": 5}},
                {"kind": "event", "name": "train_degraded_step",
                 "ts": 990.0, "attrs": {"step": 12}},
                {"kind": "span", "name": "checkpoint_save",
                 "ts": 995.0, "duration_s": 0.5, "status": "ok",
                 "attrs": {"step": 20}},
                {"kind": "event", "name": "sigterm", "ts": 999.0,
                 "attrs": {}},
            ],
        }

    def test_step_range_filter(self):
        dump_tool = _load_tool("obs_dump")
        text = dump_tool.render(self._payload(),
                                step_range=(10, 20))
        assert "train_degraded_step" in text
        assert "checkpoint_save" in text
        assert "replan_applied" not in text
        assert "sigterm" not in text     # no step attr: hidden
        assert "shown: 2/4" in text

    def test_single_step_spec(self):
        dump_tool = _load_tool("obs_dump")
        assert dump_tool.parse_step_range("7") == (7, 7)
        assert dump_tool.parse_step_range("3:9") == (3, 9)
        with pytest.raises(ValueError):
            dump_tool.parse_step_range("9:3")

    def test_since_filter_anchors_at_dump_moment(self):
        dump_tool = _load_tool("obs_dump")
        text = dump_tool.render(self._payload(), since_s=15.0)
        assert "replan_applied" not in text    # 100 s before the dump
        assert "train_degraded_step" in text
        assert "sigterm" in text
        assert "shown: 3/4" in text

    def test_cli_rejects_bad_step_spec(self, tmp_path, capsys):
        dump_tool = _load_tool("obs_dump")
        path = tmp_path / "d.json"
        path.write_text(json.dumps(self._payload()))
        assert dump_tool.main([str(path), "--step", "bogus"]) == 2


# ---------------------------------------------------------------------------
# flight-ring capacity knobs (satellite)
# ---------------------------------------------------------------------------


class TestFlightRingKnobs:
    def test_env_override_sizes_the_rings(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_FLIGHT_RING_EVENTS", "16")
        monkeypatch.setenv("DLROVER_TPU_FLIGHT_RING_SPANS", "8")
        Context.reset()
        assert Context.singleton().flight_ring_events == 16
        recorder = obs.flight_recorder.FlightRecorder()
        for index in range(40):
            recorder.record_event("knob_test", index=index)
        assert len(recorder.snapshot()) == 16
        assert recorder._seen_span_ids.maxlen == 8

    def test_explicit_capacity_keeps_old_behavior(self):
        recorder = obs.flight_recorder.FlightRecorder(capacity=4)
        for index in range(10):
            recorder.record_event("knob_test", index=index)
        assert len(recorder.snapshot()) == 4
        assert recorder._seen_span_ids.maxlen == 4

    def test_defaults_unchanged(self):
        recorder = obs.flight_recorder.FlightRecorder()
        assert recorder._events.maxlen == 4096
        assert recorder._seen_span_ids.maxlen == 4096


# ---------------------------------------------------------------------------
# in-process 2-slice acceptance: a chaos-delayed rank is NAMED
# ---------------------------------------------------------------------------


class _FakeSyncClient:
    """The MasterClient surface SliceGradSync needs (kv + registry)."""

    def __init__(self, kv, status):
        self.kv = kv
        self.status = status

    def kv_set(self, key, value):
        self.kv[key] = value
        return True

    def kv_get(self, key):
        return self.kv.get(key, b"")

    def get_slice_status(self):
        return json.loads(json.dumps(self.status))


def _worker_body(sync, recorder, rank, steps, compute_s, barrier,
                 failures):
    """One slice's steady-state loop: the same per-step decomposition
    elastic_loop._record_steptrace builds, against the REAL
    SliceGradSync (its info["trace"] marks)."""
    try:
        grads = [np.full((8,), float(rank + 1), np.float32)]
        for step in range(1, steps + 1):
            barrier.wait(timeout=30.0)
            t_step = time.monotonic()
            time.sleep(0.001)                    # data wait
            t_data = time.monotonic()
            time.sleep(compute_s)                # "compute" (the chaos
            _, info = sync.reduce(list(grads), step)   # delay lives here)
            apply_done = time.monotonic()
            trace = info["trace"]
            data_d = t_data - t_step
            ready = trace["grads_ready"] - t_step
            post = max(ready, trace["local_post"] - t_step)
            coll = max(post, trace["collect_done"] - t_step)
            apply_end = max(coll, apply_done - t_step)
            phases = [("data_wait", 0.0, data_d),
                      ("compute", data_d, max(0.0, ready - data_d)),
                      ("local_post", ready, post - ready),
                      ("cross_slice_wait", post, coll - post),
                      ("apply", coll, apply_end - coll)]
            peers = {sid: max(0.0, t - t_step)
                     for sid, t in (trace.get("peers") or {}).items()}
            t0_wall = time.time() - (time.monotonic() - t_step)
            recorder.record(step, 0, t0_wall, phases,
                            peers=peers or None)
    except Exception as exc:  # noqa: BLE001 — surface in the test
        failures.append((rank, exc))


def test_two_slice_acceptance_delayed_rank_named(tmp_path):
    """ISSUE 17 acceptance: two slices in-process over the real
    SliceGradSync, one chaos-delayed; the delayed rank must be named
    gating on >= 80 % of traced steps with cross_slice_wait attributed
    on the surviving slice, the waterfall must render byte-identically
    from a flight dump, and the CriticalPathRule must emit evidence
    naming the phase."""
    from dlrover_tpu.parallel.dcn_sync import SliceGradSync

    Context.singleton().update(dcn_sync_timeout_s=10.0,
                               dcn_sync_poll_s=0.001)
    kv = {}
    status = {"total": 2, "fleet_step": 0,
              "slices": {"0": {"formed": True},
                         "1": {"formed": True}}}
    syncs = [SliceGradSync(_FakeSyncClient(kv, status), 0),
             SliceGradSync(_FakeSyncClient(kv, status), 1)]
    recorders = [StepTraceRecorder(capacity=64, rank=r, slice_id=r)
                 for r in (0, 1)]
    steps, delayed_rank = 10, 1
    barrier = threading.Barrier(2)
    failures = []
    threads = [
        threading.Thread(target=_worker_body, args=(
            syncs[rank], recorders[rank], rank, steps,
            0.030 if rank == delayed_rank else 0.002, barrier,
            failures))
        for rank in (0, 1)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=60.0)
        assert not thread.is_alive()
    assert not failures, failures

    asm = StepTraceAssembler(ring_steps=64)
    for recorder in recorders:
        asm.ingest(recorder.drain())
    payload = asm.query_payload(last_n=128)
    solved = payload["steps"]
    assert len(solved) == steps

    # the chaos-delayed rank is named gating on >= 80% of traced steps
    named = [g for g in solved if g["gating_rank"] == delayed_rank]
    assert len(named) >= 0.8 * steps, \
        [(g["step"], g["gating_rank"], g["gating_phase"])
         for g in solved]
    # ... by its own work, not by the wait the survivor saw
    assert all(g["gating_phase"] != "cross_slice_wait" for g in named)
    assert statistics.median(
        [g["gating_s"] for g in named]) >= 0.02

    # cross_slice_wait is attributed on the SURVIVING slice's lane
    for group in solved:
        surviving = [ln for ln in group["lanes"] if ln["rank"] == 0]
        assert surviving
        waits = phase_seconds(
            {"phases": surviving[0]["phases"]})
        assert waits.get("cross_slice_wait", 0.0) > 0.0
    assert summarize_solved(solved)["cross_slice_wait_fraction"] > 0.0

    # the waterfall renders byte-identically live vs flight dump
    tool = _load_tool("steptrace")
    live = tool.render_waterfall(payload)
    flight = obs.flight_recorder.FlightRecorder(capacity=64)
    flight.record_event("steptrace", snapshot=asm.flight_snapshot())
    with open(flight.dump(str(tmp_path / "dump.json"))) as f:
        dump = json.load(f)
    assert tool.render_waterfall(
        tool.payload_from_flight(dump)).encode() == live.encode()

    # the diagnosis rule fires with evidence naming the phase
    from dlrover_tpu.master.diagnosis.rules import (
        CriticalPathRule,
        DiagnosisSnapshot,
    )

    ctx = Context.singleton()
    ctx.update(straggler_trigger_windows=1,
               diagnosis_min_worker_samples=2)
    rule = CriticalPathRule()
    snap = DiagnosisSnapshot(ts=time.time(), worker_speeds={},
                             steptrace=asm.summary())
    (report,) = rule.evaluate(snap, ctx)
    assert report.worker_id == delayed_rank
    assert report.details["gating_phase"] in TRACE_PHASES
    assert report.details["gating_phase"] != "cross_slice_wait"
    assert "gating" in report.summary
