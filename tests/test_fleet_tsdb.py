"""Fleet time-series plane (ISSUE 13): tsdb retention/downsampling/
query alignment property-style over injected clocks, device-truth HBM
watermark telemetry, planner prediction<->measurement calibration (incl.
the state-backend roundtrip across a simulated master restart), the
PlanRegressionRule / HbmPressureRule evidence upgrades, the
TimeSeriesQuery RPC over a real master (>= 3 resolution tiers, bounded
memory asserted), `tools/top.py --once` golden renders from a flight
dump and a live master, the master-ingest + worker-sampling overhead
bound, and the graftlint gate on every new/changed module."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from dlrover_tpu import obs
from dlrover_tpu.common import messages as msg
from dlrover_tpu.common.config import Context
from dlrover_tpu.obs.tsdb import (
    TimeSeriesSidecar,
    TimeSeriesStore,
    TsdbCollector,
)
from dlrover_tpu.parallel import planner
from dlrover_tpu.parallel.calibration import (
    PlanCalibration,
    plan_signature,
)

REPO = str(Path(__file__).resolve().parent.parent)


@pytest.fixture(autouse=True)
def _reset_context():
    """Knob-mutating tests (regression thresholds, state dirs) must not
    leak into the rest of the suite."""
    yield
    Context.reset()


class FakeClock:
    def __init__(self, now=1_000_000.0):
        self.now = now

    def __call__(self):
        return self.now

    def advance(self, seconds):
        self.now += seconds
        return self.now


# ---------------------------------------------------------------------------
# TimeSeriesStore: retention / downsampling / alignment (injected clock)
# ---------------------------------------------------------------------------


class TestTimeSeriesStore:
    def test_downsampling_property_sweep(self):
        """Property-style over several cadences: every tier's buckets
        are grid-aligned, ascending, bounded, and each bucket's
        aggregates are internally consistent (min <= mean <= max, count
        matches the points that landed in it)."""
        for cadence_s, n_points in ((0.5, 3000), (2.0, 1500),
                                    (7.0, 600), (33.0, 400)):
            clock = FakeClock()
            store = TimeSeriesStore(clock=clock)
            values = {}
            for i in range(n_points):
                ts = clock.advance(cadence_s)
                value = float((i * 37) % 101)   # deterministic, varied
                store.ingest("sweep", value, ts=ts)
                values[ts] = value
            for tier in store.tiers():
                res = tier["resolution_s"]
                if res <= 0:
                    continue
                (series,) = store.query("sweep", resolution_s=res)
                assert series["resolution_s"] == res
                points = series["points"]
                assert 0 < len(points) <= tier["capacity"]
                starts = [p[0] for p in points]
                assert starts == sorted(starts)
                for start, mean, lo, hi, count, last in points:
                    assert start % res == 0, "bucket not grid-aligned"
                    landed = [(ts, v) for ts, v in values.items()
                              if start <= ts < start + res]
                    # the ring may have evicted early raw points but
                    # the retained buckets must match what landed
                    if len(landed) == count:
                        landed_values = [v for _, v in landed]
                        assert lo == min(landed_values)
                        assert hi == max(landed_values)
                        assert mean == pytest.approx(
                            sum(landed_values) / len(landed_values))
                        assert last == max(landed)[1]
                    assert lo <= mean <= hi

    def test_retention_is_bounded_and_query_windows(self):
        clock = FakeClock()
        store = TimeSeriesStore(raw_capacity=50, tier_capacity=20,
                                clock=clock)
        for i in range(5000):
            store.ingest("m", float(i), ts=clock.advance(1.0))
        stats = store.stats()
        assert stats["raw_points"] == 50
        assert stats["tier_buckets"] <= 3 * 20
        # a window query answers only points inside the window (both
        # boundaries inclusive: 11 points at 1 s cadence over 10 s)
        (raw,) = store.query("m", window_s=10.0)
        assert len(raw["points"]) == 11
        assert all(p[0] >= clock.now - 10.0 for p in raw["points"])
        # auto resolution escalates to a covering tier for long windows
        (coarse,) = store.query("m", window_s=3000.0)
        assert coarse["resolution_s"] == 300.0

    def test_resolution_snaps_up_never_down(self):
        store = TimeSeriesStore(clock=FakeClock())
        store.ingest("m", 1.0)
        (res,) = store.query("m", resolution_s=30.0)
        assert res["resolution_s"] == 60.0     # 10 < 30 <= 60
        (res,) = store.query("m", resolution_s=9999.0)
        assert res["resolution_s"] == 300.0    # coarsest available

    def test_label_subset_match_and_prefix(self):
        store = TimeSeriesStore(clock=FakeClock())
        store.ingest("a_metric", 1.0, {"node": "0", "slice": "1"})
        store.ingest("a_metric", 2.0, {"node": "1", "slice": "1"})
        store.ingest("b_metric", 3.0)
        assert len(store.query("a_metric")) == 2
        assert len(store.query("a_metric", labels={"node": "1"})) == 1
        assert len(store.query("a_*")) == 2
        assert store.names() == ["a_metric", "b_metric"]

    def test_series_cap_and_memory_bound(self):
        clock = FakeClock()
        store = TimeSeriesStore(max_series=8, raw_capacity=16,
                                tier_capacity=8, clock=clock)
        for i in range(64):       # 8x the cap
            for _ in range(100):
                store.ingest("flood", 1.0, {"node": str(i)},
                             ts=clock.advance(1.0))
        stats = store.stats()
        assert stats["series"] == 8
        assert stats["dropped_series"] > 0
        assert stats["approx_bytes"] <= stats["memory_bound_bytes"]
        # the bound itself is a construction-time constant, small here
        assert store.memory_bound_bytes() < (1 << 20)

    def test_nan_and_garbage_rejected(self):
        store = TimeSeriesStore(clock=FakeClock())
        assert not store.ingest("m", float("nan"))
        assert not store.ingest("m", "not-a-number")
        assert store.stats()["ingested_total"] == 0

    def test_late_point_folds_into_its_bucket(self):
        clock = FakeClock()
        store = TimeSeriesStore(clock=clock)
        store.ingest("m", 1.0, ts=1000.0)
        store.ingest("m", 3.0, ts=1015.0)   # opens the 1010 bucket
        store.ingest("m", 5.0, ts=1002.0)   # late: belongs to 1000
        (series,) = store.query("m", resolution_s=10.0)
        bucket = {p[0]: p for p in series["points"]}
        assert bucket[1000.0][4] == 2       # count: on-time + late
        assert bucket[1000.0][3] == 5.0     # max folded in

    def test_export_restore_keeps_tiers_drops_raw(self):
        clock = FakeClock()
        store = TimeSeriesStore(clock=clock)
        for i in range(100):
            store.ingest("m", float(i), {"node": "0"},
                         ts=clock.advance(5.0))
        state = store.export_state()
        restored = TimeSeriesStore(clock=clock)
        assert restored.restore_state(state) == 1
        (before,) = store.query("m", resolution_s=10.0)
        (after,) = restored.query("m", resolution_s=10.0)
        assert after["points"] == before["points"]
        # raw deliberately not kept: the ring restarts empty...
        assert restored.stats()["raw_points"] == 0
        # ...and an unbounded auto query answers from the restored tier
        # history instead of the empty ring — a restarted master or
        # promoted standby must not read as "history lost"
        (auto,) = restored.query("m")
        assert auto["resolution_s"] > 0
        assert auto["points"]

    def test_unbounded_query_prefers_tiers_once_raw_wraps(self):
        """A wrapped raw ring hides history the tiers still retain; the
        unbounded auto query must answer the tier that reaches back to
        the oldest retained bucket (raw remains the answer while it
        still spans everything)."""
        clock = FakeClock()
        store = TimeSeriesStore(raw_capacity=20, clock=clock)
        store.ingest("m", 1.0, ts=clock.advance(1.0))
        (young,) = store.query("m")
        assert young["resolution_s"] == 0.0    # raw spans all history
        for i in range(500):
            store.ingest("m", float(i), ts=clock.advance(1.0))
        (aged,) = store.query("m")
        assert aged["resolution_s"] > 0
        # reaches further back than the 20-point raw ring does
        assert aged["points"][0][0] < clock.now - 20.0

    def test_sidecar_roundtrip_and_corruption(self, tmp_path):
        clock = FakeClock()
        store = TimeSeriesStore(clock=clock)
        for i in range(50):
            store.ingest("m", float(i), ts=clock.advance(3.0))
        sidecar = TimeSeriesSidecar(str(tmp_path))
        assert sidecar.save(store)
        fresh = TimeSeriesStore(clock=clock)
        assert TimeSeriesSidecar(str(tmp_path)).load(fresh) == 1
        assert fresh.query("m", resolution_s=10.0)[0]["points"] == \
            store.query("m", resolution_s=10.0)[0]["points"]
        # a torn/corrupt sidecar reads as absent, never raises
        Path(sidecar.path).write_text('{"version": 1, "torn')
        assert TimeSeriesSidecar(str(tmp_path)).load(
            TimeSeriesStore(clock=clock)) == 0


class TestCollector:
    def test_samples_allowlisted_gauges_and_goodput(self):
        registry = obs.MetricsRegistry()
        registry.gauge("dlrover_tpu_training_mfu", "t").set(0.5)
        registry.gauge("dlrover_tpu_slice_mfu", "t",
                       labelnames=("slice",)).labels(slice="0").set(0.4)
        registry.gauge("unrelated_gauge", "t").set(9.0)

        class Ledger:
            def snapshot(self):
                return {"goodput_fraction": 0.8,
                        "buckets": {"productive": 100.0}}

        clock = FakeClock()
        store = TimeSeriesStore(clock=clock)
        collector = TsdbCollector(store, registry=registry,
                                  goodput_ledger=Ledger(),
                                  sample_interval_s=0,
                                  clock=clock)
        count = collector.sample_once()
        assert count >= 4
        assert "unrelated_gauge" not in store.names()
        (mfu,) = store.query("dlrover_tpu_training_mfu")
        assert mfu["points"][-1][1] == 0.5
        (frac,) = store.query("dlrover_tpu_goodput_fraction")
        assert frac["points"][-1][1] == 0.8
        (bucket,) = store.query("dlrover_tpu_goodput_seconds_total",
                                labels={"bucket": "productive"})
        assert bucket["points"][-1][1] == 100.0

    def test_goodput_series_fed_once_per_tick(self):
        """The master registry carries the ledger's own fraction gauge
        + seconds counter (obs/goodput.py registers them), so the
        collector's manual ledger ingest must skip series the registry
        sample already emitted this tick — double-landing would double
        bucket sums and fill the raw ring at 2x."""
        registry = obs.MetricsRegistry()
        registry.gauge("dlrover_tpu_goodput_fraction",
                       "t").set_function(lambda: 0.8)
        registry.counter("dlrover_tpu_goodput_seconds_total", "t",
                         labelnames=("bucket",)).labels(
            bucket="productive").inc(100.0)

        class Ledger:
            def snapshot(self):
                return {"goodput_fraction": 0.8,
                        "buckets": {"productive": 100.0,
                                    "restore": 5.0}}

        clock = FakeClock()
        store = TimeSeriesStore(clock=clock)
        collector = TsdbCollector(store, registry=registry,
                                  goodput_ledger=Ledger(),
                                  sample_interval_s=0, clock=clock)
        collector.sample_once()
        (frac,) = store.query("dlrover_tpu_goodput_fraction")
        assert len(frac["points"]) == 1
        (prod,) = store.query("dlrover_tpu_goodput_seconds_total",
                              labels={"bucket": "productive"})
        assert len(prod["points"]) == 1
        # a ledger bucket the registry did NOT emit still lands
        (rest,) = store.query("dlrover_tpu_goodput_seconds_total",
                              labels={"bucket": "restore"})
        assert len(rest["points"]) == 1

    def test_negative_sentinel_gauges_not_ingested(self):
        """Allowlisted families are physically non-negative; a -1
        reading is a "no evidence yet" sentinel (training_mfu before a
        FLOPs model) that must not land as data and poison bucket
        mins/means."""
        registry = obs.MetricsRegistry()
        registry.gauge("dlrover_tpu_training_mfu", "t").set(-1.0)
        registry.gauge("dlrover_tpu_training_steps_per_second",
                       "t").set(2.0)
        clock = FakeClock()
        store = TimeSeriesStore(clock=clock)
        TsdbCollector(store, registry=registry, sample_interval_s=0,
                      clock=clock).sample_once()
        assert "dlrover_tpu_training_mfu" not in store.names()
        assert "dlrover_tpu_training_steps_per_second" in store.names()

    def test_worker_mfu_gauge_is_not_resampled(self):
        """The servicer ingests dlrover_tpu_worker_mfu per step report
        under {node}; the collector must not store a second,
        (node,slice)-labeled copy of the same evidence (double
        series-cap cost, ambiguous label-subset queries)."""
        registry = obs.MetricsRegistry()
        registry.gauge("dlrover_tpu_worker_mfu", "t",
                       labelnames=("node", "slice")).labels(
            node="0", slice="0").set(0.4)
        clock = FakeClock()
        store = TimeSeriesStore(clock=clock)
        collector = TsdbCollector(store, registry=registry,
                                  sample_interval_s=0, clock=clock)
        collector.sample_once()
        assert "dlrover_tpu_worker_mfu" not in store.names()

    def test_fence_gate_stops_sidecar_writes(self, tmp_path):
        """A superseded primary (PR 10 generation fencing) must stop
        overwriting the promoted lineage's history sidecar: the gate
        makes flush() a no-op while restore keeps working."""
        clock = FakeClock()
        store = TimeSeriesStore(clock=clock)
        store.ingest("dlrover_tpu_training_mfu", 0.5)
        collector = TsdbCollector(store, registry=obs.MetricsRegistry(),
                                  state_dir=str(tmp_path),
                                  sample_interval_s=0, clock=clock)
        assert collector.flush()
        sidecar = tmp_path / "tsdb-state.json"
        stamped = sidecar.read_bytes()
        collector.gate = lambda: True          # fenced
        store.ingest("dlrover_tpu_training_mfu", 0.9)
        assert not collector.flush()
        assert sidecar.read_bytes() == stamped  # file untouched


# ---------------------------------------------------------------------------
# device-truth telemetry (obs/device.py)
# ---------------------------------------------------------------------------


class TestDeviceTelemetry:
    def test_watermark_window_and_rise_step(self):
        peaks = {"value": 100.0}

        def sampler():
            return [{"index": 0.0, "bytes_in_use": 50.0,
                     "peak_bytes_in_use": peaks["value"],
                     "bytes_limit": 1000.0}]

        telemetry = obs.DeviceTelemetry(sampler=sampler)
        telemetry.on_step(1)
        peaks["value"] = 100.0 + 2 * (1 << 20)   # a real rise
        telemetry.on_step(2)
        out = telemetry.drain()
        assert out["hbm_peak_bytes"] == peaks["value"]
        assert out["hbm_rise_step"] == 2.0
        assert out["hbm_limit_bytes"] == 1000.0
        # the window re-arms: no new samples -> 0 window peak, the
        # lifetime watermark stands
        assert telemetry.drain()["hbm_peak_bytes"] == 0.0
        assert telemetry.peak_mb() == pytest.approx(
            peaks["value"] / (1 << 20))

    def test_steady_state_pressure_survives_a_flat_counter(self):
        """A fixed program peaking at the same level every step keeps
        the watermark on every window (a flat MONOTONE counter means
        "still peaking", not "resolved") — only a recompile that does
        not re-reach it lets the window fall back to live bytes_in_use
        so HbmPressureRule can clear."""
        mem = {"in_use": 400.0, "peak": float(960 << 20)}

        def sampler():
            return [{"index": 0.0, "bytes_in_use": mem["in_use"],
                     "peak_bytes_in_use": mem["peak"],
                     "bytes_limit": float(1000 << 20)}]

        telemetry = obs.DeviceTelemetry(sampler=sampler)
        telemetry.on_step(1)
        assert telemetry.drain()["hbm_peak_bytes"] == mem["peak"]
        # windows 2..n: the counter never moves, the pressure recurs —
        # every sampled window still carries the watermark
        for step in (2, 3):
            telemetry.on_step(step)
            assert telemetry.drain()["hbm_peak_bytes"] == mem["peak"]
        # an EMPTY window stays honest: no steps ran, no in-step peak
        assert telemetry.drain()["hbm_peak_bytes"] == 0.0
        # recompile (replan, smaller batch): the old program's peak is
        # no longer evidence — the window reports live bytes_in_use
        telemetry.note_recompile()
        telemetry.on_step(4)
        assert telemetry.drain()["hbm_peak_bytes"] == mem["in_use"]
        # the new program re-reaches a higher peak: a new episode
        mem["peak"] = float(980 << 20)
        telemetry.on_step(5)
        assert telemetry.drain()["hbm_peak_bytes"] == mem["peak"]
        telemetry.on_step(6)
        assert telemetry.drain()["hbm_peak_bytes"] == mem["peak"]

    def test_cpu_backend_is_a_no_op_after_one_probe(self):
        calls = {"n": 0}

        def sampler():
            calls["n"] += 1
            return None

        telemetry = obs.DeviceTelemetry(sampler=sampler)
        for step in range(5):
            telemetry.on_step(step)
        assert calls["n"] == 1              # probed once, then off
        assert telemetry.available is False
        assert telemetry.drain()["hbm_peak_bytes"] == 0.0

    def test_real_cpu_jax_probes_unavailable(self):
        telemetry = obs.DeviceTelemetry()
        telemetry.on_step(0)
        # conftest pins the cpu backend: no memory stats there
        assert telemetry.available is False

    def test_cost_summary_handles_unanswerable_backends(self):
        from dlrover_tpu.obs.device import cost_summary

        assert cost_summary(None) == {"flops": 0.0,
                                      "bytes_accessed": 0.0}

        class Fake:
            def cost_analysis(self):
                return [{"flops": 123.0, "bytes accessed": 456.0}]

        assert cost_summary(Fake()) == {"flops": 123.0,
                                        "bytes_accessed": 456.0}


class TestChipStatsExport:
    def test_cpu_backend_omits_hbm_fields(self, tmp_path, monkeypatch):
        """Satellite: memory_stats() unavailable (CPU) must OMIT the
        hbm fields instead of exporting a forever-0 series."""
        from dlrover_tpu.agent.monitor import export_chip_stats
        from dlrover_tpu.common.constants import NodeEnv

        path = str(tmp_path / "chips.json")
        monkeypatch.setenv(NodeEnv.CHIP_STATS_FILE, path)
        export_chip_stats(step=5, step_time_s=0.01)
        chips = json.loads(Path(path).read_text())
        assert chips
        for chip in chips:
            assert "hbm_used_mb" not in chip
            assert "hbm_total_mb" not in chip
            assert "hbm_peak_mb" not in chip
        # the message layer's defaults read the omission honestly
        stats = [msg.ChipStats(**chip) for chip in chips]
        assert all(c.hbm_total_mb == 0.0 for c in stats)
        assert all(c.hbm_peak_mb == -1.0 for c in stats)

    def test_peak_export_is_windowed_not_lifetime(self, tmp_path,
                                                  monkeypatch):
        """peak_bytes_in_use never resets within a process, so the
        export carries hbm_peak_mb only when the counter ROSE since
        the last export — a long-resolved spike must stop feeding
        HbmPressureRule (the DeviceTelemetry windowing, applied to
        the chip-stats relay)."""
        import jax

        from dlrover_tpu.agent import monitor as monitor_mod

        mem = {"bytes_in_use": 100 << 20, "bytes_limit": 1000 << 20,
               "peak_bytes_in_use": 900 << 20}

        class Dev:
            id = 0

            def memory_stats(self):
                return dict(mem)

        monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
        path = str(tmp_path / "chips.json")
        monitor_mod.export_chip_stats(path)
        (chip,) = json.loads(Path(path).read_text())
        assert chip["hbm_peak_mb"] == pytest.approx(900.0)  # first rise
        # episode resolved (smaller batch): the counter stays latched —
        # the export must stop relaying the old high so the rule can
        # judge the live bytes_in_use instead
        mem["bytes_in_use"] = 60 << 20
        monitor_mod.export_chip_stats(path)
        (chip,) = json.loads(Path(path).read_text())
        assert "hbm_peak_mb" not in chip
        assert chip["hbm_used_mb"] == pytest.approx(60.0)
        # a NEW pressure episode (the counter rises again) re-reports
        mem["peak_bytes_in_use"] = 950 << 20
        monitor_mod.export_chip_stats(path)
        (chip,) = json.loads(Path(path).read_text())
        assert chip["hbm_peak_mb"] == pytest.approx(950.0)

    def test_publish_node_stats_gates_hbm_on_real_totals(self):
        registry = obs.MetricsRegistry()
        stats = msg.NodeResourceStats(
            node_id=0, node_type="worker", cpu_percent=10.0,
            memory_mb=100.0,
            chip_stats=[msg.ChipStats(index=0)])   # no memory stats
        obs.publish_node_stats(stats, registry)
        assert "dlrover_tpu_node_hbm_used_mb" not in registry.render()
        stats.chip_stats = [msg.ChipStats(
            index=0, hbm_used_mb=10.0, hbm_total_mb=100.0,
            hbm_peak_mb=42.0)]
        obs.publish_node_stats(stats, registry)
        rendered = registry.render()
        assert "dlrover_tpu_node_hbm_used_mb" in rendered
        assert 'dlrover_tpu_node_hbm_peak_mb{node="0",type="worker"}' \
            " 42" in rendered
        # the export windows the peak (no rise -> field absent): the
        # gauge must follow the worst current in-use, not latch the
        # resolved spike the collector would then record forever
        stats.chip_stats = [msg.ChipStats(
            index=0, hbm_used_mb=10.0, hbm_total_mb=100.0)]
        obs.publish_node_stats(stats, registry)
        assert 'dlrover_tpu_node_hbm_peak_mb{node="0",type="worker"}' \
            " 10" in registry.render()


# ---------------------------------------------------------------------------
# planner calibration (parallel/calibration.py)
# ---------------------------------------------------------------------------


def _profile():
    return planner.ModelProfile(
        param_count=10_000, param_bytes=40_000,
        flops_per_token=60_000.0, peak_flops_per_chip=1e12,
        seq_len=32, global_batch=8)


class TestPlanCalibration:
    def test_measurements_attribute_to_the_current_signature(self):
        cal = PlanCalibration(min_samples=2)
        plan_a = planner.plan_parallelism(
            {r: 1 for r in range(4)}, _profile())
        plan_b = planner.plan_parallelism(
            {r: 1 for r in range(8)}, _profile())
        cal.observe_step(9.9)                 # no plan yet: dropped
        cal.observe_plan(plan_a)
        cal.observe_step(0.5, mfu=0.3)
        cal.observe_plan(plan_b)
        cal.observe_step(0.2, mfu=0.6)
        table = {e["total_devices"]: e for e in cal.table()}
        assert table[4]["samples"] == 1
        assert table[4]["measured_step_s"] == 0.5
        assert table[8]["samples"] == 1
        assert table[8]["current"]
        assert cal.current()["measured_mfu"] == 0.6
        # predictions came from the real planner
        assert table[4]["predicted_step_s"] > 0

    def test_generation_attribution_beats_a_straggling_old_report(self):
        """A resize stamps the new plan while old incarnations are
        still finishing their windows: a report naming the plan
        generation its sender ACTUALLY ran lands on that shape, never
        on the freshly-stamped one (the false-PlanRegression-after-
        every-grow class)."""
        cal = PlanCalibration(min_samples=2)
        plan_a = planner.plan_parallelism(
            {r: 1 for r in range(4)}, _profile())
        plan_a["generation"] = 3
        plan_b = planner.plan_parallelism(
            {r: 1 for r in range(8)}, _profile())
        plan_b["generation"] = 4
        cal.observe_plan(plan_a)
        cal.observe_step(0.5, plan_generation=3)
        cal.observe_plan(plan_b)              # grow stamped: current flips
        cal.observe_step(0.52, plan_generation=3)   # old-shape straggler
        cal.observe_step(0.2, plan_generation=4)
        table = {e["total_devices"]: e for e in cal.table()}
        assert table[4]["samples"] == 2       # straggler landed on 4-chip
        assert table[8]["samples"] == 1
        assert table[8]["measured_step_s"] == 0.2
        # a fallback-mesh worker (-2) and a superseded unknown
        # generation attribute nowhere
        cal.observe_step(9.9, plan_generation=-2)
        cal.observe_step(9.9, plan_generation=77)
        assert cal.current()["samples"] == 1
        # the generation map survives an export/restore roundtrip
        restored = PlanCalibration(min_samples=2)
        restored.restore_state(
            json.loads(json.dumps(cal.export_state())))
        restored.observe_step(0.21, plan_generation=4)
        assert restored.current()["samples"] == 2

    def test_infeasible_plans_are_not_subjects(self):
        cal = PlanCalibration(min_samples=1)
        cal.observe_plan({"mesh": {"data": 4}, "feasible": False})
        assert cal.current() is None

    def test_axis_discounts_learn_a_slow_axis(self):
        """Shapes using the tensor axis measured 2x slower than
        predicted while plain-DP shapes measured at prediction: the
        learned tensor discount must drop below 1 (normalized), plain
        axes learn nothing, and the clamp holds."""
        cal = PlanCalibration(min_samples=2)
        dp_plan = {"mesh": {"dcn": 1, "data": 8, "fsdp": 1,
                            "tensor": 1, "pipe": 1},
                   "total_devices": 8, "global_batch": 8,
                   "feasible": True, "predicted_step_s": 1.0,
                   "predicted_efficiency": 0.6}
        tp_plan = {"mesh": {"dcn": 1, "data": 4, "fsdp": 1,
                            "tensor": 2, "pipe": 1},
                   "total_devices": 8, "global_batch": 8,
                   "feasible": True, "predicted_step_s": 1.0,
                   "predicted_efficiency": 0.55}
        cal.observe_plan(dp_plan)
        for _ in range(3):
            cal.observe_step(1.0)             # dp: exactly as predicted
        cal.observe_plan(tp_plan)
        for _ in range(3):
            cal.observe_step(2.0)             # tensor: 2x slower
        discounts = cal.axis_discounts()
        assert discounts["tensor"] == pytest.approx(0.5, abs=0.01)
        assert "data" not in discounts        # no non-data baseline
        # and the planner actually re-ranks with them: the discounted
        # tensor candidate's predicted step inflates
        plain = planner.score_candidate(
            planner.MeshCandidate(data=4, tensor=2), _profile())
        discounted = planner.score_candidate(
            planner.MeshCandidate(data=4, tensor=2), _profile(),
            axis_discounts=discounts)
        assert discounted["predicted_step_s"] > \
            plain["predicted_step_s"]

    def test_observe_plan_anchors_to_the_raw_prior(self):
        """A re-stamped plan's prediction already includes the learned
        discounts (planner._efficiency): calibrating against it would
        learn the correction against its own output — the ratio
        re-centers on 1.0 and the discount decays/oscillates. The
        stamped discounts must be divided back out (step time scales
        1/efficiency) so the learned ratio stays anchored to the raw
        analytic prior."""
        cal = PlanCalibration(min_samples=1)
        plan = {"mesh": {"dcn": 1, "data": 4, "fsdp": 1, "tensor": 2,
                         "pipe": 1},
                "total_devices": 8, "global_batch": 8, "feasible": True,
                # raw prior 1.0 s, re-stamped with tensor discount 0.5
                # -> efficiency halves -> prediction doubles to 2.0 s
                "predicted_step_s": 2.0,
                "axis_discounts": {"tensor": 0.5}}
        cal.observe_plan(plan)
        assert cal.current()["predicted_step_s"] == pytest.approx(1.0)
        # inactive axes' stamped discounts do not apply
        plain = {"mesh": {"dcn": 1, "data": 8, "fsdp": 1, "tensor": 1,
                          "pipe": 1},
                 "total_devices": 8, "global_batch": 8,
                 "feasible": True, "predicted_step_s": 1.0,
                 "axis_discounts": {"tensor": 0.5}}
        cal.observe_plan(plain)
        assert cal.current()["predicted_step_s"] == pytest.approx(1.0)

    def test_state_roundtrip_preserves_everything(self):
        cal = PlanCalibration(min_samples=1)
        plan = planner.plan_parallelism({0: 1, 1: 1}, _profile())
        cal.observe_plan(plan)
        cal.observe_step(0.25, mfu=0.4)
        restored = PlanCalibration(min_samples=1)
        restored.restore_state(
            json.loads(json.dumps(cal.export_state())))
        assert restored.current() == cal.current()
        assert restored.table() == cal.table()
        assert plan_signature(plan) == cal.current()["signature"]

    def test_master_restart_roundtrip_through_state_backend(
            self, tmp_path):
        """Satellite: calibration survives the PR 3 state backend
        across a simulated master restart/promotion (the full
        promotion drill lives in test_controlplane.py)."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.job_master import JobMaster

        ctx = Context.singleton()
        old = (ctx.master_state_dir, ctx.master_bootstrap_file)
        ctx.update(master_state_dir=str(tmp_path / "state"),
                   master_bootstrap_file=str(tmp_path / "boot"))
        try:
            master1 = JobMaster(port=0, min_nodes=1, max_nodes=1,
                                host="127.0.0.1")
            master1.prepare()
            client = MasterClient(master1.addr, node_id=0, node_rank=0)
            try:
                client.join_rendezvous(4)
                client.report_model_info(
                    param_count=1000, param_bytes=4000,
                    flops_per_token=6000.0, peak_flops_per_chip=1e12,
                    batch_size=8, seq_len=32)
                for i in range(3):
                    client.report_global_step(
                        i + 1, step_time_s=0.05, mfu=0.4,
                        hbm_peak_bytes=128.0 * (1 << 20))
                master1.tsdb_collector.flush()
                # a cold mutation snapshots the measurement evidence
                client.kv_set("seal", b"1")
                before = master1.plan_calibration.current()
                assert before["samples"] == 3
            finally:
                client.close()
            master1.stop(grace_s=0.1)

            master2 = JobMaster(port=0, min_nodes=1, max_nodes=1,
                                host="127.0.0.1")
            try:
                after = master2.plan_calibration.current()
                assert after is not None
                assert after["samples"] == 3
                assert after["measured_step_s"] == \
                    before["measured_step_s"]
                assert after["signature"] == before["signature"]
                # fleet history came back through the sidecar too
                history = master2.tsdb.query(
                    "dlrover_tpu_worker_hbm_peak_mb",
                    labels={"node": "0"}, resolution_s=10.0)
                assert history and history[0]["points"]
                assert history[0]["points"][-1][1] == 128.0
            finally:
                master2.stop(grace_s=0.1)
        finally:
            ctx.update(master_state_dir=old[0],
                       master_bootstrap_file=old[1])


# ---------------------------------------------------------------------------
# diagnosis rules: plan regression + watermark-fed HBM pressure
# ---------------------------------------------------------------------------


def _snapshot(**overrides):
    from dlrover_tpu.master.diagnosis.rules import DiagnosisSnapshot

    base = dict(ts=time.time(), worker_speeds={}, running_speed=0.0,
                peak_speed=0.0, running_workers=1, node_stats={})
    base.update(overrides)
    return DiagnosisSnapshot(**base)


class TestPlanRegressionRule:
    def _entry(self, predicted=0.1, measured=0.3, samples=5,
               signature="sig-a"):
        return {"signature": signature, "mesh": {"data": 4},
                "predicted_step_s": predicted,
                "measured_step_s": measured, "samples": samples}

    def test_hysteresis_trigger_and_clear(self):
        from dlrover_tpu.master.diagnosis.rules import PlanRegressionRule

        ctx = Context.singleton()
        ctx.update(plan_regression_ratio=1.5, plan_regression_windows=3,
                   plan_regression_clear_windows=2,
                   calibration_min_samples=3)
        rule = PlanRegressionRule()
        slow = _snapshot(plan_calibration=self._entry())
        assert rule.evaluate(slow) == []      # window 1
        assert rule.evaluate(slow) == []      # window 2
        reports = rule.evaluate(slow)         # window 3: fires
        assert len(reports) == 1
        assert reports[0].rule == "plan_regression"
        assert reports[0].severity == "warning"
        assert reports[0].details["ratio"] == pytest.approx(3.0)
        assert rule.evaluate(slow) == []      # no re-fire while slow
        ok = _snapshot(plan_calibration=self._entry(measured=0.1))
        assert rule.evaluate(ok) == []        # clear window 1
        cleared = rule.evaluate(ok)           # clear window 2
        assert len(cleared) == 1
        assert cleared[0].severity == "info"

    def test_new_signature_resets_the_evidence(self):
        from dlrover_tpu.master.diagnosis.rules import PlanRegressionRule

        Context.singleton().update(
            plan_regression_ratio=1.5, plan_regression_windows=2,
            plan_regression_clear_windows=1, calibration_min_samples=1)
        rule = PlanRegressionRule()
        a = _snapshot(plan_calibration=self._entry(signature="a"))
        assert rule.evaluate(a) == []
        b = _snapshot(plan_calibration=self._entry(signature="b"))
        assert rule.evaluate(b) == []         # reset: window 1 again
        assert len(rule.evaluate(b)) == 1

    def test_disabled_and_under_sampled(self):
        from dlrover_tpu.master.diagnosis.rules import PlanRegressionRule

        ctx = Context.singleton()
        ctx.update(plan_regression_ratio=0.0)
        assert PlanRegressionRule().evaluate(
            _snapshot(plan_calibration=self._entry())) == []
        ctx.update(plan_regression_ratio=1.5,
                   calibration_min_samples=10)
        assert PlanRegressionRule().evaluate(
            _snapshot(plan_calibration=self._entry(samples=2))) == []


class TestHbmPressureWatermark:
    def test_peak_watermark_triggers_where_trough_would_not(self):
        """Satellite: the between-steps trough sits under the threshold
        while the in-step peak is over it — the rule must fire on the
        peak (the thing that actually OOMs on the next batch bump)."""
        from dlrover_tpu.master.diagnosis.rules import HbmPressureRule

        trough_only = _snapshot(node_stats={0: {
            "ts": time.time(),
            "chips": [{"index": 0, "hbm_used_mb": 500.0,
                       "hbm_total_mb": 1000.0, "hbm_peak_mb": -1.0}],
        }})
        assert HbmPressureRule().evaluate(trough_only) == []
        with_peak = _snapshot(node_stats={0: {
            "ts": time.time(),
            "chips": [{"index": 0, "hbm_used_mb": 500.0,
                       "hbm_total_mb": 1000.0, "hbm_peak_mb": 950.0}],
        }})
        reports = HbmPressureRule().evaluate(with_peak)
        assert len(reports) == 1
        assert reports[0].details["signal"] == "peak_watermark"
        assert reports[0].details["worst_chip_pct"] == 95.0

    def test_step_report_watermark_beats_chip_file(self):
        from dlrover_tpu.master.diagnosis.rules import HbmPressureRule

        snap = _snapshot(node_stats={0: {
            "ts": time.time(),
            "hbm_peak_mb": 980.0,              # from the step report
            "chips": [{"index": 0, "hbm_used_mb": 100.0,
                       "hbm_total_mb": 1000.0, "hbm_peak_mb": -1.0}],
        }})
        reports = HbmPressureRule().evaluate(snap)
        assert len(reports) == 1
        assert reports[0].details["signal"] == "step_peak_watermark"


# ---------------------------------------------------------------------------
# acceptance: TimeSeriesQuery over a real master, top.py renders
# ---------------------------------------------------------------------------


@pytest.fixture()
def live_master(tmp_path):
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.master.job_master import JobMaster

    ctx = Context.singleton()
    old = (ctx.master_state_dir, ctx.master_bootstrap_file)
    ctx.update(master_state_dir=str(tmp_path / "state"),
               master_bootstrap_file=str(tmp_path / "boot"))
    master = JobMaster(port=0, min_nodes=1, max_nodes=1,
                       host="127.0.0.1")
    master.prepare()
    client = MasterClient(master.addr, node_id=0, node_rank=0)
    try:
        yield master, client
    finally:
        client.close()
        master.stop(grace_s=0.1)
        ctx.update(master_state_dir=old[0],
                   master_bootstrap_file=old[1])


def _feed_master(client, master):
    client.join_rendezvous(4)
    client.report_model_info(
        param_count=1000, param_bytes=4000, flops_per_token=6000.0,
        peak_flops_per_chip=1e12, batch_size=8, seq_len=32)
    for i in range(4):
        client.report_global_step(10 + i, step_time_s=0.05, mfu=0.42,
                                  hbm_peak_bytes=512.0 * (1 << 20))
    master.tsdb_collector.sample_once()


class TestTimeSeriesRpcAcceptance:
    def test_query_returns_three_tiers_with_bounded_memory(
            self, live_master):
        master, client = live_master
        _feed_master(client, master)
        payload = client.query_timeseries(
            "dlrover_tpu_worker_hbm_peak_mb", window_s=600.0)
        downsampled = [t for t in payload["tiers"]
                       if t["kind"] == "downsampled"]
        assert len(downsampled) >= 3            # acceptance criterion
        assert payload["series"]
        assert payload["series"][0]["labels"] == {"node": "0"}
        assert payload["series"][0]["points"][-1][1] == 512.0
        stats = payload["stats"]
        assert stats["approx_bytes"] <= stats["memory_bound_bytes"]
        # the bound is a construction constant, not a growing number
        assert stats["memory_bound_bytes"] == \
            master.tsdb.memory_bound_bytes()
        # the listing answers too
        names = client.query_timeseries()["names"]
        assert "dlrover_tpu_training_global_step" in names
        # and calibration closed the loop over the same RPC channel
        calib = client.get_plan_calibration()
        assert calib["table"]
        current = [e for e in calib["table"] if e["current"]]
        assert current and current[0]["measured_step_s"] == 0.05

    def test_global_step_series_has_one_feed(self, live_master):
        """The fleet-step series is fed ONLY by the collector sampling
        the SpeedMonitor gauge — per-rank step reports must not
        interleave straggler steps into the same unlabeled key (the
        worker_mfu/goodput one-feed discipline)."""
        master, client = live_master
        _feed_master(client, master)     # 4 reports + 1 collector tick
        (series,) = master.tsdb.query("dlrover_tpu_training_global_step")
        assert series["labels"] == {}
        assert len(series["points"]) == 1   # per tick, not per report
        assert series["points"][-1][1] == float(
            master.speed_monitor.completed_global_step)

    def test_top_once_renders_live_master(self, live_master):
        master, client = live_master
        _feed_master(client, master)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "top.py"),
             "--master", master.addr, "--once"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        assert "== fleet vitals" in out.stdout
        assert "== hbm watermarks" in out.stdout
        assert "peak     512.0MiB" in out.stdout
        assert "== plan calibration" in out.stdout
        assert "1x4x1x1x1" in out.stdout
        assert "== history store" in out.stdout


# deterministic flight fixture for the golden render: a master dump
# carrying a tsdb snapshot event, goodput, diagnosis + replan history
_FLIGHT_FIXTURE = {
    "version": 1, "role": "master", "pid": 7, "host": "h",
    "reason": "master-stop", "dumped_at": 2000.0,
    "events": [
        {"kind": "event", "name": "tsdb", "ts": 1999.0, "pid": 7,
         "attrs": {
             "snapshot": {
                 "version": 1, "window_s": 900.0,
                 "series": [
                     {"name":
                      "dlrover_tpu_training_steps_per_second",
                      "labels": {}, "resolution_s": 10.0,
                      "points": [[1900.0, 2.0, 1.5, 2.5, 4],
                                 [1910.0, 4.0, 3.0, 5.0, 4]]},
                     {"name": "dlrover_tpu_training_mfu",
                      "labels": {}, "resolution_s": 10.0,
                      "points": [[1900.0, 0.5, 0.4, 0.6, 4]]},
                     {"name": "dlrover_tpu_training_global_step",
                      "labels": {}, "resolution_s": 10.0,
                      "points": [[1910.0, 1234.0, 1230.0,
                                  1238.0, 4]]},
                     {"name": "dlrover_tpu_slice_mfu",
                      "labels": {"slice": "0"}, "resolution_s": 10.0,
                      "points": [[1910.0, 0.44, 0.4, 0.5, 4]]},
                     {"name": "dlrover_tpu_slice_steps_per_second",
                      "labels": {"slice": "0"}, "resolution_s": 10.0,
                      "points": [[1910.0, 3.0, 2.0, 4.0, 4]]},
                     {"name": "dlrover_tpu_slice_workers",
                      "labels": {"slice": "0"}, "resolution_s": 10.0,
                      "points": [[1910.0, 4.0, 4.0, 4.0, 4]]},
                     {"name": "dlrover_tpu_goodput_fraction",
                      "labels": {}, "resolution_s": 10.0,
                      "points": [[1910.0, 0.91, 0.9, 0.92, 4]]},
                     {"name": "dlrover_tpu_worker_hbm_peak_mb",
                      "labels": {"node": "3"}, "resolution_s": 10.0,
                      "points": [[1910.0, 900.0, 890.0, 910.0, 4]]},
                 ],
                 "stats": {"series": 7, "raw_points": 70,
                           "tier_buckets": 9,
                           "memory_bound_bytes": 1048576},
             },
             "calibration": [
                 {"signature": "s1",
                  "mesh": {"dcn": 1, "data": 4, "fsdp": 1,
                           "tensor": 1, "pipe": 1},
                  "total_devices": 4, "global_batch": 8,
                  "predicted_step_s": 0.11, "measured_step_s": 0.12,
                  "ratio": 1.09, "samples": 12, "current": True},
                 {"signature": "s2",
                  "mesh": {"dcn": 1, "data": 2, "fsdp": 1,
                           "tensor": 2, "pipe": 1},
                  "total_devices": 4, "global_batch": 8,
                  "predicted_step_s": 0.10, "measured_step_s": 0.20,
                  "ratio": 2.0, "samples": 9, "current": False},
             ],
             "axis_discounts": {"tensor": 0.865}}},
        {"kind": "event", "name": "diagnosis", "ts": 1950.0, "pid": 7,
         "attrs": {"rule": "plan_regression", "severity": "warning",
                   "worker": -1,
                   "summary": "plan regression: measured 0.200s/step "
                              "is 2.00x the planner's 0.100s "
                              "prediction"}},
        {"kind": "event", "name": "replan_stamped", "ts": 1940.0,
         "pid": 7,
         "attrs": {"world_size": 4, "devices": 4,
                   "generation": 3, "batch_adjusted": False}},
        {"kind": "event", "name": "goodput", "ts": 1999.5, "pid": 7,
         "attrs": {"reason": "master-stop", "snapshot": {
             "version": 1, "elapsed_rank_seconds": 1000.0,
             "buckets": {"productive": 910.0, "restore": 50.0,
                         "idle": 40.0},
             "goodput_fraction": 0.91,
             "per_rank": {"0": {"elapsed_s": 500.0},
                          "3": {"elapsed_s": 500.0}},
             "incarnations": [
                 {"round": 0, "world": 2, "reason": "job_start"},
                 {"round": 1, "world": 1, "reason": "replan"}],
             "replans": [{"rank": 3, "generation": 3, "ts": 1941.0,
                          "phases": {"plan": 0.02, "migrate": 0.9,
                                     "rebuild": 1.2}}],
         }}},
    ],
}


class TestTopGolden:
    def test_flight_golden_render(self, tmp_path):
        """Satellite acceptance: `tools/top.py --once` on a flight
        dump is a deterministic render — per-slice MFU, HBM watermark,
        goodput, calibration and the resize history all present."""
        dump = tmp_path / "flight-master-7.json"
        dump.write_text(json.dumps(_FLIGHT_FIXTURE))
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "top.py"),
             "--flight", str(dump), "--once"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        first = out.stdout
        golden_lines = [
            "step       1234   workers   2   goodput  91.0%",
            "  steps/s      4.000 ▁█",
            "== slices (1)",
            "  0          3.000   0.440        4 ?",
            "  node 3     [########################] peak     "
            "900.0MiB",
            " *1x4x1x1x1            4      8         0.11         "
            "0.12    1.09       12",
            "  1x2x1x2x1            4      8          0.1          "
            "0.2    2.00        9",
            "  learned axis discounts: tensor=0.865",
            "plan_regression",
            "  replan rank 3 gen 3: 2.12s total  migrate=0.90s "
            "plan=0.02s rebuild=1.20s",
            "  incarnation #2 round=1 world=1 trigger=replan",
            "  replan_stamped: batch_adjusted=False devices=4 "
            "generation=3 world_size=4",
        ]
        for line in golden_lines:
            assert line in first, (
                f"golden line missing:\n{line}\n--- got:\n{first}")
        # deterministic: byte-identical across runs
        again = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "top.py"),
             "--flight", str(dump), "--once"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert again.stdout == first

    def test_sparkline_and_bar_primitives(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import top
        finally:
            sys.path.pop(0)
        assert top.sparkline([]) == ""
        assert top.sparkline([1.0, 1.0]) == "▄▄"
        line = top.sparkline([0.0, 5.0, 10.0])
        assert line[0] == "▁" and line[-1] == "█"
        assert top.hbar(0.0, 4) == "[....]"
        assert top.hbar(1.0, 4) == "[####]"
        assert top.hbar(2.0, 4) == "[####]"   # clamped


# ---------------------------------------------------------------------------
# overhead bound: ingest + watermark sampling under 1% of a bench step
# ---------------------------------------------------------------------------


class TestOverheadBound:
    def test_ingest_and_watermark_under_one_percent(self):
        """CI gate (satellite): master-side tsdb ingest per step report
        plus the worker's per-step watermark sampling must cost < 1 %
        of a 10 ms CPU-bench step. Medians so a loaded box's scheduler
        blips don't flake the bound (same discipline as the timeline
        overhead test)."""
        import statistics

        step_s = 0.010
        store = TimeSeriesStore()
        ingest_costs = []
        for i in range(2000):
            t0 = time.perf_counter()
            # what one GlobalStepReport ingests (servicer
            # _observe_step_evidence): step-time + mfu + hbm
            store.ingest("dlrover_tpu_worker_step_time_seconds",
                         0.01, {"node": "0"})
            store.ingest("dlrover_tpu_worker_mfu", 0.5, {"node": "0"})
            store.ingest("dlrover_tpu_worker_hbm_peak_mb", 512.0,
                         {"node": "0"})
            ingest_costs.append(time.perf_counter() - t0)

        def sampler():
            return [{"index": 0.0, "bytes_in_use": 1.0,
                     "peak_bytes_in_use": 2.0, "bytes_limit": 3.0}]

        telemetry = obs.DeviceTelemetry(sampler=sampler)
        sample_costs = []
        for step in range(2000):
            t0 = time.perf_counter()
            telemetry.on_step(step)
            sample_costs.append(time.perf_counter() - t0)
        per_step = (statistics.median(ingest_costs)
                    + statistics.median(sample_costs))
        assert per_step < 0.01 * step_s, (
            f"tsdb+watermark overhead {per_step * 1e6:.1f}us/step "
            f"exceeds 1% of a {step_s * 1e3:.0f}ms step")
        # the CPU no-op path is cheaper still: one probe then nothing
        off = obs.DeviceTelemetry(sampler=lambda: None)
        off.on_step(0)
        t0 = time.perf_counter()
        for step in range(2000):
            off.on_step(step)
        assert (time.perf_counter() - t0) / 2000 < 0.01 * step_s


# ---------------------------------------------------------------------------
# CI gate: graftlint clean on every new/changed module
# ---------------------------------------------------------------------------


def test_graftlint_clean_on_tsdb_modules():
    from dlrover_tpu.analysis import run_analysis

    result = run_analysis([
        os.path.join(REPO, "dlrover_tpu", "obs", "tsdb.py"),
        os.path.join(REPO, "dlrover_tpu", "obs", "device.py"),
        os.path.join(REPO, "dlrover_tpu", "obs", "metrics.py"),
        os.path.join(REPO, "dlrover_tpu", "parallel",
                     "calibration.py"),
        os.path.join(REPO, "dlrover_tpu", "parallel", "planner.py"),
        os.path.join(REPO, "dlrover_tpu", "master", "servicer.py"),
        os.path.join(REPO, "dlrover_tpu", "master", "job_master.py"),
        os.path.join(REPO, "dlrover_tpu", "master", "diagnosis",
                     "rules.py"),
        os.path.join(REPO, "dlrover_tpu", "master", "diagnosis",
                     "manager.py"),
        os.path.join(REPO, "dlrover_tpu", "agent", "monitor.py"),
        os.path.join(REPO, "dlrover_tpu", "trainer",
                     "elastic_loop.py"),
    ])
    assert result.findings == [], [str(f) for f in result.findings]
