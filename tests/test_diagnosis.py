"""Training diagnosis engine tests: phase timeline, profiler capture,
rules (hysteresis / attribution), the action round-trip over real RPC,
tools/diagnose.py rendering, and the < 1 % timeline-overhead bound
(ISSUE 4 acceptance)."""

import importlib.util
import json
import os
import time
from pathlib import Path

import pytest

from dlrover_tpu import obs
from dlrover_tpu.agent.elastic_agent import ElasticAgent, WorkerSpec
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common.config import Context
from dlrover_tpu.master.diagnosis import (
    DataPipelineBoundRule,
    DiagnosisManager,
    DiagnosisSnapshot,
    HbmPressureRule,
    StragglerRule,
    ThroughputCollapseRule,
    parse_action,
    straggler_scores,
)
from dlrover_tpu.master.job_master import JobMaster
from dlrover_tpu.master.speed_monitor import SpeedMonitor, WorkerSpeed
from dlrover_tpu.obs.profiler import ProfilerSession, write_profile_request
from dlrover_tpu.obs.timeline import StepTimeline, load_timeline

_REPO = Path(__file__).resolve().parent.parent
_diagnose_mod = None


def _diagnose():
    """tools/diagnose.py as a module (tools/ is not a package)."""
    global _diagnose_mod
    if _diagnose_mod is None:
        spec = importlib.util.spec_from_file_location(
            "diagnose_tool", _REPO / "tools" / "diagnose.py")
        _diagnose_mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_diagnose_mod)
    return _diagnose_mod


_DIAG_KNOBS = dict(
    diagnosis_min_worker_samples=2,
    straggler_trigger_windows=2,
    diagnosis_actions_enabled=True,
    diagnosis_action_cooldown_s=0.0,
    diagnosis_profile_steps=3,
)


@pytest.fixture()
def diag_ctx():
    ctx = Context.singleton()
    saved = {key: getattr(ctx, key) for key in _DIAG_KNOBS}
    ctx.update(**_DIAG_KNOBS)
    yield ctx
    ctx.update(**saved)


def _speeds(**per_worker):
    """{'w0': (step_time, wait_frac), ...} → worker_speeds dict."""
    out = {}
    for name, (step_time, wait) in per_worker.items():
        rank = int(name[1:])
        out[rank] = WorkerSpeed(worker_id=rank, samples=5,
                                mean_step_time_s=step_time,
                                data_wait_fraction=wait,
                                last_report_ts=time.time(), step=100)
    return out


def _snap(worker_speeds=None, **kw):
    return DiagnosisSnapshot(ts=time.time(),
                             worker_speeds=worker_speeds or {}, **kw)


# -- timeline ---------------------------------------------------------------


class TestStepTimeline:
    def test_record_residual_and_window_stats(self):
        tl = StepTimeline(capacity=8)
        for step in range(1, 5):
            tl.record(step, 0.10, data_wait=0.05, compute=0.04)
        stats = tl.window_stats()
        assert stats["samples"] == 4
        assert stats["mean_step_s"] == pytest.approx(0.10)
        assert stats["data_wait_fraction"] == pytest.approx(0.5)
        assert stats["compute_fraction"] == pytest.approx(0.4)
        assert stats["other_fraction"] == pytest.approx(0.1)

    def test_capacity_bound_and_empty_stats(self):
        tl = StepTimeline(capacity=4)
        for step in range(10):
            tl.record(step, 0.01, compute=0.01)
        assert len(tl.snapshot()) == 4
        assert tl.snapshot()[0]["step"] == 6
        empty = StepTimeline().window_stats()
        assert empty["samples"] == 0
        assert empty["data_wait_fraction"] == -1.0

    def test_export_parse_roundtrip(self, tmp_path):
        tl = StepTimeline(capacity=8, role="worker", rank=3)
        tl.record(7, 0.2, data_wait=0.15, compute=0.05)
        path = str(tmp_path / "timeline.json")
        assert tl.export(path)
        payload = load_timeline(path)
        assert payload["rank"] == 3
        assert payload["steps"][0]["step"] == 7
        assert payload["steps"][0]["phases"]["data_wait"] == \
            pytest.approx(0.15)
        assert load_timeline(str(tmp_path / "missing.json")) is None
        (tmp_path / "bad.json").write_text("{not json")
        assert load_timeline(str(tmp_path / "bad.json")) is None


class TestTimelineOverhead:
    def test_under_one_percent_of_step_time(self, tmp_path):
        """Acceptance: per-step timeline cost < 1 % of step time on the
        CPU bench. Simulated 10 ms steps (the small-model CPU-bench
        regime); the per-step record plus the exact report-interval
        work the loop does (window_stats every 10 steps + the
        1-s-throttled tail export, mirroring
        elastic_loop._report_progress) must stay under 1 % of the
        stepped wall time."""
        import statistics

        tl = StepTimeline(capacity=256)
        path = str(tmp_path / "t.json")
        interval = 10
        step_s = 0.010
        record_costs = []
        window_costs = []
        export_costs = []
        for step in range(150):
            t0 = time.perf_counter()
            tl.record(step, step_s, data_wait=0.004, compute=0.005)
            record_costs.append(time.perf_counter() - t0)
            if step % interval == 0:
                t0 = time.perf_counter()
                tl.window_stats(interval)
                window_costs.append(time.perf_counter() - t0)
            if step % 100 == 0:   # the 1-export/s throttle at 10ms steps
                t0 = time.perf_counter()
                tl.export(path, last_n=2 * interval)
                export_costs.append(time.perf_counter() - t0)
        # medians so a loaded CI box's scheduler blips don't flake the
        # bound; amortization mirrors the loop's real cadences
        per_step = (statistics.median(record_costs)
                    + statistics.median(window_costs) / interval
                    + statistics.median(export_costs) / 100)
        assert per_step < 0.01 * step_s, (
            f"timeline overhead {per_step * 1e6:.1f}us/step exceeds 1% "
            f"of a {step_s * 1e3:.0f}ms step")
        # the hot-path export is a tail; the payload still parses
        assert len(load_timeline(path)["steps"]) == 2 * interval


# -- profiler ---------------------------------------------------------------


class TestProfilerSession:
    def test_on_demand_capture_roundtrip(self, tmp_path):
        request = str(tmp_path / "req.json")
        dump_dir = str(tmp_path / "profiles")
        session = ProfilerSession(request_path=request)
        session.poll(0)
        assert not session.active
        write_profile_request(request, request_id=1, num_steps=2,
                              dump_dir=dump_dir)
        session.poll(1)
        assert session.active
        session.poll(2)   # within window
        assert session.active
        session.poll(3)   # window done → capture finalized
        assert not session.active
        # the capture artifact: a per-capture dir with a manifest
        captures = [d for d in os.listdir(dump_dir)
                    if d.startswith("capture-1-")]
        assert len(captures) == 1
        with open(os.path.join(dump_dir, captures[0],
                               "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["id"] == 1
        assert manifest["num_steps"] == 2
        # the agent-visible completion marker
        with open(request + ".done") as f:
            done = json.load(f)
        assert done["id"] == 1
        # a replayed (same-id) request must not start a second capture
        session.poll(4)
        assert not session.active

    def test_same_mtime_tick_rewrite_still_delivered(self, tmp_path):
        request = str(tmp_path / "req.json")
        dump_dir = str(tmp_path / "profiles")
        session = ProfilerSession(request_path=request)
        write_profile_request(request, request_id=1, num_steps=1,
                              dump_dir=dump_dir)
        st = os.stat(request)
        session.poll(0)
        session.poll(1)   # finalize capture 1
        assert not session.active
        # a coarse-mtime filesystem (1 s NFS ticks) can stamp the next
        # request with the SAME mtime: the rename's fresh inode must
        # still be noticed (same contract as the drain-request channel)
        write_profile_request(request, request_id=2, num_steps=1,
                              dump_dir=dump_dir)
        os.utime(request, ns=(st.st_atime_ns, st.st_mtime_ns))
        session.poll(2)
        assert session.active
        session.stop()

    def test_respawn_does_not_replay_completed_request(self, tmp_path):
        request = str(tmp_path / "req.json")
        dump_dir = str(tmp_path / "profiles")
        write_profile_request(request, request_id=1, num_steps=1,
                              dump_dir=dump_dir)
        session = ProfilerSession(request_path=request)
        session.poll(0)
        session.poll(1)   # finalizes → .done carries id 1
        assert not session.active
        # a respawned worker builds a FRESH session; the request file is
        # still on disk (the agent never deletes it) — the served id in
        # the .done manifest must stop a replay capture
        respawned = ProfilerSession(request_path=request)
        respawned.poll(0)
        assert not respawned.active
        # ...but a genuinely newer request is still picked up
        write_profile_request(request, request_id=2, num_steps=1,
                              dump_dir=dump_dir)
        respawned.poll(1)
        assert respawned.active
        # release the process-wide jax profiler session (one at a time)
        respawned.stop()

    def test_static_window_and_teardown_flush(self, tmp_path):
        static = str(tmp_path / "static")
        session = ProfilerSession(static_dir=static, static_start=1,
                                  static_num=50)
        session.poll(0)
        assert not session.active
        session.poll(1)
        assert session.active
        session.stop()    # step failure path: must finalize cleanly
        assert not session.active
        dirs = os.listdir(static)
        assert len(dirs) == 1 and dirs[0].startswith("capture-0-")


# -- speed monitor per-worker evidence --------------------------------------


class TestSpeedMonitorWorkerStats:
    def test_worker_speeds_and_eviction(self):
        monitor = SpeedMonitor()
        for step in range(1, 6):
            monitor.collect_worker_step(0, step, step_time_s=0.1,
                                        data_wait_fraction=0.2)
            monitor.collect_worker_step(1, step, step_time_s=0.4,
                                        data_wait_fraction=0.7)
        speeds = monitor.worker_speeds()
        assert speeds[0].mean_step_time_s == pytest.approx(0.1)
        assert speeds[1].data_wait_fraction == pytest.approx(0.7)
        # a report without timing adds no window entry
        monitor.collect_worker_step(2, 6)
        assert 2 not in monitor.worker_speeds()
        evicted = monitor.evict_departed({0})
        assert 1 in evicted and 2 in evicted
        assert set(monitor.worker_speeds()) == {0}

    def test_membership_reset_clears_baseline_and_windows(self):
        monitor = SpeedMonitor()
        for step in range(1, 8):
            monitor.collect_worker_step(
                0, step, step_time_s=0.1,
                timestamp=1000.0 + step * 0.1)
        assert monitor.peak_speed() > 0
        monitor.reset_running_speed()
        assert monitor.peak_speed() == 0.0
        assert monitor.worker_speeds() == {}


# -- rules ------------------------------------------------------------------


class TestStragglerRule:
    def test_hysteresis_trigger_and_clear(self, diag_ctx):
        rule = StragglerRule()
        slow = _speeds(w0=(0.1, 0.1), w1=(0.1, 0.1), w2=(0.5, 0.1))
        fast = _speeds(w0=(0.1, 0.1), w1=(0.1, 0.1), w2=(0.1, 0.1))
        # window 1: over threshold but below trigger count → no report
        assert rule.evaluate(_snap(slow), diag_ctx) == []
        assert rule.flagged == set()
        # window 2: consecutive → flagged, profile action addressed
        reports = rule.evaluate(_snap(slow), diag_ctx)
        assert len(reports) == 1
        assert reports[0].worker_id == 2
        assert "profile:2" in reports[0].actions
        assert rule.flagged == {2}
        # stays flagged, no duplicate report
        assert rule.evaluate(_snap(slow), diag_ctx) == []
        # recovery: needs STRAGGLER_CLEAR_WINDOWS (2) consecutive clean
        assert rule.evaluate(_snap(fast), diag_ctx) == []
        assert rule.flagged == {2}
        cleared = rule.evaluate(_snap(fast), diag_ctx)
        assert len(cleared) == 1 and cleared[0].severity == "info"
        assert rule.flagged == set()

    def test_one_slow_window_is_noise(self, diag_ctx):
        rule = StragglerRule()
        slow = _speeds(w0=(0.1, 0.1), w1=(0.5, 0.1))
        fast = _speeds(w0=(0.1, 0.1), w1=(0.1, 0.1))
        assert rule.evaluate(_snap(slow), diag_ctx) == []
        assert rule.evaluate(_snap(fast), diag_ctx) == []
        # the counter reset: another single slow window still no report
        assert rule.evaluate(_snap(slow), diag_ctx) == []
        assert rule.flagged == set()

    def test_scoring_needs_two_eligible_workers(self, diag_ctx):
        assert straggler_scores(_speeds(w0=(0.5, 0.1))) == {}
        few = _speeds(w0=(0.1, 0.1), w1=(0.5, 0.1))
        few[1].samples = 1   # below diagnosis_min_worker_samples
        assert straggler_scores(few, 2) == {}


class TestOtherRules:
    def test_data_bound_attribution(self, diag_ctx):
        rule = DataPipelineBoundRule()
        speeds = _speeds(w0=(0.1, 0.8), w1=(0.1, 0.1))
        reports = rule.evaluate(_snap(speeds), diag_ctx)
        assert len(reports) == 1
        assert reports[0].worker_id == 0
        assert "data-pipeline bound" in reports[0].summary
        # sticky: no duplicate while it stays bound
        assert rule.evaluate(_snap(speeds), diag_ctx) == []
        # recovery then regression re-reports
        healthy = _speeds(w0=(0.1, 0.1), w1=(0.1, 0.1))
        assert rule.evaluate(_snap(healthy), diag_ctx) == []
        assert len(rule.evaluate(_snap(speeds), diag_ctx)) == 1

    def test_throughput_collapse_uses_world_peak(self, diag_ctx):
        rule = ThroughputCollapseRule()
        ok = _snap(running_speed=9.0, peak_speed=10.0)
        collapsed = _snap(running_speed=2.0, peak_speed=10.0)
        assert rule.evaluate(ok, diag_ctx) == []
        reports = rule.evaluate(collapsed, diag_ctx)
        assert len(reports) == 1 and reports[0].severity == "critical"
        # latched while collapsed; re-arms after recovery
        assert rule.evaluate(collapsed, diag_ctx) == []
        assert rule.evaluate(ok, diag_ctx) == []
        assert len(rule.evaluate(collapsed, diag_ctx)) == 1
        # no baseline (fresh world) → no judgement
        assert rule.evaluate(_snap(running_speed=1.0, peak_speed=0.0),
                             diag_ctx) == []

    def test_hbm_pressure(self, diag_ctx):
        rule = HbmPressureRule()
        stats = {1: {"ts": time.time(), "chips": [
            {"hbm_used_mb": 15000.0, "hbm_total_mb": 16000.0}]}}
        reports = rule.evaluate(_snap(node_stats=stats), diag_ctx)
        assert len(reports) == 1
        assert "93.8%" in reports[0].summary

    def test_parse_action_grammar(self):
        assert parse_action("profile:3") == {"kind": "profile", "rank": 3}
        assert parse_action("restart:0") == {"kind": "restart", "rank": 0}
        assert parse_action("alert") == {"kind": "alert", "rank": -1}
        # unknown kinds degrade to observe (forward compatibility)
        assert parse_action("explode:1")["kind"] == "observe"
        assert parse_action("profile:x")["rank"] == -1


# -- manager ----------------------------------------------------------------


class TestDiagnosisManager:
    def _manager_with_straggler(self, diag_ctx):
        monitor = SpeedMonitor()
        for step in range(1, 6):
            monitor.collect_worker_step(0, step, step_time_s=0.1)
            monitor.collect_worker_step(1, step, step_time_s=0.5)
        manager = DiagnosisManager(monitor)
        # the monitor's steps/s is wall-clock between collect calls made
        # microseconds apart: the throughput rule fires on that noise once
        # in some thirty runs, and is not what these tests are about
        manager._rules = [r for r in manager._rules
                          if r.name != "throughput_collapse"]
        return manager

    def test_action_queue_cooldown_and_single_delivery(self, diag_ctx):
        manager = self._manager_with_straggler(diag_ctx)
        assert manager.diagnose_once() == []      # window 1 of 2
        reports = manager.diagnose_once()         # hysteresis met
        assert [r.rule for r in reports] == ["straggler"]
        actions = manager.poll_actions(1)
        assert len(actions) == 1
        assert actions[0]["kind"] == "profile"
        assert actions[0]["num_steps"] == 3       # diagnosis_profile_steps
        assert manager.poll_actions(1) == []      # single delivery
        assert manager.poll_actions(0) == []      # wrong rank gets nothing
        # persisted report survives export/restore; queues do not
        manager2 = DiagnosisManager(SpeedMonitor())
        manager2.restore_state(manager.export_state())
        assert [r["rule"] for r in manager2.reports()] == ["straggler"]
        assert manager2.poll_actions(1) == []

    def test_cooldown_suppresses_repeat_actions(self, diag_ctx):
        diag_ctx.update(diagnosis_action_cooldown_s=3600.0,
                        straggler_trigger_windows=1)
        try:
            manager = self._manager_with_straggler(diag_ctx)
            assert len(manager.diagnose_once()) == 1
            assert len(manager.poll_actions(1)) == 1
            # force a re-flag: clear + re-trigger emits a report, but the
            # rank is still cooling down → no second queued action
            manager._rules[0]._flagged.clear()
            assert len(manager.diagnose_once()) == 1
            assert manager.poll_actions(1) == []
        finally:
            diag_ctx.update(**{k: _DIAG_KNOBS[k] for k in (
                "diagnosis_action_cooldown_s",
                "straggler_trigger_windows")})

    def test_actions_kill_switch(self, diag_ctx):
        diag_ctx.update(diagnosis_actions_enabled=False)
        try:
            manager = self._manager_with_straggler(diag_ctx)
            manager.diagnose_once()
            reports = manager.diagnose_once()
            assert reports and manager.poll_actions(1) == []
        finally:
            diag_ctx.update(diagnosis_actions_enabled=True)

    def test_kill_switch_covers_urgent_checkpoint_fanout(self, diag_ctx):
        # diagnose-only means NO agent-side effects: the drain path's
        # urgent checkpoint fan-out must honor the switch too (only the
        # per-rank cooldown bypass is intentional)
        manager = DiagnosisManager(SpeedMonitor())
        diag_ctx.update(diagnosis_actions_enabled=False)
        try:
            assert manager.request_checkpoint([1, 2], deadline=0.0) == []
            assert manager.poll_actions(1) == []
        finally:
            diag_ctx.update(diagnosis_actions_enabled=True)
        assert manager.request_checkpoint([1], deadline=0.0) == [1]
        assert [a["kind"] for a in manager.poll_actions(1)] == [
            "checkpoint"]

    def test_evict_workers_drops_queues_and_stats(self, diag_ctx):
        manager = self._manager_with_straggler(diag_ctx)
        manager.diagnose_once()
        manager.diagnose_once()
        assert manager.pending_action_counts() == {1: 1}
        manager.evict_workers({0})
        assert manager.poll_actions(1) == []

    def test_step_watermark_expires_by_its_own_age(self, diag_ctx):
        from dlrover_tpu.common import messages as msg

        manager = DiagnosisManager(SpeedMonitor())
        manager.observe_step_watermark(0, 900.0)
        stats = msg.NodeResourceStats(node_id=0, node_rank=0,
                                      cpu_percent=10.0)
        # a fresh chip relay preserves the step-report watermark...
        manager.observe_resource_stats(stats)
        assert manager._node_stats[0]["hbm_peak_mb"] == 900.0
        # ...but a wedged loop (no new step reports while the relay
        # keeps refreshing the entry) must not latch it forever: the
        # watermark expires by ITS age, not the relay's
        manager._node_stats[0]["hbm_peak_ts"] -= 1000.0
        manager.observe_resource_stats(stats)
        assert "hbm_peak_mb" not in manager._node_stats[0]

    def test_discount_push_rides_the_diagnosis_cadence(self, diag_ctx):
        from dlrover_tpu.parallel.calibration import PlanCalibration

        cal = PlanCalibration(min_samples=1)
        manager = DiagnosisManager(SpeedMonitor(), plan_calibration=cal)
        pushed = []
        manager.discount_sink = pushed.append
        manager.diagnose_once()
        assert pushed == [{}]     # no evidence yet: prior stands

    def test_resource_stats_keyed_by_rank(self, diag_ctx):
        from dlrover_tpu.common import messages as msg

        manager = DiagnosisManager(SpeedMonitor())
        # after a relaunch node_id (7) diverges from rank (1): evidence
        # must land under the rank so membership eviction (rank sets)
        # and profile:{rank} actions agree on identity
        manager.observe_resource_stats(msg.NodeResourceStats(
            node_id=7, node_rank=1, cpu_percent=50.0))
        assert set(manager.snapshot().node_stats) == {1}
        manager.evict_workers({0})
        assert manager.snapshot().node_stats == {}
        # legacy senders without the field keep their node_id key
        manager.observe_resource_stats(msg.NodeResourceStats(
            node_id=3, cpu_percent=50.0))
        assert set(manager.snapshot().node_stats) == {3}

    def test_membership_drop_spares_live_rank_sharing_dead_node_id(
            self, diag_ctx):
        from dlrover_tpu.common.node import Node
        from dlrover_tpu.master.node.event_callback import (
            RendezvousMembershipCallback,
        )

        class _Rdzv:
            def __init__(self, alive):
                self.alive_nodes = set(alive)

            def remove_alive_node(self, rank, graceful=False):
                self.alive_nodes.discard(rank)

        monitor = SpeedMonitor()
        for rank in (0, 1, 3):
            monitor.add_running_worker(rank)
            monitor.collect_worker_step(rank, 5, step_time_s=0.1)
        rdzv = _Rdzv({0, 1, 3})
        callback = RendezvousMembershipCallback(
            {"elastic-training": rdzv}, monitor)
        # the departed node's id (3) collides with a LIVE worker's rank:
        # only rank 1's membership + step entry may go — rank 3 must
        # keep ranking (timing windows reset wholesale by design at a
        # membership change; steps and membership must not)
        callback.on_node_failed(
            Node("worker", node_id=3, rank_index=1))
        assert set(monitor._worker_steps) == {0, 3}
        assert monitor.num_running_workers == 2


# -- the in-process integration: slow worker → flag → profile → artifact ----


class TestDiagnosisRoundTrip:
    def test_straggler_to_capture_artifact(self, diag_ctx, tmp_path,
                                           monkeypatch):
        monkeypatch.setenv(obs.FLIGHT_DIR_ENV, str(tmp_path / "flight"))
        master = JobMaster(min_nodes=2, max_nodes=2, host="127.0.0.1")
        master.prepare()
        clients = [MasterClient(master.addr, node_id=rank, node_rank=rank)
                   for rank in (0, 1)]
        agent1 = None
        try:
            # stubbed step reports: rank 1 is artificially 5x slower
            for step in range(1, 6):
                clients[0].report_global_step(step, step_time_s=0.1,
                                              data_wait_fraction=0.1)
                clients[1].report_global_step(step, step_time_s=0.5,
                                              data_wait_fraction=0.1)
            # flagged within the configured window (2 evaluations)
            master.diagnosis_manager.diagnose_once()
            reports = master.diagnosis_manager.diagnose_once()
            assert any(r.rule == "straggler" and r.worker_id == 1
                       for r in reports)
            # the RPC surface shows the report
            assert any(r["rule"] == "straggler"
                       for r in clients[0].get_diagnosis_reports())
            # rank 0's agent polls: nothing addressed to it
            assert clients[0].poll_diagnosis_actions() == []
            # rank 1's agent picks the profile action up and executes it
            agent1 = ElasticAgent(clients[1], WorkerSpec(
                entrypoint=["true"], monitor_interval_s=0.1))
            agent1._poll_diagnosis_actions()
            assert os.path.exists(agent1.profile_request_file)
            # ... and the action is single-delivery
            assert clients[1].poll_diagnosis_actions() == []
            # the worker side rounds the request into a capture artifact
            session = ProfilerSession(
                request_path=agent1.profile_request_file)
            session.poll(0)
            assert session.active
            session.poll(diag_ctx.diagnosis_profile_steps)
            assert not session.active
            captures = os.listdir(agent1.profile_dump_dir)
            assert len(captures) == 1
            manifest_path = os.path.join(
                agent1.profile_dump_dir, captures[0], "manifest.json")
            with open(manifest_path) as f:
                assert json.load(f)["num_steps"] == \
                    diag_ctx.diagnosis_profile_steps
            # the flight dump carries the whole decision trail ...
            dump_path = obs.get_flight_recorder().dump(
                reason="test-diagnosis")
            with open(dump_path) as f:
                dump = json.load(f)
            names = [e.get("name") for e in dump["events"]]
            assert "diagnosis" in names
            assert "diagnosis_action" in names
            assert "diagnosis_action_executed" in names
            # ... and tools/diagnose.py renders the report from it
            tool = _diagnose()
            rendered = tool.render_reports(tool.reports_from_flight(dump))
            assert "straggler" in rendered
            assert "worker 1" in rendered
        finally:
            if agent1 is not None:
                agent1.shutdown()
            for client in clients:
                client.close()
            master.stop()

    def test_reports_survive_master_restart(self, diag_ctx, tmp_path):
        state_dir = str(tmp_path / "state")
        master = JobMaster(min_nodes=2, max_nodes=2, host="127.0.0.1",
                          state_dir=state_dir)
        client = MasterClient(master.addr, node_id=0, node_rank=0)
        try:
            for step in range(1, 6):
                master.speed_monitor.collect_worker_step(
                    0, step, step_time_s=0.1)
                master.speed_monitor.collect_worker_step(
                    1, step, step_time_s=0.5)
            master.diagnosis_manager.diagnose_once()
            assert master.diagnosis_manager.diagnose_once()
        finally:
            client.close()
            master.stop()
        restarted = JobMaster(min_nodes=2, max_nodes=2, host="127.0.0.1",
                              state_dir=state_dir)
        try:
            rules = [r["rule"]
                     for r in restarted.diagnosis_manager.reports()]
            assert "straggler" in rules
        finally:
            restarted.stop()


# -- tools/diagnose.py golden output ---------------------------------------


class TestDiagnoseRendering:
    def test_render_reports_golden(self):
        render_reports = _diagnose().render_reports
        reports = [
            {"rule": "straggler", "severity": "warning", "worker_id": 1,
             "summary": "worker 1 is a straggler: 0.500s/step is 5.00x "
                        "the fleet median",
             "actions": ["profile:1", "alert"], "ts": 100.0},
            {"rule": "throughput_collapse", "severity": "critical",
             "worker_id": -1,
             "summary": "throughput collapsed to 20% of this world's "
                        "peak (2.00 vs 10.00 steps/s)",
             "actions": ["alert"], "ts": 130.5},
        ]
        expected = "\n".join([
            "diagnosis reports: 2",
            "+     0.0s  warning  straggler              worker 1   "
            "worker 1 is a straggler: 0.500s/step is 5.00x the fleet "
            "median  [profile:1,alert]",
            "+    30.5s  critical throughput_collapse    job        "
            "throughput collapsed to 20% of this world's peak "
            "(2.00 vs 10.00 steps/s)  [alert]",
        ])
        assert render_reports(reports) == expected

    def test_render_timeline_golden(self):
        render_timeline = _diagnose().render_timeline
        payload = {
            "role": "worker", "rank": 2,
            "steps": [
                {"step": 10, "total_s": 0.1,
                 "phases": {"data_wait": 0.06, "compute": 0.03,
                            "other": 0.01}},
                {"step": 11, "total_s": 0.1,
                 "phases": {"data_wait": 0.06, "compute": 0.03,
                            "other": 0.01}},
            ],
        }
        rendered = render_timeline(payload)
        lines = rendered.splitlines()
        assert lines[0] == "step timeline: role=worker rank=2 steps=2"
        assert lines[1] == ("mean step 0.1000s | data_wait 60% "
                            "compute 30% other 10%")
        assert lines[3].split() == [
            "10", "0.1000s", "0.0600", "0.0000", "0.0300", "0.0000",
            "0.0000", "0.0100"]

    def test_cli_on_timeline_file(self, tmp_path, capsys):
        main = _diagnose().main
        tl = StepTimeline(role="worker", rank=0)
        tl.record(1, 0.05, data_wait=0.02, compute=0.03)
        path = str(tmp_path / "timeline.json")
        tl.export(path)
        assert main(["--timeline", path]) == 0
        out = capsys.readouterr().out
        assert "step timeline: role=worker rank=0 steps=1" in out
        assert main(["--timeline", str(tmp_path / "nope.json")]) == 2


# -- monitor satellites -----------------------------------------------------


class TestMonitorSatellites:
    def test_export_chip_stats_duty_proxy(self, tmp_path, monkeypatch):
        from dlrover_tpu.agent import monitor as monitor_mod

        path = str(tmp_path / "chips.json")
        # first export: no previous sample → duty omitted, not 0.0
        monitor_mod.export_chip_stats(path, step=10, step_time_s=0.1)
        chips = json.loads(open(path).read())
        assert chips and all("duty_cycle_pct" not in c for c in chips)
        # second export: 20 steps x 0.1s over the elapsed wall time
        prev = monitor_mod._chip_export_prev[path]
        prev["ts"] -= 4.0   # pretend 4s elapsed
        monitor_mod.export_chip_stats(path, step=30, step_time_s=0.1)
        chips = json.loads(open(path).read())
        assert chips
        for chip in chips:
            assert chip["duty_cycle_pct"] == pytest.approx(50.0, abs=5.0)
        # no step info at all → field honestly absent
        path2 = str(tmp_path / "chips2.json")
        monitor_mod.export_chip_stats(path2)
        chips = json.loads(open(path2).read())
        assert all("duty_cycle_pct" not in c for c in chips)

    def test_resource_monitor_primes_cpu_sampling(self, monkeypatch):
        psutil = pytest.importorskip("psutil")
        from dlrover_tpu.agent.monitor import ResourceMonitor

        class _Client:
            node_id = 0

        calls = []
        real = psutil.cpu_percent
        monkeypatch.setattr(
            psutil, "cpu_percent",
            lambda interval=None: calls.append(interval) or real(
                interval=interval))
        # construction alone must make the throwaway priming call —
        # psutil's first cpu_percent(interval=None) returns a
        # meaningless 0.0, so an unprimed monitor's first report lies
        monitor = ResourceMonitor(_Client(), interval_s=3600)
        assert len(calls) == 1
        stats = monitor.sample()
        assert len(calls) == 2
        assert stats.memory_mb > 0

    def test_publish_node_stats_skips_unknown_duty(self):
        from dlrover_tpu.common import messages as msg

        registry = obs.MetricsRegistry()
        stats = msg.NodeResourceStats(
            node_id=5, node_type="worker", cpu_percent=10.0,
            memory_mb=100.0,
            chip_stats=[msg.ChipStats(index=0, hbm_used_mb=10.0,
                                      hbm_total_mb=16.0)])
        obs.publish_node_stats(stats, registry)
        rendered = registry.render()
        assert "dlrover_tpu_node_hbm_used_mb" in rendered
        assert "duty_cycle" not in rendered
        stats.chip_stats[0].duty_cycle_pct = 75.0
        obs.publish_node_stats(stats, registry)
        assert 'dlrover_tpu_node_chip_duty_cycle_pct{node="5"' in \
            registry.render()
