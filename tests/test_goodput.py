"""Goodput ledger + MFU accounting + restore decomposition (ISSUE 8):
fake-clock bucket classification from a synthetic span/report stream,
MFU golden math against the bench formula, master-failover state
roundtrip, restore-path breakdown fields, exposition of the new series,
the goodput alert rule, the < 1 % ledger-overhead bound, and the
tools/goodput.py rendering acceptance."""

import importlib.util
import json
import time
from pathlib import Path

import pytest

from dlrover_tpu import obs
from dlrover_tpu.common.config import Context
from dlrover_tpu.master.diagnosis import (
    DiagnosisSnapshot,
    GoodputRule,
    ThroughputCollapseRule,
)
from dlrover_tpu.master.speed_monitor import SpeedMonitor
from dlrover_tpu.obs.goodput import (
    GoodputLedger,
    classify_span,
    render_snapshot,
    snapshot_from_flight,
)

_REPO = Path(__file__).resolve().parent.parent
_tool_mods = {}


def _tool(name):
    """tools/<name>.py as a module (tools/ is not a package)."""
    if name not in _tool_mods:
        spec = importlib.util.spec_from_file_location(
            f"{name}_tool", _REPO / "tools" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _tool_mods[name] = mod
    return _tool_mods[name]


class FakeClock:
    def __init__(self, start=1000.0):
        self.t = start

    def __call__(self):
        return self.t

    def advance(self, seconds):
        self.t += seconds


def _ledger(start=1000.0):
    clock = FakeClock(start)
    ledger = GoodputLedger(registry=obs.MetricsRegistry(), now_fn=clock)
    return ledger, clock


def _span(name, duration, span_id, ts=0.0, **attrs):
    return {"kind": "span", "name": name, "span_id": span_id,
            "duration_s": duration, "ts": ts, "attrs": attrs}


# -- ledger classification (fake clock) -------------------------------------


class TestLedgerClassification:
    def test_step_reports_split_productive_and_data_wait(self):
        ledger, clock = _ledger()
        ledger.observe_step_report(0, 10, step_time_s=0.5,
                                   data_wait_fraction=0.2)
        clock.advance(10.0)
        ledger.observe_step_report(0, 20, step_time_s=0.5,
                                   data_wait_fraction=0.2)
        snap = ledger.snapshot()
        buckets = snap["buckets"]
        assert buckets["productive"] == pytest.approx(4.0)
        assert buckets["data_wait"] == pytest.approx(1.0)
        # idle is the residual of the rank's 10 s lifetime
        assert buckets["idle"] == pytest.approx(5.0)
        assert snap["goodput_fraction"] == pytest.approx(0.4)

    def test_step_accrual_clamped_to_wall_clock(self):
        """A post-failover report whose step delta spans the gap must
        never attribute more productive time than the wall between
        reports."""
        ledger, clock = _ledger()
        ledger.observe_step_report(0, 0, step_time_s=1.0,
                                   data_wait_fraction=0.0)
        clock.advance(5.0)
        ledger.observe_step_report(0, 100, step_time_s=1.0,
                                   data_wait_fraction=0.0)
        buckets = ledger.snapshot()["buckets"]
        assert buckets["productive"] == pytest.approx(5.0)

    def test_no_timing_evidence_accrues_nothing(self):
        ledger, clock = _ledger()
        ledger.observe_step_report(0, 10)
        clock.advance(10.0)
        ledger.observe_step_report(0, 20)   # step_time_s = 0
        buckets = ledger.snapshot()["buckets"]
        assert buckets["productive"] == 0.0
        assert buckets["idle"] == pytest.approx(10.0)

    def test_span_classification_table(self):
        assert classify_span("recompile", {"phase": "relower"}) \
            == "compile"
        # the AOT compile overlaps restore_or_init: not double-counted
        assert classify_span("recompile", {"phase": "aot"}) == ""
        assert classify_span("rendezvous") == "rendezvous"
        assert classify_span("restore_or_init") == "restore"
        assert classify_span("checkpoint_wait") == "checkpoint_stall"
        assert classify_span("emergency_checkpoint") \
            == "checkpoint_stall"
        # nested/master-side spans are not ledger evidence
        assert classify_span("rendezvous_join") == ""
        assert classify_span("checkpoint_restore") == ""
        assert classify_span("checkpoint_save") == ""
        assert classify_span("master_restore") == ""

    def test_span_stream_accrual_and_dedup(self):
        ledger, clock = _ledger()
        ts = clock() - 5
        assert ledger.observe_span(
            _span("rendezvous", 2.0, "s1", ts), rank=0)
        # the standalone double delivery: same span id arrives again
        assert not ledger.observe_span(
            _span("rendezvous", 2.0, "s1", ts), rank=0)
        ledger.observe_span(_span("restore_or_init", 3.0, "s2", ts),
                            rank=0)
        ledger.observe_span(_span("recompile", 1.0, "s3", ts,
                                  phase="aot"), rank=0)
        buckets = ledger.snapshot()["buckets"]
        assert buckets["rendezvous"] == pytest.approx(2.0)
        assert buckets["restore"] == pytest.approx(3.0)
        assert buckets["compile"] == 0.0

    def test_drain_interval_and_state_gauge(self):
        ledger, clock = _ledger()
        ledger.observe_step_report(1, 5, step_time_s=0.1)
        ledger.mark_draining(1, deadline=clock() + 30)
        assert ledger.snapshot()["per_rank"]["1"]["state"] == "draining"
        clock.advance(3.0)
        ledger.complete_drain(1)
        row = ledger.snapshot()["per_rank"]["1"]
        assert row["gone"]
        assert row["buckets"]["drain"] == pytest.approx(3.0)

    def test_drain_residual_not_double_counted(self):
        """The emergency-checkpoint span lands inside the notice →
        departure interval: drain accrues only the residual, so the
        same rank-second is never booked twice."""
        ledger, clock = _ledger()
        ledger.observe_step_report(1, 5, step_time_s=0.1)
        ledger.mark_draining(1)
        clock.advance(3.0)
        ledger.observe_span(_span("emergency_checkpoint", 1.2, "ec1",
                                  clock() - 1.2), rank=1)
        ledger.complete_drain(1)
        buckets = ledger.snapshot()["per_rank"]["1"]["buckets"]
        assert buckets["checkpoint_stall"] == pytest.approx(1.2)
        assert buckets["drain"] == pytest.approx(1.8)

    def test_window_truncation_is_honest(self):
        """A full accrual ring that no longer reaches back the whole
        window must shrink the effective window (and say so) instead of
        reading the evicted accruals as idle — a busy job must not
        raise a false goodput alert."""
        from collections import deque

        ledger, clock = _ledger()
        ledger._window = deque(maxlen=4)
        ledger.observe_step_report(0, 0, step_time_s=1.0)
        for i in range(8):
            clock.advance(10.0)
            ledger.observe_step_report(0, (i + 1) * 10,
                                       step_time_s=1.0)
        window = ledger.window_summary(600.0)
        assert window["truncated"]
        # the ring holds the last 4 accruals (2 reports' worth = 20 s
        # of wall): the denominator shrinks to match the evidence, so
        # the fraction stays honest instead of collapsing toward 0
        assert window["effective_window_s"] <= 40.0
        assert window["goodput_fraction"] >= 0.9

    def test_hang_estimate_bounded_by_watchdog(self):
        ledger, clock = _ledger()
        ledger.observe_step_report(2, 5, step_time_s=0.1)
        clock.advance(40.0)   # silent for 40 s, watchdog bound 25 s
        ledger.observe_hang(2, hang_bound_s=25.0)
        buckets = ledger.snapshot()["buckets"]
        assert buckets["hang"] == pytest.approx(25.0)

    def test_incarnations_attribute_badput_to_trigger(self):
        ledger, clock = _ledger()
        ledger.observe_world(0, 2)
        ledger.observe_span(_span("rendezvous", 1.0, "a", clock()),
                            rank=0)
        ledger.note_elasticity_event("worker_lost")
        clock.advance(5.0)
        ledger.observe_world(1, 1)
        ledger.observe_span(_span("restore_or_init", 4.0, "b", clock()),
                            rank=0)
        incs = ledger.snapshot()["incarnations"]
        assert len(incs) == 2
        # the job's first world adopts the bootstrap segment
        assert incs[0]["round"] == 0
        assert incs[0]["reason"] == "job_start"
        assert incs[0]["badput_buckets"]["rendezvous"] \
            == pytest.approx(1.0)
        assert incs[1]["round"] == 1
        assert incs[1]["reason"] == "worker_lost"
        assert incs[1]["badput_buckets"]["restore"] == pytest.approx(4.0)
        # repeat polls of the same round do not open new incarnations
        ledger.observe_world(1, 1)
        assert len(ledger.snapshot()["incarnations"]) == 2

    def test_buckets_account_for_all_wall_clock(self):
        """Acceptance shape: productive + badput (incl. derived idle)
        cover the elapsed rank-seconds."""
        ledger, clock = _ledger()
        ledger.observe_step_report(0, 0, step_time_s=0.2,
                                   data_wait_fraction=0.3)
        ledger.observe_step_report(1, 0, step_time_s=0.2)
        clock.advance(20.0)
        ledger.observe_step_report(0, 50, step_time_s=0.2,
                                   data_wait_fraction=0.3)
        ledger.observe_span(_span("recompile", 2.5, "c", clock(),
                                  phase="relower"), rank=1)
        snap = ledger.snapshot()
        covered = sum(snap["buckets"].values())
        assert covered >= 0.95 * snap["elapsed_rank_seconds"]

    def test_window_summary_names_dominant_badput(self):
        ledger, clock = _ledger()
        ledger.observe_step_report(0, 0, step_time_s=0.1)
        clock.advance(100.0)
        ledger.observe_span(_span("restore_or_init", 30.0, "w1",
                                  clock() - 30), rank=0)
        ledger.observe_span(_span("rendezvous", 5.0, "w2",
                                  clock() - 30), rank=0)
        window = ledger.window_summary(60.0)
        assert window["dominant_badput"] == "restore"
        assert window["dominant_badput_s"] == pytest.approx(30.0)
        assert window["elapsed_rank_seconds"] == pytest.approx(60.0)

    def test_evict_ends_lifetime(self):
        ledger, clock = _ledger()
        ledger.observe_step_report(0, 5, step_time_s=0.1)
        ledger.observe_step_report(1, 5, step_time_s=0.1)
        clock.advance(10.0)
        ledger.evict(live={0})
        clock.advance(50.0)
        snap = ledger.snapshot()
        assert snap["per_rank"]["1"]["gone"]
        assert snap["per_rank"]["1"]["elapsed_s"] == pytest.approx(10.0)
        assert snap["per_rank"]["0"]["elapsed_s"] == pytest.approx(60.0)


# -- MFU math ---------------------------------------------------------------


class TestMfuMath:
    def test_flops_per_token_matches_bench_formula(self):
        """The program has one FLOPs formula (obs/mfu.py) — golden-check
        it against the hand formula."""
        from dlrover_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig.tiny()
        seq = 64
        uncounted = (cfg.vocab_size * cfg.hidden_size
                     if cfg.embed_impl == "gather"
                     and not cfg.tie_embeddings else 0)
        expected = (6.0 * (cfg.param_count() - uncounted)
                    + 6.0 * cfg.num_layers * cfg.hidden_size * seq)
        got = obs.mfu.flops_per_token(
            cfg.param_count(), num_layers=cfg.num_layers,
            hidden_size=cfg.hidden_size, seq_len=seq,
            uncounted_embed_params=uncounted)
        assert got == pytest.approx(expected)
        # degraded mode: no shape info → the bare 6·params floor
        assert obs.mfu.flops_per_token(100) == pytest.approx(600.0)

    def test_peak_flops_longest_prefix_wins(self):
        assert obs.mfu.peak_flops_per_chip("TPU v5 lite") == 197e12
        assert obs.mfu.peak_flops_per_chip("TPU v5p") == 459e12
        assert obs.mfu.peak_flops_per_chip("TPU v4i") == 275e12
        # no default peak: an unknown TPU is an error, a non-TPU backend
        # has no peak at all (achieved_mfu reads 0.0 as "no evidence")
        with pytest.raises(ValueError, match="TPU v9"):
            obs.mfu.peak_flops_per_chip("TPU v9", backend="tpu")
        assert obs.mfu.peak_flops_per_chip("cpu", backend="cpu") == 0.0

    def test_achieved_mfu_golden_and_sentinels(self):
        # 1000 tok/s × 2e9 FLOPs/tok over a 4e12 peak = 0.5 MFU
        assert obs.mfu.achieved_mfu(1000.0, 2e9, 4e12) \
            == pytest.approx(0.5)
        assert obs.mfu.achieved_mfu(1000.0, 0.0, 4e12) == -1.0
        assert obs.mfu.achieved_mfu(1000.0, 2e9, 0.0) == -1.0
        assert obs.mfu.achieved_mfu(-1.0, 2e9, 4e12) == -1.0

    def test_cross_check_adopts_only_on_divergence(self):
        # within 2x: the analytic model stands
        assert obs.mfu.cross_check(100.0, 150.0 * 8, 8.0) is None
        # >2x divergence: adopt the measurement
        assert obs.mfu.cross_check(100.0, 300.0 * 8, 8.0) \
            == pytest.approx(300.0)
        assert obs.mfu.cross_check(100.0, 30.0 * 8, 8.0) \
            == pytest.approx(30.0)
        # no measurement → no adoption
        assert obs.mfu.cross_check(100.0, 0.0, 8.0) is None

    def test_cost_analysis_flops_on_compiled_matmul(self, cpu_devices):
        """Cross-check against XLA's own accounting: a compiled m×k·k×n
        matmul costs 2mkn FLOPs (skipped when this backend/jax version
        returns no analysis)."""
        import jax
        import jax.numpy as jnp

        m = k = n = 64

        def f(a, b):
            return a @ b

        compiled = jax.jit(f).lower(
            jnp.zeros((m, k)), jnp.zeros((k, n))).compile()
        measured = obs.mfu.cost_analysis_flops(compiled)
        if measured <= 0.0:
            pytest.skip("backend returns no cost analysis")
        assert measured == pytest.approx(2 * m * k * n, rel=0.25)
        assert obs.mfu.cost_analysis_flops(None) == 0.0


# -- SpeedMonitor / exposition ---------------------------------------------


class TestMfuExposition:
    def test_speed_monitor_publishes_mfu_gauges(self):
        monitor = SpeedMonitor()
        monitor.set_tokens_per_step(1000)
        monitor.set_model_flops(2e9, 4e12)
        now = time.time()
        monitor.collect_worker_step(0, 10, step_time_s=0.5, mfu=0.41,
                                    timestamp=now - 1.0)
        monitor.collect_worker_step(0, 20, step_time_s=0.5, mfu=0.43,
                                    timestamp=now)
        # steps/s ≈ 10; MFU = 10 × 1000 tok/s × 2e9 / 4e12 = 0.005
        assert monitor.running_mfu() == pytest.approx(
            monitor.running_speed() * 1000 * 2e9 / 4e12)
        assert monitor.peak_mfu() > 0.0
        speeds = monitor.worker_speeds()
        assert speeds[0].mfu == pytest.approx(0.42)
        rendered = obs.get_registry().render()
        assert "dlrover_tpu_training_mfu" in rendered
        assert "dlrover_tpu_training_model_flops_per_token" in rendered

    def test_mfu_model_survives_state_roundtrip(self):
        monitor = SpeedMonitor()
        monitor.set_model_flops(3e9, 9e12)
        state = monitor.export_state()
        fresh = SpeedMonitor()
        fresh.restore_state(state)
        assert fresh.export_state()["flops_per_token"] == 3e9
        assert fresh.export_state()["peak_flops_total"] == 9e12

    def test_goodput_series_render(self):
        registry = obs.MetricsRegistry()
        clock = FakeClock()
        ledger = GoodputLedger(registry=registry, now_fn=clock)
        ledger.observe_step_report(0, 0, step_time_s=0.1)
        clock.advance(4.0)
        ledger.observe_step_report(0, 20, step_time_s=0.1)
        ledger.observe_span(_span("rendezvous", 1.0, "r1", clock()),
                            rank=0)
        ledger.mark_draining(0)
        rendered = registry.render()
        assert ('dlrover_tpu_goodput_seconds_total{bucket="productive"} '
                '2' in rendered)
        assert ('dlrover_tpu_goodput_seconds_total{bucket="rendezvous"} '
                '1' in rendered)
        assert "dlrover_tpu_goodput_fraction 0.5" in rendered
        assert ('dlrover_tpu_worker_goodput_state{node="0",'
                'slice="-1",state="draining"} 1' in rendered)


# -- rules ------------------------------------------------------------------


@pytest.fixture()
def goodput_ctx():
    ctx = Context.singleton()
    knobs = dict(goodput_alert_threshold=0.5, goodput_window_s=600.0)
    saved = {key: getattr(ctx, key) for key in knobs}
    ctx.update(**knobs)
    yield ctx
    ctx.update(**saved)


def _goodput_evidence(fraction, dominant="restore", dominant_s=200.0,
                      elapsed=600.0, window=600.0):
    return {"window_s": window, "elapsed_rank_seconds": elapsed,
            "goodput_fraction": fraction, "dominant_badput": dominant,
            "dominant_badput_s": dominant_s,
            "buckets": {"productive": fraction * elapsed,
                        dominant: dominant_s}}


class TestGoodputRule:
    def test_alert_names_dominant_bucket(self, goodput_ctx):
        rule = GoodputRule()
        snap = DiagnosisSnapshot(
            ts=time.time(), worker_speeds={}, running_workers=1,
            goodput=_goodput_evidence(0.2))
        reports = rule.evaluate(snap, goodput_ctx)
        assert len(reports) == 1
        assert reports[0].severity == "critical"
        assert "restore" in reports[0].summary
        assert "20%" in reports[0].summary
        assert reports[0].actions == ["alert"]
        # hysteresis: no repeat while still below the floor
        assert rule.evaluate(snap, goodput_ctx) == []
        # recovery clears; a later drop re-alerts
        ok = DiagnosisSnapshot(
            ts=time.time(), worker_speeds={}, running_workers=1,
            goodput=_goodput_evidence(0.9))
        assert rule.evaluate(ok, goodput_ctx) == []
        assert len(rule.evaluate(snap, goodput_ctx)) == 1

    def test_window_coverage_gate(self, goodput_ctx):
        rule = GoodputRule()
        # only 100 of 600 window-seconds observed: not evidence yet
        snap = DiagnosisSnapshot(
            ts=time.time(), worker_speeds={}, running_workers=1,
            goodput=_goodput_evidence(0.1, elapsed=100.0))
        assert rule.evaluate(snap, goodput_ctx) == []

    def test_disabled_by_default(self):
        rule = GoodputRule()
        snap = DiagnosisSnapshot(
            ts=time.time(), worker_speeds={}, running_workers=1,
            goodput=_goodput_evidence(0.0))
        assert rule.evaluate(snap, Context.singleton()) == []


class TestCollapseOnMfu:
    def test_prefers_mfu_evidence(self, goodput_ctx):
        rule = ThroughputCollapseRule()
        snap = DiagnosisSnapshot(
            ts=time.time(), worker_speeds={}, running_speed=9.0,
            peak_speed=10.0, running_mfu=0.1, peak_mfu=0.6)
        reports = rule.evaluate(snap, goodput_ctx)
        # steps/s alone (0.9 ratio) would NOT fire; MFU (0.17) does
        assert len(reports) == 1
        assert reports[0].details["signal"] == "mfu"
        assert "MFU" in reports[0].summary

    def test_falls_back_to_steps_without_flops_model(self, goodput_ctx):
        rule = ThroughputCollapseRule()
        snap = DiagnosisSnapshot(
            ts=time.time(), worker_speeds={}, running_speed=2.0,
            peak_speed=10.0)
        reports = rule.evaluate(snap, goodput_ctx)
        assert len(reports) == 1
        assert reports[0].details["signal"] == "steps_per_second"


# -- restore decomposition --------------------------------------------------


class TestRestoreDecomposition:
    def test_flash_checkpoint_restore_phases(self, cpu_devices,
                                             tmp_path):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax

        from dlrover_tpu.checkpoint import FlashCheckpointer
        from dlrover_tpu.models.llama import (
            Llama,
            LlamaConfig,
            cross_entropy_loss,
        )
        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
        from dlrover_tpu.trainer.train_step import build_trainer

        cfg = LlamaConfig.tiny(attn_impl="reference")
        mesh = create_mesh(MeshSpec(), jax.devices("cpu")[:1])
        sample = jnp.zeros((2, 16), jnp.int32)
        trainer = build_trainer(Llama(cfg), optax.adamw(1e-3), mesh,
                                sample, cross_entropy_loss,
                                accum_steps=1, micro_batch=2)
        state = trainer.init(jax.random.PRNGKey(0))
        captured = []
        sink = captured.append
        obs.add_span_sink(sink)
        try:
            with FlashCheckpointer(str(tmp_path / "ckpt"),
                                   save_interval_steps=1) as ckpt:
                assert ckpt.maybe_save(1, state, {})
                ckpt.wait()
                abstract = jax.tree.map(
                    lambda leaf: jax.ShapeDtypeStruct(
                        leaf.shape, leaf.dtype, sharding=leaf.sharding),
                    state)
                restored, _, step = ckpt.restore(abstract)
                phases = dict(ckpt.last_restore_phases)
        finally:
            obs.remove_span_sink(sink)
        assert step == 1
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(restored.params)[0]),
            np.asarray(jax.tree.leaves(state.params)[0]))
        # the decomposed phases the peer-to-peer restore work baselines
        for key in ("step_discovery_s", "metadata_read_s",
                    "tensor_read_s", "restored_bytes"):
            assert key in phases, phases
        assert phases["restored_bytes"] > 0
        assert phases.get("read_bandwidth_mbps", 0.0) > 0.0
        names = {span.name for span in captured}
        assert {"restore_step_discovery", "restore_metadata_read",
                "restore_tensor_read"} <= names
        rendered = obs.get_registry().render()
        assert "dlrover_tpu_checkpoint_restore_bytes" in rendered
        assert "dlrover_tpu_checkpoint_restore_bandwidth_mbps" \
            in rendered


# -- state roundtrip --------------------------------------------------------


class TestStateRoundtrip:
    def test_export_restore_preserves_totals(self):
        ledger, clock = _ledger()
        ledger.observe_world(0, 2)
        ledger.observe_step_report(0, 0, step_time_s=0.1)
        clock.advance(10.0)
        ledger.observe_step_report(0, 50, step_time_s=0.1)
        ledger.observe_span(_span("rendezvous", 2.0, "rt1", clock()),
                            rank=1)
        exported = ledger.export_state()
        # export must be deterministic (snapshot-dedup contract)
        assert exported == ledger.export_state()

        registry = obs.MetricsRegistry()
        clock2 = FakeClock(clock() + 100.0)
        fresh = GoodputLedger(registry=registry, now_fn=clock2)
        fresh.restore_state(exported)
        snap = fresh.snapshot()
        assert snap["buckets"]["productive"] == pytest.approx(5.0)
        assert snap["buckets"]["rendezvous"] == pytest.approx(2.0)
        assert snap["incarnations"][0]["round"] == 0
        # the outage gap lands in idle (elapsed keeps running)
        assert snap["per_rank"]["0"]["buckets"]["idle"] >= 99.9
        # counters are process-lifetime and must NOT replay restored
        # totals (an in-process restart shares the registry — a replay
        # would double-count; the snapshot carries the cumulative view)
        assert "dlrover_tpu_goodput_seconds_total" not in \
            registry.render().replace(
                "# HELP dlrover_tpu_goodput_seconds_total", "").replace(
                "# TYPE dlrover_tpu_goodput_seconds_total", "")
        # the next world re-formation is attributed to the failover
        fresh.observe_world(1, 2)
        assert fresh.snapshot()["incarnations"][-1]["reason"] \
            == "master_failover"
        # a post-restore report only re-anchors cadence: its delta
        # spans the outage and must not become productive time
        fresh.observe_step_report(0, 1000, step_time_s=0.5)
        assert fresh.snapshot()["buckets"]["productive"] \
            == pytest.approx(5.0)

    def test_master_failover_roundtrip(self, tmp_path):
        """The acceptance shape of PR 3 persistence: drive a master over
        real RPC, restart it from its snapshot lineage, and the ledger +
        FLOPs model survive."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.job_master import JobMaster

        ctx = Context.singleton()
        saved = {k: getattr(ctx, k) for k in
                 ("rpc_timeout_s", "rpc_retries", "master_state_dir")}
        ctx.update(rpc_timeout_s=2.0, rpc_retries=2,
                   master_state_dir=str(tmp_path / "state"))
        try:
            master1 = JobMaster(port=0, min_nodes=1, max_nodes=1,
                                host="127.0.0.1")
            master1.prepare()
            client = MasterClient(master1.addr, node_id=0, node_rank=0)
            try:
                client.join_rendezvous(local_world_size=1)
                client.get_comm_world()
                client.report_model_info(
                    param_count=1000, param_bytes=4000, batch_size=8,
                    seq_len=128, flops_per_token=6000.0,
                    peak_flops_per_chip=1e12, chips=1)
                client.report_global_step(10, step_time_s=0.05,
                                          data_wait_fraction=0.1,
                                          mfu=0.5)
                time.sleep(0.2)
                client.report_global_step(20, step_time_s=0.05,
                                          data_wait_fraction=0.1,
                                          mfu=0.5)
                client.report_telemetry(spans=[_span(
                    "restore_or_init", 0.7, "fo1", time.time())])
                # a mutating RPC snapshots the accrued ledger state
                client.kv_set("flush", b"1")
                before = master1.goodput_ledger.snapshot()
            finally:
                client.close()
            master1.stop(grace_s=0.1)

            master2 = JobMaster(port=0, min_nodes=1, max_nodes=1,
                                host="127.0.0.1")
            master2.prepare()
            client2 = MasterClient(master2.addr, node_id=0, node_rank=0)
            try:
                after = client2.get_goodput()
                assert after["buckets"]["productive"] == pytest.approx(
                    before["buckets"]["productive"], abs=1e-3)
                assert after["buckets"]["restore"] == pytest.approx(0.7)
                assert master2.speed_monitor.export_state()[
                    "flops_per_token"] == 6000.0
            finally:
                client2.close()
            master2.stop(grace_s=0.1)
        finally:
            ctx.update(**saved)


# -- overhead bound ---------------------------------------------------------


class TestLedgerOverhead:
    def test_update_under_one_percent_of_step_time(self):
        """CI bound mirroring the PR 4 timeline bound: the ledger's
        per-report update (one observe_step_report per report interval
        of 10 steps, plus a span batch) must amortize to < 1 % of a
        10 ms CPU-bench step."""
        import statistics

        ledger, clock = _ledger()
        interval = 10
        step_s = 0.010
        report_costs = []
        span_costs = []
        for i in range(200):
            clock.advance(step_s * interval)
            t0 = time.perf_counter()
            ledger.observe_step_report(0, i * interval,
                                       step_time_s=step_s,
                                       data_wait_fraction=0.1, mfu=0.5)
            report_costs.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            ledger.observe_span(_span("rendezvous", 0.01, f"ov{i}",
                                      clock()), rank=0)
            span_costs.append(time.perf_counter() - t0)
        per_step = (statistics.median(report_costs)
                    + statistics.median(span_costs)) / interval
        assert per_step < 0.01 * step_s, (
            f"ledger overhead {per_step * 1e6:.1f}us/step exceeds 1% "
            f"of a {step_s * 1e3:.0f}ms step")


# -- tools ------------------------------------------------------------------


class TestTools:
    def _dump_payload(self):
        ledger, clock = _ledger()
        ledger.observe_world(0, 1)
        ledger.observe_step_report(0, 0, step_time_s=0.1)
        clock.advance(10.0)
        ledger.observe_step_report(0, 80, step_time_s=0.1)
        ledger.observe_span(_span("restore_or_init", 2.0, "t1", clock()),
                            rank=0)
        return {"version": 1, "role": "master", "pid": 1, "host": "h",
                "reason": "test", "dumped_at": clock(),
                "events": [{"kind": "event", "name": "goodput",
                            "ts": clock(),
                            "attrs": {"reason": "master-stop",
                                      "snapshot": ledger.snapshot()}}]}

    def test_render_snapshot_golden(self):
        payload = self._dump_payload()
        snap = snapshot_from_flight(payload)
        out = render_snapshot(snap)
        assert "goodput ledger:" in out
        assert "productive" in out and "restore" in out
        assert "time lost to elasticity events, per incarnation:" in out
        assert "rank    0" in out

    def test_goodput_cli_on_flight_dump(self, tmp_path, capsys):
        path = tmp_path / "flight-master-1.json"
        path.write_text(json.dumps(self._dump_payload()))
        assert _tool("goodput").main(["--flight", str(path)]) == 0
        out = capsys.readouterr().out
        assert "goodput ledger:" in out
        assert "trigger=job_start" in out

    def test_goodput_cli_rebuilds_from_spans(self, tmp_path, capsys):
        """Dumps predating the snapshot event still render, from their
        span records, with the caveat printed."""
        payload = {"version": 1, "events": [
            _span("rendezvous", 1.5, "cli1", 100.0),
            _span("recompile", 2.0, "cli2", 102.0, phase="relower"),
        ]}
        path = tmp_path / "flight-old.json"
        path.write_text(json.dumps(payload))
        assert _tool("goodput").main(["--flight", str(path)]) == 0
        out = capsys.readouterr().out
        assert "rebuilt from spans" in out
        assert "rendezvous" in out

    def test_goodput_cli_no_evidence(self, tmp_path, capsys):
        path = tmp_path / "flight-empty.json"
        path.write_text(json.dumps({"version": 1, "events": []}))
        assert _tool("goodput").main(["--flight", str(path)]) == 2

    def test_diagnose_cli_renders_goodput_section(self, tmp_path,
                                                  capsys):
        path = tmp_path / "flight-master-2.json"
        path.write_text(json.dumps(self._dump_payload()))
        assert _tool("diagnose").main(["--flight", str(path)]) == 0
        out = capsys.readouterr().out
        assert "goodput ledger:" in out

    def test_obs_dump_appends_goodput_section(self, tmp_path, capsys):
        path = tmp_path / "flight-master-3.json"
        path.write_text(json.dumps(self._dump_payload()))
        assert _tool("obs_dump").main([str(path)]) == 0
        out = capsys.readouterr().out
        # the inline row is a one-line summary, the section follows
        assert "goodput_fraction=" in out
        assert "goodput ledger:" in out


# -- acceptance: in-process failover + flight rendering --------------------


class TestAcceptance:
    def test_failover_dump_ledger_and_mfu_exposition(
            self, tmp_path, monkeypatch):
        """ISSUE 8 acceptance: on the in-process failover shape (two
        ranks, steps, a restore span, a drain, a master restart),
        `tools/goodput.py --flight <dump>` renders a ledger whose
        productive + badput buckets account for >= 95 % of the elapsed
        rank wall-clock, and the MFU gauges are present in the
        Prometheus exposition."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.job_master import JobMaster

        flight_dir = tmp_path / "flight"
        monkeypatch.setenv(obs.FLIGHT_DIR_ENV, str(flight_dir))
        ctx = Context.singleton()
        saved = {k: getattr(ctx, k) for k in
                 ("rpc_timeout_s", "rpc_retries", "master_state_dir")}
        ctx.update(rpc_timeout_s=2.0, rpc_retries=2,
                   master_state_dir=str(tmp_path / "state"))
        try:
            master1 = JobMaster(port=0, min_nodes=2, max_nodes=2,
                                host="127.0.0.1")
            master1.prepare()
            c0 = MasterClient(master1.addr, node_id=0, node_rank=0)
            c1 = MasterClient(master1.addr, node_id=1, node_rank=1)
            try:
                c0.join_rendezvous(local_world_size=1)
                c1.join_rendezvous(local_world_size=1)
                c0.get_comm_world()
                c0.report_model_info(
                    param_count=1000, param_bytes=4000, batch_size=8,
                    seq_len=128, flops_per_token=6000.0,
                    peak_flops_per_chip=1e12, chips=2)
                for client, mfu in ((c0, 0.5), (c1, 0.4)):
                    client.report_global_step(
                        10, step_time_s=0.05, data_wait_fraction=0.1,
                        mfu=mfu)
                time.sleep(0.3)
                for client, mfu in ((c0, 0.5), (c1, 0.4)):
                    client.report_global_step(
                        20, step_time_s=0.05, data_wait_fraction=0.1,
                        mfu=mfu)
                c0.report_telemetry(spans=[_span(
                    "restore_or_init", 0.2, "acc1", time.time())])
                c1.report_drain(deadline=time.time() + 5,
                                reason="spot", phase="notice")
                time.sleep(0.1)
                c1.report_drain(deadline=0, phase="complete")
                c0.kv_set("flush", b"1")
            finally:
                c0.close()
                c1.close()
            master1.stop(grace_s=0.1)

            # the restarted master carries the ledger forward
            master2 = JobMaster(port=0, min_nodes=2, max_nodes=2,
                                host="127.0.0.1")
            master2.prepare()
            assert master2.generation == 2
            snap2 = master2.goodput_ledger.snapshot()
            assert snap2["buckets"]["productive"] > 0.0
            assert snap2["buckets"]["drain"] > 0.0
            master2.stop(grace_s=0.1)

            dumps = sorted(flight_dir.glob("flight-*.json"))
            assert dumps, "master stop must leave a flight dump"
            payload = json.loads(dumps[-1].read_text())
            snap = snapshot_from_flight(payload)
            assert snap is not None and not snap.get(
                "rebuilt_from_spans")
            covered = sum(snap["buckets"].values())
            assert covered >= 0.95 * snap["elapsed_rank_seconds"], snap
            # the CLI renders the same dump
            assert _tool("goodput").main(
                ["--flight", str(dumps[-1])]) == 0
            # drain badput attributed per rank + incarnation history
            assert snap["per_rank"]["1"]["buckets"].get("drain", 0) > 0
            assert snap["incarnations"]
            # MFU gauges present in the exposition (the acceptance's
            # Prometheus clause)
            rendered = obs.get_registry().render()
            assert "dlrover_tpu_training_mfu" in rendered
            assert ("dlrover_tpu_training_model_flops_per_token 6000"
                    in rendered)
        finally:
            ctx.update(**saved)


# -- tooling gate -----------------------------------------------------------


def test_graftlint_clean_on_goodput_and_mfu():
    from dlrover_tpu.analysis import run_analysis

    result = run_analysis([
        str(_REPO / "dlrover_tpu" / "obs" / "goodput.py"),
        str(_REPO / "dlrover_tpu" / "obs" / "mfu.py"),
    ])
    assert result.findings == [], [str(f) for f in result.findings]
