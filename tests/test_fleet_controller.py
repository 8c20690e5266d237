"""Fleet controller (ISSUE 18): the diagnosis→actuation loop.

Units run the controller on fakes and an injectable clock (hysteresis,
cooldown, rate limit, rollback quarantine + backoff, claim economics,
shed gating, state roundtrip); satellites cover the warmup task-latency
feed, speed-weighted dispatch (exactly-once coverage, knob-off
byte-identical), the prefetch autotuner, and the tools renderers
(live RPC vs flight payload byte-identical). The in-process acceptance
drill (offer → claim → one-round rejoin → revoke → clean drain, plus
the bad-claim rollback) runs against a real JobMaster under
``@pytest.mark.slow``.
"""

from __future__ import annotations

import importlib.util
import time
from pathlib import Path

import pytest

from dlrover_tpu.brain.fleet_controller import (
    FleetController,
    LocalCapacityProvider,
)
from dlrover_tpu.common.config import Context
from dlrover_tpu.common.constants import TaskType
from dlrover_tpu.common.messages import DatasetShardParams
from dlrover_tpu.master.shard.task_manager import TaskManager
from dlrover_tpu.master.speed_monitor import SpeedMonitor

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


# -- fakes -------------------------------------------------------------------


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def now(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeLedger:
    """window_summary/snapshot/note_elasticity_event, settable."""

    def __init__(self, goodput=0.8):
        self.goodput = goodput
        self.incarnations = []
        self.noted = []

    def window_summary(self, window_s):
        return {"goodput_fraction": self.goodput}

    def snapshot(self, window_s=0.0):
        return {"incarnations": list(self.incarnations),
                "degraded_steps_total": 0}

    def note_elasticity_event(self, kind):
        self.noted.append(kind)


class FakeSteptrace:
    def __init__(self, gating_rank=-1, dcn_wait=-1.0):
        self.gating_rank = gating_rank
        self.dcn_wait = dcn_wait

    def summary(self):
        return {"dominant_gating_rank": self.gating_rank,
                "cross_slice_wait_fraction": self.dcn_wait,
                "dominant_gating_phase": "allreduce"}


class FakeRendezvous:
    def __init__(self, slice_map):
        self.slice_map = dict(slice_map)   # rank -> slice

    def slice_of(self, rank):
        return self.slice_map.get(rank, -1)

    def slice_members(self, sid):
        return [r for r, s in self.slice_map.items() if s == sid]


_KNOBS = dict(
    autoscale_hysteresis_windows=1,
    autoscale_cooldown_s=0.0,
    autoscale_max_decisions_per_hour=100,
    autoscale_rollback_window_s=60.0,
)


@pytest.fixture()
def ctl_ctx():
    ctx = Context.singleton()
    saved = {k: getattr(ctx, k) for k in _KNOBS}
    ctx.update(**_KNOBS)
    yield ctx
    ctx.update(**saved)


def _controller(clock, ledger=None, provider=None, **kw):
    return FleetController(ledger=ledger, provider=provider,
                           now_fn=clock.now, **kw)


def _granting_provider(clock, granted=(1,)):
    provider = LocalCapacityProvider(now_fn=clock.now)
    provider.grant_fn = lambda offer: list(granted)
    return provider


# -- claim economics ---------------------------------------------------------


def test_claim_refused_without_goodput_evidence(ctl_ctx):
    clock = FakeClock()
    ledger = FakeLedger(goodput=-1.0)   # no measured window yet
    provider = _granting_provider(clock)
    ctl = _controller(clock, ledger, provider)
    provider.offer(slices=1, ttl_s=600.0)
    assert ctl.evaluate_once() is None  # claiming blind is refused


def test_claim_refused_below_margin(ctl_ctx):
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.9)
    provider = _granting_provider(clock)
    ctl = _controller(clock, ledger, provider)
    # gain = 30 × 0.9 = 27s < 1.2 × 45s default cost
    provider.offer(slices=1, ttl_s=30.0)
    assert ctl.evaluate_once() is None


def test_claim_actuates_and_prices_under_autoscale(ctl_ctx):
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.9)
    provider = _granting_provider(clock, granted=(2,))
    ctl = _controller(clock, ledger, provider)
    provider.offer(slices=1, ttl_s=600.0)
    record = ctl.evaluate_once()
    assert record["kind"] == "claim"
    assert record["outcome"] == "pending"
    assert record["evidence"]["granted"] == [2]
    # the next world re-formation is attributed to the autoscale kind
    assert ledger.noted == ["autoscale"]
    assert not provider.open_offers()


def test_claim_cost_learned_from_ledger_incarnations(ctl_ctx):
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.9)
    # measured join+re-plan badput: 500s mean — the same 600s offer
    # that passes on the 45s prior must now fail the margin test
    ledger.incarnations = [
        {"reason": "replan", "badput": 450.0},
        {"reason": "autoscale", "badput": 550.0},
    ]
    provider = _granting_provider(clock)
    ctl = _controller(clock, ledger, provider)
    provider.offer(slices=1, ttl_s=600.0)   # gain 540 < 1.2 × 500
    assert ctl.evaluate_once() is None


# -- guardrails --------------------------------------------------------------


def test_hysteresis_requires_consecutive_windows(ctl_ctx):
    Context.singleton().update(autoscale_hysteresis_windows=2)
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.9)
    provider = _granting_provider(clock)
    ctl = _controller(clock, ledger, provider)
    provider.offer(slices=1, ttl_s=600.0)
    first = ctl.evaluate_once()
    assert first["kind"] == "hold"
    assert "hysteresis" in first["reason"]
    second = ctl.evaluate_once()
    assert second["kind"] == "claim"


def test_hysteresis_resets_when_candidate_vanishes(ctl_ctx):
    Context.singleton().update(autoscale_hysteresis_windows=2)
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.9)
    provider = _granting_provider(clock)
    ctl = _controller(clock, ledger, provider)
    offer = provider.offer(slices=1, ttl_s=600.0)
    assert ctl.evaluate_once()["kind"] == "hold"
    assert provider.claim(offer.offer_id) is not None  # offer taken away
    assert ctl.evaluate_once() is None                 # no candidate
    provider.offer(slices=1, ttl_s=600.0)
    # the count restarted: consecutive means consecutive
    assert ctl.evaluate_once()["kind"] == "hold"


def test_cooldown_blocks_back_to_back_actuations(ctl_ctx):
    Context.singleton().update(autoscale_cooldown_s=120.0,
                               autoscale_rollback_window_s=10.0)
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.9)
    provider = _granting_provider(clock)
    ctl = _controller(clock, ledger, provider)
    provider.offer(slices=1, ttl_s=600.0)
    assert ctl.evaluate_once()["kind"] == "claim"
    # past the watch window (goodput stable → watch resolves ok) but
    # inside the cooldown
    clock.advance(30.0)
    provider.offer(slices=1, ttl_s=600.0)
    held = ctl.evaluate_once()
    assert held["kind"] == "hold" and "cooldown" in held["reason"]
    clock.advance(120.0)
    assert ctl.evaluate_once()["kind"] == "claim"


def test_hourly_rate_limit(ctl_ctx):
    Context.singleton().update(autoscale_max_decisions_per_hour=2,
                               autoscale_rollback_window_s=1.0)
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.9)
    provider = _granting_provider(clock)
    ctl = _controller(clock, ledger, provider)
    for _ in range(2):
        provider.offer(slices=1, ttl_s=600.0)
        assert ctl.evaluate_once()["kind"] == "claim"
        clock.advance(10.0)   # resolves the watch, cooldown is 0
        assert ctl.evaluate_once() is None
    provider.offer(slices=1, ttl_s=600.0)
    held = ctl.evaluate_once()
    assert held["kind"] == "hold" and "rate limit" in held["reason"]
    clock.advance(3600.0)   # the hour rolls over (old offers expired)
    provider.offer(slices=1, ttl_s=600.0)
    assert ctl.evaluate_once()["kind"] == "claim"


def test_watchdog_window_blocks_new_actuations(ctl_ctx):
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.9)
    provider = _granting_provider(clock)
    ctl = _controller(clock, ledger, provider)
    provider.offer(slices=1, ttl_s=600.0)
    assert ctl.evaluate_once()["kind"] == "claim"
    provider.offer(slices=1, ttl_s=600.0)
    held = ctl.evaluate_once()   # watch still open: one experiment at a time
    assert held["kind"] == "hold" and "watchdog" in held["reason"]


# -- rollback watchdog -------------------------------------------------------


def test_rollback_reverts_quarantines_and_backs_off(ctl_ctx):
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.8)
    provider = _granting_provider(clock, granted=(3,))
    rdzv = FakeRendezvous({0: 0, 7: 3})   # slice 3 = the claimed one
    shed_calls = []
    ctl = _controller(clock, ledger, provider, rendezvous=rdzv)
    ctl.shed_sink = lambda rank, deadline, reason: \
        shed_calls.append((rank, reason))

    provider.offer(slices=1, ttl_s=600.0)
    claim = ctl.evaluate_once()
    assert claim["kind"] == "claim"
    # the claim made things worse: goodput collapses past the 20% drop
    ledger.goodput = 0.5
    clock.advance(61.0)
    rollback = ctl.evaluate_once()
    assert rollback["kind"] == "rollback"
    assert rollback["evidence"]["quarantine_level"] == 1
    assert rollback["evidence"]["reverted"] == [3]
    # the revert shed the claimed slice through the drain chain
    assert shed_calls and shed_calls[0][0] == 7
    assert "rollback" in shed_calls[0][1]
    status = ctl.status()
    assert status["quarantine"]["claim"]["level"] == 1
    by_id = {d["id"]: d for d in status["decisions"]}
    assert by_id[claim["id"]]["outcome"] == "rolled_back"

    # quarantined: the same candidate is held
    provider.offer(slices=1, ttl_s=600.0)
    ledger.goodput = 0.8
    held = ctl.evaluate_once()
    assert held["kind"] == "hold" and "quarantined" in held["reason"]

    # after the backoff: a second failure doubles the quarantine
    clock.advance(601.0)
    provider.offer(slices=1, ttl_s=600.0)   # the earlier offer expired
    assert ctl.evaluate_once()["kind"] == "claim"
    ledger.goodput = 0.5
    clock.advance(61.0)
    second = ctl.evaluate_once()
    assert second["evidence"]["quarantine_level"] == 2
    assert second["evidence"]["quarantine_s"] == pytest.approx(1200.0)


def test_watch_resolving_ok_resets_quarantine_level(ctl_ctx):
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.8)
    provider = _granting_provider(clock)
    ctl = _controller(clock, ledger, provider)
    provider.offer(slices=1, ttl_s=600.0)
    claim = ctl.evaluate_once()
    clock.advance(61.0)   # goodput held: the actuation was good
    assert ctl.evaluate_once() is None
    status = ctl.status()
    assert status["quarantine"] == {}
    by_id = {d["id"]: d for d in status["decisions"]}
    assert by_id[claim["id"]]["outcome"] == "ok"


def test_market_revocation_cancels_watch_without_penalty(ctl_ctx):
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.8)
    provider = _granting_provider(clock, granted=(5,))
    ctl = _controller(clock, ledger, provider)
    provider.offer(slices=1, ttl_s=600.0)
    claim = ctl.evaluate_once()
    assert claim["kind"] == "claim"
    # the market takes the slice back while the claim is on watch
    provider.revoke(5, grace_s=10.0)
    ledger.goodput = 0.1   # the dip is the market's doing
    clock.advance(61.0)
    assert ctl.evaluate_once() is None
    status = ctl.status()
    assert status["quarantine"] == {}
    by_id = {d["id"]: d for d in status["decisions"]}
    assert by_id[claim["id"]]["outcome"] == "revoked"


# -- shed --------------------------------------------------------------------


def test_shed_requires_gating_and_dcn_wait(ctl_ctx):
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.8)
    rdzv = FakeRendezvous({0: 0, 1: 0, 2: 1, 3: 1})
    shed_calls = []
    # gating rank but calm DCN: no candidate
    ctl = _controller(clock, ledger,
                      steptrace=FakeSteptrace(gating_rank=2,
                                              dcn_wait=0.1),
                      rendezvous=rdzv)
    assert ctl.evaluate_once() is None
    # gating rank AND hot DCN wait: shed its slice
    ctl = _controller(clock, ledger,
                      steptrace=FakeSteptrace(gating_rank=2,
                                              dcn_wait=0.5),
                      rendezvous=rdzv)
    ctl.shed_sink = lambda rank, deadline, reason: \
        shed_calls.append(rank)
    record = ctl.evaluate_once()
    assert record["kind"] == "shed"
    assert record["evidence"]["slice"] == 1
    assert shed_calls == [2]   # notice lands on the slice's first member


def test_shed_never_fires_on_single_slice_fleet(ctl_ctx):
    clock = FakeClock()
    ctl = _controller(clock, FakeLedger(goodput=0.8),
                      steptrace=FakeSteptrace(gating_rank=1,
                                              dcn_wait=0.9),
                      rendezvous=FakeRendezvous({0: 0, 1: 0}))
    assert ctl.evaluate_once() is None


# -- state roundtrip ---------------------------------------------------------


def test_state_roundtrip_preserves_guardrails(ctl_ctx):
    Context.singleton().update(autoscale_cooldown_s=300.0)
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.9)
    provider = _granting_provider(clock)
    ctl = _controller(clock, ledger, provider)
    provider.offer(slices=1, ttl_s=600.0)
    assert ctl.evaluate_once()["kind"] == "claim"
    state = ctl.export_state()

    # a promoted standby restores on the same wall clock
    heir = _controller(clock, ledger,
                       _granting_provider(clock))
    heir.restore_state(state)
    assert heir.export_state() == state
    # ...and inherits the open watch + cooldown: a flapping master
    # must not double-actuate
    heir._provider.offer(slices=1, ttl_s=600.0)
    held = heir.evaluate_once()
    assert held["kind"] == "hold" and "watchdog" in held["reason"]
    # decision ids keep counting instead of colliding
    assert held["id"] > state["decisions"][-1]["id"]


# -- warmup task-latency feed (regression) -----------------------------------


def test_task_latency_scores_ranks_before_any_step_report():
    monitor = SpeedMonitor()
    for _ in range(4):
        monitor.collect_task_latency(0, latency_s=1.0, records=100)
        monitor.collect_task_latency(1, latency_s=3.0, records=100)
    scores = monitor.relative_speeds()
    # two task-only ranks scored against their class median rate:
    # rates 100/s and 33.3/s, median 66.7 → 1.5 / 0.5
    assert scores[0] == pytest.approx(1.5)
    assert scores[1] == pytest.approx(0.5)


def test_step_evidence_owns_the_rank_over_task_latency():
    monitor = SpeedMonitor()
    monitor.collect_task_latency(0, latency_s=9.0, records=10)
    monitor.collect_worker_step(0, step=10, step_time_s=1.0)
    monitor.collect_worker_step(1, step=10, step_time_s=2.0)
    scores = monitor.relative_speeds()
    # rank 0 has step timing: its (terrible) shard latency is ignored
    # — a shard fetch and a training step are not the same second
    assert scores[0] == pytest.approx(1.5)
    assert scores[1] == pytest.approx(0.75)


def test_report_dataset_task_feeds_the_monitor():
    manager = TaskManager()
    manager.speed_monitor = SpeedMonitor()
    manager.new_dataset(DatasetShardParams(
        dataset_name="warmup", dataset_size=8, shard_size=2,
        num_epochs=1, task_type=TaskType.TRAINING))
    task = manager.get_dataset_task(0, "warmup")
    assert not task.is_empty
    time.sleep(0.01)
    assert manager.report_dataset_task("warmup", task.task_id, True)
    # the completion latency reached the monitor: the rank is scored
    # from its first shard, before any step report exists
    assert manager.speed_monitor.relative_speeds() == {
        0: pytest.approx(1.0)}


# -- speed-weighted dispatch -------------------------------------------------


_DISPATCH_KNOBS = dict(dispatch_speed_weighted=True)


@pytest.fixture()
def dispatch_ctx():
    ctx = Context.singleton()
    saved = {k: getattr(ctx, k) for k in _DISPATCH_KNOBS}
    ctx.update(**_DISPATCH_KNOBS)
    yield ctx
    ctx.update(**saved)


def _speed_pair_manager(slow_factor=3.0):
    manager = TaskManager()
    manager.speed_monitor = SpeedMonitor()
    for _ in range(4):
        manager.speed_monitor.collect_task_latency(
            0, latency_s=1.0, records=100)
        manager.speed_monitor.collect_task_latency(
            1, latency_s=slow_factor, records=100)
    manager.new_dataset(DatasetShardParams(
        dataset_name="d", dataset_size=24, shard_size=1,
        num_epochs=1, task_type=TaskType.TRAINING))
    return manager


def test_slow_rank_gets_fewer_shards_per_window(dispatch_ctx):
    manager = _speed_pair_manager()
    served = {0: [], 1: []}
    for _ in range(12):
        for rank in (0, 1):
            task = manager.get_dataset_task(rank, "d")
            if task.task_type != TaskType.WAIT and not task.is_empty:
                served[rank].append(task)
    # the 3×-slow rank is paced to its weight (0.5 here), the fast
    # rank never waits
    assert len(served[0]) == 12
    assert len(served[1]) == 6


def test_dispatch_coverage_stays_exactly_once(dispatch_ctx):
    manager = _speed_pair_manager()
    shards = []
    for _ in range(200):
        for rank in (0, 1):
            task = manager.get_dataset_task(rank, "d")
            if task.task_type == TaskType.WAIT or task.is_empty:
                continue
            shards.append((task.shard.start, task.shard.end))
            manager.report_dataset_task("d", task.task_id, True)
        if manager.finished():
            break
    assert manager.finished()
    # a deferral delays a pop, never duplicates or drops one
    assert sorted(shards) == [(i, i + 1) for i in range(24)]


def test_dispatch_knob_off_is_byte_identical(dispatch_ctx):
    Context.singleton().update(dispatch_speed_weighted=False)
    weighted = _speed_pair_manager()     # evidence present, knob off
    control = TaskManager()              # no monitor at all
    control.new_dataset(DatasetShardParams(
        dataset_name="d", dataset_size=24, shard_size=1,
        num_epochs=1, task_type=TaskType.TRAINING))
    seq_weighted, seq_control = [], []
    for _ in range(12):
        for rank in (0, 1):
            for manager, seq in ((weighted, seq_weighted),
                                 (control, seq_control)):
                task = manager.get_dataset_task(rank, "d")
                seq.append((task.task_id, task.task_type,
                            task.shard.start, task.shard.end))
    assert seq_weighted == seq_control


def test_dispatch_needs_a_pack_to_pace_against(dispatch_ctx):
    manager = TaskManager()
    manager.speed_monitor = SpeedMonitor()
    manager.speed_monitor.collect_task_latency(
        0, latency_s=5.0, records=1)   # one lonely (slow) rank
    manager.new_dataset(DatasetShardParams(
        dataset_name="d", dataset_size=4, shard_size=1,
        num_epochs=1, task_type=TaskType.TRAINING))
    for _ in range(4):
        task = manager.get_dataset_task(0, "d")
        assert task.task_type != TaskType.WAIT and not task.is_empty


# -- prefetch autotune -------------------------------------------------------


_TUNE_KNOBS = dict(prefetch_autotune=True)


@pytest.fixture()
def tune_ctx():
    ctx = Context.singleton()
    saved = {k: getattr(ctx, k) for k in _TUNE_KNOBS}
    ctx.update(**_TUNE_KNOBS)
    yield ctx
    ctx.update(**saved)


def test_prefetch_tuner_grows_shrinks_with_dead_band(tune_ctx):
    from dlrover_tpu.data.prefetch import PrefetchAutoTuner

    tuner = PrefetchAutoTuner(depth=1)
    assert tuner.depth == 1
    tuner.observe(0.5)            # starving: grow immediately
    tuner.observe(0.5)
    assert tuner.depth == 3
    tuner.observe(0.1)            # dead band: neither grow nor shrink
    assert tuner.depth == 3
    tuner.observe(0.01)           # calm window 1 of 2
    assert tuner.depth == 3
    tuner.observe(0.01)           # calm window 2: shrink
    assert tuner.depth == 2
    tuner.observe(-1.0)           # no evidence: no change
    assert tuner.depth == 2
    for _ in range(20):
        tuner.observe(0.9)
    assert tuner.depth == 8       # clamped at PREFETCH_DEPTH_MAX
    assert tuner.ring_capacity(base_capacity=64) == 64 * 4


# -- tools renderers (live vs flight byte-identical) -------------------------


def _status_fixture(ctl_ctx):
    clock = FakeClock()
    ledger = FakeLedger(goodput=0.9)
    provider = _granting_provider(clock, granted=(3,))
    ctl = _controller(clock, ledger, provider)
    provider.offer(slices=1, ttl_s=600.0)
    ctl.evaluate_once()            # claim
    ledger.goodput = 0.4
    clock.advance(61.0)
    ctl.evaluate_once()            # rollback + quarantine
    provider.offer(slices=2, ttl_s=120.0)
    ctl.evaluate_once()            # hold (quarantined), offer stays open
    return ctl.status()


def test_render_autoscale_live_equals_flight(ctl_ctx):
    status = _status_fixture(ctl_ctx)
    diagnose = _tool("diagnose")
    flight = {"events": [
        {"kind": "event", "name": "autoscale",
         "attrs": {"status": status}},
    ]}
    live = diagnose.render_autoscale(status)
    postmortem = diagnose.render_autoscale(
        diagnose.autoscale_from_flight(flight))
    assert live == postmortem
    assert "claim" in live and "rollback" in live
    assert "quarantined: claim" in live
    assert "open offer" in live
    assert diagnose.render_autoscale({}) == \
        "autoscale controller: no evidence"


def test_top_autoscale_panel_live_equals_flight(ctl_ctx):
    status = _status_fixture(ctl_ctx)
    top = _tool("top")
    live = top.render_autoscale_panel({"autoscale": status})
    postmortem = top.render_autoscale_panel({"autoscale": status})
    assert live == postmortem
    joined = "\n".join(live)
    assert "fleet controller (3 decisions)" in joined
    assert "cost=" in joined       # the priced claim evidence renders
    assert "quarantined claim" in joined
    assert top.render_autoscale_panel({}) == [
        "== fleet controller (0 decisions)",
        "  (controller disabled / no evidence)"]


# -- in-process acceptance (real JobMaster) ----------------------------------


def _wait_world(client, size, timeout_s=15.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        _, _, world = client.get_comm_world()
        if world and len(world) >= size:
            return world
        time.sleep(0.02)
    raise TimeoutError(f"world of {size} never formed")


_ACCEPT_KNOBS = dict(
    fleet_controller_enabled=True,
    autoscale_hysteresis_windows=1,
    autoscale_cooldown_s=0.0,
    autoscale_max_decisions_per_hour=100,
    goodput_window_s=30.0,
)


@pytest.fixture()
def accept_ctx():
    ctx = Context.singleton()
    saved = {k: getattr(ctx, k) for k in _ACCEPT_KNOBS}
    ctx.update(**_ACCEPT_KNOBS)
    yield ctx
    ctx.update(**saved)


@pytest.mark.slow
def test_acceptance_offer_claim_rejoin_revoke_drain(accept_ctx):
    """The whole loop against a live master: a chaos-shaped offer is
    claimed (grant joins a second node in one round), the market
    revokes it, the slice drains through the PR 5 path, and every
    transition is priced in the ledger + on the flight record."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.master.job_master import JobMaster
    from dlrover_tpu import obs

    master = JobMaster(port=0, min_nodes=1, max_nodes=2,
                       host="127.0.0.1")
    master.prepare()
    c0 = MasterClient(master.addr, node_id=0, node_rank=0)
    c1_holder = {}
    try:
        c0.join_rendezvous(local_world_size=1)
        _wait_world(c0, 1)
        for step in range(1, 7):   # the economics need measured goodput
            c0.report_global_step(step, step_time_s=0.02,
                                  data_wait_fraction=0.05)
            time.sleep(0.02)

        def grant(offer):
            c1 = MasterClient(master.addr, node_id=1, node_rank=1)
            c1.join_rendezvous(local_world_size=1)
            c0.join_rendezvous(local_world_size=1)
            _wait_world(c0, 2)
            c1_holder["c1"] = c1
            return [1]

        provider = master.capacity_provider
        provider.grant_fn = grant
        provider.offer(slices=1, ttl_s=600.0, step=6)
        record = master.fleet_controller.evaluate_once()
        assert record["kind"] == "claim"
        assert c1_holder and len(_wait_world(c0, 2)) == 2

        c1 = c1_holder["c1"]
        for step in range(7, 12):
            c0.report_global_step(step, step_time_s=0.02)
            c1.report_global_step(step, step_time_s=0.02)
            time.sleep(0.02)

        # the market takes it back: books through the provider AND
        # drains through the ordinary preemption path
        provider.revoke(1, grace_s=2.0, step=11)
        c1.report_drain(deadline=time.time() + 2.0,
                        reason="capacity revoked", phase="notice")
        time.sleep(0.05)
        c1.report_drain(deadline=0, phase="complete")
        c1.close()
        c1_holder.clear()
        c0.join_rendezvous(local_world_size=1)
        assert len(_wait_world(c0, 1)) >= 1

        # every transition priced in the ledger under its own kind
        reasons = [inc.get("reason") for inc in
                   master.goodput_ledger.snapshot()["incarnations"]]
        assert "autoscale" in reasons
        assert "drain" in reasons

        # the claim's watch was cancelled by the revocation, no penalty
        status = master.fleet_controller.status()
        by_kind = {d["kind"]: d for d in status["decisions"]}
        assert by_kind["claim"]["outcome"] == "revoked"
        assert status["quarantine"] == {}

        events = [e.get("name") for e in
                  obs.get_flight_recorder().snapshot()]
        for name in ("capacity_offer", "autoscale_decision",
                     "capacity_revoke"):
            assert name in events, f"missing flight event {name}"
    finally:
        c1 = c1_holder.get("c1")
        if c1 is not None:
            c1.close()
        c0.close()
        master.stop(grace_s=0.1)


@pytest.mark.slow
def test_acceptance_bad_claim_rolls_back(accept_ctx):
    """A claim whose capacity never materializes: the goodput window
    collapses during the watch, the watchdog reverts and quarantines
    the class — asserted from the live status and the flight events."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.master.job_master import JobMaster
    from dlrover_tpu import obs

    ctx = Context.singleton()
    saved = {k: getattr(ctx, k) for k in
             ("autoscale_rollback_window_s", "goodput_window_s")}
    ctx.update(autoscale_rollback_window_s=0.3, goodput_window_s=1.0)
    master = JobMaster(port=0, min_nodes=1, max_nodes=2,
                       host="127.0.0.1")
    master.prepare()
    c0 = MasterClient(master.addr, node_id=0, node_rank=0)
    try:
        c0.join_rendezvous(local_world_size=1)
        _wait_world(c0, 1)
        for step in range(1, 9):
            c0.report_global_step(step, step_time_s=0.02,
                                  data_wait_fraction=0.05)
            time.sleep(0.02)

        provider = master.capacity_provider
        provider.grant_fn = lambda offer: [1]   # promises, delivers nothing
        provider.offer(slices=1, ttl_s=600.0, step=8)
        record = master.fleet_controller.evaluate_once()
        assert record["kind"] == "claim"

        # the fleet goes idle through the watch window: the windowed
        # goodput fraction collapses well past the drop threshold
        time.sleep(0.8)
        rollback = master.fleet_controller.evaluate_once()
        assert rollback is not None and rollback["kind"] == "rollback"

        status = master.fleet_controller.status()
        assert status["quarantine"]["claim"]["level"] == 1
        by_kind = {d["kind"]: d for d in status["decisions"]}
        assert by_kind["claim"]["outcome"] == "rolled_back"
        events = [e.get("name") for e in
                  obs.get_flight_recorder().snapshot()]
        assert "autoscale_rollback" in events
    finally:
        c0.close()
        master.stop(grace_s=0.1)
        ctx.update(**saved)


@pytest.mark.slow
def test_bench_controller_on_beats_controller_off():
    """Chaos-churn acceptance (ISSUE 18): on the same scripted
    offer/revoke/straggler schedule the controller-on fleet produces at
    least the controller-off goodput — both asserted from the master's
    own ledger — and the claim is priced under ``autoscale``."""
    import bench_autoscale

    result = bench_autoscale.run_bench(smoke=True)
    on, off = result["controller_on"], result["controller_off"]
    assert result["value"] >= 1.0, result
    assert on["goodput_rate"] >= off["goodput_rate"], result
    assert on["world_peak"] == 2
    assert "autoscale" in on["incarnation_reasons"]
    kinds = [d["kind"] for d in on["decision_history"]]
    assert "claim" in kinds
    # the off leg saw the identical offer but nothing claimed it
    assert off["world_peak"] == 1
    assert off["decision_history"] == []
