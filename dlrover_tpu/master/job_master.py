"""Job master: composition root + serving loop.

Capability parity: dlrover/python/master/local_master.py:38 (LocalJobMaster)
and dist_master.py:53 (DistributedJobMaster composition :62-71, 30 s watch
loop :165-222). The master owns every control-plane component and runs the
gRPC service; `prepare()` starts serving, `run()` polls for job completion /
hang; the node manager (when attached) owns node lifecycle.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from dlrover_tpu import obs
from dlrover_tpu.common.comm import build_server
from dlrover_tpu.common.config import Context
from dlrover_tpu.common.constants import (
    DefaultValues,
    JobStage,
    NodeType,
    RendezvousName,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.master.kv_store import KVStoreService
from dlrover_tpu.master.state_backend import MasterStateBackend, MutationLog
from dlrover_tpu.master.rendezvous import (
    ElasticTrainingRendezvousManager,
    NetworkCheckRendezvousManager,
    RendezvousParameters,
)
from dlrover_tpu.master.rendezvous_shards import ShardedRendezvousManager
from dlrover_tpu.master.servicer import MasterServicer
from dlrover_tpu.master.shard.task_manager import TaskManager
from dlrover_tpu.master.speed_monitor import SpeedMonitor
from dlrover_tpu.master.sync_service import ElasticPsService, SyncService


class JobMaster:
    """One instance per job. With no node manager attached this is the
    standalone/local master (the `dlrover-run --standalone` equivalent)."""

    def __init__(
        self,
        port: int = 0,
        min_nodes: int = 1,
        max_nodes: int = 1,
        node_unit: int = 1,
        job_manager=None,
        job_args=None,
        cluster=None,
        host: str = "0.0.0.0",
        brain_addr: str = "",
        state_dir: Optional[str] = None,
        preloaded_state: Optional[tuple] = None,
    ):
        ctx = Context.singleton()
        params = RendezvousParameters(
            min_nodes=min_nodes,
            max_nodes=max_nodes,
            wait_new_node_s=DefaultValues.RDZV_WAIT_NEW_NODE_S,
            node_unit=node_unit,
        )
        self.task_manager = TaskManager()
        self.speed_monitor = SpeedMonitor()
        self.task_manager.speed_monitor = self.speed_monitor
        # sharded by default: per-slice rendezvous shards behind a thin
        # router, so one slice's join storm (or a wedged shard) can
        # never delay another slice's cut (rendezvous_shards.py).
        # rdzv_sharded=False keeps the single-lock manager — the bench
        # baseline and an escape hatch.
        training_mgr = (ShardedRendezvousManager(params)
                        if ctx.rdzv_sharded
                        else ElasticTrainingRendezvousManager(params))
        self.rdzv_managers = {
            RendezvousName.TRAINING: training_mgr,
            RendezvousName.NETWORK_CHECK:
                NetworkCheckRendezvousManager(
                    RendezvousParameters(
                        min_nodes, max_nodes,
                        DefaultValues.RDZV_WAIT_NEW_NODE_S)
                ),
        }
        self.kv_store = KVStoreService(
            keep_generations=DefaultValues.KV_GC_KEEP_GENERATIONS)
        self.sync_service = SyncService(expected_workers=min_nodes)
        self.elastic_ps_service = ElasticPsService()
        self.job_manager = job_manager
        # the goodput ledger classifies every rank-second of the job
        # (obs/goodput.py); fed by the servicer, persisted with the
        # control-plane state, queried over RPC by tools/goodput.py
        self.goodput_ledger = obs.GoodputLedger()
        # the fleet time-series plane (obs/tsdb.py): bounded multi-
        # resolution history of the gauges/goodput/device truth, served
        # over TimeSeriesQuery and rendered by tools/top.py; the
        # collector (built with the state backend below — its sidecar
        # lives in the state dir) samples + persists it
        self.tsdb = obs.TimeSeriesStore()
        self.tsdb_collector = None
        # planner prediction <-> measurement calibration
        # (parallel/calibration.py): stamped plans register their
        # predicted step time, worker step reports register measured,
        # learned per-axis discounts feed back into planner scoring
        from dlrover_tpu.parallel.calibration import PlanCalibration

        self.plan_calibration = PlanCalibration()
        # per-step critical-path assembly (master/steptrace.py): joins
        # worker trace records, feeds the tsdb gating series, evidences
        # CriticalPathRule, serves tools/steptrace.py + the flight embed
        from dlrover_tpu.master.steptrace import StepTraceAssembler

        self.steptrace = StepTraceAssembler(tsdb=self.tsdb)
        from dlrover_tpu.master.diagnosis import DiagnosisManager

        self.diagnosis_manager = DiagnosisManager(
            self.speed_monitor,
            goodput_ledger=self.goodput_ledger,
            plan_calibration=self.plan_calibration,
            steptrace=self.steptrace)
        # the goodput-optimal fleet controller
        # (brain/fleet_controller.py): closes the diagnosis→actuation
        # loop — claims offered preemptible slices, sheds gating ones,
        # holds behind guardrails. Gated on its OWN knob: node-count
        # autoscaling (JobAutoScaler) acts on a different layer.
        self.capacity_provider = None
        self.fleet_controller = None
        if ctx.fleet_controller_enabled:
            from dlrover_tpu.brain.fleet_controller import (
                FleetController,
                LocalCapacityProvider,
            )

            self.capacity_provider = LocalCapacityProvider()
            self.fleet_controller = FleetController(
                ledger=self.goodput_ledger,
                speed_monitor=self.speed_monitor,
                steptrace=self.steptrace,
                plan_calibration=self.plan_calibration,
                rendezvous=training_mgr,
                diagnosis=self.diagnosis_manager,
                provider=self.capacity_provider)
        self.servicer = MasterServicer(
            task_manager=self.task_manager,
            rdzv_managers=self.rdzv_managers,
            kv_store=self.kv_store,
            speed_monitor=self.speed_monitor,
            sync_service=self.sync_service,
            elastic_ps_service=self.elastic_ps_service,
            job_manager=job_manager,
            diagnosis_manager=self.diagnosis_manager,
            goodput_ledger=self.goodput_ledger,
            tsdb=self.tsdb,
            plan_calibration=self.plan_calibration,
            steptrace=self.steptrace,
            fleet_controller=self.fleet_controller,
        )
        if self.fleet_controller is not None:
            # a shed actuates through the EXISTING slice-unit drain
            # chain (the servicer's notice-phase handler)
            self.fleet_controller.shed_sink = self._controller_shed
        # learned-discount feedback rides the diagnosis cadence, not the
        # per-report hot path (the medians only move as samples
        # accumulate)
        self.diagnosis_manager.discount_sink = \
            self.servicer.push_axis_discounts
        self._host = host
        self._server, self.port = build_server(
            self.servicer.get_bytes, self.servicer.report_bytes,
            port=port, host=host,
        )
        self._init_coord_tier(host)
        self._stopped = threading.Event()
        self._exit_reason = ""
        self.metric_collector = None
        self.auto_scaler = None
        self._metrics_server = None
        self.metrics_port = 0
        if job_manager is None and job_args is not None:
            from dlrover_tpu.master.node.event_callback import (
                PsFailoverCallback,
                RendezvousMembershipCallback,
                TaskRescheduleCallback,
            )
            from dlrover_tpu.master.node.job_manager import create_job_manager

            manager = create_job_manager(
                job_args, master_addr=self.addr,
                speed_monitor=self.speed_monitor, cluster=cluster)
            manager.add_event_callback(
                TaskRescheduleCallback(self.task_manager))
            manager.add_event_callback(
                RendezvousMembershipCallback(
                    self.rdzv_managers, self.speed_monitor,
                    diagnosis_manager=self.diagnosis_manager))
            manager.add_event_callback(
                PsFailoverCallback(self.elastic_ps_service))
            self.job_manager = manager
            self.servicer.job_manager = manager
            self._attach_optimization(job_args, brain_addr)
        self._init_state_backend(
            state_dir if state_dir is not None else ctx.master_state_dir,
            preloaded_state=preloaded_state,
        )
        self._arm_master_chaos()

    # -- the coordination tier (master/coord_service.py) ----------------
    def _init_coord_tier(self, host: str) -> None:
        """Bind the KV/coordination tier on its own port + thread pool
        so a join/telemetry storm on the control tier can never stall a
        step's dcn/ exchange (coord_port -1 = single-tier: the main
        servicer answers everything, as it always has)."""
        from dlrover_tpu.master.coord_service import CoordServicer

        self._coord_server = None
        self.coord_port = 0
        port = Context.singleton().coord_port
        if port < 0:
            return
        self.coord_servicer = CoordServicer(
            self.kv_store,
            rdzv_manager=self.rdzv_managers[RendezvousName.TRAINING],
            speed_monitor=self.speed_monitor)
        try:
            # a full-width pool: blocked KVWaits hold threads, and the
            # tier must keep answering per-step gets through a world
            # formation's wait pile-up
            self._coord_server, self.coord_port = build_server(
                self.coord_servicer.get_bytes,
                self.coord_servicer.report_bytes,
                port=port, host=host, max_workers=64)
        except RuntimeError as e:
            logger.warning("coordination tier failed to bind: %s "
                           "(serving coordination on the main port)", e)
            self._coord_server = None
            return
        self.servicer.coord_addr = self.coord_addr

    # -- crash-consistent control-plane state --------------------------
    def _init_state_backend(self, state_dir: str,
                            preloaded_state: Optional[tuple] = None
                            ) -> None:
        """Attach the snapshot store and, when a prior master left valid
        state behind, rebuild every manager from it BEFORE serving. The
        generation token bumps once per (re)start over one state lineage
        so reconnecting agents can tell a restarted master from a
        transient outage. ``preloaded_state`` is the hot standby's warm
        copy — promotion skips the disk read it already did.

        The hot-key mutation log is replayed OVER the snapshot: the
        dcn/ and coord/ keys deliberately do not trigger snapshots, so
        their last values live in the log (state_backend.MutationLog)."""
        self._snapshot_lock = threading.Lock()
        self._state_backend = None
        self._mutation_log = None
        self._last_snapshot_ts = 0.0
        # double-primary fencing extends to the STATE DIR: once a
        # higher-generation master owns the bootstrap file, this one
        # must stop writing snapshots + mutation-log appends into the
        # shared lineage (interleaved writers would corrupt the log and
        # let a stale later-versioned snapshot win the next restore)
        with self._snapshot_lock:
            self._fenced = False
            self._last_fence_check = 0.0
            self._snapshot_timer: Optional[threading.Timer] = None
        self.generation = 0
        if state_dir:
            self._state_backend = MasterStateBackend(
                state_dir, retain=DefaultValues.MASTER_SNAPSHOT_RETAIN)
            # snapshots stop the moment a higher-generation master owns
            # the lineage.  The gate reads the latched flag, NOT
            # _check_fenced: backend saves run under _snapshot_lock,
            # which _check_fenced itself acquires (the deep bootstrap
            # probe already ran at _maybe_snapshot entry, pre-lock).
            # Lock-free read is safe: _fenced only ever goes False→True
            self._state_backend.gate = (
                lambda: self._fenced)  # graftlint: disable=GL201
            self.generation = 1
            loaded = (preloaded_state if preloaded_state is not None
                      else self._state_backend.load_latest())
            if loaded is not None:
                state, version = loaded
                with obs.span("master_restore",
                              {"snapshot_version": version,
                               "preloaded": preloaded_state
                               is not None}):
                    self._restore_state(state)
                    replayed = self.kv_store.replay_mutations(
                        MutationLog.read(state_dir))
                logger.info(
                    "master state restored from snapshot v%d "
                    "(generation %d, %d hot mutations replayed)",
                    version, self.generation, replayed)
                obs.get_flight_recorder().record_event(
                    "master_restore", snapshot_version=version,
                    generation=self.generation,
                    hot_mutations_replayed=replayed)
                obs.get_registry().counter(
                    "dlrover_tpu_master_restores_total",
                    "Masters rebuilt from a state snapshot").inc()
            self._mutation_log = MutationLog(state_dir)
            # the drainer consults the fence before every write: hot-
            # only traffic (which never snapshots) must still stop the
            # moment a higher-generation master owns the lineage
            self._mutation_log.gate = self._check_fenced
            self.kv_store.attach_mutation_log(self._mutation_log)
            self.servicer.state_sink = self._maybe_snapshot
            if self._coord_server is not None:
                self.coord_servicer.state_sink = self._maybe_snapshot
            self.diagnosis_manager.state_sink = self._maybe_snapshot
            if self.fleet_controller is not None:
                self.fleet_controller.state_sink = self._maybe_snapshot
            # the generation bump itself must be durable before the
            # first RPC is served
            self._maybe_snapshot()
        self.servicer.generation = self.generation
        # the time-series collector: samples fleet vitals into the
        # store and persists the downsampled tiers to a checksummed
        # sidecar in the state dir (deliberately NOT the snapshot
        # export — background samples must not churn save_if_changed
        # versions). A restarted master or a promoted standby sharing
        # the state dir reloads fleet history here.
        self.tsdb_collector = obs.TsdbCollector(
            self.tsdb, goodput_ledger=self.goodput_ledger,
            state_dir=state_dir or "")
        if state_dir:
            # same fence as snapshots + the mutation log: a superseded
            # primary's background flush must not clobber the promoted
            # lineage's history sidecar
            self.tsdb_collector.gate = self._check_fenced
        restored_series = self.tsdb_collector.restore()
        if restored_series:
            logger.info("fleet time-series history restored: %d "
                        "series", restored_series)
            obs.get_flight_recorder().record_event(
                "tsdb_restored", series=restored_series,
                generation=self.generation)

    def _export_state(self) -> dict:
        state = {
            "generation": self.generation,
            "rendezvous": {name: mgr.export_state()
                           for name, mgr in self.rdzv_managers.items()},
            "task_manager": self.task_manager.export_state(),
            "kv_store": self.kv_store.export_state(),
            "speed_monitor": self.speed_monitor.export_state(),
            "goodput": self.goodput_ledger.export_state(),
            "plan_calibration": self.plan_calibration.export_state(),
            "diagnosis": self.diagnosis_manager.export_state(),
        }
        if self.fleet_controller is not None:
            state["fleet_controller"] = \
                self.fleet_controller.export_state()
        if self.job_manager is not None and \
                hasattr(self.job_manager, "export_state"):
            state["job_manager"] = self.job_manager.export_state()
        return state

    def _restore_state(self, state: dict) -> None:
        self.generation = int(state.get("generation", 0)) + 1
        for name, rdzv_state in state.get("rendezvous", {}).items():
            mgr = self.rdzv_managers.get(name)
            if mgr is not None:
                mgr.restore_state(rdzv_state)
        # re-fan the restored rank→slice registry to every slice-labeled
        # consumer NOW (speed monitor, diagnosis, goodput): joins are the
        # only other push site, and reconnecting agents whose worlds are
        # intact never re-join — without this, per-slice gauges and
        # eviction-by-slice would mislabel until the next real join
        training = self.rdzv_managers.get(RendezvousName.TRAINING)
        if training is not None and training.slice_map:
            self.servicer._push_slice_map(training)
        self.task_manager.restore_state(state.get("task_manager", {}))
        self.kv_store.restore_state(state.get("kv_store", {}))
        self.speed_monitor.restore_state(state.get("speed_monitor", {}))
        if "goodput" in state:
            self.goodput_ledger.restore_state(state["goodput"])
        if "plan_calibration" in state:
            self.plan_calibration.restore_state(
                state["plan_calibration"])
            # re-arm the planner with the restored evidence's learned
            # discounts NOW — waiting for the first post-failover step
            # report would score the re-formation plan with the bare
            # prior the calibration already corrected
            discounts = self.plan_calibration.axis_discounts()
            if discounts:
                self.servicer.push_axis_discounts(discounts)
        if "diagnosis" in state:
            self.diagnosis_manager.restore_state(state["diagnosis"])
        if self.fleet_controller is not None and \
                "fleet_controller" in state:
            # a promoted standby inherits decision history, cooldowns,
            # quarantines and any open rollback watch — the guardrails
            # must survive failover
            self.fleet_controller.restore_state(
                state["fleet_controller"])
        if self.job_manager is not None and "job_manager" in state and \
                hasattr(self.job_manager, "restore_state"):
            self.job_manager.restore_state(state["job_manager"])

    def _maybe_snapshot(self, force: bool = False) -> None:
        """Persist the control-plane state if it changed (the servicer's
        post-mutation hook). Serialized: concurrent RPC handlers must
        not interleave exports with version assignment.

        master_snapshot_min_interval_s > 0 coalesces bursts (e.g. a
        worker fleet draining a many-shard dataset would otherwise pay
        one full export+fsync per dispatch): at most one snapshot per
        interval, trading up to that much durability lag on a crash.
        A skipped mutation arms a trailing timer so the lag is bounded
        by the interval even when no later mutation ever arrives (the
        last TaskResult of a dataset must not stay doing-only forever).
        The default (0) is strict write-through."""
        if self._state_backend is None:
            return
        if self._check_fenced():
            return
        interval = Context.singleton().master_snapshot_min_interval_s
        with self._snapshot_lock:
            remaining = self._last_snapshot_ts + interval - time.time()
            if not force and interval > 0 and remaining > 0:
                if self._snapshot_timer is None:
                    timer = threading.Timer(remaining,
                                            self._trailing_snapshot)
                    timer.daemon = True
                    self._snapshot_timer = timer
                    timer.start()
                return
            # sample the mutation-log fence BEFORE exporting: every
            # mutation the export can contain already holds a smaller
            # seq (appends ride the same kv lock), so rotation keeps
            # anything newer — a hot set landing between export and
            # rotate stays durable in the rewritten log
            fence = (self._mutation_log.current_seq()
                     if self._mutation_log is not None else 0)
            try:
                written = self._state_backend.save_if_changed(
                    self._export_state())
            except Exception:  # noqa: BLE001 — durability is best-effort
                logger.exception("master state snapshot failed")
                return
            if written is not None:
                self._last_snapshot_ts = time.time()
                if self._mutation_log is not None:
                    # the snapshot's kv export includes every hot
                    # mutation below the fence: those are durable now
                    self._mutation_log.rotate(up_to_seq=fence)

    def _trailing_snapshot(self) -> None:
        """Timer body: flush the mutation that fell inside the
        coalescing window."""
        with self._snapshot_lock:
            self._snapshot_timer = None
        self._maybe_snapshot(force=True)

    @staticmethod
    def _bootstrap_file_generation() -> int:
        """The generation the bootstrap file currently carries (-1 =
        no file / pre-JSON / unreadable). One parser for the whole
        contract: the same ``resolve_bootstrap`` agents re-resolve
        through (env override included)."""
        from dlrover_tpu.agent.master_client import MasterClient

        try:
            return int(MasterClient.resolve_bootstrap().get(
                "generation", -1))
        except (TypeError, ValueError):
            return -1

    def _check_fenced(self, throttle_s: float = 2.0) -> bool:
        """Has a higher-generation master taken over the lineage? Read
        the bootstrap file at most once per ``throttle_s``; on the
        first detection, STOP this master's state writes for good —
        snapshots AND hot-key mutation-log appends — so the promoted
        primary's lineage can never be clobbered by a stale writer
        (e.g. a network-blip promotion while this one is still
        alive)."""
        with self._snapshot_lock:
            if self._fenced:
                return True
            now = time.time()
            if now - self._last_fence_check < throttle_s:
                return False
            self._last_fence_check = now
        file_gen = self._bootstrap_file_generation()
        if not self.generation or file_gen <= self.generation:
            return False
        self._mark_fenced(file_gen)
        return True

    def _mark_fenced(self, file_generation: int) -> None:
        with self._snapshot_lock:
            if self._fenced:
                return
            self._fenced = True
        # stop NEW appends; already-queued entries are discarded by the
        # drainer's gate (this method may BE on the drainer thread via
        # that gate, so closing the log here would self-join)
        self.kv_store.attach_mutation_log(None)
        logger.critical(
            "FENCED: generation %d owns the bootstrap file (ours is "
            "%d) — another master promoted past us; stopping every "
            "state write into the shared lineage", file_generation,
            self.generation)
        obs.get_flight_recorder().record_event(
            "master_fenced", file_generation=file_generation,
            our_generation=self.generation)
        obs.get_registry().counter(
            "dlrover_tpu_master_fenced_total",
            "Bootstrap publishes refused because a higher-generation "
            "master already owns the file").inc()

    def _arm_master_chaos(self) -> None:
        """kill:master:0@step — fed from worker GlobalStepReports so a
        chaos run can assassinate the control plane at a chosen step —
        plus the shard-scoped faults: kill:shard:S@step restarts slice
        S's rendezvous shard from its state partition, hang:shard:S@step
        wedges it (every other shard provably keeps serving)."""
        from dlrover_tpu.diagnostics.chaos import ChaosInjector

        chaos = ChaosInjector(role=NodeType.MASTER, rank=0)
        training = self.rdzv_managers[RendezvousName.TRAINING]
        if hasattr(training, "restart_shard"):
            chaos.shard_kill_fn = training.restart_shard
            chaos.shard_wedge_fn = training.wedge_shard
        if self.capacity_provider is not None:
            # the preemptible-market faults (offer:slice:+k@step,
            # revoke:slice:S@step) feed the local capacity provider —
            # the fleet controller's spot market in-process
            chaos.offer_fn = self.capacity_provider.offer
            chaos.revoke_fn = self.capacity_provider.revoke
        if chaos.faults:
            self.servicer.master_chaos = chaos

    def _controller_shed(self, rank: int, deadline: float,
                         reason: str) -> None:
        """Fleet-controller shed actuator: a synthetic advance-notice
        drain through the servicer's EXISTING slice-unit chain. The
        notice rank itself also gets a save-and-exit drain action — in
        a real preemption the OS notice file drives its exit, but a
        controller-initiated shed has no notice file, so the action
        queue carries the order instead."""
        from dlrover_tpu.common import messages as msg

        self.diagnosis_manager.request_drain(
            [rank], deadline, reason=reason)
        self.servicer._handle_drain(msg.DrainReport(
            node_rank=rank, phase="notice", deadline=deadline,
            reason=reason))

    def _attach_optimization(self, job_args, brain_addr: str) -> None:
        """Wire stats collection + resource optimization + auto-scaling
        (reference: dist_master.py:116-127 reporter selection and the
        JobResourceOptimizer/JobAutoScaler composition)."""
        from dlrover_tpu.common.constants import OptimizeMode
        from dlrover_tpu.master.node.job_auto_scaler import JobAutoScaler
        from dlrover_tpu.master.stats.job_collector import JobMetricCollector
        from dlrover_tpu.master.stats.reporter import (
            ReporterType,
            StatsReporter,
        )

        use_brain = (job_args.optimize_mode == OptimizeMode.CLUSTER
                     and brain_addr)
        if use_brain:
            from dlrover_tpu.brain.client import BrainResourceOptimizer

            reporter = StatsReporter.new_reporter(
                ReporterType.BRAIN, addr=brain_addr,
                job_name=job_args.job_name, job_uuid=job_args.job_uuid)
            optimizer = BrainResourceOptimizer(brain_addr,
                                               job_args.job_name)
        else:
            from dlrover_tpu.master.resource.local_optimizer import (
                LocalResourceOptimizer,
            )

            reporter = StatsReporter.new_reporter(ReporterType.LOCAL)
            optimizer = LocalResourceOptimizer()
        self.metric_collector = JobMetricCollector(
            job_args.job_name, reporter, stats=optimizer.stats)
        self.metric_collector.attach(speed_monitor=self.speed_monitor,
                                     job_manager=self.job_manager)
        self.servicer.metric_collector = self.metric_collector
        worker_args = job_args.worker_args()
        if worker_args is not None:
            resource = worker_args.group_resource.node_resource
            self.metric_collector.report_job_meta(
                worker_count=worker_args.group_resource.count,
                cpu=resource.cpu, memory_mb=resource.memory_mb,
                chips=resource.chips, chip_type=resource.chip_type,
                distribution_strategy=job_args.distribution_strategy,
            )
        if job_args.optimize_mode != OptimizeMode.MANUAL:
            self.auto_scaler = JobAutoScaler(
                self.job_manager, optimizer,
                speed_monitor=self.speed_monitor,
                interval_s=DefaultValues.SECONDS_PER_SCALE_CHECK,
            )
            self.auto_scaler.paral_config_sink = (
                self.servicer.merge_paral_config)

    # ------------------------------------------------------------------
    def prepare(self) -> None:
        self._server.start()
        if self._coord_server is not None:
            self._coord_server.start()
            logger.info("coordination tier serving on port %d",
                        self.coord_port)
        if self.job_manager is not None:
            self.job_manager.start()
        if self.metric_collector is not None:
            self.metric_collector.start()
        if self.auto_scaler is not None:
            self.auto_scaler.start()
        self.task_manager.start_timeout_recovery()
        self.diagnosis_manager.start()
        if self.fleet_controller is not None:
            self.fleet_controller.start()
        if self.tsdb_collector is not None:
            self.tsdb_collector.start()
        self._start_metrics_exporter()
        self._publish_bootstrap_addr()
        # an unhandled master crash still leaves the job timeline on disk
        obs.get_flight_recorder().install_excepthook()
        logger.info("job master serving on port %d", self.port)

    def _publish_bootstrap_addr(self) -> None:
        """Atomically write the advertised addresses + generation token
        to the bootstrap file (JSON since the hot-standby work; plain
        pre-JSON files are still read by resolve_bootstrap) so agents in
        master-lost mode can re-resolve a restarted OR promoted master.

        Generation fencing: a file already carrying a HIGHER generation
        is never overwritten — a revived old primary coming back after a
        standby promoted must not steal the fleet back (double-primary
        split-brain). The fenced master logs CRITICAL and keeps serving
        whoever still dials its old address; agents re-resolve to the
        higher generation."""
        import json

        path = Context.singleton().master_bootstrap_file
        if not path:
            return
        try:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            # the read-check-replace must be one critical section: two
            # masters racing it bare could interleave so the LOWER
            # generation's replace lands last and permanently points
            # the fleet at the stale primary. Advisory flock on a
            # sidecar serializes every publisher using this code.
            with self._bootstrap_publish_lock(path):
                current_gen = self._bootstrap_file_generation()
                if self.generation and current_gen > self.generation:
                    # fencing covers the whole lineage, not just the
                    # file: this master also stops snapshot/mutation-
                    # log writes
                    self._mark_fenced(current_gen)
                    return
                tmp = f"{path}.{os.getpid()}.tmp"
                with open(tmp, "w") as f:
                    json.dump({"addr": self.addr,
                               "coord_addr": self.coord_addr,
                               "generation": self.generation}, f)
                os.replace(tmp, path)
        except OSError as e:
            logger.warning("cannot publish master address to %s: %s",
                           path, e)
            return
        logger.info("master address %s (coord %s, generation %d) "
                    "published to %s", self.addr,
                    self.coord_addr or "-", self.generation, path)

    @staticmethod
    def _bootstrap_publish_lock(path: str):
        """Advisory exclusive lock over the bootstrap publish critical
        section (best-effort: a filesystem without flock degrades to
        the bare race, which is still bounded by the fence check)."""
        import contextlib

        @contextlib.contextmanager
        def held():
            lock_file = None
            try:
                import fcntl

                lock_file = open(f"{path}.lock", "w")
                fcntl.flock(lock_file, fcntl.LOCK_EX)
            except (ImportError, OSError):
                # acquisition failure only — a body exception must
                # never land here (it would make the manager re-yield)
                if lock_file is not None:
                    lock_file.close()
                lock_file = None
            try:
                yield
            finally:
                if lock_file is not None:
                    try:
                        fcntl.flock(lock_file, fcntl.LOCK_UN)
                        lock_file.close()
                    except OSError:
                        pass
        return held()

    def _start_metrics_exporter(self) -> None:
        """Serve the Prometheus exposition (metrics_port: 0 = any free
        port, negative = disabled). Scrape: GET /metrics — see
        docs/observability.md."""
        port = Context.singleton().metrics_port
        if port < 0:
            return
        try:
            # bound during prepare(), before run_in_thread() spawns:
            # the run thread only reads it at shutdown
            self._metrics_server, self.metrics_port = (  # graftlint: disable=GL701
                obs.start_http_exporter(port=port))
        except OSError as e:
            logger.warning("metrics exporter failed to bind: %s", e)
            return
        logger.info("metrics exposition on :%d/metrics", self.metrics_port)

    def run(self, poll_interval_s: float = 30.0) -> int:
        """Block until the job finishes; returns an exit code (reference:
        dist_master.py:165-222)."""
        ctx = Context.singleton()
        exit_code = 0
        while not self._stopped.is_set():
            if self.job_manager is not None:
                stage = self.job_manager.job_stage()
                if stage == JobStage.SUCCEEDED:
                    break
                if stage == JobStage.FAILED:
                    exit_code = 1
                    # single writer (this loop); read after run() exits
                    self._exit_reason = self.job_manager.exit_reason()  # graftlint: disable=GL701
                    break
            elif self.task_manager.finished():
                logger.info("all datasets exhausted: job succeeded")
                break
            if self.speed_monitor.is_hanged(ctx.hang_seconds):
                logger.error("job hanged > %.0fs without step progress",
                             ctx.hang_seconds)
                exit_code = 1
                # single writer (this loop); read after run() exits
                self._exit_reason = "hang"  # graftlint: disable=GL701
                break
            self._stopped.wait(poll_interval_s)
        self.stop()
        return exit_code

    def run_in_thread(self, poll_interval_s: float = 1.0) -> threading.Thread:
        thread = threading.Thread(
            target=self.run, args=(poll_interval_s,), daemon=True,
            name="job-master",
        )
        thread.start()
        return thread

    def stop(self, grace_s: float = 1.0) -> None:
        if not self._stopped.is_set():
            self._stopped.set()
            if self.metric_collector is not None:
                stage = (self.job_manager.job_stage()
                         if self.job_manager else "")
                self.metric_collector.report_job_exit(stage,
                                                      self._exit_reason)
                self.metric_collector.stop()
            if self.auto_scaler is not None:
                self.auto_scaler.stop()
            self.diagnosis_manager.stop()
            if self.fleet_controller is not None:
                self.fleet_controller.stop()
                try:
                    # the decision history rides in the dump so
                    # `tools/diagnose.py --flight` renders the exact
                    # payload the live RPC served
                    obs.get_flight_recorder().record_event(
                        "autoscale",
                        status=self.fleet_controller.status())
                except Exception:  # noqa: BLE001 — the dump must land
                    logger.exception("autoscale flight snapshot failed")
            if self.job_manager is not None:
                self.job_manager.stop()
            if self._metrics_server is not None:
                self._metrics_server.shutdown()
                self._metrics_server.server_close()  # release the socket
            with self._snapshot_lock:
                if self._snapshot_timer is not None:
                    self._snapshot_timer.cancel()
                    self._snapshot_timer = None
            # queued telemetry is replayed before the final flight dump
            # (a graceful stop must not silently drop spans), then the
            # drainer stops
            self.servicer.telemetry_queue.flush(timeout_s=2.0)
            self.servicer.telemetry_queue.stop()
            # a coalesced mutation must not die with the process when
            # the stop is graceful
            self._maybe_snapshot(force=True)
            if self._mutation_log is not None:
                self._mutation_log.close()
            # the master's half of the postmortem timeline; the goodput
            # snapshot rides in the dump so `tools/goodput.py --flight`
            # renders the ledger from the postmortem alone
            self.goodput_ledger.record_flight_snapshot(
                reason="master-stop")
            if self.tsdb_collector is not None:
                # final history flush + a compact tsdb snapshot in the
                # dump so `tools/top.py --flight` renders sparklines
                # from the postmortem alone
                self.tsdb_collector.stop()
                try:
                    obs.get_flight_recorder().record_event(
                        "tsdb",
                        snapshot=self.tsdb_collector.flight_snapshot(),
                        calibration=self.plan_calibration.table(),
                        axis_discounts=self.plan_calibration
                        .axis_discounts())
                except Exception:  # noqa: BLE001 — the dump must land
                    logger.exception("tsdb flight snapshot failed")
            try:
                # the assembled waterfall rides in the dump so
                # `tools/steptrace.py --flight` renders the exact
                # payload the live RPC served
                obs.get_flight_recorder().record_event(
                    "steptrace",
                    snapshot=self.steptrace.flight_snapshot())
            except Exception:  # noqa: BLE001 — the dump must land
                logger.exception("steptrace flight snapshot failed")
            obs.get_flight_recorder().record_event(
                "master_stop", exit_reason=self._exit_reason)
            obs.get_flight_recorder().dump(reason="master-stop")
            if self._coord_server is not None:
                self._coord_server.stop(grace_s)
            self._server.stop(grace_s)

    @property
    def addr(self) -> str:
        """Address agents should dial. A 0.0.0.0 bind is advertised as the
        host's routable IP so multi-host agents don't dial their own
        loopback."""
        return f"{self._advertised_host()}:{self.port}"

    @property
    def coord_addr(self) -> str:
        """The coordination tier's advertised address ("" = single-tier:
        coordination served on the main port)."""
        if self._coord_server is None:
            return ""
        return f"{self._advertised_host()}:{self.coord_port}"

    def _advertised_host(self) -> str:
        from dlrover_tpu.common.comm import local_ip

        host = self._host
        if host in ("0.0.0.0", "::", ""):
            host = local_ip()
        return host


def run_master_main(args=None) -> int:
    """CLI entry: `python -m dlrover_tpu.master.job_master --port ...`
    (reference: master/main.py:55, platform dispatch main.py:37-52).

    On `--platform k8s` the master fetches its own ElasticJob CR (the
    operator only passes the job name — reference: the Go master pod gets
    the job name and reads the CRD) and runs the full node-lifecycle
    composition with the pod scaler/watcher; otherwise it is the
    standalone/local rendezvous master."""
    import argparse

    parser = argparse.ArgumentParser("dlrover-tpu master")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--min-nodes", type=int, default=1)
    parser.add_argument("--max-nodes", type=int, default=1)
    parser.add_argument("--node-unit", type=int, default=1)
    parser.add_argument("--platform", default="local",
                        choices=["local", "k8s"])
    parser.add_argument("--job-name", default="")
    parser.add_argument("--namespace", default="default")
    parser.add_argument("--brain-addr", default="")
    parser.add_argument("--metrics-port", type=int,
                        default=Context.singleton().metrics_port,
                        help="Prometheus /metrics port (0 = any free "
                             "port, -1 = disabled)")
    parser.add_argument("--state-dir",
                        default=Context.singleton().master_state_dir,
                        help="directory for crash-consistent control-"
                             "plane snapshots; a restarted master "
                             "recovers from the latest valid one "
                             "('' = disabled)")
    parser.add_argument("--bootstrap-file",
                        default=Context.singleton().master_bootstrap_file,
                        help="file the master atomically writes its "
                             "advertised address into; agents re-resolve "
                             "from it after a master restart")
    parser.add_argument("--standby", action="store_true",
                        help="run as a HOT STANDBY instead of the "
                             "primary: tail the primary's snapshot "
                             "stream under --state-dir, health-check "
                             "the address it publishes in "
                             "--bootstrap-file, and promote (serve from "
                             "warm state, bumped generation, no worker "
                             "restarts) when it stops answering")
    ns = parser.parse_args(args)
    Context.singleton().update(metrics_port=ns.metrics_port,
                               master_state_dir=ns.state_dir,
                               master_bootstrap_file=ns.bootstrap_file)
    if ns.standby:
        from dlrover_tpu.master.standby import StandbyMaster

        standby = StandbyMaster(
            state_dir=ns.state_dir, bootstrap_file=ns.bootstrap_file,
            port=ns.port, min_nodes=ns.min_nodes,
            max_nodes=ns.max_nodes, node_unit=ns.node_unit)
        print("DLROVER_TPU_STANDBY=watching", flush=True)
        return standby.run()
    if ns.platform == "k8s":
        from dlrover_tpu.operator.crd import (
            ELASTICJOB_PLURAL,
            GROUP,
            VERSION,
            ElasticJob,
        )
        from dlrover_tpu.scheduler.kubernetes import K8sClient

        client = K8sClient(namespace=ns.namespace)
        manifest = client.api.request(
            "GET",
            f"/apis/{GROUP}/{VERSION}/namespaces/{ns.namespace}"
            f"/{ELASTICJOB_PLURAL}/{ns.job_name}")
        job = ElasticJob.from_manifest(manifest)
        job_args = job.to_job_args()
        worker = job_args.worker_args()
        if worker is not None:
            count = worker.group_resource.count
            min_nodes = max(1, worker.min_count or count)
            max_nodes = max(min_nodes, worker.max_count or count)
        else:
            min_nodes = max_nodes = 1
        master = JobMaster(port=ns.port, min_nodes=min_nodes,
                           max_nodes=max_nodes, node_unit=ns.node_unit,
                           job_args=job_args, cluster=client,
                           brain_addr=ns.brain_addr)
    else:
        master = JobMaster(port=ns.port, min_nodes=ns.min_nodes,
                           max_nodes=ns.max_nodes, node_unit=ns.node_unit)
    master.prepare()
    print(f"DLROVER_TPU_MASTER_ADDR={master.addr}", flush=True)
    return master.run()


if __name__ == "__main__":
    raise SystemExit(run_master_main())
