"""Global context singleton of runtime tunables.

Capability parity: dlrover/python/common/global_context.py — one place for
timeouts, thresholds and ports, overridable via env vars (``DLROVER_TPU_<KEY>``)
or programmatically (tests), and updatable at runtime from a resource-plan
service (the Brain-equivalent) without restarting the master.

A field lives here only while something sets it: a deployment (ports,
paths), a cloud's contract, a test, an example. A number with one value
in use is a constant in ``constants.DefaultValues`` and its reader takes
it from there (tests/test_common.py holds the rule).
"""

from __future__ import annotations

import os
import threading

from dlrover_tpu.common.constants import DefaultValues


class Context:
    _instance = None
    _lock = threading.Lock()

    def __init__(self):
        self.metrics_port: int = DefaultValues.METRICS_PORT
        self.hang_seconds: float = DefaultValues.HANG_SECONDS
        self.dead_node_timeout_s: float = (
            DefaultValues.DEAD_NODE_TIMEOUT_S
        )
        # client RPC budget (agent/master_client.py): per-call deadline,
        # attempt count, and the jittered-exponential-backoff envelope —
        # tests shrink these so failure paths run in milliseconds
        self.rpc_timeout_s: float = DefaultValues.RPC_TIMEOUT_S
        self.rpc_retries: int = DefaultValues.RPC_RETRIES
        self.rpc_backoff_s: float = DefaultValues.RPC_BACKOFF_S
        self.rpc_backoff_max_s: float = DefaultValues.RPC_BACKOFF_MAX_S
        self.master_reconnect_timeout_s: float = (
            DefaultValues.MASTER_RECONNECT_TIMEOUT_S
        )
        # crash-consistent master state: snapshots land here ("" = state
        # persistence disabled); the bootstrap file carries the master's
        # advertised address across restarts ("" = env-only resolution)
        self.master_state_dir: str = ""
        self.master_bootstrap_file: str = ""
        self.master_snapshot_min_interval_s: float = (
            DefaultValues.MASTER_SNAPSHOT_MIN_INTERVAL_S
        )
        # sharded control plane (master/rendezvous_shards.py +
        # master/coord_service.py + master/standby.py): per-slice
        # rendezvous shards, the KV/coordination tier's own port, and
        # the hot-standby promoter
        self.rdzv_sharded: bool = DefaultValues.RDZV_SHARDED
        self.coord_port: int = DefaultValues.COORD_PORT
        self.standby_health_interval_s: float = (
            DefaultValues.STANDBY_HEALTH_INTERVAL_S
        )
        self.standby_promote_failures: int = (
            DefaultValues.STANDBY_PROMOTE_FAILURES
        )
        # training diagnosis engine (master/diagnosis/): the rule
        # thresholds tests move and the action kill-switch — see
        # docs/observability.md
        self.diagnosis_min_worker_samples: int = (
            DefaultValues.DIAGNOSIS_MIN_WORKER_SAMPLES
        )
        self.straggler_trigger_windows: int = (
            DefaultValues.STRAGGLER_TRIGGER_WINDOWS
        )
        self.diagnosis_actions_enabled: bool = (
            DefaultValues.DIAGNOSIS_ACTIONS_ENABLED
        )
        self.diagnosis_profile_steps: int = (
            DefaultValues.DIAGNOSIS_PROFILE_STEPS
        )
        self.diagnosis_action_cooldown_s: float = (
            DefaultValues.DIAGNOSIS_ACTION_COOLDOWN_S
        )
        # goodput ledger alerting (obs/goodput.py, GoodputRule):
        # threshold 0 = disabled
        self.goodput_alert_threshold: float = (
            DefaultValues.GOODPUT_ALERT_THRESHOLD
        )
        self.goodput_window_s: float = DefaultValues.GOODPUT_WINDOW_S
        # planner calibration (parallel/calibration.py) + the
        # PlanRegressionRule thresholds (master/diagnosis/rules.py)
        self.calibration_min_samples: int = (
            DefaultValues.CALIBRATION_MIN_SAMPLES
        )
        self.plan_regression_ratio: float = (
            DefaultValues.PLAN_REGRESSION_RATIO
        )
        self.plan_regression_windows: int = (
            DefaultValues.PLAN_REGRESSION_WINDOWS
        )
        self.plan_regression_clear_windows: int = (
            DefaultValues.PLAN_REGRESSION_CLEAR_WINDOWS
        )
        # preemption-aware graceful drain (agent/preemption.py)
        self.preempt_default_grace_s: float = (
            DefaultValues.PREEMPT_DEFAULT_GRACE_S
        )
        self.preempt_notice_poll_s: float = (
            DefaultValues.PREEMPT_NOTICE_POLL_S
        )
        self.preempt_env_horizon_s: float = (
            DefaultValues.PREEMPT_ENV_HORIZON_S
        )
        # peer-to-peer elastic restore (checkpoint/peer_restore.py):
        # replacement ranks restore from surviving hosts' staged state,
        # falling back to Orbax shard-wise when no replica survived
        self.peer_restore_enabled: bool = (
            DefaultValues.PEER_RESTORE_ENABLED
        )
        self.peer_donor_port: int = DefaultValues.PEER_DONOR_PORT
        # online parallelism re-planning (parallel/planner.py): the
        # worker builds its mesh + batch/accumulation shape from the
        # master's shard plan; False pins the configured mesh (resizes
        # then only re-form the same DP shape — pre-PR-9 behavior)
        self.replan_enabled: bool = DefaultValues.REPLAN_ENABLED
        # multi-slice hierarchical DP (parallel/dcn_sync.py): degraded-
        # mode budget while a slice is absent, the per-step DCN collect
        # deadline and its poll cadence
        self.slice_absent_max_steps: int = (
            DefaultValues.SLICE_ABSENT_MAX_STEPS
        )
        self.dcn_sync_timeout_s: float = DefaultValues.DCN_SYNC_TIMEOUT_S
        self.dcn_sync_poll_s: float = DefaultValues.DCN_SYNC_POLL_S
        # step-hang watchdog (trainer/watchdog.py); 0 = disabled
        self.hang_watchdog_s: float = DefaultValues.HANG_WATCHDOG_S
        # per-step critical-path tracing (obs/steptrace.py +
        # master/steptrace.py): the CriticalPathRule gating-fraction
        # threshold (0 disables the rule)
        self.critical_path_gating_fraction: float = (
            DefaultValues.CRITICAL_PATH_GATING_FRACTION
        )
        # flight-recorder rings (obs/flight_recorder.py): per-process
        # event ring + span-id dedup ring capacities
        self.flight_ring_events: int = DefaultValues.FLIGHT_RING_EVENTS
        self.flight_ring_spans: int = DefaultValues.FLIGHT_RING_SPANS
        # per-rank relaunch backoff + quarantine (agent/elastic_agent.py)
        self.relaunch_backoff_base_s: float = (
            DefaultValues.RELAUNCH_BACKOFF_BASE_S
        )
        self.relaunch_backoff_max_s: float = (
            DefaultValues.RELAUNCH_BACKOFF_MAX_S
        )
        self.quarantine_failures: int = DefaultValues.QUARANTINE_FAILURES
        self.quarantine_window_s: float = (
            DefaultValues.QUARANTINE_WINDOW_S
        )
        # goodput-optimal fleet controller (brain/fleet_controller.py):
        # claim/shed/hold decisions from the measured ledger, guarded by
        # hysteresis + cooldown + rate limit + the rollback watchdog
        self.fleet_controller_enabled: bool = (
            DefaultValues.FLEET_CONTROLLER_ENABLED
        )
        self.autoscale_cooldown_s: float = (
            DefaultValues.AUTOSCALE_COOLDOWN_S
        )
        self.autoscale_hysteresis_windows: int = (
            DefaultValues.AUTOSCALE_HYSTERESIS_WINDOWS
        )
        self.autoscale_max_decisions_per_hour: int = (
            DefaultValues.AUTOSCALE_MAX_DECISIONS_PER_HOUR
        )
        self.autoscale_rollback_window_s: float = (
            DefaultValues.AUTOSCALE_ROLLBACK_WINDOW_S
        )
        # speed-aware dynamic sharding (master/shard/task_manager.py):
        # False = byte-identical legacy round-robin dispatch
        self.dispatch_speed_weighted: bool = (
            DefaultValues.DISPATCH_SPEED_WEIGHTED
        )
        # data-pipeline auto-tune (data/prefetch.py): advisory depth
        # sizing from the timeline's data_wait fraction
        self.prefetch_autotune: bool = DefaultValues.PREFETCH_AUTOTUNE
        self._load_env_overrides()

    def _load_env_overrides(self) -> None:
        for name, value in vars(self).items():
            if name.startswith("_"):
                continue
            env_key = f"DLROVER_TPU_{name.upper()}"
            raw = os.getenv(env_key)
            if raw is None:
                continue
            kind = type(value)
            if kind is bool:
                setattr(self, name, raw.lower() in ("1", "true", "yes"))
            else:
                setattr(self, name, kind(raw))

    def update(self, **kwargs) -> None:
        """Runtime override (e.g. from a resource-plan service)."""
        for key, value in kwargs.items():
            if hasattr(self, key) and not key.startswith("_"):
                setattr(self, key, value)

    @classmethod
    def singleton(cls) -> "Context":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        """For tests: drop the singleton so env overrides re-apply."""
        with cls._lock:
            cls._instance = None
