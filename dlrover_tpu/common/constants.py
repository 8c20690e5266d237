"""Framework-wide constants.

Capability parity with the reference's constant vocabulary
(dlrover/python/common/constants.py) — node types, statuses, the env-var
contract between master/agent/worker, rendezvous names, message levels —
re-spelled for a TPU/JAX deployment (hosts own TPU chips; worker processes are
JAX processes on TPU hosts).
"""


class PlatformType:
    LOCAL = "local"          # single-machine dev: master + agents as processes
    KUBERNETES = "k8s"       # GKE / k8s: pods per TPU host
    RAY = "ray"


class DistributionStrategy:
    ALLREDUCE = "allreduce"   # SPMD data/model parallel over a mesh
    PS = "ps"                 # parameter-server-style (elastic embeddings)
    LOCAL = "local"


class OptimizeMode:
    MANUAL = "manual"
    SINGLE_JOB = "single-job"
    CLUSTER = "cluster"       # ask the brain service for resource plans


class NodeType:
    MASTER = "master"
    WORKER = "worker"        # a TPU host running one JAX process
    CHIEF = "chief"          # worker rank 0 (does checkpoint writes, logging)
    EVALUATOR = "evaluator"
    PS = "ps"                # parameter-server-style state holder (embeddings)


class NodeStatus:
    INITIAL = "initial"
    PENDING = "pending"
    RUNNING = "running"
    SUCCEEDED = "succeeded"
    FAILED = "failed"
    DELETED = "deleted"
    UNKNOWN = "unknown"
    BREAKDOWN = "breakdown"  # machine-level fault (host unreachable)

    @classmethod
    def terminal(cls):
        return {cls.SUCCEEDED, cls.FAILED, cls.DELETED}


class NodeEventType:
    ADDED = "added"
    MODIFIED = "modified"
    DELETED = "deleted"


class NodeExitReason:
    SUCCEEDED = "succeeded"
    KILLED = "killed"            # deleted/force-killed by the platform
    # clean graceful drain (advance preemption notice honored: emergency
    # checkpoint completed, worker exited WorkerExit.DRAIN) — a planned
    # departure, not a failure: no relaunch-budget charge
    DRAINED = "drained"
    # self-aborted by the step-hang watchdog (stacks in the flight dump)
    HANG = "hang"
    OOM = "oom"                  # host or HBM out-of-memory
    FATAL_ERROR = "fatal_error"  # un-relaunchable user error
    HARDWARE_ERROR = "hardware_error"  # TPU chip / ICI fault
    UNKNOWN_ERROR = "unknown_error"
    RELAUNCHED = "relaunched"


class WorkerExit:
    """Worker exit-code vocabulary shared by the trainer (producer), the
    agent (classifier) and the k8s watcher (pod exit parsing)."""

    SUCCESS = 0
    # graceful drain after a preemption notice: the loop consumed the
    # drain request, ran the deadline-bounded emergency checkpoint and
    # exited clean. Chosen outside the shell (126/127) and signal
    # (128+n) ranges.
    DRAIN = 76
    # SIGABRT: the step-hang watchdog self-aborts so the agent restarts
    # the worker; Popen reports -6, k8s containers 128+6
    _SIGABRT_POPEN = -6
    _SIGABRT_SHELL = 134
    # platform SIGKILL/SIGTERM (eviction, force delete)
    _KILL_CODES = (-9, -15, 137, 143)

    @classmethod
    def classify(cls, code: int, hang_enabled: bool = True) -> str:
        """Exit code → NodeExitReason.* (the agent/diagnosis layer must
        tell drain from hang from crash from platform kill).

        ``hang_enabled``: with the step-hang watchdog off
        (``Context.hang_watchdog_s == 0``) a SIGABRT cannot be the
        watchdog — it is an ordinary crash (glibc abort, C++ terminate)
        and must charge the relaunch budget like one.
        """
        if code == cls.SUCCESS:
            return NodeExitReason.SUCCEEDED
        if code == cls.DRAIN:
            return NodeExitReason.DRAINED
        if code in (cls._SIGABRT_POPEN, cls._SIGABRT_SHELL):
            return (NodeExitReason.HANG if hang_enabled
                    else NodeExitReason.UNKNOWN_ERROR)
        if code in cls._KILL_CODES:
            return NodeExitReason.KILLED
        return NodeExitReason.UNKNOWN_ERROR

    @classmethod
    def to_exit_status(cls, code: int) -> int:
        """Popen's negative signal codes → the POSIX 128+N exit status
        a container reports. An agent re-exiting its worker's code must
        normalize, or -6 truncates to 250 at the process boundary and
        the pod-side classification can never see the hang/kill."""
        return 128 - code if code < 0 else code


class JobStage:
    CREATED = "created"
    RUNNING = "running"
    SUCCEEDED = "succeeded"
    FAILED = "failed"
    STOPPING = "stopping"


class RendezvousName:
    TRAINING = "elastic-training"
    NETWORK_CHECK = "network-check"


class NodeEnv:
    """Env-var contract (reference: constants.py NodeEnv /
    NodeEnv.DLROVER_MASTER_ADDR)."""

    MASTER_ADDR = "DLROVER_TPU_MASTER_ADDR"
    # File a (re)started master atomically writes its advertised address
    # into; agents in master-lost mode re-resolve from it (the address of
    # a restarted master usually differs — new pod IP / new free port).
    MASTER_BOOTSTRAP = "DLROVER_TPU_MASTER_BOOTSTRAP_FILE"
    # Coordination-tier address (master/coord_service.py): hot KV
    # traffic (dcn/ gradient exchange, coord/ barriers) dials this
    # instead of the control tier. Set by the agent for its worker from
    # the join result; "" / unset = single-tier master.
    COORD_ADDR = "DLROVER_TPU_COORD_ADDR"
    NODE_ID = "DLROVER_TPU_NODE_ID"
    NODE_TYPE = "DLROVER_TPU_NODE_TYPE"
    NODE_RANK = "DLROVER_TPU_NODE_RANK"
    NODE_NUM = "DLROVER_TPU_NODE_NUM"
    JOB_NAME = "DLROVER_TPU_JOB_NAME"
    # Per-worker (set by the agent for the spawned training process):
    WORLD_SIZE = "DLROVER_TPU_WORLD_SIZE"          # number of JAX processes
    PROCESS_ID = "DLROVER_TPU_PROCESS_ID"          # jax process index
    COORDINATOR_ADDR = "DLROVER_TPU_COORDINATOR"   # jax.distributed coordinator
    RDZV_ROUND = "DLROVER_TPU_RDZV_ROUND"
    PARAL_CONFIG_PATH = "DLROVER_TPU_PARAL_CONFIG" # tuned-config hot-reload file
    DEVICES_PER_NODE = "DLROVER_TPU_DEVICES_PER_NODE"
    # worker → agent handoff files (monitors tail these)
    METRICS_FILE = "DLROVER_TPU_METRICS_FILE"      # step-progress JSON lines
    CHIP_STATS_FILE = "DLROVER_TPU_CHIP_STATS"     # per-chip HBM usage JSON
    # per-step phase timeline ring the worker exports (obs/timeline.py)
    TIMELINE_FILE = "DLROVER_TPU_TIMELINE_FILE"
    # agent → worker handoff: on-demand profiler capture requests
    # (obs/profiler.py; the agent writes it when executing a master
    # `profile:{rank}` diagnosis action)
    PROFILE_REQUEST_FILE = "DLROVER_TPU_PROFILE_REQUEST"
    # agent → worker handoff: drain/checkpoint requests the step loop
    # polls (agent/preemption.py write_drain_request; the agent writes
    # it on a preemption notice — save+exit — or when executing a
    # master `checkpoint:{rank}` action — save+continue)
    DRAIN_REQUEST_FILE = "DLROVER_TPU_DRAIN_REQUEST"
    # host-RAM peer-state cache (checkpoint/peer_restore.py): the worker
    # stages its live state here at checkpoint boundaries; the agent's
    # donor server serves it to replacement ranks
    PEER_CACHE_DIR = "DLROVER_TPU_PEER_CACHE_DIR"
    # restore plan the agent received in its join result (JSON file);
    # workers with a master client re-fetch a fresh plan via RPC instead
    RESTORE_PLAN_FILE = "DLROVER_TPU_RESTORE_PLAN"
    # parallelism plan for the new world (parallel/planner.py), written
    # by the agent from its join result; workers with a master client
    # re-fetch fresh via ShardPlanRequest at loop build
    SHARD_PLAN_FILE = "DLROVER_TPU_SHARD_PLAN"
    # chaos `resize:+k@step` handoff: the injector atomically writes
    # the scale-up request here; the LAUNCHER (bench/test harness,
    # operator) consumes it and starts k more agents — adding ranks
    # needs a process spawner, which lives outside the worker
    RESIZE_REQUEST_FILE = "DLROVER_TPU_RESIZE_REQUEST"
    # total ICI slices of the job (slice-unit chaos resize targets the
    # k highest slice ids; unset = slice-unit resize faults disabled)
    NUM_SLICES = "DLROVER_TPU_NUM_SLICES"
    # platform/chaos → agent: a preemption-notice file the agent's
    # PreemptionWatcher polls ({"deadline": ts} or {"grace_s": n})
    PREEMPTION_NOTICE_FILE = "DLROVER_TPU_PREEMPTION_NOTICE"
    # k8s-style static notice: a unix timestamp set at pod creation
    # ("this VM goes away at T" — maintenance windows, spot reclaim)
    PREEMPTION_AT = "DLROVER_TPU_PREEMPTION_AT"
    # ICI slice this host belongs to (multi-slice hierarchical DP):
    # the slice is the failure domain — rendezvous worlds, drains and
    # restore-plan donor preference are all scoped by it. -1/unset =
    # single-slice job (every slice-scoped path disabled).
    SLICE_ID = "DLROVER_TPU_SLICE_ID"


class TrainingMsgLevel:
    PROCESS_ERROR = "process_error"
    NODE_ERROR = "node_error"
    RDZV_ERROR = "rdzv_error"
    WARNING = "warning"
    INFO = "info"


class TaskType:
    """Dynamic-sharding task types (reference: master/shard)."""

    TRAINING = "training"
    EVALUATION = "evaluation"
    PREDICTION = "prediction"
    WAIT = "wait"
    NONE = "none"


class NetworkCheckResult:
    NORMAL = "normal"
    FAULT = "fault"
    STRAGGLER = "straggler"


class MeshAxis:
    """Canonical named mesh axes (replaces the reference's named process groups,
    atorch/distributed/distributed.py:323 create_parallel_group)."""

    # cross-slice data parallelism over the slow DCN fabric (multi-slice
    # hierarchical DP): the OUTERMOST axis — gradient sync runs in-slice
    # over ICI first, then (all-)reduces over this axis
    DCN = "dcn"
    DATA = "data"
    FSDP = "fsdp"
    TENSOR = "tensor"
    SEQUENCE = "sequence"
    EXPERT = "expert"
    PIPE = "pipe"

    ALL = ("dcn", "data", "fsdp", "tensor", "sequence", "expert", "pipe")


class TraceScope:
    """`jax.named_scope` names of the step program where no Flax module
    gives one (a module scopes its own call; forward and backward are
    told apart by JAX's own `jvp(` / `transpose(jvp(` in an op's
    `op_name`). They reach the compiled program's metadata and, through
    it, a profiler trace. The trace readers match these names
    (benchmarks/metrics/step.*_share.py): a contract
    (docs/observability.md), not labels to reword."""

    EMBED = "embed"              # the token-embedding lookup
    HEAD_LOSS = "head_loss"      # final norm + head matmul + loss
    OPTIMIZER = "optimizer"      # tx.update + apply_updates
    GRAD_ACCUM = "grad_accum"    # the scan over micro-batches
    # models/keye.py and what it runs of ops/ and parallel/moe.py
    INDEXER = "indexer"          # index scores, selection, KL, backward
    SPARSE_ATTN = "sparse_attn"  # attention over the selected keys
    MOE = "moe"                  # router, sort, grouped products, combine
    # models/minicpm_sala.py and what it runs of ops/
    LIGHTNING_ATTN = "lightning_attn"        # the decayed linear recurrence
    BLOCK_SELECT = "block_select"            # compressed keys, block top-k
    BLOCK_SPARSE_ATTN = "block_sparse_attn"  # attention over chosen blocks


class DefaultValues:
    METRICS_PORT = 0                # /metrics exposition; 0 → free port,
    #                                 -1 → disabled
    RDZV_TIMEOUT_S = 600.0
    RDZV_WAIT_NEW_NODE_S = 30.0     # grace window for extra nodes past min
    TASK_TIMEOUT_S = 1800.0
    HANG_SECONDS = 1800.0
    # an agent silent this long is declared dead: its rendezvous world is
    # invalidated so survivors re-form (the scale-DOWN path). Liveness is
    # touched by join/get_comm_world/num_nodes_waiting RPCs — any healthy
    # agent beats far faster than this.
    DEAD_NODE_TIMEOUT_S = 90.0
    MAX_RELAUNCH = 3
    GRPC_MAX_MESSAGE_MB = 64
    # client-side RPC budget: jittered exponential backoff between
    # attempts, capped (agent/master_client.py retry_rpc)
    RPC_TIMEOUT_S = 30.0
    RPC_RETRIES = 10
    RPC_BACKOFF_S = 0.5
    RPC_BACKOFF_MAX_S = 15.0
    # master-loss handling (agent/elastic_agent.py): how long an agent
    # keeps its workers alive while reconnecting to a restarted master
    MASTER_RECONNECT_TIMEOUT_S = 1800.0
    # crash-consistent master state (master/state_backend.py)
    MASTER_SNAPSHOT_RETAIN = 5
    # 0 = write-through (a snapshot per control-plane mutation: strict
    # no-loss/no-double-assign recovery). > 0 coalesces snapshots to at
    # most one per interval — bounds write amplification on
    # dispatch-heavy phases at the cost of up to that much durability
    # lag on a crash (docs/fault_tolerance.md)
    MASTER_SNAPSHOT_MIN_INTERVAL_S = 0.0
    # -- sharded control plane (master/rendezvous_shards.py) ------------
    # per-slice rendezvous shards behind a router: a wedged slice's
    # joins cannot delay another slice's cut, and a shard restarts
    # alone. False reverts JobMaster to the single-lock manager (the
    # bench baseline).
    RDZV_SHARDED = True
    # the KV/coordination tier's own port (master/coord_service.py):
    # 0 = any free port, -1 = serve coordination on the main port only
    COORD_PORT = 0
    # bounded telemetry ingest: reports queued past this are dropped
    # oldest-first (dlrover_tpu_telemetry_dropped_total)
    TELEMETRY_QUEUE_SIZE = 256
    # kv episode hygiene: generations of a namespaced hot-key group
    # retained (current + N-1 for in-flight readers of the superseded
    # episode); older generations are garbage-collected on write
    KV_GC_KEEP_GENERATIONS = 2
    # -- hot-standby master (master/standby.py) -------------------------
    # cadence of the standby's primary health probe, and how many
    # consecutive failed probes trigger promotion
    STANDBY_HEALTH_INTERVAL_S = 2.0
    STANDBY_PROMOTE_FAILURES = 3
    MONITOR_INTERVAL_S = 5.0
    REPORT_RESOURCE_INTERVAL_S = 15.0
    SPEED_SAMPLE_WINDOW = 20
    STRAGGLER_MEDIAN_RATIO = 2.0    # t > ratio × median ⇒ straggler
    SECONDS_PER_SCALE_CHECK = 60.0
    # training diagnosis engine (master/diagnosis/): the rule-based
    # inference chain over per-worker step reports + resource stats
    DIAGNOSIS_INTERVAL_S = 30.0
    # per-worker step-time window (samples) straggler scoring runs over
    DIAGNOSIS_WORKER_WINDOW = 20
    # a worker needs this many samples before rules will judge it (a
    # fresh joiner's first post-compile reports are not evidence)
    DIAGNOSIS_MIN_WORKER_SAMPLES = 3
    # hysteresis: consecutive over-threshold evaluations before a
    # straggler is flagged, and consecutive clean ones before it clears
    STRAGGLER_TRIGGER_WINDOWS = 2
    STRAGGLER_CLEAR_WINDOWS = 2
    # data-pipeline-bound attribution: windowed data-wait fraction above
    # this means the step loop starves on input, not on compute
    DIAGNOSIS_DATA_WAIT_FRACTION = 0.5
    # HBM-pressure warning threshold (per-chip used/total %)
    DIAGNOSIS_HBM_PRESSURE_PCT = 92.0
    # throughput collapse: windowed steps/s under ratio × the observed
    # high-water mark (with training in steady state) raises a report
    DIAGNOSIS_COLLAPSE_RATIO = 0.5
    # action grammar: observe / profile:{rank} / restart:{rank} / alert.
    # False = diagnose-only (reports + metrics, no actions dispatched)
    DIAGNOSIS_ACTIONS_ENABLED = True
    # steps an on-demand profiler capture traces on the target worker
    DIAGNOSIS_PROFILE_STEPS = 5
    # per-rank cooldown between dispatched actions (a straggler that
    # stays slow must not get a profile request every interval)
    DIAGNOSIS_ACTION_COOLDOWN_S = 300.0
    # goodput alerting (obs/goodput.py + GoodputRule): alert when the
    # productive fraction over the trailing window drops below the
    # threshold, naming the dominant badput bucket. 0 = disabled (the
    # default: an acceptable goodput floor is job-specific).
    GOODPUT_ALERT_THRESHOLD = 0.0
    GOODPUT_WINDOW_S = 600.0
    # the window must be at least this covered (elapsed rank-seconds /
    # window) before the rule judges it — a freshly-started world's
    # first half-window is not evidence of lost goodput
    GOODPUT_MIN_COVERAGE = 0.5
    # -- fleet time-series plane (obs/tsdb.py) --------------------------
    # cadence the master's collector samples the allowlisted registry
    # gauges + goodput snapshot into the history store; 0 = no sampler
    # thread (direct step-report ingest still runs)
    TSDB_SAMPLE_INTERVAL_S = 5.0
    # cadence the downsampled tiers persist to the state-dir sidecar
    # (bounded history loss on a hard master kill); 0 = flush only on
    # graceful stop
    TSDB_FLUSH_INTERVAL_S = 30.0
    # -- planner calibration (parallel/calibration.py) ------------------
    # measurements a plan signature needs before it is calibration
    # evidence (each sample is already a windowed worker mean)
    CALIBRATION_MIN_SAMPLES = 3
    # PlanRegressionRule: alert when measured step time exceeds the
    # planner's prediction by this ratio for PLAN_REGRESSION_WINDOWS
    # consecutive diagnosis rounds (hysteresis like StragglerRule);
    # clears after PLAN_REGRESSION_CLEAR_WINDOWS under it. ratio 0 =
    # rule disabled.
    PLAN_REGRESSION_RATIO = 1.5
    PLAN_REGRESSION_WINDOWS = 3
    PLAN_REGRESSION_CLEAR_WINDOWS = 2
    # -- preemption-aware graceful drain (agent/preemption.py) ----------
    # grace window assumed when a notice carries no deadline (a bare
    # SIGTERM): k8s default terminationGracePeriodSeconds
    PREEMPT_DEFAULT_GRACE_S = 30.0
    # cadence of the agent's notice-source poll (file/env sources)
    PREEMPT_NOTICE_POLL_S = 1.0
    # how far ahead of a static env deadline ($DLROVER_TPU_PREEMPTION_AT)
    # the drain fires; 0 = use preempt_default_grace_s. Jobs whose full
    # save takes longer than the bare-SIGTERM grace must widen this or
    # the emergency save is skipped despite hours of advance notice.
    PREEMPT_ENV_HORIZON_S = 0.0
    # emergency checkpoint: skip-and-log when the remaining window is
    # below this floor (a save that cannot commit only produces a torn
    # step the restore fallback then has to walk past)
    EMERGENCY_CKPT_MIN_WINDOW_S = 2.0
    # -- peer-to-peer elastic restore (checkpoint/peer_restore.py) ------
    # serve a replacement rank's shards from surviving hosts' staged
    # state instead of Orbax storage (restore time independent of model
    # size); False reverts every restore to the storage path
    PEER_RESTORE_ENABLED = True
    # wall-clock budget for the peer shard transfer: past it the restore
    # aborts shard-wise to the Orbax fallback instead of hanging
    PEER_RESTORE_TIMEOUT_S = 120.0
    # donor server port (0 = ephemeral; the advertised addr rides the
    # PeerStoreReport RPC either way)
    PEER_DONOR_PORT = 0
    # -- online parallelism re-planning (parallel/planner.py) -----------
    # apply the master's shard plan when building the worker's mesh
    # (mesh spec + batch/accumulation override); False pins the
    # configured mesh — resizes then only re-form the same DP shape
    REPLAN_ENABLED = True
    # -- step-hang watchdog (trainer/watchdog.py) -----------------------
    # no step progress for this long → dump all-thread stacks + the
    # flight record and self-abort so the agent restarts the worker.
    # 0 = disabled (the default: legitimate step times vary too much to
    # pick a universal bound; jobs opt in via DLROVER_TPU_HANG_WATCHDOG_S)
    HANG_WATCHDOG_S = 0.0
    # -- multi-slice hierarchical DP (parallel/dcn_sync.py) -------------
    # degraded-mode budget: surviving slices keep stepping with the
    # gradient mean renormalized over PRESENT slices for this many
    # consecutive steps while a slice is absent (draining/re-forming);
    # past it they hard-stall with a CRITICAL alert instead of silently
    # training on a shrunken mean
    SLICE_ABSENT_MAX_STEPS = 100
    # per-step deadline for collecting a formed peer slice's gradient
    # contribution over DCN; a formed slice silent past it is treated
    # absent for THIS step (degraded accounting, loud warning)
    DCN_SYNC_TIMEOUT_S = 60.0
    # cadence of the collector's poll against the master KV store
    DCN_SYNC_POLL_S = 0.05
    # int8/int4 groupwise quantization of the host-level cross-slice
    # gradient payloads (checkpoint/quantized.py codec — the same
    # scheme quant_collectives puts on the wire in-program); 0 = exact
    # float32 bytes
    DCN_SYNC_QUANT_BITS = 0
    # -- per-step critical-path tracing (obs/steptrace.py) --------------
    # worker-side: one compact trace record per step, batched over the
    # TelemetryReport channel, in a bounded drop-oldest record ring
    # between flushes (a wedged master must not grow worker memory)
    STEPTRACE_RING = 512
    # NTP-style clock-offset refresh cadence against the master (the
    # join-time probe always runs; refreshes ride the report cadence)
    STEPTRACE_PROBE_INTERVAL_S = 30.0
    # master-side: assembled (gen, step) groups the StepTraceAssembler
    # retains for queries / the flight embed
    STEPTRACE_RING_STEPS = 512
    # CriticalPathRule: flag a rank after it gated at least this
    # fraction of the window's solved steps for
    # STRAGGLER_TRIGGER_WINDOWS consecutive evaluations (clears after
    # STRAGGLER_CLEAR_WINDOWS under — the same hysteresis knobs as
    # StragglerRule); 0 disables the rule
    CRITICAL_PATH_GATING_FRACTION = 0.5
    # -- flight recorder rings (obs/flight_recorder.py) -----------------
    # per-process bounded event ring and span-id dedup ring (historically
    # one hard-coded 4096)
    FLIGHT_RING_EVENTS = 4096
    FLIGHT_RING_SPANS = 4096
    # -- goodput-optimal fleet controller (brain/fleet_controller.py) ---
    # master-side control loop that claims offered preemptible slices,
    # sheds a gating slice, or holds — every actuation through the
    # existing drain/rejoin machinery. Off by default: the controller
    # changes fleet membership on its own authority; jobs opt in.
    FLEET_CONTROLLER_ENABLED = False
    # evaluation cadence of the control loop
    AUTOSCALE_INTERVAL_S = 30.0
    # after any actuation, no new decision for this long (lets the
    # rollback watchdog's observation window conclude first)
    AUTOSCALE_COOLDOWN_S = 120.0
    # hysteresis: consecutive evaluations agreeing on the same decision
    # before it actuates (one noisy window must not resize the fleet)
    AUTOSCALE_HYSTERESIS_WINDOWS = 2
    # hard ceiling on actuations per hour, claims and sheds combined
    # (rollbacks are exempt — undoing damage must never be rate-limited)
    AUTOSCALE_MAX_DECISIONS_PER_HOUR = 6
    # rollback watchdog: windowed goodput fraction dropping by more than
    # this (absolute) versus the pre-actuation window reverts the
    # decision and quarantines its class
    AUTOSCALE_ROLLBACK_DROP_FRACTION = 0.2
    # how long after an actuation the watchdog compares windows
    AUTOSCALE_ROLLBACK_WINDOW_S = 120.0
    # quarantine base for a rolled-back decision class; doubles per
    # consecutive rollback of the same class, capped at 8x
    AUTOSCALE_QUARANTINE_BACKOFF_S = 600.0
    # claim economics: predicted marginal goodput (rank-seconds over the
    # offer's expected lifetime) must exceed the join+re-plan cost
    # estimate by this ratio before a claim fires
    AUTOSCALE_CLAIM_MARGIN = 1.2
    # shed trigger: steptrace must name the slice gating AND the fleet's
    # cross-slice wait fraction must exceed this
    AUTOSCALE_SHED_WAIT_FRACTION = 0.3
    # -- speed-aware dynamic sharding (master/shard/) -------------------
    # weight get_task dispatch by observed per-rank speed so faster
    # workers pull more shards; False = byte-identical legacy dispatch
    DISPATCH_SPEED_WEIGHTED = False
    # the slowest rank is still served at least one shard per this many
    # fleet dispatches (throttle, never starvation)
    DISPATCH_WEIGHT_FLOOR = 0.25
    # -- data-pipeline auto-tune (data/prefetch.py) ---------------------
    # grow device-prefetch depth / shm-ring capacity while the
    # timeline's data_wait fraction stays above the trigger; shrink back
    # when the pipeline stops starving. Advisory values consumed at
    # (re)build boundaries — never mid-step.
    PREFETCH_AUTOTUNE = True
    PREFETCH_DEPTH_MIN = 1
    PREFETCH_DEPTH_MAX = 8
    DATA_WAIT_TUNE_FRACTION = 0.2
    # -- per-rank relaunch backoff + quarantine (agent) -----------------
    # exponential delay between worker relaunches: base * 2^(k-1) for the
    # k-th recent failure, capped — a flapping worker must not hot-loop
    RELAUNCH_BACKOFF_BASE_S = 1.0
    RELAUNCH_BACKOFF_MAX_S = 60.0
    # quarantine the rank (stop relaunching; agent exits with the worker
    # code) after this many failures inside the window; 0 disables
    QUARANTINE_FAILURES = 5
    QUARANTINE_WINDOW_S = 600.0


# The hot-tier KV contract, shared by the master (snapshot exemption +
# mutation log + generation GC, master/kv_store.py) and the client
# (coordination-tier routing, agent/master_client.py): keys under these
# prefixes are on the gradient path. ONE constant — a prefix added to
# only one side would silently route hot traffic to the control tier or
# skip snapshotting a cold key.
HOT_KV_PREFIXES = ("dcn/", "coord/")

# The durable subset of the hot prefixes: coord/ barrier mutations ride
# the mutation log (a promoted master must answer the coordinator
# addresses agents kv_wait on), dcn/ payloads are per-step ephemeral by
# protocol and never logged. Lives HERE beside HOT_KV_PREFIXES — the
# same single-sourcing contract (graftlint GL403): a prefix split
# between kv_store and a future standby replay path would silently
# diverge durability.
LOGGED_KV_PREFIXES = ("coord/",)
