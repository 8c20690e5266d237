"""ElasticTrainLoop: the user-facing elastic training driver.

Capability parity: `ElasticTrainer` (dlrover/trainer/torch/elastic/
trainer.py:225 — fixed-global-batch grad accumulation as the world resizes,
step reporting, the checkpoint hook the reference left unimplemented
:295-319) — TPU re-design:

- The loop OWNS re-lowering: it builds the mesh from the live device set,
  picks (accum, micro) to hold the global batch fixed via
  `choose_accumulation`, and jits the train step once per world shape.
- Flash checkpoint at intervals + forced save on SIGTERM (the agent sends
  SIGTERM before a membership-change restart, elastic_agent.py), so an
  elastic resize resumes from the last committed step with data position.
- Global-step reports feed the master SpeedMonitor (parity:
  TorchTrainingMonitor elastic_agent/monitor/training.py:78).
"""

from __future__ import annotations

import dataclasses
import os
import signal
import threading
import time as _time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from dlrover_tpu import obs
from dlrover_tpu.agent.preemption import DrainRequestSource
from dlrover_tpu.checkpoint import FlashCheckpointer
from dlrover_tpu.common.constants import DefaultValues, WorkerExit
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh, dp_size
from dlrover_tpu.trainer.sampler import ElasticDistributedSampler
from dlrover_tpu.trainer.train_step import (
    build_trainer,
    choose_accumulation,
)
from dlrover_tpu.trainer.watchdog import StepHangWatchdog


class DrainExit(SystemExit):
    """Clean graceful drain: the loop consumed a preemption drain
    request, ran the emergency checkpoint, and the process must exit
    with the clean-drain code the agent classifies as NON-failure."""

    def __init__(self, reason: str = ""):
        super().__init__(WorkerExit.DRAIN)
        self.reason = reason


@dataclasses.dataclass
class TrainLoopConfig:
    global_batch: int
    seq_len: int
    max_micro_per_replica: int = 8
    max_steps: int = 0                    # 0 = until data exhausted
    checkpoint_dir: str = ""
    save_interval_steps: int = 100
    # 8/4 = groupwise int-quantized state payloads (~4x fewer restore
    # bytes; see checkpoint/quantized.py); 0 = exact dtypes
    checkpoint_quantize_bits: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "DLROVER_TPU_CKPT_QUANT_BITS", "0")))
    report_interval_steps: int = 10
    mesh_spec: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    rules: Optional[Any] = None
    # jax.profiler trace window (reference tracing parity, SURVEY §5a):
    # a perfetto/xplane trace of steps [start, start+num) is written to
    # profile_dir (defaults to $DLROVER_TPU_PROFILE_DIR)
    profile_dir: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "DLROVER_TPU_PROFILE_DIR", ""))
    profile_start_step: int = 3           # skip compile steps
    profile_num_steps: int = 3
    # AOT-compile the train step concurrently with the checkpoint read
    # (restore pays max(read, compile) instead of their sum)
    overlap_restore_compile: bool = True


class ElasticTrainLoop:
    def __init__(
        self,
        model,
        tx,
        loss_fn: Callable,
        config: TrainLoopConfig,
        master_client=None,
        devices=None,
        trainer=None,
    ):
        """`trainer` overrides the built dense trainer with any object
        exposing the ShardedTrainer surface (init/abstract_state/step/
        shard_batch/accum_steps/micro_batch) — e.g. a PipelinedTrainer,
        making pipeline training elastic with checkpoint-resume."""
        self.config = config
        self.client = master_client
        # finished spans batched for the master (flushed at report
        # intervals); registered before any span below so the recompile
        # span of THIS (re)build is part of the shipped timeline. A
        # failed construction must deregister (the global sink list
        # outlives this instance).
        self._span_exporter = obs.SpanExporter()
        obs.add_span_sink(self._span_exporter)
        try:
            self._init_inner(model, tx, loss_fn, config, devices, trainer)
        except BaseException:
            obs.remove_span_sink(self._span_exporter)
            raise

    def _init_inner(self, model, tx, loss_fn, config, devices,
                    trainer) -> None:
        from dlrover_tpu.common.constants import NodeEnv

        # multi-slice hierarchical DP: this worker's slice identity.
        # With a slice id and a master, the gradient sync is two-level —
        # the jitted step returns the in-slice mean (split grad/apply)
        # and the cross-slice mean is exchanged host-side over DCN
        # (parallel/dcn_sync.py), tolerating an absent slice.
        self._slice_id = int(os.environ.get(NodeEnv.SLICE_ID, "-1"))
        slice_mode = self._slice_id >= 0 and self.client is not None
        # the host-level sync moves full gradient/state values through
        # host memory (np.asarray) — only valid when this process can
        # address every shard, i.e. a single-process slice world.
        # Multi-host slices use the single-program hierarchical path
        # instead (MeshSpec.dcn + the in-program dcn-axis reduce).
        slice_world = int(os.environ.get(NodeEnv.WORLD_SIZE, "1"))
        if slice_mode and slice_world > 1:
            logger.warning(
                "slice %d spans %d processes: the host-level DCN "
                "gradient sync needs a single-process slice world — "
                "disabling it (use the in-program hierarchical mesh, "
                "MeshSpec.dcn, for multi-host slices)",
                self._slice_id, slice_world)
            slice_mode = False
        # online parallelism re-plan (parallel/planner.py): the master's
        # deterministic mesh + batch shape for THIS world. Applied
        # before the mesh is built; any failure is LOUD
        # (replan_fallback flight event) and falls back to the
        # configured shape — the checkpoint-restart path of old.
        self._shard_plan: Optional[Dict[str, Any]] = None
        self._plan_mesh_spec: Optional[MeshSpec] = None
        self._replan_applied = ""       # "" | "batch" | "mesh+batch"
        # True when the applied plan's execution shape differs from
        # what the PREVIOUS incarnation ran (sidecar signature): only
        # then is this rebuild a RESIZE worth pricing — a plain
        # relaunch re-applying the unchanged plan must not mint
        # replan_* spans the goodput tools read as "a resize happened"
        self._replan_changed = False
        self.global_batch = config.global_batch
        self._trim_batch = 0
        # device-truth HBM peak watermark (obs/device.py): one
        # memory_stats read per local device per step, CPU-safe no-op
        # after one probe; the report-window peak rides the step report
        # so HbmPressureRule judges the IN-step transient, not the
        # between-steps trough the monitor tick samples. Built BEFORE
        # the trainer so every (re)build can mark a program-episode
        # boundary (note_recompile).
        self.device_telemetry = obs.DeviceTelemetry()
        if trainer is not None:
            self.trainer = trainer
            self.mesh = trainer.mesh
            self.dp = dp_size(self.mesh)
            self.accum = trainer.accum_steps
            self.micro_global = trainer.micro_batch
            # custom trainers (pipeline) own their step: no split path
            slice_mode = slice_mode and trainer.grad_fn is not None
        else:
            self._resolve_shard_plan(config, devices)
            try:
                self._build_dense_trainer(model, tx, loss_fn, config,
                                          devices, slice_mode)
            except Exception as e:  # noqa: BLE001 — a plan mesh the
                # MODEL cannot shard over (an axis size not dividing a
                # model dim the planner cannot see) must fall back to
                # the configured shape, loudly — never a crash-looping
                # worker
                if self._plan_mesh_spec is None:
                    raise
                self._replan_fallback(
                    self._shard_plan,
                    f"planned mesh rejected by the model/trainer: {e}")
                self._build_dense_trainer(model, tx, loss_fn, config,
                                          devices, slice_mode)
        self._slice_sync = None
        if slice_mode:
            from dlrover_tpu.parallel.dcn_sync import SliceGradSync

            # the slice's process 0 posts payloads; every rank collects
            is_leader = int(os.environ.get(NodeEnv.PROCESS_ID,
                                           "0")) == 0
            self._slice_sync = SliceGradSync(
                self.client, self._slice_id, is_leader=is_leader,
                abort_fn=lambda: self._stop_requested.is_set())
            logger.info("slice-scoped hierarchical DP armed: slice=%d "
                        "leader=%s", self._slice_id, is_leader)
        self.checkpointer = (
            FlashCheckpointer(config.checkpoint_dir,
                              config.save_interval_steps,
                              quantize_bits=config.checkpoint_quantize_bits)
            if config.checkpoint_dir else None
        )
        self._stop_requested = threading.Event()
        self.last_restore_timings: Dict[str, float] = {}
        # where the last restore's state came from: "peer" (surviving
        # hosts' staged memory), "mixed" (peer + shard-wise Orbax),
        # "orbax" (storage), "init" (fresh)
        self.last_restore_source = ""
        # peer-to-peer restore (checkpoint/peer_restore.py): the staging
        # store mirrors the live state host-side at every checkpoint
        # boundary; the restorer turns a master restore plan into a
        # shard transfer from surviving donors
        from dlrover_tpu.checkpoint.peer_restore import (
            PeerRestorer,
            PeerStateStore,
        )

        self._peer_store = (PeerStateStore.from_env()
                            if self.checkpointer is not None else None)
        self._peer_restorer = (
            PeerRestorer.from_env(client=self.client)
            if self.checkpointer is not None else None)
        if self._peer_restorer is not None and self._replan_changed:
            # re-plan migration: restore plans stripe each shard's byte
            # ranges across every same-step holder (the resharding
            # transfer primitive, checkpoint/peer_restore.py)
            self._peer_restorer.stripe = True
        self._chaos = None  # built lazily: env may be set post-init
        # completion accounting of the running loop (obs/stepmarks.py);
        # a fresh one per run()
        self._flight = obs.StepsInFlight()
        self._prev_sigterm = None
        # per-step phase attribution (data-wait / h2d / compute /
        # checkpoint), exported beside the metrics file for the agent +
        # tools/diagnose.py; the windowed means ride on step reports as
        # the master's straggler / data-bound evidence
        from dlrover_tpu.common.constants import NodeEnv

        self.timeline = obs.StepTimeline(
            role="worker",
            rank=int(os.environ.get(NodeEnv.NODE_RANK, "-1")))
        self._timeline_path = os.environ.get(NodeEnv.TIMELINE_FILE, "")
        self._timeline_exported_at = 0.0
        self._progress_logged_at = float("-inf")
        # data-pipeline auto-tune (data/prefetch.py): fed the timeline's
        # windowed data_wait fraction at each progress report; the input
        # pipeline consumes `prefetch_tuner.depth_fn` (and its ring
        # recommendation at rebuild boundaries) to stop starving steps
        from dlrover_tpu.common.config import Context as _TuneCtx

        if _TuneCtx.singleton().prefetch_autotune:
            from dlrover_tpu.data.prefetch import PrefetchAutoTuner

            self.prefetch_tuner = PrefetchAutoTuner()
            obs.get_registry().gauge(
                "dlrover_tpu_prefetch_depth",
                "Auto-tuned device-prefetch depth (data/prefetch.py; "
                "grows while the timeline's data_wait fraction exceeds "
                "the tune threshold, decays when the pipeline is calm)",
            ).set_function(self.prefetch_tuner.depth_fn)
        else:
            self.prefetch_tuner = None
        # per-step critical-path trace (obs/steptrace.py): one compact
        # record per step, clock-aligned against the master and batched
        # over the telemetry channel; the join-time probe anchors the
        # offset before the first step, report-cadence refreshes keep
        # the drift allowance small
        self._clock_sync = obs.ClockSync(
            probe_fn=(self.client.probe_clock
                      if self.client is not None else None))
        self._steptrace = obs.StepTraceRecorder(
            capacity=DefaultValues.STEPTRACE_RING,
            rank=int(os.environ.get(NodeEnv.NODE_RANK, "-1")),
            slice_id=self._slice_id,
            clock_sync=self._clock_sync)
        if self.client is not None:
            self._clock_sync.probe()
        # SliceGradSync's per-reduce marks, stashed by _slice_step for
        # the record built at the step boundary
        self._last_sync_trace: Optional[Dict[str, Any]] = None
        # profiler: static window (config) + on-demand captures the
        # agent requests on behalf of a master `profile:{rank}` action
        self.profiler = obs.ProfilerSession(
            request_path=os.environ.get(NodeEnv.PROFILE_REQUEST_FILE, ""),
            static_dir=config.profile_dir,
            static_start=config.profile_start_step,
            static_num=config.profile_num_steps,
        )
        # preemption drain / urgent-checkpoint requests from the agent,
        # consumed at step boundaries (one os.stat per step when armed)
        self._drain_source = DrainRequestSource()
        # step-hang backstop: no progress past hang_watchdog_s → stack
        # dump + self-abort so the agent restarts this worker (0 = off)
        from dlrover_tpu.common.config import Context

        watchdog_s = Context.singleton().hang_watchdog_s
        self._watchdog = (StepHangWatchdog(watchdog_s)
                          if watchdog_s > 0 else None)
        logger.info(
            "elastic loop: dp=%d accum=%d micro(global)=%d mesh=%s",
            self.dp, self.accum, self.micro_global,
            dict(self.mesh.shape),
        )
        # MFU accounting (obs/mfu.py): FLOPs/token + the mesh's
        # aggregate peak; 0 until _report_model_info derives them
        self._flops_per_token = 0.0
        self._peak_flops_total = 0.0
        self._flops_cross_checked = False
        self._report_model_info(model)

    # -- online parallelism re-planning (parallel/planner.py) --------------
    def _build_dense_trainer(self, model, tx, loss_fn, config, devices,
                             slice_mode) -> None:
        """Mesh + accumulation + jitted programs for the current shape
        (the planned mesh when a shard plan applied, the configured one
        otherwise). The trace is PROBED via ``abstract_state`` before
        returning so an invalid planned mesh fails here — inside the
        caller's fallback — instead of at first restore/step."""
        import contextlib

        import jax.numpy as jnp

        mesh_spec = self._plan_mesh_spec or config.mesh_spec
        self.mesh = create_mesh(mesh_spec, devices)
        self.dp = dp_size(self.mesh)
        if self.global_batch % self.dp:
            # the last line of "any world size": even the fallback
            # (configured) mesh must not crash-loop on a world whose dp
            # does not divide the batch — apply the planner's
            # round-DOWN-to-dp rule locally, loudly (the same
            # deliberate adjustment, never a silent wrong batch)
            adjusted = (self.global_batch // self.dp) * self.dp
            if adjusted <= 0:
                raise ValueError(
                    f"dp size {self.dp} exceeds the global batch "
                    f"{self.global_batch}: no mesh over this world can "
                    f"hold even one sample per replica")
            logger.error(
                "world dp %d does not divide the global batch %d: "
                "DELIBERATELY adjusting it to %d (input batches are "
                "trimmed; the sampler advances by the adjusted size)",
                self.dp, self.global_batch, adjusted)
            obs.get_flight_recorder().record_event(
                "replan_batch_adjusted", dp=self.dp,
                requested=self.global_batch, adjusted=adjusted,
                planned=self._plan_mesh_spec is not None)
            self.global_batch = adjusted
            self._trim_batch = adjusted
        self.accum, self.micro_global = choose_accumulation(
            self.global_batch, self.dp,
            config.max_micro_per_replica,
        )
        sample = jnp.zeros((self.micro_global, config.seq_len),
                           jnp.int32)
        # the re-lower after an elastic resize: trace + shardings +
        # jit wrappers for THIS world shape (XLA compile itself lands
        # in the recompile/aot span, train_step.precompile). Under a
        # re-plan the whole rebuild additionally lands in a
        # `replan_rebuild` span — the "rebuild" leg of the re-plan
        # decomposition (plan → migrate → rebuild) the goodput tools
        # price per resize. The nested relower `recompile` span
        # stays the ledger's compile evidence (no double count).
        # a new program is about to be built: the old one's recurring
        # in-step peak stops being HBM-pressure evidence unless the new
        # program re-reaches it (obs/device.py episode semantics)
        self.device_telemetry.note_recompile()
        rebuild_cm = (
            obs.span("replan_rebuild",
                     {"generation": self._shard_plan.get(
                         "generation", 0),
                      "mesh": dict(self.mesh.shape)})
            if self._replan_applied and self._replan_changed
            else contextlib.nullcontext())
        with rebuild_cm, obs.span(
                "recompile",
                {"phase": "relower",
                 "devices": self.dp,
                 "mesh": dict(self.mesh.shape)}) as relower_span:
            trainer = build_trainer(
                model, tx, self.mesh, sample, loss_fn,
                accum_steps=self.accum, micro_batch=self.micro_global,
                rules=config.rules,
                split_grad_apply=slice_mode,
            )
            relower_span.set_attr("head_loss_path", trainer.head_loss_path)
            relower_span.set_attr("head_loss_slices",
                                  trainer.head_loss_slices)
            if self._plan_mesh_spec is not None:
                import jax

                # cheap shape-only probe: surfaces "axis does not
                # divide dim" sharding rejections NOW (they otherwise
                # raise lazily at the first eval_shape/step)
                trainer.abstract_state(jax.random.PRNGKey(0))
        self.trainer = trainer

    def _resolve_shard_plan(self, config, devices=None) -> None:
        """Fetch + apply the master's parallelism plan for this world.

        The plan decides the mesh spec AND the (possibly deliberately
        adjusted) global batch before anything is traced, so a resize
        to ANY world size re-plans instead of crashing on a
        non-divisor batch. No plan at all (standalone runs, masters
        predating the planner) is silent — that is not a failure; a
        plan that cannot be applied is a LOUD ``replan_fallback``."""
        import json

        from dlrover_tpu.common.config import Context
        from dlrover_tpu.common.constants import NodeEnv

        if not Context.singleton().replan_enabled:
            return

        t0 = _time.monotonic()
        plan = None
        if self.client is not None:
            try:
                plan = self.client.get_shard_plan() or None
            except Exception:  # noqa: BLE001 — degrade to the file
                logger.warning("shard-plan RPC failed; trying the "
                               "join-result plan file",
                               exc_info=True)
        if plan is None:
            path = os.environ.get(NodeEnv.SHARD_PLAN_FILE, "")
            if path:
                try:
                    with open(path) as f:
                        loaded = json.load(f)
                    if isinstance(loaded, dict) and \
                            loaded.get("mesh"):
                        plan = loaded
                except (OSError, json.JSONDecodeError):
                    pass
        if plan is None:
            return
        try:
            self._apply_shard_plan(plan, config, devices)
        except Exception as e:  # noqa: BLE001 — the fallback path
            # must always be reachable: a broken plan falls back to
            # the configured shape, loudly, never a wedged worker
            self._replan_fallback(plan,
                                  f"plan application failed: {e}")
        if self._replan_changed:
            # the "plan" leg of the per-resize pricing — recorded only
            # when this rebuild IS a resize (see _replan_changed)
            obs.record_span(
                "replan_plan", _time.monotonic() - t0,
                attrs={"applied": self._replan_applied,
                       "generation": plan.get("generation", 0),
                       "epoch": plan.get("epoch", 0)})

    def _applied_plan_signature(self, plan: Dict[str, Any],
                                batch: int) -> str:
        """The execution shape this incarnation will run, as a stable
        string (mesh + effective batch + device count)."""
        import json

        return json.dumps({"mesh": plan.get("mesh"),
                           "global_batch": batch,
                           "total_devices": plan.get("total_devices"),
                           "applied": self._replan_applied},
                          sort_keys=True)

    def _note_replan_changed(self, plan: Dict[str, Any],
                             batch: int) -> None:
        """Decide whether this application is a RESIZE (shape differs
        from the previous incarnation's, remembered in a sidecar next
        to the agent-published plan file) or a plain relaunch
        re-applying the same plan. No sidecar path (RPC-only runs) →
        no memory → treated as changed. The sidecar is only READ
        here — it is written once the migration actually completes
        (``_commit_applied_plan``), so a worker that dies mid-resize
        re-runs (and re-prices) the resize on respawn instead of being
        misread as a plain relaunch."""
        from dlrover_tpu.common.constants import NodeEnv

        self._pending_plan_signature = self._applied_plan_signature(
            plan, batch)
        path = os.environ.get(NodeEnv.SHARD_PLAN_FILE, "")
        if not path:
            self._replan_changed = True
            return
        previous = None
        try:
            with open(f"{path}.applied") as f:
                previous = f.read()
        except OSError:
            pass
        self._replan_changed = previous != self._pending_plan_signature

    def _commit_applied_plan(self) -> None:
        """The resize completed (state restored/migrated under the new
        shape): remember the applied signature so the NEXT incarnation
        can tell a plain relaunch from a resize."""
        signature = getattr(self, "_pending_plan_signature", None)
        if not signature:
            return
        from dlrover_tpu.common.constants import NodeEnv

        path = os.environ.get(NodeEnv.SHARD_PLAN_FILE, "")
        if not path:
            return
        try:
            tmp = f"{path}.applied.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                f.write(signature)
            os.replace(tmp, f"{path}.applied")
        except OSError:
            pass

    def _apply_shard_plan(self, plan: Dict[str, Any], config,
                          devices=None) -> None:
        import math

        import jax

        from dlrover_tpu.parallel import planner

        # base sanity (feasibility, mesh factors the planned devices,
        # positive batch) is the shared helper's job; the device-count
        # comparison is layered below because slice mode and the
        # independent-replica harness legitimately build less than the
        # plan's global device count
        error = planner.validate_plan(plan, n_devices=0)
        if error is not None:
            self._replan_fallback(plan, error)
            return
        # slice mode builds the per-slice portion (dcn=1): each slice
        # is its own jax program, the dcn axis lives in the host-level
        # cross-slice sync (parallel/dcn_sync.py)
        mesh_dict = (planner.slice_mesh(plan) if self._slice_id >= 0
                     else dict(plan.get("mesh", {})))
        mesh_total = math.prod(int(mesh_dict.get(k, 1)) for k in
                               ("dcn", "data", "fsdp", "tensor", "pipe"))
        n_devices = (len(devices) if devices is not None
                     else jax.device_count())
        world_size = max(1, int(plan.get("world_size", 1) or 1))
        apply_mesh = mesh_total == n_devices
        if not apply_mesh:
            # the CPU multi-process harness runs each rank as an
            # independent full replica (no cross-process SPMD): the
            # global mesh cannot be built locally, but the BATCH plan —
            # the part a divisor-unfriendly resize actually needs —
            # still applies. Anything else is a real mismatch.
            replica_mode = (jax.process_count() == 1
                            and world_size > 1
                            and mesh_total == n_devices * world_size)
            if not replica_mode:
                self._replan_fallback(
                    plan, f"plan mesh covers {mesh_total} device(s); "
                          f"this process sees {n_devices}")
                return
        # the batch contract: honor the planned batch when the plan was
        # computed for the batch this loop was configured with; a plan
        # from a stale profile adjusts LOCALLY by the same
        # round-down-to-dp rule (deliberate either way, never silent)
        planned_batch = int(plan.get("global_batch", 0) or 0)
        requested = int(plan.get("requested_global_batch", 0) or 0)
        if requested != config.global_batch \
                or planned_batch > config.global_batch:
            dp = int(plan.get("dp", 0) or 0) or 1
            planned_batch, _ = planner.adjust_global_batch(
                config.global_batch, dp)
            if planned_batch <= 0:
                self._replan_fallback(
                    plan, f"planned dp {dp} exceeds the configured "
                          f"global batch {config.global_batch}")
                return
        if apply_mesh:
            self._plan_mesh_spec = MeshSpec(
                data=int(mesh_dict.get("data", 1)),
                fsdp=int(mesh_dict.get("fsdp", 1)),
                tensor=int(mesh_dict.get("tensor", 1)),
                pipe=int(mesh_dict.get("pipe", 1)),
                dcn=int(mesh_dict.get("dcn", 1)),
            )
        self.global_batch = planned_batch
        self._trim_batch = (planned_batch
                            if planned_batch < config.global_batch
                            else 0)
        self._shard_plan = plan
        self._replan_applied = "mesh+batch" if apply_mesh else "batch"
        self._note_replan_changed(plan, planned_batch)
        obs.get_flight_recorder().record_event(
            "replan_applied",
            applied=self._replan_applied,
            changed=self._replan_changed,
            mesh=mesh_dict,
            global_batch=planned_batch,
            requested_global_batch=config.global_batch,
            batch_adjusted=planned_batch != config.global_batch,
            resharded=bool(plan.get("resharded")),
            generation=plan.get("generation", 0),
            epoch=plan.get("epoch", 0),
            world_size=world_size)
        obs.get_registry().counter(
            "dlrover_tpu_replan_applied_total",
            "Parallelism plans applied at worker (re)build",
            labelnames=("applied",),
        ).labels(applied=self._replan_applied).inc()
        if planned_batch != config.global_batch:
            logger.warning(
                "re-plan DELIBERATELY adjusted the global batch "
                "%d -> %d (dp %s does not divide it); input batches "
                "are trimmed, the sampler advances by the adjusted "
                "size", config.global_batch, planned_batch,
                plan.get("dp"))
        logger.info(
            "shard plan applied (%s): mesh=%s batch=%d generation=%s "
            "epoch=%s", self._replan_applied, mesh_dict, planned_batch,
            plan.get("generation"), plan.get("epoch"))

    def _replan_fallback(self, plan: Optional[Dict[str, Any]],
                         reason: str) -> None:
        """The hard fallback: today's checkpoint-restart path (the
        configured mesh + Orbax/peer restore at the configured batch).
        Loud by contract — a planner or plan-application failure must
        be visible in the flight dump, never a silently wrong shape."""
        self._shard_plan = None
        self._plan_mesh_spec = None
        self._replan_applied = ""
        self._replan_changed = False
        self.global_batch = self.config.global_batch
        self._trim_batch = 0
        obs.get_flight_recorder().record_event(
            "replan_fallback", reason=reason[:256],
            generation=(plan or {}).get("generation", 0),
            epoch=(plan or {}).get("epoch", 0),
            mesh=(plan or {}).get("mesh"))
        obs.get_registry().counter(
            "dlrover_tpu_replan_fallbacks_total",
            "Re-plans abandoned for the configured-shape "
            "checkpoint-restart path").inc()
        logger.error(
            "parallelism re-plan falling back to the configured shape: "
            "%s (the checkpoint-restart path still applies)", reason)

    def _report_model_info(self, model=None) -> None:
        """One-shot static stats to the master's resource optimizer
        (reference: profile_extractor → ModelInfo) plus the FLOPs model
        behind every MFU number (obs/mfu.py:model_flops_per_token): the
        model's own count where its config gives one, else analytic
        6·params with the causal attention term when the config exposes
        its shape; cross-checked later against the compiled step's XLA cost
        analysis (_maybe_cross_check_flops)."""
        try:
            abstract = self.trainer.abstract_state(jax.random.PRNGKey(0))
            leaves = jax.tree.leaves(abstract.params)
            param_count = sum(int(np.prod(l.shape)) for l in leaves)
            param_bytes = sum(
                int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves)
            tokens_per_step = self.global_batch * self.config.seq_len
            cfg = getattr(model, "config", None)
            self._param_count = param_count
            self._param_bytes = param_bytes
            # the model is asked where it can answer (active experts,
            # selected pairs); else its shape is read off attribute names
            self._flops_per_token = obs.mfu.model_flops_per_token(
                cfg, param_count, self.config.seq_len)
            # a model's own count stands: XLA's cost analysis sees no
            # kernel's FLOPs and counts a recomputed block twice
            self._flops_from_model = callable(
                getattr(cfg, "flops_per_token", None))
            # the chips THIS loop trains on: the mesh, which is every
            # device unless the caller handed the loop a subset
            device = self.mesh.devices.flat[0]
            peak_chip = obs.mfu.peak_flops_per_chip(
                getattr(device, "device_kind", ""),
                backend=jax.default_backend())
            chips = self.mesh.size
            self._peak_flops_total = peak_chip * max(1, chips)
            if self.client is None:
                return
            # dim-divisibility granules for the planner: a tensor way
            # must divide every tensor-sharded dim (heads/kv/mlp/
            # vocab), an fsdp way the embed dim — gcd'ed so the master
            # can filter candidates it cannot trace-probe itself
            import math as _math

            tensor_dims = [int(getattr(cfg, k, 0) or 0)
                           for k in ("num_heads", "n_head",
                                     "num_kv_heads",
                                     "intermediate_size", "vocab_size")]
            tensor_dims = [d for d in tensor_dims if d > 0]
            tensor_divisor = (_math.gcd(*tensor_dims)
                              if tensor_dims else 0)
            fsdp_divisor = int(getattr(cfg, "hidden_size", 0)
                               or getattr(cfg, "n_embd", 0) or 0)
            # batch_size = the CONFIGURED batch (the planner's
            # requested baseline — reporting the adjusted one would
            # ratchet the profile down after every adjusting resize);
            # effective_global_batch = what this incarnation actually
            # trains (the tokens/s denominator)
            self.client.report_model_info(
                param_count=param_count, param_bytes=param_bytes,
                flops_per_step=self._flops_per_token * tokens_per_step,
                batch_size=self.config.global_batch,
                seq_len=self.config.seq_len,
                flops_per_token=self._flops_per_token,
                peak_flops_per_chip=peak_chip,
                chips=chips,
                flops_source="analytic",
                tensor_divisor=tensor_divisor,
                fsdp_divisor=fsdp_divisor,
                effective_global_batch=self.global_batch,
            )
        except Exception:   # noqa: BLE001 — stats are advisory
            logger.warning("model-info report failed", exc_info=True)

    def _maybe_cross_check_flops(self) -> None:
        """Once, after the step is AOT-compiled: cross-check the
        analytic FLOPs/token against XLA's cost analysis of the actual
        program. On a >2x divergence (an exotic model the 6·params
        formula misjudges) the measured value is adopted and
        re-reported, so MFU gauges track what the hardware really
        executes."""
        if self._flops_cross_checked:
            return
        compiled = getattr(self.trainer, "_compiled_step", None)
        if compiled is None:
            return
        self._flops_cross_checked = True
        # one compile event per AOT build: the compiled step's
        # cost-analysis FLOPs/bytes into the flight record and gauges
        # (obs/device.py) — the device truth behind the MFU cross-check
        # below and the calibration table's predictions (the compile's
        # time is the AOT `recompile` span's)
        try:
            obs.device.record_compile_event(
                compiled=compiled, kind="aot", mesh=dict(self.mesh.shape))
        except Exception:  # noqa: BLE001 — telemetry, never the loop
            logger.warning("compile event record failed", exc_info=True)
        measured = obs.mfu.cost_analysis_flops(compiled)
        tokens_per_step = self.global_batch * self.config.seq_len
        adopted = obs.mfu.cross_check(self._flops_per_token, measured,
                                      tokens_per_step)
        if adopted is None or getattr(self, "_flops_from_model", False):
            return
        logger.warning(
            "FLOPs model cross-check: analytic %.3e/token vs XLA cost "
            "analysis %.3e/token — adopting the measured value",
            self._flops_per_token, adopted)
        self._flops_per_token = adopted
        if self.client is not None:
            try:
                device = self.mesh.devices.flat[0]
                self.client.report_model_info(
                    param_count=getattr(self, "_param_count", 0),
                    param_bytes=getattr(self, "_param_bytes", 0),
                    flops_per_step=adopted * tokens_per_step,
                    batch_size=self.config.global_batch,
                    effective_global_batch=self.global_batch,
                    seq_len=self.config.seq_len,
                    flops_per_token=adopted,
                    peak_flops_per_chip=obs.mfu.peak_flops_per_chip(
                        getattr(device, "device_kind", ""),
                        backend=jax.default_backend()),
                    chips=self.mesh.size,
                    flops_source="cost_analysis",
                )
            except Exception:  # noqa: BLE001 — stats are advisory
                pass

    # -- signals -----------------------------------------------------------
    def install_signal_handler(self) -> None:
        """SIGTERM (agent restart) → finish the step, force-save, exit."""

        def _handler(signum, frame):
            logger.info("SIGTERM: will checkpoint and stop after this step")
            recorder = obs.get_flight_recorder()
            recorder.record_event("sigterm", pid=os.getpid())
            recorder.dump(reason="sigterm")
            self._stop_requested.set()

        self._prev_sigterm = signal.signal(signal.SIGTERM, _handler)

    # -- restore -----------------------------------------------------------
    def restore_or_init(self, rng,
                        sampler: Optional[ElasticDistributedSampler] = None
                        ) -> Tuple[Any, int]:
        """Restore the latest checkpoint onto THIS mesh (resharding as
        needed) or initialize fresh. Returns (state, start_step).

        Restore is attempted against an ABSTRACT target (shapes +
        shardings, no allocation) so a resume never holds two full copies
        of params+optimizer state in HBM.

        While the checkpoint bytes stream, the train step is AOT-compiled
        in a background thread (trace + lower + XLA compile / persistent-
        cache load from the abstract state) so a respawned worker pays
        max(read, compile), not read + compile. Per-phase wall-clock lands
        in `self.last_restore_timings`."""

        timings: Dict[str, float] = {}
        self.last_restore_timings = timings
        with obs.span("restore_or_init") as restore_span:
            t_migrate = _time.monotonic()
            compile_thread = None
            if (self.config.overlap_restore_compile
                    and hasattr(self.trainer, "precompile")):
                # the thread's AOT `recompile` span nests under this one
                compile_thread = threading.Thread(
                    target=self._precompile_quietly,
                    args=(restore_span.context(),), daemon=True)
                t_compile_start = _time.monotonic()
                compile_thread.start()
            if self.checkpointer is None:
                state, step = self._init_state(rng), 0
                self.last_restore_source = "init"
            else:
                t0 = _time.monotonic()
                abstract = self.trainer.abstract_state(rng)
                timings["abstract_state_s"] = round(
                    _time.monotonic() - t0, 2)
                source = "orbax"
                restored = None
                if self._peer_restorer is not None:
                    # the peer branch: surviving hosts' staged state
                    # instead of the storage round-trip, overlapped with
                    # the same background compile as the Orbax read
                    peer = None
                    try:
                        peer = self._peer_restorer.restore(
                            abstract, self.checkpointer, timings)
                    except Exception:  # noqa: BLE001 — peers are an
                        # optimization; storage is the ground truth
                        logger.warning("peer restore failed; falling "
                                       "back to Orbax", exc_info=True)
                    if peer is not None:
                        p_state, p_data, p_step, source = peer
                        restored = (p_state, p_data, p_step)
                if restored is None:
                    source = "orbax"
                    t0 = _time.monotonic()
                    restored = self.checkpointer.restore(abstract)
                    timings["orbax_read_s"] = round(
                        _time.monotonic() - t0, 2)
                    # the checkpointer's own per-phase decomposition
                    # (step discovery / metadata / tensor read / decode,
                    # bytes + bandwidth) nests under orbax_read_s
                    for key, value in getattr(self.checkpointer,
                                              "last_restore_phases",
                                              {}).items():
                        timings[f"restore_{key}"] = value
                if restored is None:
                    state, step = self._init_state(rng), 0
                    self.last_restore_source = "init"
                else:
                    self.last_restore_source = source
                    if source == "orbax":
                        # peer/mixed count themselves (with the donor
                        # table) inside the restorer
                        obs.get_registry().counter(
                            "dlrover_tpu_restore_source_total",
                            "Elastic restores by state source",
                            labelnames=("source",),
                        ).labels(source="orbax").inc()
                    state, data_state, step = restored
                    # split the read from any deferred host->device
                    # transfer (remote-execution backends materialize
                    # lazily)
                    t0 = _time.monotonic()
                    with obs.span("restore_device_put", {"step": step}):
                        jax.block_until_ready(state)
                    timings["device_ready_s"] = round(
                        _time.monotonic() - t0, 2)
                    # post-restore host sync: data position back into
                    # the sampler + the master's shard checkpoint
                    t0 = _time.monotonic()
                    with obs.span("restore_post_sync", {"step": step}):
                        if sampler is not None and \
                                "sampler" in data_state:
                            sampler.load_state_dict(data_state["sampler"])
                        if self.client is not None and \
                                data_state.get("shards"):
                            try:
                                self.client.report_shard_checkpoint(
                                    data_state["shards"])
                            except Exception:
                                logger.warning(
                                    "could not restore master shard "
                                    "checkpoint")
                    timings["post_sync_s"] = round(
                        _time.monotonic() - t0, 2)
            if self._shard_plan is not None and self._replan_changed:
                # the "migrate" leg of the re-plan decomposition
                # (plan → migrate → rebuild): live state landed under
                # the NEW sharding — from peers when any survive, with
                # the shard-wise Orbax fallback otherwise — WITHOUT a
                # checkpoint round-trip on the happy path. Recorded as
                # its own span (nested evidence for the flight dump /
                # goodput tools; the restore_or_init span remains the
                # ledger's restore bucket). Gated on _replan_changed: a
                # plain relaunch re-applying the unchanged plan is not
                # a resize and must not be priced as one.
                migrate_s = _time.monotonic() - t_migrate
                timings["replan_migrate_s"] = round(migrate_s, 3)
                obs.record_span(
                    "replan_migrate", migrate_s,
                    attrs={"step": step,
                           "source": self.last_restore_source,
                           "bytes": timings.get("peer_bytes", 0.0),
                           "generation": self._shard_plan.get(
                               "generation", 0),
                           "resharded": bool(self._shard_plan.get(
                               "resharded"))})
            if compile_thread is not None:
                t0 = _time.monotonic()
                compile_thread.join()
                timings["compile_wait_after_read_s"] = round(
                    _time.monotonic() - t0, 2)
                timings["compile_total_s"] = round(
                    _time.monotonic() - t_compile_start, 2)
                timings.update(
                    getattr(self.trainer, "precompile_timings", {}))
            restore_span.set_attr("start_step", step)
            restore_span.set_attr("source", self.last_restore_source)
            for key, value in timings.items():
                restore_span.set_attr(key, value)
        if timings:
            logger.info("restore timings: %s", timings)
        if self._slice_sync is not None:
            # a re-formed slice behind the fleet adopts the current
            # state over DCN (restore_source/step above still record
            # what the RESTORE produced — the catch-up is on top)
            state, step = self._maybe_slice_catch_up(state, step,
                                                     sampler)
        # the migration landed: commit the applied-plan signature so a
        # future PLAIN relaunch is not re-priced as a resize (a crash
        # before this point deliberately leaves the old signature — the
        # respawn re-runs the resize)
        self._commit_applied_plan()
        self._flush_telemetry()
        return state, step

    def _init_state(self, rng):
        """Weights from the seed, ready on the device, inside a
        ``state_init`` span (``bytes``: the state's). The wait costs
        nothing: the first step would wait for them, and the AOT compile
        keeps running on its thread meanwhile."""
        with obs.span("state_init") as init_span:
            state = self.trainer.init(rng)
            jax.block_until_ready(state)
            init_span.set_attr("bytes", sum(
                leaf.nbytes for leaf in jax.tree_util.tree_leaves(state)))
        return state

    def _precompile_quietly(self, parent: Dict[str, str]) -> None:
        try:
            with obs.attach(parent):
                self.trainer.precompile()
        except Exception:
            # AOT is an optimization: the jitted path compiles on first
            # step regardless
            logger.warning("train-step precompile failed; first step "
                           "will compile inline", exc_info=True)

    # -- main loop ---------------------------------------------------------
    def run(
        self,
        state,
        batches: Iterable[Tuple[np.ndarray, np.ndarray]],
        start_step: int = 0,
        sampler: Optional[ElasticDistributedSampler] = None,
    ) -> Tuple[Any, Dict[str, float]]:
        """Train over (tokens, targets) global batches. Returns the final
        state and last metrics."""
        raw_metrics: Dict[str, Any] = {}
        if self._watchdog is not None:
            self._watchdog.start()
        try:
            return self._run_inner(state, batches, start_step, sampler,
                                   raw_metrics)
        finally:
            if self._watchdog is not None:
                self._watchdog.stop()
            # a step failure (the expected failure mode here) must still
            # flush an active profiler trace, or the next loop's
            # start_trace raises on the dangling session
            self.profiler.stop()

    def _run_inner(self, state, batches, start_step, sampler,
                   raw_metrics):
        config = self.config
        step = start_step
        if self._chaos is None:
            from dlrover_tpu.diagnostics.chaos import ChaosInjector

            self._chaos = ChaosInjector()
        batch_iter = iter(batches)
        # the step's marks are taken ONCE per iteration (obs/stepmarks.py)
        # and feed the timeline, steptrace and the train_window span;
        # each phase is also a profiler annotation, so with a profiler
        # session on the same intervals lie on the host lines of the
        # trace, on the device events' clock (with none they cost a flag
        # test each). `dlrover/...`, never `input`: that name belongs
        # to whoever feeds the loop.
        clock = _time.monotonic
        flight = self._flight = obs.StepsInFlight(clock)
        window = obs.LoopWindow(first_step=step + 1)
        boundary = clock()
        while True:
            marks = obs.StepMarks(clock, TraceAnnotation, started=boundary)
            with StepTraceAnnotation("dlrover/train_step",
                                     step_num=step + 1):
                # the step BOUNDARY is where a drain request is
                # consumed: `state` is a complete post-step state here,
                # so the emergency save never snapshots mid-accumulation
                drain = self._drain_source.poll()
                if drain is not None:
                    # the deadline-bounded emergency save can
                    # legitimately block for minutes of Orbax commit:
                    # disarm the watchdog (a save is not a stall),
                    # re-arm for save-and-continue
                    if self._watchdog is not None:
                        self._watchdog.stop()
                    with marks.phase("save", "dlrover/save"):
                        self._consume_drain(drain, step, state, sampler)
                    if self._watchdog is not None:
                        self._watchdog.start()
                # data-wait measured explicitly: the time this loop
                # starves on the input pipeline is the diagnosis
                # engine's "pipeline-bound, not a hardware straggler"
                # signal
                try:
                    with marks.phase("fetch", "dlrover/fetch"):
                        tokens, targets = next(batch_iter)
                except StopIteration:
                    # the data ran out: this iteration's time counts in
                    # the window, but it is no step
                    marks.close()
                    window.add(marks, 0, len(flight), took_step=False)
                    break
                if self._trim_batch and len(tokens) > self._trim_batch:
                    # the re-plan's deliberate batch adjustment: the
                    # input pipeline still yields the configured batch;
                    # train on the planned (dp-divisible) prefix.
                    # Recorded once in the replan_applied event — never
                    # a silent truncation.
                    tokens = tokens[:self._trim_batch]
                    targets = targets[:self._trim_batch]
                self.profiler.poll(step - start_step)
                with marks.phase("shard", "dlrover/shard_batch"):
                    tok, tgt = self.trainer.shard_batch(tokens, targets)
                with marks.phase("dispatch", "dlrover/dispatch"):
                    if self._slice_sync is not None:
                        state, raw_metrics = self._slice_step(
                            state, tok, tgt, step + 1)
                    else:
                        state, raw_metrics = self.trainer.step(
                            state, tok, tgt)
                marks.dispatch_done = clock()
                step += 1
                # completion accounting: one scalar of this step's
                # outputs joins the queue, and whatever the device has
                # finished meanwhile leaves it (is_ready, never a wait)
                flight.dispatched(
                    next(iter(raw_metrics.values()), None),
                    {name: value for name, value in raw_metrics.items()
                     if name not in ("loss", "grad_norm")})
                completed = flight.poll()
                # scripted fault injection (no-op unless
                # DLROVER_TPU_CHAOS)
                self._chaos.maybe_inject(step)
                if sampler is not None:
                    # the EFFECTIVE batch (re-plan adjusted when the
                    # world does not divide the configured one): the
                    # sampler's position advances by what was actually
                    # consumed
                    sampler.record_batch(self.global_batch)
                if self.checkpointer is not None:
                    forced = self._stop_requested.is_set()
                    data_state = self._data_state(sampler)
                    with marks.phase("save", "dlrover/save"):
                        saved = self.checkpointer.maybe_save(
                            step, state, data_state, force=forced,
                        )
                    if saved:
                        # mirror the saved cut into the host-RAM peer
                        # cache: peer step N and Orbax step N are the
                        # same cut, so a shard-wise restore across both
                        # sources stays consistent (with a quantized
                        # checkpoint the peer copy keeps live precision
                        # — strictly higher fidelity than the storage
                        # path's dequantized leaves)
                        with marks.phase("save", "dlrover/peer_stage"):
                            self._stage_peer(step, state, data_state)
                if self._watchdog is not None:
                    self._watchdog.notify_step(step)
                self.device_telemetry.on_step(step)
                # the timeline and steptrace see the step as it stands
                # before the report-cadence work, which reads them
                seconds = marks.seconds
                self.timeline.record(
                    step, marks.elapsed(),
                    data_wait=seconds["fetch"], h2d=seconds["shard"],
                    compute=seconds["dispatch"],
                    checkpoint=seconds["save"],
                )
                self._record_steptrace(step, marks)
                due = step % config.report_interval_steps == 0
                if due and self.client is not None:
                    with marks.phase("report", "dlrover/report"):
                        self._report_progress(step)
                        self._flush_telemetry()
                boundary = marks.close()
                window.add(marks, completed, len(flight),
                           counted=flight.take_counted())
                if due:
                    self._emit_train_window(window)
                    window = obs.LoopWindow(first_step=step + 1)
            if self._stop_requested.is_set():
                logger.info("stopping at step %d on request", step)
                obs.get_flight_recorder().record_event(
                    "train_stop_requested", step=step)
                break
            if config.max_steps and step - start_step >= config.max_steps:
                break
        # what the last full interval left over, before the sync below
        # makes every step in flight "seen done" at once
        self._emit_train_window(window)
        # out of the step loop: disarm the watchdog before the final
        # sync/commit waits (a long but legitimate final checkpoint
        # commit is not a step hang)
        if self._watchdog is not None:
            self._watchdog.stop()
        # the device→host sync point: converting metrics blocks on the
        # last step's results (the only host sync the steady-state loop
        # pays — worth a span so slow syncs are visible in postmortems)
        with obs.span("host_sync", {"step": step}):
            metrics = {k: float(v) for k, v in raw_metrics.items()}
        # the step actually REACHED (an early stop — SIGTERM, exhausted
        # data — ends below start_step + max_steps; callers must not
        # assume the request was met)
        metrics["step"] = float(step)
        if self.checkpointer is not None:
            with obs.span("checkpoint_wait"):
                self.checkpointer.wait()
        if self._timeline_path:
            # final flush: runs shorter than a report interval must
            # still leave a timeline on disk for postmortems
            self.timeline.export(self._timeline_path)
        self._flush_telemetry()
        return state, metrics

    @staticmethod
    def _emit_train_window(window) -> None:
        """One lifecycle-rate span per report interval (and one for the
        remainder at loop end), with or without a master: the loop's own
        account of where its host time went and of what the device
        finished. Not one span per step: the flight recorder's span ring
        and the SpanExporter hold lifecycle spans a postmortem needs."""
        if window.wall_s > 0.0:
            obs.record_span("train_window", window.wall_s, window.attrs())
            counters = window.counters()
            if counters:
                logger.info(
                    "train window from step %d, the model's counters "
                    "(mean over the steps seen done): %s", window.first_step,
                    ", ".join(f"{name}={mean:.4g} ({steps})" for name,
                              (mean, steps) in sorted(counters.items())))

    # -- multi-slice hierarchical DP ---------------------------------------
    def _slice_step(self, state, tok, tgt, step: int):
        """One hierarchical step: in-slice grads from the jitted
        grad_fn, cross-slice mean over DCN (tolerating an absent
        slice — degraded mode), optimizer update from the fleet mean.
        The pre-update ``state`` doubles as the rejoin-handoff payload
        the fleet leader may publish for a re-formed slice."""
        import jax

        grads, raw_metrics = self.trainer.grad_step(state, tok, tgt)
        leaves, treedef = jax.tree.flatten(grads)
        host_leaves = [np.asarray(leaf) for leaf in leaves]

        def _state_leaves():
            return [np.asarray(leaf) for leaf in jax.tree.leaves(state)]

        reduced, info = self._slice_sync.reduce(
            host_leaves, step, state_leaves_fn=_state_leaves)
        if info.get("degraded") or info.get("stalled_s"):
            obs.get_flight_recorder().record_event(
                "train_degraded_step", step=step,
                present=info.get("present"), absent=info.get("absent"),
                stalled_s=round(float(info.get("stalled_s", 0.0)), 1))
        fleet_grads = jax.tree.unflatten(treedef, [
            jax.device_put(leaf, sharding)
            for leaf, sharding in zip(
                reduced,
                jax.tree.leaves(self.trainer.state_shardings.params))
        ])
        state, apply_metrics = self.trainer.apply_grads(state,
                                                        fleet_grads)
        if info.get("trace"):
            # the sync's clock() marks share the loop's monotonic
            # domain; apply-dispatch end completes the decomposition
            stashed = dict(info["trace"])
            stashed["apply_done"] = _time.monotonic()
            self._last_sync_trace = stashed
        raw_metrics = dict(raw_metrics)
        raw_metrics.update(apply_metrics)
        return state, raw_metrics

    def _trace_generation(self) -> int:
        """The membership episode steptrace records group under: the
        world epoch the slice sync saw last, else the applied plan's
        epoch, else 0 (static single-slice world)."""
        if self._slice_sync is not None:
            epoch = self._slice_sync.world_epoch
            if epoch >= 0:
                return epoch
        if self._shard_plan is not None:
            try:
                return int(self._shard_plan.get("epoch", 0) or 0)
            except (TypeError, ValueError):
                pass
        return 0

    def _record_steptrace(self, step: int, marks) -> None:
        """Build one per-step trace record from the iteration's marks
        (+ the stashed SliceGradSync decomposition). Hot path: a handful
        of float ops and one bounded-ring append."""
        now_mono = _time.monotonic()
        t_step, t_compute_end = marks.started, marks.dispatch_done
        # local wall-clock anchor for the step start, derived from the
        # same monotonic domain as every mark (a wall-clock step between
        # t_step and now lands in the offset estimate, not the phases)
        t0_wall = _time.time() - (now_mono - t_step)
        data_d = marks.seconds["fetch"]
        h2d_d = marks.seconds["shard"]
        ckpt_s = marks.seconds["save"]
        phases = [("data_wait", 0.0, data_d), ("h2d", data_d, h2d_d)]
        cursor = data_d + h2d_d
        peers = None
        stashed, self._last_sync_trace = self._last_sync_trace, None
        if stashed is not None:
            ready = stashed.get("grads_ready", t_compute_end) - t_step
            post = max(ready, stashed.get("local_post", 0.0) - t_step)
            coll = max(post, stashed.get("collect_done", 0.0) - t_step)
            apply_end = max(coll,
                            stashed.get("apply_done", t_compute_end)
                            - t_step)
            phases.append(("compute", cursor, max(0.0, ready - cursor)))
            phases.append(("local_post", ready, post - ready))
            phases.append(("cross_slice_wait", post, coll - post))
            phases.append(("apply", coll, apply_end - coll))
            cursor = max(cursor, apply_end)
            raw_peers = stashed.get("peers") or {}
            if raw_peers:
                peers = {sid: max(0.0, t - t_step)
                         for sid, t in raw_peers.items()}
        else:
            compute_end = max(cursor, t_compute_end - t_step)
            phases.append(("compute", cursor, compute_end - cursor))
            cursor = compute_end
        if ckpt_s > 0:
            phases.append(("checkpoint", cursor, ckpt_s))
        self._steptrace.record(step, self._trace_generation(), t0_wall,
                               phases, peers=peers)

    def _maybe_slice_catch_up(self, state, start_step: int, sampler
                              ) -> Tuple[Any, int]:
        """A re-formed slice restored at the checkpointed step while
        the fleet kept (degraded-mode) stepping: adopt the fleet-current
        state a surviving slice leader publishes over DCN, so this
        slice resumes in lockstep instead of re-treading steps the
        survivors already took."""
        import jax

        result = self._slice_sync.catch_up(start_step)
        if result is None:
            return state, start_step
        leaves, fleet_step = result
        template_leaves, treedef = jax.tree.flatten(state)
        if len(leaves) != len(template_leaves):
            logger.error(
                "fleet state handoff has %d leaves, local state %d: "
                "model mismatch — ignoring the handoff",
                len(leaves), len(template_leaves))
            return state, start_step
        shardings = jax.tree.leaves(self.trainer.state_shardings)
        adopted = jax.tree.unflatten(treedef, [
            jax.device_put(
                np.asarray(leaf).astype(tmpl.dtype).reshape(tmpl.shape),
                sharding)
            for leaf, tmpl, sharding in zip(leaves, template_leaves,
                                            shardings)
        ])
        if sampler is not None:
            for _ in range(max(0, fleet_step - start_step)):
                sampler.record_batch(self.global_batch)
        self.last_restore_timings["catch_up_steps"] = float(
            fleet_step - start_step)
        return adopted, fleet_step

    # -- preemption drain --------------------------------------------------
    def _consume_drain(self, drain: Dict[str, Any], step, state,
                       sampler) -> None:
        """Act on a drain/checkpoint request from the agent at a step
        boundary. ``exit=True`` (preemption): deadline-bounded emergency
        save, flush the postmortem, and leave with the clean-drain exit
        code (raises :class:`DrainExit`). ``exit=False`` (the master's
        urgent ``checkpoint`` fan-out): save now, keep training."""

        deadline = float(drain.get("deadline", 0.0) or 0.0)
        reason = str(drain.get("reason", ""))
        exit_worker = bool(drain.get("exit", True))
        recorder = obs.get_flight_recorder()
        recorder.record_event(
            "train_drain", step=step, deadline=deadline,
            exit=exit_worker, reason=reason[:256])
        logger.warning(
            "drain request at step %d (deadline in %.0fs, exit=%s): %s",
            step,
            max(0.0, deadline - _time.time()) if deadline else -1.0,
            exit_worker, reason or "-")
        outcome = "no-checkpointer"
        data_state = self._data_state(sampler)
        if self.checkpointer is not None:
            # the deadline is a hard bound only on the way OUT (this
            # VM dies then). A survivor's save-and-continue inherits
            # the draining PEER's deadline — advisory at best: this
            # worker is not dying, and skipping/aborting its save
            # because the peer's window is short defeats the fan-out
            outcome = self.checkpointer.save_emergency(
                step, state, data_state,
                deadline=deadline if exit_worker else 0.0)
            if outcome == "saved" and not exit_worker:
                # a survivor's save-and-continue: mirror the cut into
                # the peer cache too — this survivor is exactly who the
                # departing rank's replacement will restore from. The
                # exiting path skips it: this host's memory dies with
                # the VM.
                self._stage_peer(step, state, data_state)
        elif exit_worker:
            logger.error("drain with no checkpointer configured: "
                         "exiting WITHOUT saving (progress since the "
                         "last external save is lost)")
        if not exit_worker:
            self._drain_source.acknowledge(int(drain.get("seq", 0) or 0))
            return
        # the way out: postmortem + telemetry first, then the distinct
        # clean-drain exit the agent classifies as non-failure
        if self._timeline_path:
            self.timeline.export(self._timeline_path)
        recorder.record_event("train_drained", step=step,
                              checkpoint=outcome)
        self._flush_telemetry()
        recorder.dump(reason="drain")
        logger.info("drained at step %d (checkpoint: %s); exiting %d",
                    step, outcome, WorkerExit.DRAIN)
        raise DrainExit(reason)

    # -- peer-state staging --------------------------------------------
    def _stage_peer(self, step: int, state, data_state) -> None:
        """Mirror the just-saved state into the host-RAM peer cache.
        The step loop pays only the device→host copy (the arrays may be
        donated away by the next step); file writes + CRCs run on the
        store's background writer. Best-effort: the loop survives a
        full cache disk."""
        if self._peer_store is None:
            return
        t0 = _time.monotonic()
        with obs.span("peer_stage", {"step": step}) as stage_span:
            staged = self._peer_store.stage(step, state, data_state,
                                            defer_write=True)
            stage_span.set_attr("staged", staged)
        obs.get_registry().gauge(
            "dlrover_tpu_peer_stage_seconds",
            "Step-loop wall-clock of the last peer-state staging "
            "(host copy only; the write is deferred)").set(
            round(_time.monotonic() - t0, 3))

    # -- progress reporting ------------------------------------------------
    def _report_progress(self, step: int) -> None:
        """Report-interval bookkeeping: ship the step report (step time
        and MFU from completions, data-wait fraction from the timeline),
        export the timeline ring and the per-chip HBM stats for the
        agent. All best-effort — the step loop must survive a dead
        master and a full disk."""
        stats = self.timeline.window_stats(
            self.config.report_interval_steps)
        # the step time is the DEVICE's: seconds per step the device was
        # seen to finish since the last report (obs/stepmarks.py). The
        # timeline's iteration time is dispatch time while the host runs
        # ahead of the device, and read as a step time it made an MFU of
        # several hundred percent. No completion since the last report =
        # no speed evidence (0.0 / -1.0 on the wire), never a dispatch
        # time.
        mean_step = self._flight.drain_step_time()
        # ... and the step is the last one the device was seen to
        # FINISH, not the last one queued: the master clocks steps/s
        # (and from it tokens/s, job MFU and the collapse rule's peak)
        # from the step deltas between reports, and the dispatch
        # counter races ~32 steps ahead in a fraction of a second at
        # every start and after every stall, which read as a peak no
        # steady state can hold (a false throughput_collapse per run)
        done_step = step - len(self._flight)
        # achieved-vs-peak over the window: the step report's MFU field
        # feeds the master's per-rank gauge
        self._maybe_cross_check_flops()
        tokens_per_step = self.global_batch * self.config.seq_len
        mfu = obs.mfu.achieved_mfu(
            tokens_per_step / mean_step if mean_step > 0 else -1.0,
            self._flops_per_token, self._peak_flops_total)
        degraded = (self._slice_sync.drain_unreported()
                    if self._slice_sync is not None else 0)
        if self.prefetch_tuner is not None:
            self.prefetch_tuner.observe(
                stats.get("data_wait_fraction", -1.0))
        # device-truth HBM window peak (0 = backend has no memory
        # stats): drained per report so the master sees each window's
        # watermark, not a stale lifetime number
        hbm = self.device_telemetry.drain()
        # calibration attributes this window's timing by the plan the
        # loop ACTUALLY applied: -2 (fallback / no plan / batch-only
        # replica mode, which runs a full local replica rather than
        # the stamped mesh) is dropped by the master rather than
        # contaminating the stamped shape
        plan_gen = (int(self._shard_plan.get("generation", 0) or 0)
                    if self._shard_plan is not None
                    and self._replan_applied == "mesh+batch" else -2)
        try:
            self.client.report_global_step(
                done_step, step_time_s=mean_step,
                data_wait_fraction=stats.get("data_wait_fraction", -1.0),
                mfu=mfu, degraded_steps=degraded,
                hbm_peak_bytes=hbm.get("hbm_peak_bytes", 0.0),
                plan_generation=plan_gen)
        except Exception:  # noqa: BLE001 — droppable by contract
            # the degraded tally must not vanish with a dropped report
            if degraded and self._slice_sync is not None:
                self._slice_sync.degraded_unreported += degraded
        # tail-only AND wall-clock throttled on the hot path: the
        # write+rename alone costs ~1 ms on slow filesystems, so fast
        # steps with a short report interval would blow the < 1 %
        # overhead budget; at most one export/second bounds the cost at
        # ~0.1 % of training regardless of step time. The end-of-run
        # flush writes the whole ring.
        now = _time.monotonic()
        if mean_step > 0 and now - self._progress_logged_at >= 5.0:
            # one line every five seconds at most: the loop's own account
            # of its speed, for an operator reading the worker's log
            self._progress_logged_at = now
            logger.info(
                "step %d done, %d in flight: %.1f ms/step%s", done_step,
                len(self._flight), 1e3 * mean_step,
                f", MFU {mfu:.3f}" if mfu >= 0 else "")
        if self._timeline_path and now - self._timeline_exported_at >= 1.0:
            self._timeline_exported_at = now
            self.timeline.export(
                self._timeline_path,
                last_n=2 * self.config.report_interval_steps)
        if self.client is not None:
            # periodic clock refresh rides the report cadence (one RPC,
            # rate-limited by the probe interval — never per step)
            self._clock_sync.maybe_probe(
                DefaultValues.STEPTRACE_PROBE_INTERVAL_S)
        try:
            from dlrover_tpu.agent.monitor import export_chip_stats

            # duty-cycle proxy wants the per-step seconds the DEVICE is
            # plausibly busy: the whole step minus the phases where the
            # host is starving it (input wait, blocking checkpoint).
            # Passing total step time would make duty ≈ 100% even on a
            # worker spending most of each step waiting on data.
            busy_fraction = max(
                0.0, 1.0 - max(0.0, stats.get("data_wait_fraction", 0.0))
                - stats.get("checkpoint_fraction", 0.0))
            export_chip_stats(step=done_step,
                              step_time_s=mean_step * busy_fraction)
        except Exception:  # noqa: BLE001 — stats are advisory
            pass

    def _data_state(self, sampler) -> Dict[str, Any]:
        data_state: Dict[str, Any] = {}
        if sampler is not None:
            data_state["sampler"] = sampler.state_dict()
        if self.client is not None:
            try:
                shards = self.client.get_shard_checkpoint("")
                # the master answers "" when no dataset is registered
                # (purely local data): nothing to restore later
                if shards:
                    data_state["shards"] = shards
            except Exception:
                pass
        return data_state

    def _flush_telemetry(self) -> None:
        if self.client is not None:
            self._span_exporter.flush_to(self.client)
            self._steptrace.flush_to(self.client)

    def close(self) -> None:
        self._flush_telemetry()
        obs.remove_span_sink(self._span_exporter)
        if self._peer_store is not None:
            # a deferred stage write still in flight must land before
            # the process goes away (the whole point of the mirror)
            self._peer_store.flush()
        if self.checkpointer is not None:
            self.checkpointer.close()
        if self._prev_sigterm is not None:
            signal.signal(signal.SIGTERM, self._prev_sigterm)
