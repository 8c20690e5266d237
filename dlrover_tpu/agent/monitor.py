"""Agent-side monitors: node resources, training progress, hang detection.

Capability parity:
- `ResourceMonitor` ≙ elastic_agent/monitor/resource.py:86 (psutil +
  pynvml → here psutil + jax TPU memory_stats) reporting every 15 s;
- `TrainingMonitor` ≙ elastic_agent/monitor/training.py:78
  (TorchTrainingMonitor reads a metrics file the training process appends
  to and forwards global step to the master);
- `HangingDetector` ≙ atorch/fault_tolerance/hanging_detector.py:86
  (heartbeat thread + no-progress window ⇒ restart workers).

The training process writes `{"step": N, "ts": ...}` JSON lines to the
metrics file named by `NodeEnv.METRICS_FILE` (the `report_step` helper);
the agent-side monitors never import jax into the training process.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, List, Optional

from dlrover_tpu import obs
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common import messages as msg
from dlrover_tpu.common.config import Context
from dlrover_tpu.common.constants import DefaultValues, NodeEnv
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.obs.device import _RISE_THRESHOLD_BYTES


def report_step(step: int, path: Optional[str] = None,
                step_time_s: float = 0.0,
                data_wait_fraction: float = -1.0,
                plan_generation: int = -1) -> None:
    """Called from the TRAINING process each step (or every k steps).
    Atomic single-record write: readers only ever need the latest record,
    and week-long jobs must not grow the file unboundedly. The optional
    timing fields (windowed mean step time + data-wait fraction, from
    the phase timeline) ride along so the agent's TrainingMonitor can
    forward the diagnosis engine's straggler evidence.
    ``plan_generation``: the shard-plan generation the trainer actually
    applied (parallel/planner.py) — forwarded so the master's plan
    calibration attributes this timing to the right mesh shape; -1 =
    sender does not track plans (calibration falls back to
    current-signature attribution); -2 = sender ran a fallback mesh
    (the master DROPS the evidence — it must ride the relay, not
    collapse into -1's current-shape attribution)."""
    path = path or os.environ.get(NodeEnv.METRICS_FILE, "")
    if not path:
        return
    record = {"step": int(step), "ts": time.time()}
    if step_time_s > 0.0:
        record["step_time_s"] = float(step_time_s)
    if data_wait_fraction >= 0.0:
        record["data_wait_fraction"] = float(data_wait_fraction)
    if plan_generation != -1:
        record["plan_generation"] = int(plan_generation)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(json.dumps(record) + "\n")
    os.replace(tmp, path)


def _read_last_step(path: str) -> Optional[dict]:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - 4096))
            lines = f.read().decode(errors="ignore").strip().splitlines()
        for line in reversed(lines):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    except OSError:
        return None
    return None


class ResourceMonitor:
    """Report host cpu/mem + TPU chip stats to the master periodically."""

    def __init__(self, client: MasterClient, node_type: str = "worker",
                 interval_s: float = (
                     DefaultValues.REPORT_RESOURCE_INTERVAL_S),
                 chip_stats_file: str = ""):
        self._client = client
        self._node_type = node_type
        self._interval_s = interval_s
        # explicit path wins; env is the worker-process export contract
        self._chip_stats_file = chip_stats_file
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # prime psutil's CPU sampler: cpu_percent(interval=None) computes
        # utilization SINCE THE LAST CALL and returns a meaningless 0.0
        # on its first — one throwaway call here makes every sample()
        # real (an all-zero first report reads as an idle node)
        try:
            import psutil

            psutil.cpu_percent(interval=None)
        except ImportError:
            pass

    def sample(self) -> msg.NodeResourceStats:
        cpu_percent = 0.0
        memory_mb = 0.0
        try:
            import psutil

            cpu_percent = psutil.cpu_percent(interval=None)
            memory_mb = psutil.virtual_memory().used / (1 << 20)
        except ImportError:  # psutil is present in the image; belt+braces
            pass
        stats = msg.NodeResourceStats(
            node_id=self._client.node_id,
            node_type=self._node_type,
            cpu_percent=cpu_percent,
            memory_mb=memory_mb,
            node_rank=getattr(self._client, "node_rank", -1),
            chip_stats=self._chip_stats(),
        )
        # same series the master exposes, in the agent's own registry
        # (local debugging; the RPC report remains the master-side feed)
        obs.publish_node_stats(stats)
        return stats

    def _chip_stats(self) -> List[msg.ChipStats]:
        """TPU HBM usage via jax memory_stats (the pynvml analog). Only
        meaningful in a process that owns the chips; the agent reads a
        stats file exported by the worker when available."""
        path = (self._chip_stats_file
                or os.environ.get(NodeEnv.CHIP_STATS_FILE, ""))
        if not path or not os.path.exists(path):
            return []
        try:
            with open(path) as f:
                raw = json.load(f)
            return [msg.ChipStats(**chip) for chip in raw]
        except (OSError, json.JSONDecodeError, TypeError):
            return []

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="resource-monitor")
        self._thread.start()

    def stop(self) -> None:
        self._stopped.set()

    def _loop(self) -> None:
        while not self._stopped.wait(self._interval_s):
            try:
                self._client.report_resource_stats(self.sample())
                self._client.report_heartbeat()
            except Exception as e:  # noqa: BLE001 - monitoring best-effort
                logger.warning("resource report failed: %s", e)


# last export's (wall time, step): the duty-cycle proxy needs a delta
# to derive busy time from. One training process = one exporter, so a
# module-level cell (no lock: only the step loop calls this) suffices.
_chip_export_prev: dict = {}
# last exported peak_bytes_in_use per (path, device): the allocator
# counter is lifetime-monotone (obs/device.py), so the export must
# window it — relaying the raw counter would latch HbmPressureRule on
# a long-resolved spike forever. Same noise threshold as the step-
# report path, imported so the two windowings cannot drift.
_chip_export_peaks: dict = {}
_PEAK_RISE_BYTES = _RISE_THRESHOLD_BYTES


def export_chip_stats(path: Optional[str] = None,
                      step: Optional[int] = None,
                      step_time_s: float = 0.0) -> None:
    """Called from the TRAINING process: dump per-chip HBM usage for the
    agent's ResourceMonitor to relay.

    Duty cycle: jax exposes no per-chip utilization counter, so a proxy
    is derived from consecutive exports — steps completed since the last
    export × the per-step DEVICE-BUSY seconds (``step_time_s``: mean
    step time minus the host-starve phases; the caller derives it from
    the phase timeline — total step time here would read ≈ 100% even
    when the chips idle on a stalled input pipeline), over the
    wall-clock elapsed. Callers that cannot supply (step, step_time_s)
    get stats WITHOUT the field — an honest absence instead of a
    hardcoded 0.0."""
    path = path or os.environ.get(NodeEnv.CHIP_STATS_FILE, "")
    if not path:
        return
    import jax

    now = time.time()
    duty: Optional[float] = None
    prev = _chip_export_prev.get(path)
    if step is not None and step_time_s > 0.0 and prev is not None:
        elapsed = now - prev["ts"]
        steps_done = step - prev["step"]
        if elapsed > 0 and steps_done >= 0:
            duty = min(100.0, 100.0 * steps_done * step_time_s / elapsed)
    if step is not None:
        _chip_export_prev[path] = {"ts": now, "step": int(step)}
    stats = []
    peaks = _chip_export_peaks.setdefault(path, {})
    for device in jax.local_devices():
        try:
            mem = device.memory_stats() or {}
        except Exception:  # noqa: BLE001 — backend support varies
            mem = {}
        chip = {"index": device.id}
        if mem:
            # hbm fields only when the backend actually answered: a CPU
            # backend's absent memory_stats used to export hbm_used_mb=0
            # forever — a 0 % series dashboards read as real headroom
            # instead of an honest absence
            chip["hbm_used_mb"] = mem.get("bytes_in_use", 0) / (1 << 20)
            chip["hbm_total_mb"] = mem.get("bytes_limit", 0) / (1 << 20)
            # the allocator's peak high-water mark: the transient
            # IN-step peak the between-steps bytes_in_use sample misses
            # (obs/device.py; what HbmPressureRule should judge).
            # Exported only when it ROSE since the last export — the
            # counter never resets within a process, so relaying it
            # unconditionally would keep a long-resolved spike in
            # HbmPressureRule's evidence forever; between rises,
            # hbm_used_mb is the honest live signal (the same
            # windowing DeviceTelemetry applies to the step report)
            peak = float(mem.get("peak_bytes_in_use", 0) or 0)
            prev_peak = peaks.get(device.id, 0.0)
            if peak > prev_peak + _PEAK_RISE_BYTES:
                chip["hbm_peak_mb"] = peak / (1 << 20)
            peaks[device.id] = max(peak, prev_peak)
        if duty is not None:
            chip["duty_cycle_pct"] = duty
        stats.append(chip)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(stats, f)
    os.replace(tmp, path)


class TrainingMonitor:
    """Tail the worker's metrics file; forward global step to the master."""

    def __init__(self, client: MasterClient, metrics_file: str,
                 interval_s: float = 15.0):
        self._client = client
        self._metrics_file = metrics_file
        self._interval_s = interval_s
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_reported = -1

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="training-monitor")
        self._thread.start()

    def stop(self) -> None:
        self._stopped.set()

    def last_progress_time(self) -> float:
        record = _read_last_step(self._metrics_file)
        return record["ts"] if record else 0.0

    def _loop(self) -> None:
        step_gauge = obs.get_registry().gauge(
            "dlrover_tpu_agent_reported_step",
            "Last worker step this agent forwarded to the master")
        while not self._stopped.wait(self._interval_s):
            record = _read_last_step(self._metrics_file)
            if record and record["step"] > self._last_reported:
                self._last_reported = record["step"]
                step_gauge.set(record["step"])
                try:
                    # forward the worker's timing evidence when the
                    # record carries it (diagnosis straggler input)
                    self._client.report_global_step(
                        record["step"],
                        step_time_s=float(
                            record.get("step_time_s", 0.0) or 0.0),
                        data_wait_fraction=float(
                            record.get("data_wait_fraction", -1.0)),
                        plan_generation=int(
                            record.get("plan_generation", -1)),
                    )
                except Exception as e:  # noqa: BLE001
                    logger.warning("step report failed: %s", e)


class HangingDetector:
    """Restart the worker when no step progress for `hang_seconds`
    (atorch --relaunch_on_hanging analog)."""

    def __init__(
        self,
        metrics_file: str,
        on_hang: Callable[[], None],
        hang_seconds: Optional[float] = None,
        check_interval_s: float = 30.0,
        warmup_s: float = 300.0,
    ):
        self._metrics_file = metrics_file
        self._on_hang = on_hang
        self._hang_seconds = (hang_seconds if hang_seconds is not None
                              else Context.singleton().hang_seconds)
        self._check_interval_s = check_interval_s
        self._warmup_s = warmup_s
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # the grace-period clock is reset from the agent thread
        # (worker restart) while the detector thread reads it
        self._clock_lock = threading.Lock()
        self._started_at = time.time()

    def start(self) -> None:
        with self._clock_lock:
            self._started_at = time.time()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="hang-detector")
        self._thread.start()

    def stop(self) -> None:
        self._stopped.set()

    def reset(self) -> None:
        """Call after a worker restart (fresh compile grace period)."""
        with self._clock_lock:
            self._started_at = time.time()

    def is_hanged(self) -> bool:
        record = _read_last_step(self._metrics_file)
        now = time.time()
        with self._clock_lock:
            started_at = self._started_at
        if record is None:
            # no step ever: hang only after warmup (first compile is slow)
            return now - started_at > max(self._warmup_s,
                                          self._hang_seconds)
        # a stale record from before the last (re)start must not re-fire:
        # progress is the newer of last-step time and last restart time
        last_progress = max(record["ts"], started_at)
        return now - last_progress > self._hang_seconds

    def _loop(self) -> None:
        while not self._stopped.wait(self._check_interval_s):
            if self.is_hanged():
                logger.error("hang detected: no step progress for %.0fs",
                             self._hang_seconds)
                try:
                    self._on_hang()
                finally:
                    self.reset()


class ParalConfigTuner:
    """Poll the master's tuned ParallelConfig and write it to the JSON
    file the ElasticDataLoader hot-reloads (reference:
    elastic_agent/config/paral_config_tuner.py:30-60)."""

    def __init__(self, client: MasterClient, config_path: str,
                 interval_s: float = 30.0):
        self._client = client
        self._config_path = config_path
        self._interval_s = interval_s
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # poll_once runs on the tuner thread and directly from tests /
        # agent shutdown: the version check-and-set must be atomic
        self._version_lock = threading.Lock()
        self._last_version = -1

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="paral-config-tuner")
        self._thread.start()

    def stop(self) -> None:
        self._stopped.set()

    def poll_once(self) -> bool:
        config = self._client.get_paral_config()
        with self._version_lock:
            if config.version <= self._last_version:
                return False
            self._last_version = config.version
        tmp = self._config_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({
                "version": config.version,
                "dataloader_batch_size": config.dataloader_batch_size,
                "dataloader_workers": config.dataloader_workers,
                "learning_rate": config.learning_rate,
                "grad_accum_steps": config.grad_accum_steps,
            }, f)
        os.replace(tmp, self._config_path)  # atomic for the reader
        return True

    def _loop(self) -> None:
        while not self._stopped.wait(self._interval_s):
            try:
                self.poll_once()
            except Exception as e:  # noqa: BLE001
                logger.warning("paral config poll failed: %s", e)
