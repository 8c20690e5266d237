"""Device-truth worker telemetry: per-step HBM peak watermark + compile
events.

The agent's 15 s monitor tick samples ``bytes_in_use`` BETWEEN steps —
the inter-step trough — so the number that actually OOMs on the next
batch bump (the transient in-step peak) was invisible. jax exposes the
truth: ``device.memory_stats()['peak_bytes_in_use']`` is the
allocator's high-water mark, and reading it once per step costs one C
call per local device. :class:`DeviceTelemetry` tracks that watermark,
notes the step it last ROSE at (the attribution a postmortem wants:
"the peak moved when the batch grew at step 1200"), and hands the
report-window peak to the step report (``GlobalStepReport.
hbm_peak_bytes``) — riding the existing channel, no new RPC.

CPU-safe no-op by contract: a backend whose ``memory_stats()`` answers
nothing disables sampling after one probe — no forever-0 series, no
per-step cost.

Compile events: :func:`record_compile_event` stamps one flight event +
gauges per AOT compile with the compiled step's ``cost_analysis``
FLOPs/bytes — the measured program cost the MFU cross-check and the
planner calibration read, not the analytic guess. The compile's time is
the AOT ``recompile`` span's; :func:`compile_cache_reads` says whether
the persistent compile cache answered it.

stdlib-only at import time (jax is imported lazily inside the sampler),
so the master, tools and jax-free test workers import this bare.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional

# a watermark move smaller than this is allocator noise, not a rise
_RISE_THRESHOLD_BYTES = 1 << 20


def _jax_sampler() -> Optional[List[Dict[str, float]]]:
    """Per-local-device memory stats; None when the backend answers
    nothing (CPU) — the availability probe's signal."""
    import jax

    out = []
    for device in jax.local_devices():
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — backend support varies
            stats = None
        if not stats:
            continue
        out.append({
            "index": float(device.id),
            "bytes_in_use": float(stats.get("bytes_in_use", 0) or 0),
            "peak_bytes_in_use": float(
                stats.get("peak_bytes_in_use",
                          stats.get("bytes_in_use", 0)) or 0),
            "bytes_limit": float(stats.get("bytes_limit", 0) or 0),
        })
    return out or None


class DeviceTelemetry:
    """Per-step HBM watermark tracker for the training loop.

    ``on_step`` is the hot-path call (one ``memory_stats`` per local
    device, nothing else); ``drain`` is the report-interval call that
    returns the window's peak + where it last rose and re-arms the
    window. All cheap enough that the overhead-bound test pins sampler
    cost under 1 % of a CPU bench step.
    """

    def __init__(self, sampler: Optional[
            Callable[[], Optional[List[Dict[str, float]]]]] = None):
        self._sampler = sampler if sampler is not None else _jax_sampler
        self._lock = threading.Lock()
        # None = not probed yet; False = backend has no memory stats
        # (CPU) — every later on_step returns immediately
        self._available: Optional[bool] = None
        self._watermark_bytes = 0.0      # lifetime high-water observed
        # peak_bytes_in_use is a MONOTONE allocator counter (never
        # resets within a process), so "the window's peak" cannot be
        # read off it directly — a drained window would just re-report
        # the lifetime high forever and a resolved pressure episode
        # could never clear. But for a FIXED compiled program the
        # in-step peak recurs every step by construction — a flat
        # counter does not mean the pressure resolved, it means the
        # same program is still peaking at the same level. So the
        # episode boundary is the RECOMPILE (note_recompile — a replan
        # or batch change builds a new program): the window carries the
        # lifetime watermark while the program that set it is still the
        # one running steps (or when it rose in-window); only after a
        # recompile that does NOT re-reach it does the window fall back
        # to its max bytes_in_use as the best live evidence.
        self._window_rose = False        # watermark advanced this window
        self._window_sampled = False     # any step sampled this window
        self._window_in_use_bytes = 0.0  # max bytes_in_use this window
        self._program_epoch = 0          # bumped per note_recompile
        self._watermark_epoch = 0        # program that set the watermark
        self._trough_bytes = 0.0         # last between-step bytes_in_use
        self._limit_bytes = 0.0
        self._rise_step = -1             # step the watermark last rose

    @property
    def available(self) -> Optional[bool]:
        with self._lock:
            return self._available

    def on_step(self, step: int) -> None:
        """Sample after a finished step; no-op once probed unavailable."""
        with self._lock:
            if self._available is False:
                return
        try:
            stats = self._sampler()
        except Exception:  # noqa: BLE001 — telemetry never kills a step
            stats = None
        with self._lock:
            if not stats:
                if self._available is None:
                    self._available = False
                return
            self._available = True
            peak = max(s["peak_bytes_in_use"] for s in stats)
            in_use = max(s["bytes_in_use"] for s in stats)
            self._trough_bytes = in_use
            self._limit_bytes = max(self._limit_bytes,
                                    max(s["bytes_limit"] for s in stats))
            if peak > self._watermark_bytes + _RISE_THRESHOLD_BYTES:
                self._rise_step = int(step)
                self._window_rose = True
                self._watermark_epoch = self._program_epoch
            self._watermark_bytes = max(self._watermark_bytes, peak)
            self._window_sampled = True
            self._window_in_use_bytes = max(self._window_in_use_bytes,
                                            in_use)

    def note_recompile(self) -> None:
        """The train step was (re)compiled: a new program is about to
        run, so the old program's recurring peak stops being evidence
        unless the new one re-reaches it."""
        with self._lock:
            self._program_epoch += 1

    def drain(self) -> Dict[str, float]:
        """Report-window summary for the step report; re-arms the
        window. ``hbm_peak_bytes`` 0 = no device truth (CPU).

        The window peak is the lifetime watermark while the program
        that set it still ran steps this window (steady-state pressure
        recurs every step — HbmPressureRule must not read a flat
        monotone counter as resolved), else the window's max
        ``bytes_in_use`` — so an episode resolved by a recompile
        (smaller batch after a replan) stops re-reporting the old high
        and the rule can actually clear."""
        with self._lock:
            episode_live = (self._window_sampled
                            and self._watermark_epoch
                            == self._program_epoch)
            peak = (self._watermark_bytes
                    if self._window_rose or episode_live
                    else self._window_in_use_bytes)
            out = {
                "hbm_peak_bytes": peak,
                "hbm_watermark_bytes": self._watermark_bytes,
                "hbm_trough_bytes": self._trough_bytes,
                "hbm_limit_bytes": self._limit_bytes,
                "hbm_rise_step": float(self._rise_step),
            }
            self._window_rose = False
            self._window_sampled = False
            self._window_in_use_bytes = 0.0
        return out

    def peak_mb(self) -> float:
        """Lifetime watermark in MiB (0 = unavailable)."""
        with self._lock:
            return self._watermark_bytes / (1 << 20)


def cost_summary(compiled) -> Dict[str, float]:
    """FLOPs + bytes-accessed of an XLA-compiled program from its
    ``cost_analysis()`` — zeros whenever the backend cannot answer
    (advisory by contract, like obs.mfu.cost_analysis_flops)."""
    out = {"flops": 0.0, "bytes_accessed": 0.0}
    if compiled is None:
        return out
    try:
        analysis = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend support varies
        return out
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return out
    for key, field in (("flops", "flops"),
                       ("bytes accessed", "bytes_accessed")):
        try:
            out[field] = float(analysis.get(key, 0.0) or 0.0)
        except (TypeError, ValueError):
            pass
    return out


def record_compile_event(compiled=None, kind: str = "aot",
                         mesh: Optional[Dict[str, Any]] = None) -> Dict[
                             str, float]:
    """One compile's device truth into the flight recorder + gauges: the
    compiled step's cost-analysis FLOPs/bytes. Returns the cost summary
    so callers reuse it."""
    from dlrover_tpu.obs.flight_recorder import get_flight_recorder
    from dlrover_tpu.obs.metrics import get_registry

    costs = cost_summary(compiled)
    get_flight_recorder().record_event(
        "compile_event", kind=kind,
        flops=costs["flops"], bytes_accessed=costs["bytes_accessed"],
        mesh=dict(mesh) if mesh else None)
    registry = get_registry()
    if costs["flops"] > 0:
        registry.gauge(
            "dlrover_tpu_compiled_step_flops",
            "XLA cost-analysis FLOPs of the last compiled train step"
        ).set(costs["flops"])
    if costs["bytes_accessed"] > 0:
        registry.gauge(
            "dlrover_tpu_compiled_step_bytes_accessed",
            "XLA cost-analysis bytes accessed of the last compiled "
            "train step").set(costs["bytes_accessed"])
    return costs


# JAX's persistent-compile-cache events: a compile that asked the cache,
# one it answered, one written after compiling anew
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
# per thread: the AOT compile runs on a thread of its own while the main
# thread compiles the state's init
_cache_counts = threading.local()
_listener_lock = threading.Lock()
_listening = False


def _count_cache_event(event: str, **_: Any) -> None:
    field = _CACHE_EVENTS.get(event)
    if field is not None:
        setattr(_cache_counts, field, getattr(_cache_counts, field, 0) + 1)


def _listen_for_cache_events() -> None:
    """One listener a process (JAX keeps its listeners for good)."""
    global _listening
    with _listener_lock:
        if not _listening:
            import jax

            jax.monitoring.register_event_listener(_count_cache_event)
            _listening = True


@contextmanager
def compile_cache_reads():
    """Yields a dict that, once the block is left, says how this
    thread's compiles inside it met the persistent compile cache:
    ``cache`` is ``off`` where none asked it, ``hit`` where it answered
    every one, else ``miss``; ``cache_hits`` and ``cache_misses`` count
    JAX's own events (a miss counts where its entry was written)."""
    _listen_for_cache_events()
    before = {field: getattr(_cache_counts, field, 0)
              for field in _CACHE_EVENTS.values()}
    reads: Dict[str, Any] = {}
    try:
        yield reads
    finally:
        seen = {field: getattr(_cache_counts, field, 0) - count
                for field, count in before.items()}
        reads["cache"] = ("off" if not seen["requests"] else
                          "hit" if seen["hits"] >= seen["requests"]
                          else "miss")
        reads["cache_hits"] = seen["hits"]
        reads["cache_misses"] = seen["misses"]
