"""The step loop's own account of an iteration, taken once.

``elastic_loop._run_inner`` used to read the clock in one place for the
timeline, in another for steptrace, and ask the trainer for two more
stopwatches. Here the marks are taken once per iteration and everything
is fed from them:

- :class:`StepMarks`: host seconds of one iteration by phase —
  ``fetch`` (``next(batch_iter)``), ``shard`` (``shard_batch``),
  ``dispatch`` (``trainer.step``), ``save`` (``maybe_save`` + peer
  staging), ``report`` (the report-cadence work) — and ``other``, the
  residual (polls, chaos hook, watchdog, device telemetry, timeline,
  steptrace). The phases and ``other`` sum to the iteration's wall time.
  Each phase can carry a profiler annotation, so the same intervals lie
  on the host lines of a ``jax.profiler`` trace.
- :class:`StepsInFlight`: completion accounting without a sync. JAX
  dispatches ahead of the device, so the host's iteration time says
  nothing about the device's step time until the device queue is full.
  The loop keeps one scalar output of every dispatched step and asks
  ``is_ready()`` (never ``block_until_ready``: the run-ahead stays as it
  is); the steps seen done, and when, are the device-side mark per step.
- :class:`LoopWindow`: the sums over ``report_interval_steps``
  iterations that one ``train_window`` span carries (docs/
  observability.md has the attrs).

stdlib-only by design, like the timeline: the annotation factory
(``jax.profiler.TraceAnnotation``) and the clock are handed in.
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

PHASES = ("fetch", "shard", "dispatch", "save", "report")


class _Phase:
    """Context manager: one timed (and annotated) stretch of a phase."""

    __slots__ = ("_marks", "_name", "_annotation", "_t0")

    def __init__(self, marks: "StepMarks", name: str, annotation):
        self._marks = marks
        self._name = name
        self._annotation = annotation

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = self._marks.clock()
        return self

    def __exit__(self, *exc):
        marks = self._marks
        marks.seconds[self._name] += marks.clock() - self._t0
        return self._annotation.__exit__(*exc)


class StepMarks:
    """Host seconds of one loop iteration, by phase."""

    __slots__ = ("clock", "started", "wall", "seconds", "dispatch_done",
                 "_annotate")

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 annotate: Optional[Callable[[str], Any]] = None,
                 started: Optional[float] = None):
        self.clock = clock
        self._annotate = annotate or (
            lambda label: contextlib.nullcontext())
        self.started = clock() if started is None else started
        self.wall = 0.0
        self.seconds: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        # monotonic time the step's dispatch returned (steptrace's
        # compute-phase end); 0.0 until a step was dispatched
        self.dispatch_done = 0.0

    def phase(self, name: str, label: str) -> _Phase:
        """Time the ``with`` body into phase ``name`` (a phase entered
        twice adds up) under the profiler annotation ``label``."""
        return _Phase(self, name, self._annotate(label))

    def elapsed(self) -> float:
        return self.clock() - self.started

    def close(self) -> float:
        """End the iteration now; returns the closing time, which is the
        next iteration's start (iterations tile the loop's time)."""
        now = self.clock()
        self.wall = now - self.started
        return now

    @property
    def other(self) -> float:
        """What no phase claims of the iteration's wall time."""
        return max(0.0, self.wall - sum(self.seconds.values()))


class StepsInFlight:
    """Which dispatched steps the device has finished, seen without
    waiting for any of them.

    ``dispatched`` takes one output of the step (a scalar metric: a
    ``jax.Array`` answers ``is_ready()`` without blocking; anything
    without the method counts as done at once) and ``poll`` pops from
    the oldest while they are ready, stamping when the loop first saw
    them done. Nothing here bounds the queue."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._pending: deque = deque()  # one output per step in flight
        self.completed = 0              # steps seen done, ever
        # the completion the last step time was measured up to:
        # (when it was seen done, the count then); before the first one,
        # the first dispatch (the device starts working then)
        self._mark: Optional[Tuple[float, int]] = None
        self._last_done_at = 0.0
        # what finished steps counted of themselves (`take_counted`)
        self._counted: list = []

    def __len__(self) -> int:
        return len(self._pending)

    def dispatched(self, handle: Any,
                   counted: Optional[Dict[str, Any]] = None) -> None:
        """``counted``: further scalar outputs of the same step (a model's
        counters among the step's metrics), read once the step is seen
        done: never a wait, their program has finished."""
        if self._mark is None:
            self._mark = (self._clock(), 0)
        self._pending.append((handle, counted))

    def poll(self) -> int:
        """Pop every leading step that is done; returns how many."""
        done = 0
        pending = self._pending
        while pending:
            is_ready = getattr(pending[0][0], "is_ready", None)
            if is_ready is not None and not is_ready():
                break
            counted = pending.popleft()[1]
            if counted:
                self._counted.append(
                    {name: float(value) for name, value in counted.items()})
            done += 1
        if done:
            self.completed += done
            self._last_done_at = self._clock()
        return done

    def take_counted(self) -> list:
        """The counters of the steps seen done since the last call."""
        counted, self._counted = self._counted, []
        return counted

    def drain_step_time(self) -> float:
        """Mean seconds per step over the completions seen since the
        last call that had any, measured from completion to completion
        (so a run-ahead host's dispatch time never enters). 0.0 = no
        completion since: no speed evidence."""
        if self._mark is None:
            return 0.0
        since, counted = self._mark
        fresh = self.completed - counted
        if fresh <= 0:
            return 0.0
        self._mark = (self._last_done_at, self.completed)
        return max(0.0, self._last_done_at - since) / fresh


class LoopWindow:
    """Sums over the iterations of one report interval: what one
    ``train_window`` span says."""

    def __init__(self, first_step: int):
        self.first_step = first_step
        self.steps = 0
        self.wall_s = 0.0
        self.seconds: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self.other_s = 0.0
        self.completed = 0
        self._in_flight_sum = 0
        self._in_flight_max = 0
        self._samples = 0
        # name -> (sum, steps) of what finished steps counted of themselves
        self._counted: Dict[str, Tuple[float, int]] = {}

    def add(self, marks: StepMarks, completed: int, in_flight: int,
            took_step: bool = True, counted=()) -> None:
        """One closed iteration. ``took_step`` False: the iteration
        that found the data exhausted; its time counts, it is no step.
        ``counted``: `StepsInFlight.take_counted()`, one dict a step seen
        done in this iteration."""
        for step_counted in counted:
            for name, value in step_counted.items():
                total, steps = self._counted.get(name, (0.0, 0))
                self._counted[name] = (total + value, steps + 1)
        self.steps += 1 if took_step else 0
        self.wall_s += marks.wall
        for name, value in marks.seconds.items():
            self.seconds[name] += value
        self.other_s += marks.other
        self.completed += completed
        self._in_flight_sum += in_flight
        self._in_flight_max = max(self._in_flight_max, in_flight)
        self._samples += 1

    def attrs(self) -> Dict[str, float]:
        """The span's attrs: all numbers."""
        out: Dict[str, float] = {
            "steps": self.steps, "first_step": self.first_step,
            "wall_s": self.wall_s}
        for name in PHASES:
            out[f"{name}_s"] = self.seconds[name]
        out["other_s"] = self.other_s
        out["completed"] = self.completed
        out["in_flight_mean"] = (self._in_flight_sum / self._samples
                                 if self._samples else 0.0)
        out["in_flight_max"] = self._in_flight_max
        for name, (mean, steps) in self.counters().items():
            out[f"{name}_mean"] = mean
            out[f"{name}_steps"] = steps
        return out

    def counters(self) -> Dict[str, Tuple[float, int]]:
        """A model's counters: name -> (the mean over the steps seen done
        here, how many they were)."""
        return {name: (total / steps, steps)
                for name, (total, steps) in self._counted.items()}
