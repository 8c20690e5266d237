"""Device prefetch: overlap host batch prep with device compute.

Capability parity: atorch data/preloader.py (CUDA-stream prefetch). TPU
re-design: `jax.device_put` is async — keeping `depth` batches in flight
overlaps the host→HBM DMA of batch i+1 with the step on batch i (the
stream role is played by XLA's async dispatch).

`PrefetchAutoTuner` closes the loop from the step timeline: when the
windowed ``data_wait`` fraction (obs/timeline.py) says the step loop is
starving on input, the recommended depth grows toward
``PREFETCH_DEPTH_MAX`` (8); when the pipeline stops starving it decays
back so idle device buffers don't pin HBM. Recommendations are advisory
and consumed at (re)build boundaries — passing ``tuner.depth_fn`` as
``depth`` makes an existing prefetch loop pick up changes batch-to-batch
without a rebuild.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Callable, Iterable, Iterator, Optional, Union

import jax

from dlrover_tpu.common.constants import DefaultValues


def prefetch_to_device(
    iterator: Iterable,
    depth: Union[int, Callable[[], int]] = 2,
    sharding: Optional[Any] = None,
    transform: Optional[Callable] = None,
) -> Iterator:
    """Yield batches already on device, `depth` ahead of consumption.

    ``depth`` may be a callable (e.g. ``PrefetchAutoTuner.depth_fn``):
    it is re-read each batch, so an auto-tuned depth change applies to
    the in-flight window without rebuilding the pipeline. A shrink
    drains naturally — queued batches are yielded, never dropped.
    """
    queue: collections.deque = collections.deque()
    depth_fn = depth if callable(depth) else (lambda: depth)

    def put(batch):
        if transform is not None:
            batch = transform(batch)
        if sharding is not None:
            return jax.tree.map(
                lambda x: jax.device_put(x, sharding), batch)
        return jax.tree.map(jax.device_put, batch)

    it = iter(iterator)
    for batch in it:
        queue.append(put(batch))
        if len(queue) >= max(1, int(depth_fn())):
            yield queue.popleft()
    while queue:
        yield queue.popleft()


class PrefetchAutoTuner:
    """data_wait-driven depth/ring sizing (knob: ctx.prefetch_autotune).

    Fed once per report window by the step loop
    (ElasticTrainLoop._report_progress) with the timeline's windowed
    ``data_wait_fraction``. Asymmetric on purpose: growth is immediate
    (a starving device is paying real badput every step) while shrink
    requires two consecutive calm windows (a single fast window after a
    refill must not thrash the depth back down).
    """

    # shrink only below this fraction of the grow trigger — the dead
    # band between shrink and grow is the hysteresis that stops a
    # pipeline sitting near the threshold from oscillating
    _SHRINK_FRACTION = 0.25
    _SHRINK_CALM_WINDOWS = 2

    def __init__(self, depth: int = 2):
        self._min = DefaultValues.PREFETCH_DEPTH_MIN
        self._max = DefaultValues.PREFETCH_DEPTH_MAX
        self._threshold = DefaultValues.DATA_WAIT_TUNE_FRACTION
        self._lock = threading.Lock()
        self._depth = max(self._min, min(self._max, int(depth)))
        self._calm_windows = 0

    @property
    def depth(self) -> int:
        with self._lock:
            return self._depth

    def depth_fn(self) -> int:
        """Bound method handed to ``prefetch_to_device(depth=...)``."""
        return self.depth

    def observe(self, data_wait_fraction: float) -> int:
        """One report window's data-wait evidence; returns the (possibly
        updated) recommended depth. Negative fractions mean "no timeline
        evidence" and change nothing."""
        if data_wait_fraction < 0.0:
            return self.depth
        with self._lock:
            if data_wait_fraction > self._threshold:
                self._calm_windows = 0
                if self._depth < self._max:
                    self._depth += 1
            elif data_wait_fraction < self._threshold * self._SHRINK_FRACTION:
                self._calm_windows += 1
                if (self._calm_windows >= self._SHRINK_CALM_WINDOWS
                        and self._depth > self._min):
                    self._depth -= 1
                    self._calm_windows = 0
            else:
                self._calm_windows = 0
            return self._depth

    def ring_capacity(self, base_capacity: int = 64 << 20) -> int:
        """Recommended ShmDataContext ring capacity for the current
        depth: scaled from the default-depth baseline so a deeper
        prefetch window never stalls its producers on ring backpressure.
        Advisory — consumed when a ring is (re)built, never live."""
        with self._lock:
            scale = max(1, self._depth) / 2.0
        return int(base_capacity * max(1.0, scale))
