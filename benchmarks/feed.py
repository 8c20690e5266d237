"""The batch iterator the benchmark hands to ``ElasticTrainLoop.run``: it is
how the window is opened, timed and closed without touching the program.

Every ``next()`` is stamped on entry and on return (monotonic and wall
clock), the batch it hands out is digested, and once the window's seconds
have passed the next call raises ``StopIteration``: the loop then syncs,
waits for its checkpoint and returns.
"""

from __future__ import annotations

import contextlib
import time
import zlib


def digest(batch) -> int:
    """CRC of a (tokens, targets) batch's bytes, in row-major order."""
    crc = 0
    for part in batch:
        crc = zlib.crc32(part.tobytes(), crc)
    return crc


class WindowFeed:
    def __init__(self, batches, clock=time.monotonic, wall=time.time,
                 annotate=None):
        self._inner = iter(batches)
        self._clock, self._wall = clock, wall
        # a context manager factory put around the loader's own next(), so
        # that a profiler trace shows the host inside the input pipeline
        self._annotate = annotate or (lambda name: contextlib.nullcontext())
        self.calls: list = []     # one dict per batch handed out
        self.opened_at = None     # monotonic
        self.opened_wall = None
        self.deadline = None
        self.stopped_at = None    # the call that raised StopIteration
        self.hooks: list = []     # callables(feed, now) run on every call

    def open(self, seconds: float) -> None:
        self.opened_at = self._clock()
        self.opened_wall = self._wall()
        self.deadline = self.opened_at + seconds

    def __iter__(self):
        return self

    def __next__(self):
        entered = self._clock()
        if self.deadline is not None and entered >= self.deadline:
            self.stopped_at = entered
            raise StopIteration
        for hook in self.hooks:
            hook(self, entered)
        started, wall = self._clock(), self._wall()
        with self._annotate("input"):
            batch = next(self._inner)
        self.calls.append({
            "entered": entered, "fetch_from": started,
            "fetch_to": self._clock(), "wall": wall,
            "in_window": self.opened_at is not None,
            "digest": digest(batch)})
        return batch

    # -- what the metrics read ---------------------------------------------
    def window_calls(self) -> list:
        return [c for c in self.calls if c["in_window"]]

    def gaps(self) -> list:
        """Seconds from each window call's entry to the next call's entry
        (the stopping call closes the last one)."""
        entries = [c["entered"] for c in self.window_calls()]
        if self.stopped_at is not None:
            entries.append(self.stopped_at)
        return [b - a for a, b in zip(entries, entries[1:])]
