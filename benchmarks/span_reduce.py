"""From the spans the window caught to numbers: plain Python on the
``run["window"]["spans"]`` dicts (name, start, end, attrs), tested on
hand-made lists.

The program's loop emits one ``train_window`` span per report interval (ten
steps) and one for the remainder when it leaves its ``while``
(``dlrover_tpu/obs/stepmarks.py``; docs/observability.md lists the attrs).
Only spans that lie wholly inside the window count: the warm-up's three
one-step runs each leave a remainder span before the window opens. A program
that emits no such span (the parent of the PR that added them) gives every
reader here nothing, and the metric is left out of the line.
"""

from __future__ import annotations

SPAN = "train_window"


def train_windows(run: dict) -> list:
    """The attrs of every ``train_window`` span wholly inside the window."""
    window = run.get("window") or {}
    opened = window.get("opened_wall")
    if opened is None:
        return []
    closed = opened + window.get("seconds", 0.0)
    return [s["attrs"] for s in window.get("spans") or []
            if s.get("name") == SPAN and s["start"] >= opened
            and s["end"] <= closed]


def total(windows: list, *keys: str) -> float:
    """Sum of the named attrs over the spans."""
    return sum(float(w.get(key, 0.0)) for w in windows for key in keys)


def ratio(windows: list, over: tuple, under: tuple):
    """Sum of the ``over`` attrs by the sum of the ``under`` attrs; None
    where there is no span or nothing to divide by."""
    if not windows:
        return None
    below = total(windows, *under)
    if below <= 0:
        return None
    return total(windows, *over) / below


def feed_hook_seconds(run: dict) -> float:
    """Seconds the benchmark's own feed spent in its hooks (the profiler's
    start and stop among them) before the loader's ``next()``, inside the
    window: the loop's ``fetch`` mark encloses them, and they are the
    benchmark's doing, not the program's."""
    calls = (run.get("window") or {}).get("calls") or []
    return sum(c["fetch_from"] - c["entered"] for c in calls)
