"""Steps dispatched and not yet seen done, at the iteration's boundary:
the mean of the window's ``train_window`` spans' ``in_flight_mean``,
weighted by their steps. What a drain request or an emergency save waits
behind, and why the window closes seconds after its feed stops."""

from benchmarks import span_reduce


def read(run: dict):
    windows = [w for w in span_reduce.train_windows(run) if w.get("steps")]
    if not windows:
        return None
    steps = sum(w["steps"] for w in windows)
    return sum(w["in_flight_mean"] * w["steps"] for w in windows) / steps
