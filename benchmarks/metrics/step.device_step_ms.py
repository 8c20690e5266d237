"""The program's own step time: wall seconds over steps the loop saw the
device finish, summed over the window's ``train_window`` spans
(``wall_s`` / ``completed``; completion accounting asks ``is_ready()`` of one
scalar of each dispatched step and never waits). While the host runs ahead
of the device this is the device's step, not the dispatch time; it should
agree with the window's seconds over its steps, the basis of ``step.mfu``."""

from benchmarks import span_reduce


def read(run: dict):
    value = span_reduce.ratio(span_reduce.train_windows(run),
                              ("wall_s",), ("completed",))
    return None if value is None else 1000.0 * value
