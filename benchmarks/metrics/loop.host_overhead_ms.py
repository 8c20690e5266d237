"""Host work per step that is neither waiting for data nor blocked in
dispatch: ``shard_s`` + ``save_s`` + ``report_s`` + ``other_s`` over
``steps``, summed over the window's ``train_window`` spans (``other`` is the
loop's polls, chaos hook, watchdog, ``memory_stats`` read, timeline and
steptrace). What a change to the loop's bookkeeping moves."""

from benchmarks import span_reduce


def read(run: dict):
    value = span_reduce.ratio(
        span_reduce.train_windows(run),
        ("shard_s", "save_s", "report_s", "other_s"), ("steps",))
    return None if value is None else 1000.0 * value
