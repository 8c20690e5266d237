"""The loop's own count of its wait for data: ``fetch_s`` over ``wall_s``,
summed over the window's ``train_window`` spans. The loop's mark encloses
the feed's whole ``__next__`` (the batch's CRC and the stamps as well as the
loader), so it reads a little over ``input.data_wait_share``; the feed's own
hooks (a traced run starts and stops the profiler there) are the
benchmark's doing and are taken out."""

from benchmarks import span_reduce


def read(run: dict):
    windows = span_reduce.train_windows(run)
    wall = span_reduce.total(windows, "wall_s")
    if not windows or wall <= 0:
        return None
    waited = (span_reduce.total(windows, "fetch_s")
              - span_reduce.feed_hook_seconds(run))
    return 100.0 * max(0.0, waited) / wall
