"""The fullest held expert's tokens over the mean of the held experts',
averaged over the window's steps: the ``train_window`` spans' attr
``moe_load_max_over_mean_mean`` (the step's own metric, read by the loop
from steps it has seen done; ``dlrover_tpu/obs/stepmarks.py``), weighted by
``moe_load_max_over_mean_steps``. 1 is an even load; the grouped products'
time follows the sum (``moe.held_rows_share``), the deployment's all-to-all
and its slowest chip the maximum. Nothing where no span carries the attr."""

from benchmarks import span_reduce


def counter_mean(run: dict, attr: str):
    """A model's counter over the window: the spans' ``<attr>_mean``
    weighted by ``<attr>_steps``."""
    windows = [w for w in span_reduce.train_windows(run)
               if w.get(attr + "_steps")]
    if not windows:
        return None
    steps = sum(w[attr + "_steps"] for w in windows)
    return sum(w[attr + "_mean"] * w[attr + "_steps"] for w in windows) / steps


def read(run: dict):
    return counter_mean(run, "moe_load_max_over_mean")
