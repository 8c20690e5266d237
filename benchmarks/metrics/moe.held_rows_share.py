"""The held experts' rows a step as a share of every assignment (tokens x
``num_experts_per_tok``), averaged over the window's steps: the
``train_window`` spans' attr ``moe_held_rows_share_mean`` (sown by
``dlrover_tpu/parallel/moe.py:HeldExpertsLayer``), in percent.
``num_local_experts / num_experts`` (12.5 % of one chip of eight) is what an
even routing gives this chip; what the window really multiplies is this
number, and a form of the expert layer that sizes its work by the held rows
is timed on it. Nothing where no span carries the attr."""

from benchmarks import harness

_load = harness.load_module("metrics", "moe.load_max_over_mean")


def read(run: dict):
    share = _load.counter_mean(run, "moe_held_rows_share")
    return None if share is None else 100.0 * share
