"""The block-sparse kernels' (q tile, kv tile) pairs visited over the causal
pairs, averaged over the window's steps, in percent: the ``train_window``
spans' attr ``block_sparse_tiles_visited_share_mean`` (sown by
``dlrover_tpu/models/minicpm_sala.py:SparseMixer`` from its block mask),
weighted by its ``_steps``. 100 is a kernel that skips nothing; what the
forward and both backward kernels of a sparse layer compute follows it.
Nothing where no span carries the attr."""

from benchmarks import harness

_load = harness.load_module("metrics", "moe.load_max_over_mean")


def read(run: dict):
    share = _load.counter_mean(run, "block_sparse_tiles_visited_share")
    return None if share is None else 100.0 * share
