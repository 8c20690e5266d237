"""The block-sparse attention backward kernels' share of their roofline:
chip 0's events named ``block_sparse_attn_dq*`` and ``block_sparse_attn_dkv*``
(one launch of each a sparse layer and step) against the least time for the
four backward matmuls over the SELECTED pairs (``benchmarks/models/
minicpm_sala.py:block_sparse_attn_bwd``). Nothing where the model class has
no such count or the trace no such event."""

from benchmarks import harness

_forward = harness.load_module("metrics", "kernels.sparse_attn_fwd_roofline")


def read(run: dict):
    return _forward.share(
        run, ("block_sparse_attn_dq", "block_sparse_attn_dkv"),
        "block_sparse_attn_bwd")
