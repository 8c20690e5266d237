"""The sparse attention backward kernels' share of their roofline: chip 0's
events named ``sparse_attn_dq*`` and ``sparse_attn_dkv*`` (one launch of each
a layer and step) against the least time for the four backward matmuls over
the SELECTED pairs (``benchmarks/models/keye.py:sparse_attn_bwd``). Nothing
where the model class has no such count or the trace no such event."""

from benchmarks import harness

_forward = harness.load_module("metrics", "kernels.sparse_attn_fwd_roofline")


def read(run: dict):
    return _forward.share(run, ("sparse_attn_dq", "sparse_attn_dkv"),
                          "sparse_attn_bwd")
