"""The block-sparse attention forward kernel's share of its roofline: chip 0's
events named ``block_sparse_attn_fwd*`` (the flash forward kernel of
``dlrover_tpu/ops/flash_attention.py`` given InfLLM-v2's block selection;
one launch a sparse layer and step) against the least time for the SELECTED
pairs' QK^T and PV (``benchmarks/models/minicpm_sala.py:
block_sparse_attn_fwd``): a kernel that computes pairs it was not asked for
reads what it wastes. Nothing where the model class has no such count or the
trace no such event."""

from benchmarks import harness

_forward = harness.load_module("metrics", "kernels.sparse_attn_fwd_roofline")


def read(run: dict):
    return _forward.share(run, ("block_sparse_attn_fwd",),
                          "block_sparse_attn_fwd")
