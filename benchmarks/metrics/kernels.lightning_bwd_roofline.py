"""The lightning backward kernel's share of its roofline: chip 0's events
named ``lightning_bwd*`` (dQ, dK and dV in one launch a lightning layer and
step) against the least time for the three (``benchmarks/models/
minicpm_sala.py:lightning_bwd``). Nothing where the model class has no such
count or the trace no such event."""

from benchmarks import harness

_forward = harness.load_module("metrics", "kernels.sparse_attn_fwd_roofline")


def read(run: dict):
    return _forward.share(run, ("lightning_bwd",), "lightning_bwd")
