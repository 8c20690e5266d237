"""The lightning forward kernel's share of its roofline, from the device
trace: the device time of chip 0's events named ``lightning_fwd*`` (the
Pallas forward of ``dlrover_tpu/ops/linear_attention.py``; one launch a
lightning layer and step, its block's recomputation keeping the output)
against the least time the chip could take for one layer's forward
(``benchmarks/models/minicpm_sala.py:lightning_fwd``: q, k, v and o once,
the chunked form's FLOPs at a chunk of 64, whatever chunk the kernel takes).
Nothing where the model class has no such count or the trace no such
event."""

from benchmarks import harness

_forward = harness.load_module("metrics", "kernels.sparse_attn_fwd_roofline")


def read(run: dict):
    return _forward.share(run, ("lightning_fwd",), "lightning_fwd")
