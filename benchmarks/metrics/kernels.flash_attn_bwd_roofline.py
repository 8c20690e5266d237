"""Flash attention's backward's share of its roofline, from the device
trace: the device time of chip 0's events named ``flash_attn_dq*`` and
``flash_attn_dkv*`` (one launch of each a layer and step) against the larger
of needed FLOPs over peak FLOP/s and needed bytes over peak HBM bytes/s for
dQ and dK/dV together, summed over the model class's attention layers
(``benchmarks/kernel_needs.py``; compute-bound at these shapes; recomputing S
in both kernels is their own choice and is not credited). Nothing where
either name is missing."""

from benchmarks import kernel_needs


def read(run: dict):
    traced = run.get("traced") or {}
    dq_s, dq_n = kernel_needs.kernel_events(traced, "flash_attn_dq")
    dkv_s, dkv_n = kernel_needs.kernel_events(traced, "flash_attn_dkv")
    if not dq_n or not dkv_n:
        return None
    return kernel_needs.roofline_share(
        run, kernel_needs.flash_attention_bwd, dq_s + dkv_s,
        (dq_n + dkv_n) / 2.0)
