"""Flash attention's forward kernel's share of its roofline, from the device
trace: the device time of chip 0's events named ``flash_attn_fwd*`` (the
program's own kernel name, ``dlrover_tpu/ops/flash_attention.py``; one launch
a layer and step) against the larger of needed FLOPs over peak FLOP/s and
needed bytes over peak HBM bytes/s, summed over the model class's attention
layers (``benchmarks/kernel_needs.py``; compute-bound at these shapes). A
trace with no event of that name gives nothing."""

from benchmarks import kernel_needs


def read(run: dict):
    seconds, launches = kernel_needs.kernel_events(
        run.get("traced") or {}, "flash_attn_fwd")
    if not launches:
        return None
    return kernel_needs.roofline_share(
        run, kernel_needs.flash_attention_fwd, seconds, launches)
