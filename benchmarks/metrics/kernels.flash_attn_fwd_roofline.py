"""Flash attention's forward kernel's share of its roofline, from the device
trace: the device time of chip 0's events named ``flash_attn_fwd*`` (the
program's own kernel name, ``dlrover_tpu/ops/flash_attention.py``), per
launch, against the larger of needed FLOPs over peak FLOP/s and needed bytes
over peak HBM bytes/s (``benchmarks/kernel_needs.py``; compute-bound at these
shapes). A trace with no event of that name gives nothing."""

from benchmarks import flops, kernel_needs


def read(run: dict):
    seconds, launches = kernel_needs.kernel_events(
        run.get("traced") or {}, "flash_attn_fwd")
    if not launches:
        return None
    least, _ = flops.roofline_seconds(
        kernel_needs.flash_attention_fwd(
            run["cfg"], kernel_needs.per_chip_batch(run),
            run["traffic"]["seq_len"]),
        run["device"]["kind"])
    return 100.0 * least * launches / seconds
