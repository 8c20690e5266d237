"""The sparse attention forward kernel's share of its roofline, from the
device trace: the device time of chip 0's events named ``sparse_attn_fwd*``
(the flash forward kernel of ``dlrover_tpu/ops/flash_attention.py`` given a
selection mask, under that name; one launch a layer in the forward pass
and one more in the block's recomputed forward) against the
least time the chip could take for the SELECTED pairs' QK^T and PV
(``benchmarks/models/keye.py:sparse_attn_fwd``: the same count whatever
computes them, so a dense kernel under a mask reads what it wastes).
Nothing where the model class has no such count or the trace no such
event."""

from benchmarks import flops, kernel_needs


def share(run: dict, kernels: tuple, needs_name: str):
    needs = getattr(run["model"], needs_name, None)
    traced = run.get("traced") or {}
    seconds, launches = 0.0, 0
    for kernel in kernels:
        spent, count = kernel_needs.kernel_events(traced, kernel)
        seconds, launches = seconds + spent, launches + count
    if needs is None or not launches:
        return None
    least = flops.roofline_seconds(
        needs(run["cfg"], kernel_needs.per_chip_batch(run),
              run["traffic"]["seq_len"]), run["device"]["kind"])[0]
    return 100.0 * least * (launches / len(kernels)) / seconds


def read(run: dict):
    return share(run, ("sparse_attn_fwd",), "sparse_attn_fwd")
