"""The fused RMSNorm kernels' share of the device's time: chip 0's events
named ``rms_norm_fwd*`` and ``rms_norm_bwd*`` (the program's own kernel
names, ``dlrover_tpu/ops/norms.py``) over all of chip 0's device-event time.

A share of the time and not of a roofline: on the v5e XLA keeps the
residual stream in its fast memory space (the operands' layouts carry
``S(1)``), where a launch moves its 33.6 MB in 11 us, 3.6 times what the
HBM's public peak allows, and that memory has no public peak to hold the
kernel against (PERF.md, PR 28). Nothing where no event carries either
name."""

from benchmarks import kernel_needs


def read(run: dict):
    traced = run.get("traced") or {}
    whole = sum((traced.get("by_name") or {}).values())
    spent, launches = 0.0, 0
    for kernel in ("rms_norm_fwd", "rms_norm_bwd"):
        seconds, count = kernel_needs.kernel_events(traced, kernel)
        spent += seconds
        launches += count
    if not launches or whole <= 0:
        return None
    return 100.0 * spent / whole
