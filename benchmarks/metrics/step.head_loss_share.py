"""Share of the device's time under the program's ``head_loss`` scope
(``TraceScope.HEAD_LOSS``): final norm, head matmul and loss, forward and
backward. Of the events' time, as ``kernels.rms_norm_share`` is."""

from benchmarks import kernel_needs


def read(run: dict):
    return kernel_needs.scope_share(run.get("traced") or {}, "head_loss")
