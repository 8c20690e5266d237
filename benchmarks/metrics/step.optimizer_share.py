"""Share of the device's time under the program's ``optimizer`` scope
(``TraceScope.OPTIMIZER``): ``tx.update`` and ``apply_updates``. A lower
bound where XLA fuses a leaf's update into its weight-gradient matmul: that
fusion's ``op_name`` is the backward's (PERF.md section 5)."""

from benchmarks import kernel_needs


def read(run: dict):
    return kernel_needs.scope_share(run.get("traced") or {}, "optimizer")
