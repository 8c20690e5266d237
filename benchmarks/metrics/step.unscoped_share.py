"""Share of the device's time in events that no ``op_name`` reaches: the
instruction is not in the step program's text, or it and what it calls carry
no metadata. The health of the join that the other scope shares stand on."""

from benchmarks import kernel_needs, trace_reduce


def read(run: dict):
    return kernel_needs.scope_share(run.get("traced") or {},
                                    trace_reduce.UNSCOPED)
