"""Share of the device's time under the program's ``lightning_attn`` scope
(``TraceScope``, docs/observability.md; model class ``minicpm_sala``): the
lightning layers' recurrence, forward and backward kernels and what the
scope holds around them. Of the events' time, as ``step.head_loss_share``
is; nothing where the program has no such scope."""

from benchmarks import kernel_needs


def read(run: dict):
    return kernel_needs.scope_share(run.get("traced") or {}, "lightning_attn")
