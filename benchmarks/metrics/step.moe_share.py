"""Share of the device's time under the program's ``moe`` scope
(``TraceScope``, docs/observability.md; model class ``keye``), forward,
recomputed forward and backward. Of the events' time, as
``step.head_loss_share`` is; nothing where the program has no such scope."""

from benchmarks import kernel_needs


def read(run: dict):
    return kernel_needs.scope_share(run.get("traced") or {}, "moe")
