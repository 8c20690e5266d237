"""Share of the device's time under the program's ``block_select`` scope
(``TraceScope``, docs/observability.md; model class ``minicpm_sala``):
InfLLM-v2's compressed keys, their scores against every query head, the
block scores and the top-k, which the sparse layer runs in plain XLA before
its attention. Of the events' time; nothing where the program has no such
scope."""

from benchmarks import kernel_needs


def read(run: dict):
    return kernel_needs.scope_share(run.get("traced") or {}, "block_select")
