"""Share of the device's time in work the compiler runs a second time to
fit the memory: chip 0's events whose instruction name carries ``.remat``
(XLA's mark on a rematerialised instruction, ``%fusion.906.remat = ...``)
over all of chip 0's device-event time. An event's name is its
instruction's whole text, operands included: the mark counts only in the
instruction's own name, before the ``=``, or a fusion that reads a
recomputed value would count as one.

0 where the trace holds events and none is marked (nothing recomputed is
a reading), nothing where there is no trace. XLA may give the only copy of
an instruction the mark (``.remat2``): the compiled step's own count,
``recompile``'s ``remat_instructions``, goes by ``op_name`` and does not."""


def read(run: dict):
    by_name = (run.get("traced") or {}).get("by_name") or {}
    whole = sum(by_name.values())
    if whole <= 0:
        return None
    again = sum(seconds for name, seconds in by_name.items()
                if ".remat" in name.split(" = ", 1)[0])
    return 100.0 * again / whole
