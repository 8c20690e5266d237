"""The trainer's build for the world's shape: trace, shardings and the jitted
programs' wrappers (the first ``recompile`` span of ``phase=relower``)."""

from benchmarks import setup_reduce


def read(run: dict):
    return setup_reduce.duration(run, "recompile", phase="relower")
