"""The step program's lower and XLA compile, or its load from the persistent
compile cache (the ``recompile`` span of ``phase=aot``). Its ``cache`` attr
says which: a run that compiles and one that loads read apart, and are not
to be averaged."""

from benchmarks import setup_reduce


def read(run: dict):
    return setup_reduce.duration(run, "recompile", phase="aot")
