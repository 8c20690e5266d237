"""The launcher's count of the local chips before the agent starts: a child
process that imports JAX and starts the TPU runtime only to count, before the
worker does the same again (the ``device_probe`` span; ``source`` says
whether the environment gave the count instead)."""

from benchmarks import setup_reduce


def read(run: dict):
    return setup_reduce.duration(run, "device_probe")
