"""The weights from the seed until they are ready on the device (the
``state_init`` span), while the step program compiles on its own thread."""

from benchmarks import setup_reduce


def read(run: dict):
    return setup_reduce.duration(run, "state_init")
