"""The worker's JAX start: the import, ``jax.distributed`` where the world
has more than one process, and the backend's start up to its devices (the
``backend_init`` span)."""

from benchmarks import setup_reduce


def read(run: dict):
    return setup_reduce.duration(run, "backend_init")
