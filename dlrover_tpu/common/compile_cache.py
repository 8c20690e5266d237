"""Where the persistent XLA compile cache lives.

The cache directory's path is part of every entry's key, so a directory
that moves (a temp name, a pid, a work dir made per run) never hits: a
respawned or re-launched worker would pay the step program's compile
again in full. One rule for every process this package starts:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else one
fixed directory inside the checkout. Callers hand the path to the
processes they spawn through that same environment variable; nothing
sets ``jax_compilation_cache_dir`` through ``jax.config``.

jax-free on purpose: the agent imports it and must stay off the chip.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    return os.environ.get(ENV) or os.path.join(_CHECKOUT, ".jax_cache")
