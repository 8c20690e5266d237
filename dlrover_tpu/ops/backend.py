"""The one place the package asks whether it runs on a TPU.

Every selection by backend goes through :func:`on_tpu`: the Pallas
kernels' interpret mode (off the chip they run in the interpreter so the
CPU tests exercise the kernel bodies), the fused-norm and flash dispatch
in the models, and the examples' defaults. It reads
``jax.default_backend()`` at call time — trace time for the kernels — so
every elastic re-trace re-resolves it, and a test steers all of them
with one ``monkeypatch.setattr(jax, "default_backend", ...)``.

Nothing here reports which path was taken: ``chip_smoke.py`` proves it
from the compiled step's text (``tpu_custom_call`` present means the
kernels are in the program, not the reference or the interpreter).
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"
