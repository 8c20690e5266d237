"""The yardstick's peaks, and the least time the chip could take for given
FLOPs and bytes. What a step needs is the model class's count
(``models/<key>.py:flops_per_token``), what a kernel's launch needs is in
``kernel_needs.py``. Stdlib only: the parent reads it without JAX.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip of ``device_kind``.
    No default: a kind that is not in ``peaks.json`` raises."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(
            f"no peak on record for device kind {device_kind!r}; add it to "
            "benchmarks/peaks.json with its public source")
    return table[device_kind]


def roofline_seconds(needs: dict, device_kind: str) -> tuple:
    """The least time the chip could take, and which bound it is."""
    peak = peaks(device_kind)
    by_flops = needs["flops"] / peak["bf16_flops_per_s"]
    by_bytes = needs["bytes"] / peak["hbm_bytes_per_s"]
    if by_flops >= by_bytes:
        return by_flops, "compute"
    return by_bytes, "memory"
