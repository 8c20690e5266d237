"""What each named kernel needs, from shapes alone: FLOPs and HBM bytes of
one launch, for the per-kernel roofline readers; and what the readers take
from a reduced trace, by kernel name or by scope. Stdlib only.

An attention layer is one entry of the model class's ``attention_layers``:
``heads``, ``kv_heads``, ``head_dim`` and ``window`` (None: causal to the
start). The counts are split where the program's kernel names split them
(``flash_attn_fwd``; ``flash_attn_dq`` + ``flash_attn_dkv``). The norm
kernels have no entry: their operands live in the chip's fast memory space,
for which there is no public peak (PERF.md, PR 28), so their reader reports a
share of the device's time instead. What a class's program does outside a
kernel is read by scope (``scope_share``).
"""

from __future__ import annotations

import math

from benchmarks import flops


def scored_pairs(seq_len: int, window) -> float:
    """(query, key) pairs one head scores: the accepted ``s^2 / 2`` of a
    causal layer, and ``w s - w^2 / 2`` where each query sees only the last
    ``w < s`` keys (the same convention, equal at ``w = s``; the exact count
    ``sum_i min(i + 1, w)`` lies the diagonal's half cells above)."""
    if window is None or window >= seq_len:
        return seq_len * seq_len / 2.0
    return window * seq_len - window * window / 2.0


def _attention_sizes(layer: dict, batch: int, seq_len: int) -> tuple:
    d, heads, kv_heads = layer["head_dim"], layer["heads"], layer["kv_heads"]
    width = 2  # bfloat16
    q_bytes = batch * heads * seq_len * d * width
    kv_bytes = batch * kv_heads * seq_len * d * width
    stats = batch * heads * seq_len * 4
    matmul = 2.0 * batch * heads * scored_pairs(seq_len, layer["window"]) * d
    return q_bytes, kv_bytes, stats, matmul


def flash_attention_fwd(layer: dict, batch: int, seq_len: int) -> dict:
    """One layer's attention forward: QK^T and PV (2 matmuls over the scored
    pairs); reads q, k, v, writes o and the fp32 row statistics."""
    q_bytes, kv_bytes, stats, matmul = _attention_sizes(layer, batch, seq_len)
    return {"flops": 2.0 * matmul,
            "bytes": float(q_bytes + 2 * kv_bytes + q_bytes + stats)}


def flash_attention_bwd(layer: dict, batch: int, seq_len: int) -> dict:
    """dQ and dK/dV together, as one layer-step needs them: 4 matmuls (dV,
    dP, dQ, dK; recomputing S is the kernels' own choice and not credited);
    reads q, k, v, o, do and the statistics once, writes dq, dk, dv."""
    q_bytes, kv_bytes, stats, matmul = _attention_sizes(layer, batch, seq_len)
    return {"flops": 4.0 * matmul,
            "bytes": float(3 * q_bytes + 2 * kv_bytes + stats
                           + q_bytes + 2 * kv_bytes)}


def roofline_share(run: dict, needs, seconds: float, launches: float):
    """Share of their roofline, in %, of ``launches`` launches (one a layer
    and step) that took ``seconds``: the sum of the layers' least times, by
    the model class's ``attention_layers`` and ``needs`` (one of the two
    above), times the steps traced, over the time taken."""
    layers = run["model"].attention_layers(run["cfg"])
    batch, seq_len = per_chip_batch(run), run["traffic"]["seq_len"]
    least_a_step = math.fsum(
        flops.roofline_seconds(needs(layer, batch, seq_len),
                               run["device"]["kind"])[0] for layer in layers)
    return 100.0 * least_a_step * (launches / len(layers)) / seconds


def kernel_events(traced: dict, kernel: str) -> tuple:
    """(seconds, launches) of chip 0's device events whose name starts with
    the kernel's name: the program names each ``pallas_call``, the name is
    the custom-call's HLO instruction name (``%flash_attn_fwd.3 = ...``) and
    so the start of the profiler's event name."""
    by_name = traced.get("by_name") or {}
    counts = traced.get("count_by_name") or {}
    seconds, launches = 0.0, 0
    for name, spent in by_name.items():
        head = name.lstrip("%")
        after = head[len(kernel):len(kernel) + 1]   # "." or " ", if ours
        if head.startswith(kernel) and not (after.isalnum() or after == "_"):
            seconds += spent
            launches += counts.get(name, 0)
    return seconds, launches


def per_chip_batch(run: dict) -> int:
    return run["traffic"]["global_batch"] // max(1, run["workload"]["chips"])


def scope_share(traced: dict, name: str):
    """Share, in %, of chip 0's device-event time spent under the scope
    ``name``: a Flax module's, a ``jax.named_scope``'s, a transformation's
    (``transpose(jvp(``: the backward) or ``unscoped``, as
    ``trace_reduce.scopes_of`` takes them from an instruction's ``op_name``.
    Over all of that chip's device-event time, as
    ``kernels.rms_norm_share`` is. None where the record has no time by
    scope (the window held no text of its program) or the program no such
    scope."""
    by_scope = traced.get("by_scope") or {}
    if name not in by_scope:
        return None
    return 100.0 * by_scope[name] / math.fsum(traced["by_name"].values())
