"""What each named kernel needs, from shapes alone: FLOPs and HBM bytes of
one launch, for the per-kernel roofline readers. Stdlib only.

``flops.flash_attention_needs`` (the accepted yardstick) counts one layer's
attention forward plus backward; here the same counts are split where the
program's kernel names split them (``flash_attn_fwd``; ``flash_attn_dq`` +
``flash_attn_dkv``), and a test holds forward + backward equal to it exactly.
The norm kernels have no entry: their operands live in the chip's fast
memory space, for which there is no public peak (PERF.md, PR 28), so their
reader reports a share of the device's time instead.
"""

from __future__ import annotations

from benchmarks import flops

def _attention_sizes(cfg: dict, batch: int, seq_len: int) -> tuple:
    d = flops.head_dim(cfg)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    width = 2  # bfloat16, as flops.flash_attention_needs has it
    q_bytes = batch * heads * seq_len * d * width
    kv_bytes = batch * kv_heads * seq_len * d * width
    stats = batch * heads * seq_len * 4
    matmul = 2.0 * batch * heads * seq_len * seq_len * d / 2.0
    return q_bytes, kv_bytes, stats, matmul


def flash_attention_fwd(cfg: dict, batch: int, seq_len: int) -> dict:
    """One layer's causal attention forward: QK^T and PV (2 matmuls, halved
    by the mask); reads q, k, v, writes o and the fp32 row statistics."""
    q_bytes, kv_bytes, stats, matmul = _attention_sizes(cfg, batch, seq_len)
    return {"flops": 2.0 * matmul,
            "bytes": float(q_bytes + 2 * kv_bytes + q_bytes + stats)}


def flash_attention_bwd(cfg: dict, batch: int, seq_len: int) -> dict:
    """dQ and dK/dV together, as one layer-step needs them: 4 matmuls (dV,
    dP, dQ, dK; recomputing S is the kernels' own choice and not credited);
    reads q, k, v, o, do and the statistics once, writes dq, dk, dv."""
    q_bytes, kv_bytes, stats, matmul = _attention_sizes(cfg, batch, seq_len)
    return {"flops": 4.0 * matmul,
            "bytes": float(3 * q_bytes + 2 * kv_bytes + stats
                           + q_bytes + 2 * kv_bytes)}


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
