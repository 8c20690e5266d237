"""The yardstick's arithmetic: what a step and a kernel need, and the peaks.

Copied in idea from ``dlrover_tpu/obs/mfu.py`` (6 x matmul parameters plus
the causal attention term, a gather embedding credited with nothing) so that
a later PR can change the program's own accounting without moving the
benchmark's. Stdlib only: the parent reads it without JAX.
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


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def param_counts(cfg: dict) -> dict:
    """Parameters of a Llama-shaped decoder, split by what they cost."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    layers = cfg["num_hidden_layers"]
    q = cfg["num_attention_heads"] * head_dim(cfg)
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    per_layer_matmul = h * q + 2 * h * kv + q * h + 3 * h * i
    tied = bool(cfg.get("tie_word_embeddings"))
    return {
        "matmul": layers * per_layer_matmul + v * h,   # blocks + head
        "norm": (2 * layers + 1) * h,
        "embedding": 0 if tied else v * h,             # a gather: no FLOPs
    }


def param_count(cfg: dict) -> int:
    return sum(param_counts(cfg).values())


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs one trained token needs, forward and backward, nothing
    recomputed: 6 per matmul parameter, and causal attention's
    QK^T + PV = 4 h s forward, x3 with the backward, /2 for the mask."""
    attention = 6.0 * cfg["num_hidden_layers"] * (
        cfg["num_attention_heads"] * head_dim(cfg)) * seq_len
    return 6.0 * param_counts(cfg)["matmul"] + attention


def flash_attention_needs(cfg: dict, batch: int, seq_len: int) -> dict:
    """FLOPs and HBM bytes that one layer's causal attention needs in one
    step, forward plus backward (dQ and dK/dV), from shapes alone.

    FLOPs: forward QK^T and PV, 2 matmuls; backward 4 (dV, dP, dQ, dK);
    each 2 b heads s^2 d, halved by the causal mask. Recomputing S in the
    backward kernels is the kernels' own choice and is not credited.
    Bytes: every operand read once and every result written once in the
    compute dtype (forward reads q, k, v and writes o; backward reads
    q, k, v, o, do and writes dq, dk, dv), plus the fp32 row statistics.
    """
    d = head_dim(cfg)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    width = 2  # bfloat16
    q_bytes = batch * heads * seq_len * d * width
    kv_bytes = batch * kv_heads * seq_len * d * width
    stats = batch * heads * seq_len * 4
    matmul = 2.0 * batch * heads * seq_len * seq_len * d / 2.0
    return {
        "flops": 6.0 * matmul,
        "bytes": float((q_bytes + 2 * kv_bytes + q_bytes + stats)
                       + (3 * q_bytes + 2 * kv_bytes + stats
                          + q_bytes + 2 * kv_bytes)),
    }


def roofline_seconds(needs: dict, device_kind: str) -> tuple:
    """The least time the chip could take, and which bound it is."""
    peak = peaks(device_kind)
    by_flops = needs["flops"] / peak["bf16_flops_per_s"]
    by_bytes = needs["bytes"] / peak["hbm_bytes_per_s"]
    if by_flops >= by_bytes:
        return by_flops, "compute"
    return by_bytes, "memory"
