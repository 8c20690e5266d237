"""Model class ``keye``: the program's ``dlrover_tpu.models.keye.Keye``
(Keye-VL-2.0's language model: attention over the keys a learned indexer
selects, a mixture of experts of which this chip holds a share) built from a
configuration file's published keys; its FLOPs a token, its attention
layers and what its own kernels need, counted here on their own so that the
program's accounting can change without moving the benchmark's.

What the comparison reads out of the program's state is what it reads of
any model's (``models/llama.py``'s three functions: the optimizer is the
same).
"""

from __future__ import annotations

from benchmarks.models.llama import (  # noqa: F401 - the contract's functions
    change_norms,
    change_norms_fn,
    first_grad_norms,
)


def tiny(cfg: dict, traffic: dict) -> tuple:
    """The rehearsal's sizes, every mechanism alive: fewer keys selected
    than the sequence has, more experts than are held (and not the first
    ones), a head width that is not ``hidden // heads``. Float32 compute:
    at these sizes one key or one expert chosen otherwise on a bfloat16
    rounding moves a leaf's gradient by percents, so the rehearsal and the
    CPU tests compare the mathematics and the chip's runs the precision."""
    cfg = dict(cfg, compute_dtype="float32", hidden_size=128,
               num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               vocab_size=256, num_experts=8, num_local_experts=4,
               first_expert=2, num_experts_per_tok=2,
               moe_intermediate_size=64,
               sa_config=dict(cfg["sa_config"], indexer_head_dim=32,
                              indexer_num_heads=2, topk=16, q_chunk_size=32,
                              kv_chunk_size=32))
    return cfg, dict(traffic, seq_len=64, rows=512)


# -- what a step and the kernels need, from the sizes alone -----------------


def selected_pairs(seq_len: int, topk: int) -> float:
    """(query, key) pairs one head scores when each query attends at most
    ``topk`` of its causal keys: ``k s - k^2 / 2``, the accepted convention
    (``kernel_needs.scored_pairs``: half the diagonal's cells left out),
    ``s^2 / 2`` while every key is selected."""
    if topk >= seq_len:
        return seq_len * seq_len / 2.0
    return topk * seq_len - topk * topk / 2.0


def param_counts(cfg: dict) -> dict:
    """Parameters on this chip, split by what they cost a token."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    layers, d, sa = cfg["num_hidden_layers"], cfg["head_dim"], cfg["sa_config"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    index = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    return {
        # every token multiplies these
        "dense": layers * (2 * h * q + 2 * h * kv + h * index
                           + h * sa["indexer_head_dim"]
                           + h * sa["indexer_num_heads"]
                           + h * cfg["num_experts"]) + v * h,
        # a token multiplies the experts it is routed to, of those held
        "experts": layers * cfg["num_local_experts"] * expert,
        "norm": layers * (2 * h + 2 * d + sa["indexer_head_dim"]) + h,
        "embedding": v * h,                      # a gather: no FLOPs
    }


def param_count(cfg: dict) -> int:
    return sum(param_counts(cfg).values())


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs one trained token needs on this chip, forward and
    backward, nothing recomputed: 6 per matmul parameter on its path (of
    its ``num_experts_per_tok`` experts, the share held here in expectation:
    uniform routing), the main attention's QK^T and PV over the SELECTED
    pairs (x3 with the backward), the indexer's scores over every causal
    pair forward (they must all be scored to select) and over the selected
    ones backward (its objective reads no other)."""
    counts, layers = param_counts(cfg), cfg["num_hidden_layers"]
    sa = cfg["sa_config"]
    routed = (cfg["num_experts_per_tok"] / cfg["num_experts"]
              * counts["experts"])
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    index = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    selected = selected_pairs(seq_len, sa["topk"]) / seq_len
    attention = 3 * 4.0 * q * selected
    indexer = 2.0 * index * seq_len / 2.0 + 4.0 * index * selected
    return (6.0 * (counts["dense"] + routed)
            + layers * (attention + indexer))


def attention_layers(cfg: dict) -> list:
    """One entry a layer, every layer the same. The dense flash kernels do
    not serve them (``window`` None describes the causal extent the
    selection is made in): this class's kernels have the ``needs`` below."""
    return [{"heads": cfg["num_attention_heads"],
             "kv_heads": cfg["num_key_value_heads"],
             "head_dim": cfg["head_dim"], "window": None}
            for _ in range(cfg["num_hidden_layers"])]


def _attention_sizes(cfg: dict, batch: int, seq_len: int) -> tuple:
    d = cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q_bytes = batch * heads * seq_len * d * 2
    kv_bytes = batch * kv_heads * seq_len * d * 2
    stats = batch * heads * seq_len * 4
    matmul = 2.0 * batch * heads * d * selected_pairs(
        seq_len, cfg["sa_config"]["topk"])
    return q_bytes, kv_bytes, stats, matmul


def sparse_attn_fwd(cfg: dict, batch: int, seq_len: int) -> dict:
    """One layer's attention forward over the selected pairs, whatever
    computes it: QK^T and PV on them; q, k, v read, o and the fp32 row
    statistics written, once. The selection itself (a mask or indices) is
    the implementation's and is credited nothing."""
    q_bytes, kv_bytes, stats, matmul = _attention_sizes(cfg, batch, seq_len)
    return {"flops": 2.0 * matmul,
            "bytes": float(q_bytes + 2 * kv_bytes + q_bytes + stats)}


def sparse_attn_bwd(cfg: dict, batch: int, seq_len: int) -> dict:
    """dQ and dK/dV together: four matmuls over the selected pairs (the
    recomputed scores are the kernels' own choice); q, k, v, o, do and the
    statistics read once, dq, dk, dv written."""
    q_bytes, kv_bytes, stats, matmul = _attention_sizes(cfg, batch, seq_len)
    return {"flops": 4.0 * matmul,
            "bytes": float(3 * q_bytes + 2 * kv_bytes + stats
                           + q_bytes + 2 * kv_bytes)}


# -- the program ------------------------------------------------------------


def build(cfg: dict, traffic: dict):
    """(model, optimizer, loss function) as the program runs them."""
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models.keye import Keye, KeyeConfig
    from dlrover_tpu.models.llama import cross_entropy_loss

    sa = cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the program's indexer has one key head")
    config = KeyeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        attn_head_dim=cfg["head_dim"], qk_norm=True,
        max_seq_len=traffic["seq_len"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        norm_impl=cfg["norm_impl"], embed_impl=cfg["embed_impl"],
        remat=cfg["remat"], tie_embeddings=cfg["tie_word_embeddings"],
        num_experts=cfg["num_experts"],
        experts_held=cfg["num_local_experts"],
        first_expert=cfg["first_expert"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_intermediate=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        index_loss_weight=cfg["index_loss_weight"])
    opt = cfg["optimizer"]
    if opt["name"] != "factored_rms":
        raise ValueError(f"no optimizer {opt['name']!r} in this model class")
    tx = optax.chain(optax.scale_by_factored_rms(),
                     optax.scale(-opt["learning_rate"]))
    return Keye(config), tx, cross_entropy_loss
