"""Model class ``minicpm_sala``: the program's
``dlrover_tpu.models.minicpm_sala.MiniCPMSala`` (layers of two kinds:
InfLLM-v2 block-sparse attention and lightning linear attention) built from
a configuration file's published keys; its FLOPs a token, its attention
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

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
LIGHTNING_CHUNK = 64    # the chunk the lightning kernels' needs are counted at


def tiny(cfg: dict, traffic: dict) -> tuple:
    """The rehearsal's sizes, both kinds of layer present and the sparse one
    sparse: 256 tokens in blocks of 16 of which a query takes 4. Float32
    compute: at these sizes one block chosen otherwise on a bfloat16
    rounding moves a leaf's gradient by percents, so the rehearsal and the
    CPU tests compare the mathematics and the chip's runs the precision."""
    cfg = dict(cfg, compute_dtype="float32", hidden_size=64,
               intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               lightning_nh=2, lightning_nkv=2, lightning_head_dim=32,
               vocab_size=256,
               sparse_config=dict(cfg["sparse_config"], block_size=16,
                                  topk=4, kernel_size=8, kernel_stride=4,
                                  window_size=32, dense_len=64))
    return cfg, dict(traffic, seq_len=256, rows=512)


def _kinds(cfg: dict) -> list:
    return cfg["mixer_types"][:cfg["num_hidden_layers"]]


def _widths(cfg: dict, kind: str) -> tuple:
    """(heads, kv heads, head width) of a layer of ``kind``."""
    if kind == SPARSE:
        return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    return cfg["lightning_nh"], cfg["lightning_nkv"], cfg["lightning_head_dim"]


# -- what a step and the kernels need, from the sizes alone -----------------


def param_counts(cfg: dict) -> dict:
    """Parameters, split by what they cost a token."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    matmul, norm = v * h, h
    for kind in _kinds(cfg):
        heads, kv_heads, d = _widths(cfg, kind)
        q, kv = heads * d, kv_heads * d
        matmul += 3 * h * q + 2 * h * kv + 3 * h * i    # q gate o, k v, MLP
        norm += 2 * h + 2 * d + (q if kind == LIGHTNING else 0)
    return {"matmul": matmul, "norm": norm,
            "embedding": v * h}                          # a gather: no FLOPs


def param_count(cfg: dict) -> int:
    return sum(param_counts(cfg).values())


def selected_pairs(cfg: dict, seq_len: int) -> float:
    """(query, key) pairs one head of a sparse layer attends, in the
    accepted convention that counts half the diagonal: ``k s - k^2 / 2`` for
    the ``k = topk x block_size`` keys a query takes from ``dense_len`` on,
    ``s^2 / 2`` below it or while every key is taken."""
    sp = cfg["sparse_config"]
    keys = sp["topk"] * sp["block_size"]
    if seq_len < sp["dense_len"] or keys >= seq_len:
        return seq_len * seq_len / 2.0
    return keys * seq_len - keys * keys / 2.0


def _lightning_flops(cfg: dict, batch: int, seq_len: int) -> float:
    """One lightning layer's forward in the chunked form at a chunk of
    ``LIGHTNING_CHUNK``: a chunk's (Q K^T) V and the state's Q S and K^T V,
    4 C d + 4 d^2 a head and token."""
    heads, _, d = _widths(cfg, LIGHTNING)
    c = LIGHTNING_CHUNK
    return float(batch * seq_len * heads * (4 * c * d + 4 * d * d))


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs one trained token needs, forward and backward, nothing
    recomputed: 6 per matmul parameter, the sparse layers' QK^T and PV over
    the selected pairs (x3 with the backward), the lightning layers'
    chunked form (x3), and the selection's scores of each query head
    against every compressed key at or before it (forward only)."""
    total = 6.0 * param_counts(cfg)["matmul"]
    sp = cfg["sparse_config"]
    for kind in _kinds(cfg):
        heads, _, d = _widths(cfg, kind)
        if kind == LIGHTNING:
            total += 3.0 * _lightning_flops(cfg, 1, seq_len) / seq_len
            continue
        total += 3 * 4.0 * heads * d * selected_pairs(cfg, seq_len) / seq_len
        if seq_len >= sp["dense_len"]:
            compressed = seq_len / 2.0 / sp["kernel_stride"]
            total += 2.0 * heads * d * compressed
    return total


def attention_layers(cfg: dict) -> list:
    """One entry a layer. The dense flash kernels serve none of them
    (``window`` None describes the causal extent the selection is made in;
    a lightning layer has no pairs): this class's kernels have the
    ``needs`` below."""
    out = []
    for kind in _kinds(cfg):
        heads, kv_heads, d = _widths(cfg, kind)
        out.append({"heads": heads, "kv_heads": kv_heads, "head_dim": d,
                    "window": None})
    return out


def lightning_fwd(cfg: dict, batch: int, seq_len: int) -> dict:
    """One lightning layer's forward, whatever computes it: the chunked
    form's FLOPs at ``LIGHTNING_CHUNK``; q, k, v read and o written once
    (bfloat16). What a form keeps for its backward is its own."""
    heads, _, d = _widths(cfg, LIGHTNING)
    rows = batch * seq_len * heads * d * 2
    return {"flops": _lightning_flops(cfg, batch, seq_len),
            "bytes": float(4 * rows)}


def lightning_bwd(cfg: dict, batch: int, seq_len: int) -> dict:
    """dQ, dK and dV: each the forward's form over other operands (three
    times its FLOPs); q, k, v and dO read, dq, dk and dv written once."""
    heads, _, d = _widths(cfg, LIGHTNING)
    rows = batch * seq_len * heads * d * 2
    return {"flops": 3.0 * _lightning_flops(cfg, batch, seq_len),
            "bytes": float(7 * rows)}


def _sparse_sizes(cfg: dict, batch: int, seq_len: int) -> tuple:
    heads, kv_heads, d = _widths(cfg, SPARSE)
    q_bytes = batch * heads * seq_len * d * 2
    kv_bytes = batch * kv_heads * seq_len * d * 2
    stats = batch * heads * seq_len * 4
    matmul = 2.0 * batch * heads * d * selected_pairs(cfg, seq_len)
    return q_bytes, kv_bytes, stats, matmul


def block_sparse_attn_fwd(cfg: dict, batch: int, seq_len: int) -> dict:
    """One sparse layer's attention forward over the SELECTED pairs only,
    whatever computes them: QK^T and PV; q, k, v read, o and the fp32 row
    statistics written, once. The block mask is credited nothing."""
    q_bytes, kv_bytes, stats, matmul = _sparse_sizes(cfg, batch, seq_len)
    return {"flops": 2.0 * matmul,
            "bytes": float(q_bytes + 2 * kv_bytes + q_bytes + stats)}


def block_sparse_attn_bwd(cfg: dict, batch: int, seq_len: int) -> dict:
    """dQ and dK/dV together: four matmuls over the selected pairs; q, k, v,
    o, do and the statistics read once, dq, dk, dv written."""
    q_bytes, kv_bytes, stats, matmul = _sparse_sizes(cfg, batch, seq_len)
    return {"flops": 4.0 * matmul,
            "bytes": float(3 * q_bytes + 2 * kv_bytes + stats
                           + q_bytes + 2 * kv_bytes)}


# -- the program ------------------------------------------------------------

# published keys whose value the program's equations take as given
_AS_BUILT = {"attn_use_rope": False, "lightning_use_rope": True,
             "qk_norm": True, "use_output_gate": True,
             "use_output_norm": True, "attn_use_output_gate": True,
             "attention_bias": False, "hidden_act": "silu",
             "lightning_scale": "1/sqrt(d)", "tie_word_embeddings": False}


def build(cfg: dict, traffic: dict):
    """(model, optimizer, loss function) as the program runs them."""
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models.llama import cross_entropy_loss
    from dlrover_tpu.models.minicpm_sala import MiniCPMSala, SalaConfig
    from dlrover_tpu.ops.block_sparse_attention import Sparsity

    for key, value in _AS_BUILT.items():
        if cfg[key] != value:
            raise ValueError(f"the program's MiniCPM-SALA has {key} {value!r}")
    if cfg["lightning_nkv"] != cfg["lightning_nh"]:
        raise ValueError("the program's lightning layers have as many key "
                         "heads as query heads")
    sp = cfg["sparse_config"]
    config = SalaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        attn_head_dim=cfg["head_dim"], max_seq_len=traffic["seq_len"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        norm_impl=cfg["norm_impl"], embed_impl=cfg["embed_impl"],
        remat=cfg["remat"], embed_scale=float(cfg["scale_emb"]),
        mixer_types=tuple(cfg["mixer_types"]),
        published_layers=cfg["published"]["num_hidden_layers"],
        scale_depth=float(cfg["scale_depth"]),
        lightning_heads=cfg["lightning_nh"],
        lightning_head_dim=cfg["lightning_head_dim"],
        sparsity=Sparsity(
            block=sp["block_size"], topk=sp["topk"],
            kernel=sp["kernel_size"], stride=sp["kernel_stride"],
            init_blocks=sp["init_blocks"], window=sp["window_size"],
            dense_len=sp["dense_len"]))
    opt = cfg["optimizer"]
    if opt["name"] != "factored_rms":
        raise ValueError(f"no optimizer {opt['name']!r} in this model class")
    tx = optax.chain(optax.scale_by_factored_rms(),
                     optax.scale(-opt["learning_rate"]))
    return MiniCPMSala(config), tx, cross_entropy_loss
