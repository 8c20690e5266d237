"""Model class ``llama``, its plain reference: the leaves of a Llama-shaped
decoder and one block's forward, in straightforward ``jax.numpy``. Nothing of
``dlrover_tpu`` is imported here; ``benchmarks/reference.py`` has the rest
(weights from the seed, operand precisions, head and loss, the optimizer, the
layer-by-layer step) and calls these four functions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import Leaf, linear, product, rms_norm

INIT_STDDEV = 0.02


def leaves(cfg: dict) -> dict:
    """name -> ``Leaf`` of every parameter, named as the program's tree is."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    out = {"embed": Leaf((v, h), (), 1, INIT_STDDEV)}
    for layer in range(cfg["num_hidden_layers"]):
        name = f"layer_{layer}"
        for norm in ("attn_norm", "mlp_norm"):
            out[f"{name}/{norm}/weight"] = Leaf((h,), (name, norm), 1, None)
        for proj, shape in (("q_proj", (h, q)), ("k_proj", (h, kv)),
                            ("v_proj", (h, kv)), ("o_proj", (q, h))):
            out[f"{name}/attn/{proj}/kernel"] = Leaf(
                shape, (name, "attn", proj), 1, INIT_STDDEV)
        for proj, shape in (("gate_proj", (h, i)), ("up_proj", (h, i)),
                            ("down_proj", (i, h))):
            out[f"{name}/mlp/{proj}/kernel"] = Leaf(
                shape, (name, "mlp", proj), 1, INIT_STDDEV)
    out["final_norm/weight"] = Leaf((h,), ("final_norm",), 1, None)
    if not cfg.get("tie_word_embeddings"):
        out["lm_head"] = Leaf((h, v), (), 2, INIT_STDDEV)
    return out


def layer_prefix(layer: int) -> str:
    """What the names of one layer's leaves start with."""
    return f"layer_{layer}/"


def layer_kind(cfg: dict, layer: int):
    """Layers of one kind share a compiled block: here all of them."""
    return "decoder"


def rope(x, theta: float):
    """Rotary embedding, halves rotated against each other (the
    published models' layout), on (batch, seq, heads, head_dim)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, mode):
    """Causal softmax attention, grouped-query: (b, s, heads, d) with
    k and v on fewer heads, each shared by heads/kv_heads queries."""
    b, s, heads, d = q.shape
    group = heads // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = product("bqhd,bkhd->bhqk", q, k, mode, -1, -1) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return product("bhqk,bkhd->bqhd", probs, v, mode, -1, 1)


def block(x, p: dict, cfg: dict, layer: int, mode: str):
    """One decoder block on (batch, seq, hidden); ``p`` holds the block's
    nine leaves by their short names. Every layer is the same."""
    b, s, _ = x.shape
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    y = rms_norm(x, p["attn_norm/weight"], eps)
    q = linear(y, p["attn/q_proj/kernel"], mode).reshape(b, s, -1, d)
    k = linear(y, p["attn/k_proj/kernel"], mode).reshape(b, s, -1, d)
    v = linear(y, p["attn/v_proj/kernel"], mode).reshape(b, s, -1, d)
    out = attention(rope(q, theta), rope(k, theta), v, mode)
    x = x + linear(out.reshape(b, s, -1), p["attn/o_proj/kernel"], mode)
    y = rms_norm(x, p["mlp_norm/weight"], eps)
    gate = linear(y, p["mlp/gate_proj/kernel"], mode)
    up = linear(y, p["mlp/up_proj/kernel"], mode)
    return x + linear(jax.nn.silu(gate) * up, p["mlp/down_proj/kernel"], mode)
