"""Model class ``twokind``, its plain reference: the leaves, and a block whose
head count, head width and rotary base follow the layer's kind. Written on
its own (nothing of ``benchmarks/models/`` and nothing of ``dlrover_tpu``);
``benchmarks/reference.py`` supplies what every class shares."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import Leaf, linear, product, rms_norm


def _heads(cfg: dict, layer: int) -> int:
    return cfg["num_attention_heads_by_type"][cfg["layer_types"][layer]]


def leaves(cfg: dict) -> dict:
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {"embed": Leaf((v, h), (), 1, 0.02),
           "lm_head": Leaf((h, v), (), 2, 0.02),
           "final_norm/weight": Leaf((h,), ("final_norm",), 1, None)}
    for layer in range(cfg["num_hidden_layers"]):
        name = f"layer_{layer}"
        kv = cfg["num_key_value_heads"] * (h // _heads(cfg, layer))
        for norm in ("attn_norm", "mlp_norm"):
            out[f"{name}/{norm}/weight"] = Leaf((h,), (name, norm), 1, None)
        for part, proj, shape in (
                ("attn", "q_proj", (h, h)), ("attn", "k_proj", (h, kv)),
                ("attn", "v_proj", (h, kv)), ("attn", "o_proj", (h, h)),
                ("mlp", "gate_proj", (h, i)), ("mlp", "up_proj", (h, i)),
                ("mlp", "down_proj", (i, h))):
            out[f"{name}/{part}/{proj}/kernel"] = Leaf(
                shape, (name, part, proj), 1, 0.02)
    return out


def layer_prefix(layer: int) -> str:
    return f"layer_{layer}/"


def layer_kind(cfg: dict, layer: int):
    return cfg["layer_types"][layer]


def _rotate(x, theta: float):
    """(batch, seq, heads, d): the two halves of a head turned against each
    other by the position's angle."""
    half = x.shape[-1] // 2
    frequency = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * frequency
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def block(x, p: dict, cfg: dict, layer: int, mode: str):
    b, s, h = x.shape
    heads, kv_heads = _heads(cfg, layer), cfg["num_key_value_heads"]
    d = h // heads
    theta = cfg["rope_theta_by_type"][cfg["layer_types"][layer]]
    y = rms_norm(x, p["attn_norm/weight"], cfg["rms_norm_eps"])
    q = _rotate(linear(y, p["attn/q_proj/kernel"], mode).reshape(
        b, s, heads, d), theta)
    k = _rotate(linear(y, p["attn/k_proj/kernel"], mode).reshape(
        b, s, kv_heads, d), theta)
    v = linear(y, p["attn/v_proj/kernel"], mode).reshape(b, s, kv_heads, d)
    # query head g * (heads / kv_heads) + r reads k and v of head g
    q = q.reshape(b, s, kv_heads, heads // kv_heads, d)
    scores = product("bqgrd,bkgd->bgrqk", q, k, mode, -1, -1) * d ** -0.5
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    mixed = product("bgrqk,bkgd->bqgrd", weights, v, mode, -1, 1)
    x = x + linear(mixed.reshape(b, s, h), p["attn/o_proj/kernel"], mode)
    y = rms_norm(x, p["mlp_norm/weight"], cfg["rms_norm_eps"])
    gate = linear(y, p["mlp/gate_proj/kernel"], mode)
    up = linear(y, p["mlp/up_proj/kernel"], mode)
    return x + linear(jax.nn.silu(gate) * up, p["mlp/down_proj/kernel"], mode)
