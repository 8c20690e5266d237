"""Model class ``twokind``, its plain reference: the leaves, and a block that
follows the layer's kind. ``global`` is a Llama-shaped block; ``local`` has a
head width of its own, a stack of experts in one leaf of three axes, and an
index whose cross entropy against a detached target is the layer's second
objective: its block returns ``(x, extra)``. Written on its own (nothing of
``benchmarks/models/`` and nothing of ``dlrover_tpu``);
``benchmarks/reference.py`` supplies what every class shares."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import Leaf, linear, product, rms_norm


def leaves(cfg: dict) -> dict:
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {"embed": Leaf((v, h), (), 1, 0.02),
           "lm_head": Leaf((h, v), (), 2, 0.02),
           "final_norm/weight": Leaf((h,), ("final_norm",), 1, None)}
    for layer, kind in enumerate(cfg["layer_types"]):
        name = f"layer_{layer}"
        d = cfg["head_dim_by_type"][kind]
        q = cfg["num_attention_heads_by_type"][kind] * d
        kv = cfg["num_key_value_heads"] * d
        for norm in ("attn_norm", "mlp_norm"):
            out[f"{name}/{norm}/weight"] = Leaf((h,), (name, norm), 1, None)
        matrices = [("attn", "q_proj", (h, q)), ("attn", "k_proj", (h, kv)),
                    ("attn", "v_proj", (h, kv)), ("attn", "o_proj", (q, h)),
                    ("mlp", "down_proj", (i, h))]
        if kind == "global":
            matrices += [("mlp", "gate_proj", (h, i)),
                         ("mlp", "up_proj", (h, i))]
        else:
            # made in their module in this order: the count is flax's
            out[f"{name}/mlp/router"] = Leaf(
                (h, cfg["num_experts"]), (name, "mlp"), 1, 0.02)
            out[f"{name}/mlp/experts_up"] = Leaf(
                (cfg["num_experts"], h, i), (name, "mlp"), 2, 0.02)
            for count, leaf in enumerate(("target", "kernel"), start=1):
                out[f"{name}/index/{leaf}"] = Leaf(
                    (h, cfg["index_width"]), (name, "index"), count, 0.02)
        for part, proj, shape in matrices:
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


def _attention(y, p: dict, cfg: dict, kind: str, mode: str):
    b, s, _ = y.shape
    heads, kv_heads = (cfg["num_attention_heads_by_type"][kind],
                       cfg["num_key_value_heads"])
    d, theta = cfg["head_dim_by_type"][kind], cfg["rope_theta_by_type"][kind]
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
    return linear(mixed.reshape(b, s, heads * d), p["attn/o_proj/kernel"],
                  mode)


def _index_gap(y, p: dict, cfg: dict, mode: str):
    """The layer's second objective, weighted: the mean cross entropy of the
    index's distribution against the target's less the target's entropy.
    The target is a constant of the step."""
    wanted = jax.lax.stop_gradient(
        jax.nn.softmax(linear(y, p["index/target"], mode), axis=-1))
    got = jax.nn.log_softmax(linear(y, p["index/kernel"], mode), axis=-1)
    gap = jnp.sum(wanted * (jnp.log(wanted) - got), axis=-1)
    return cfg["index_loss_weight"] * jnp.mean(gap)


def block(x, p: dict, cfg: dict, layer: int, mode: str):
    kind, eps = cfg["layer_types"][layer], cfg["rms_norm_eps"]
    y = rms_norm(x, p["attn_norm/weight"], eps)
    x = x + _attention(y, p, cfg, kind, mode)
    z = rms_norm(x, p["mlp_norm/weight"], eps)
    if kind == "global":
        gate = linear(z, p["mlp/gate_proj/kernel"], mode)
        up = linear(z, p["mlp/up_proj/kernel"], mode)
        return x + linear(jax.nn.silu(gate) * up, p["mlp/down_proj/kernel"],
                          mode)
    share = jax.nn.softmax(linear(z, p["mlp/router"], mode), axis=-1)
    act = jax.nn.silu(product("bsh,ehi->bsei", z, p["mlp/experts_up"], mode,
                              -1, 1))
    mixed = product("bsei,bse->bsi", act, share, mode, 2, -1)
    x = x + linear(mixed, p["mlp/down_proj/kernel"], mode)
    return x, _index_gap(y, p, cfg, mode)
