"""Model class ``twokind``, owned by the tests: a decoder whose layers are of
two kinds (``layer_types``), each kind with its own number of query heads,
head width and rotary base, built from the product's own ``DecoderBlock``.
No file under ``benchmarks/`` names it: ``test_model_class.py`` points the
harness's lookup (``harness.MODELS``) at this directory."""

from __future__ import annotations

from benchmarks.models.llama import (  # noqa: F401 - the contract's functions
    change_norms,
    change_norms_fn,
    first_grad_norms,
)


def heads_of(cfg: dict, layer: int) -> int:
    return cfg["num_attention_heads_by_type"][cfg["layer_types"][layer]]


def tiny(cfg: dict, traffic: dict) -> tuple:
    """The widths are the rehearsal's already; the per-layer list is cut
    with the depth."""
    cfg = dict(cfg, num_hidden_layers=2, layer_types=cfg["layer_types"][:2])
    return cfg, dict(traffic, seq_len=64, rows=512)


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """6 per matmul parameter (k and v are narrower where heads are) plus
    causal attention, 6 x heads x head width x sequence a layer."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    matmul = cfg["vocab_size"] * h
    for layer in attention_layers(cfg):
        q = layer["heads"] * layer["head_dim"]
        kv = layer["kv_heads"] * layer["head_dim"]
        matmul += 2 * h * q + 2 * h * kv + 3 * h * i
    return 6.0 * matmul + 6.0 * cfg["num_hidden_layers"] * h * seq_len


def attention_layers(cfg: dict) -> list:
    return [{"heads": heads_of(cfg, layer),
             "kv_heads": cfg["num_key_value_heads"],
             "head_dim": cfg["hidden_size"] // heads_of(cfg, layer),
             "window": None}
            for layer in range(cfg["num_hidden_layers"])]


def build(cfg: dict, traffic: dict):
    import flax.linen as nn
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models.llama import (
        DecoderBlock,
        LlamaConfig,
        RMSNorm,
        _logical,
        cross_entropy_loss,
        embed_lookup,
    )

    layers = tuple(
        LlamaConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"], num_heads=heads_of(cfg, k),
            num_kv_heads=cfg["num_key_value_heads"],
            max_seq_len=traffic["seq_len"],
            rope_theta=cfg["rope_theta_by_type"][cfg["layer_types"][k]],
            rms_norm_eps=cfg["rms_norm_eps"],
            dtype=jnp.dtype(cfg["compute_dtype"]),
            param_dtype=jnp.dtype(cfg["param_dtype"]),
            attn_impl=cfg["attn_impl"], norm_impl=cfg["norm_impl"],
            embed_impl=cfg["embed_impl"])
        for k in range(cfg["num_hidden_layers"]))

    class TwoKind(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            one = layers[0]
            normal = nn.initializers.normal(0.02)
            embed = self.param(
                "embed", _logical(normal, "vocab", "embed"),
                (one.vocab_size, one.hidden_size), one.param_dtype)
            x = embed_lookup(embed, tokens, one)
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[-1]),
                                         tokens.shape)
            for k, layer in enumerate(layers):
                x = DecoderBlock(layer, name=f"layer_{k}")(x, positions)
            x = RMSNorm(one.rms_norm_eps, one.dtype, one.norm_impl,
                        name="final_norm")(x)
            head = self.param(
                "lm_head", _logical(normal, "embed", "vocab"),
                (one.hidden_size, one.vocab_size), one.param_dtype)
            return jnp.dot(x, head.astype(one.dtype)).astype(jnp.float32)

    tx = optax.chain(optax.scale_by_factored_rms(),
                     optax.scale(-cfg["optimizer"]["learning_rate"]))
    return TwoKind(), tx, cross_entropy_loss
