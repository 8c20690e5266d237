"""Model class ``twokind``, owned by the tests: a decoder whose layers are of
two kinds (``layer_types``). ``global`` is the product's own ``DecoderBlock``.
``local`` is a block of the tests' own with the shapes a harness written for
Llama would trip on: a head width that is not ``hidden // heads`` (q is
``heads x head_dim`` wide), an MLP whose up-projections are one leaf of three
axes (a stack of experts, mixed by a router), a leaf whose gradient is
exactly zero (the ``index`` target: its output is detached) and a second
objective, sown into ``losses``, that alone trains one leaf (the index's
kernel). No file under ``benchmarks/`` names it: ``bench_cells.py`` enters
it and ``conftest.py`` points the harness's lookup at this directory."""

from __future__ import annotations

from benchmarks.models.llama import (  # noqa: F401 - the contract's functions
    change_norms,
    change_norms_fn,
    first_grad_norms,
)


def tiny(cfg: dict, traffic: dict) -> tuple:
    """The widths are the rehearsal's already; the per-layer list is cut
    with the depth."""
    cfg = dict(cfg, num_hidden_layers=2, layer_types=cfg["layer_types"][:2])
    return cfg, dict(traffic, seq_len=64, rows=512)


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """6 per matmul parameter on a token's path (every expert: the mixture is
    dense) plus causal attention, 6 x heads x head width x sequence a
    layer."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    mlp = {"global": 3 * h * i,           # gate, up, down
           "local": ((cfg["num_experts"] + 1) * h * i      # experts, down
                     + h * cfg["num_experts"]              # router
                     + 2 * h * cfg["index_width"])}        # index
    matmul, attention = cfg["vocab_size"] * h, 0
    for kind, layer in zip(cfg["layer_types"], attention_layers(cfg)):
        q = layer["heads"] * layer["head_dim"]
        kv = layer["kv_heads"] * layer["head_dim"]
        matmul += 2 * h * q + 2 * h * kv + mlp[kind]
        attention += q
    return 6.0 * matmul + 6.0 * attention * seq_len


def attention_layers(cfg: dict) -> list:
    return [{"heads": cfg["num_attention_heads_by_type"][kind],
             "kv_heads": cfg["num_key_value_heads"],
             "head_dim": cfg["head_dim_by_type"][kind], "window": None}
            for kind in cfg["layer_types"]]


def build(cfg: dict, traffic: dict):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models.llama import (
        DecoderBlock,
        LlamaConfig,
        RMSNorm,
        _logical,
        apply_rope,
        cross_entropy_loss,
        embed_lookup,
        functools_partial_dense,
    )
    from dlrover_tpu.ops.flash_attention import mesh_flash_attention

    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    kv_heads = cfg["num_key_value_heads"]
    normal = nn.initializers.normal(0.02)

    def layer_config(kind):
        return LlamaConfig(
            vocab_size=cfg["vocab_size"], hidden_size=h, intermediate_size=i,
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads_by_type"][kind],
            num_kv_heads=kv_heads, max_seq_len=traffic["seq_len"],
            rope_theta=cfg["rope_theta_by_type"][kind],
            rms_norm_eps=cfg["rms_norm_eps"],
            dtype=jnp.dtype(cfg["compute_dtype"]),
            param_dtype=jnp.dtype(cfg["param_dtype"]),
            attn_impl=cfg["attn_impl"], norm_impl=cfg["norm_impl"],
            embed_impl=cfg["embed_impl"])

    one, c = layer_config("global"), layer_config("local")
    if one.head_dim != cfg["head_dim_by_type"]["global"]:
        raise ValueError("the product's block has head_dim = hidden / heads")

    # the local kind's modules take their sizes from this scope, not from
    # fields: the harness loads this file under no module name, where a flax
    # module cannot resolve a field's annotation
    class WideAttention(nn.Module):
        """``Attention`` of the product with a head width of its own."""

        @nn.compact
        def __call__(self, x, positions):
            d = cfg["head_dim_by_type"]["local"]
            batch, seq, _ = x.shape
            dense = functools_partial_dense(c)
            q = dense("q_proj", (h, c.num_heads * d), ("embed", "heads"))(x)
            k = dense("k_proj", (h, kv_heads * d), ("embed", "kv"))(x)
            v = dense("v_proj", (h, kv_heads * d), ("embed", "kv"))(x)
            q = apply_rope(q.reshape(batch, seq, c.num_heads, d), positions,
                           c.rope_theta)
            k = apply_rope(k.reshape(batch, seq, kv_heads, d), positions,
                           c.rope_theta)
            v = v.reshape(batch, seq, kv_heads, d)
            out = mesh_flash_attention(*(t.transpose(0, 2, 1, 3)
                                         for t in (q, k, v)), True)
            out = out.transpose(0, 2, 1, 3).reshape(batch, seq, -1)
            return dense("o_proj", (c.num_heads * d, h),
                         ("heads", "embed"))(out)

    class Experts(nn.Module):
        """Every expert's up-projection in one leaf of three axes; a router
        mixes their activations (all of them: nothing is selected, so the
        program and its reference cannot part ways over a near tie)."""

        @nn.compact
        def __call__(self, y):
            experts = cfg["num_experts"]
            router = self.param("router", _logical(normal, "embed", None),
                                (h, experts), jnp.float32)
            up = self.param("experts_up",
                            _logical(normal, None, "embed", "mlp"),
                            (experts, h, i), c.param_dtype)
            share = jax.nn.softmax(y.astype(jnp.float32) @ router, axis=-1)
            act = nn.silu(jnp.einsum("bsh,ehi->bsei", y, up.astype(c.dtype)))
            mixed = jnp.einsum("bsei,bse->bsi", act, share.astype(c.dtype))
            return functools_partial_dense(c)(
                "down_proj", (i, h), ("mlp", "embed"))(mixed)

    class Index(nn.Module):
        """The second objective: ``kernel`` learns to predict, from the
        layer's normed input, a distribution that ``target`` draws from the
        same input. The target is detached, so ``target`` gets a gradient of
        exactly zero; the cross entropy's weighted mean goes into ``losses``
        and is all that trains ``kernel`` (and, through the input, what lies
        below)."""

        @nn.compact
        def __call__(self, y):
            width = cfg["index_width"]
            target = self.param("target", _logical(normal, "embed", None),
                                (h, width), jnp.float32)
            kernel = self.param("kernel", _logical(normal, "embed", None),
                                (h, width), jnp.float32)
            y = y.astype(jnp.float32)
            wanted = jax.lax.stop_gradient(jax.nn.softmax(y @ target, -1))
            got = jax.nn.log_softmax(y @ kernel, axis=-1)
            gap = jnp.sum(wanted * (jnp.log(wanted) - got), axis=-1)
            self.sow("losses", "index_gap",
                     cfg["index_loss_weight"] * jnp.mean(gap))

    class LocalBlock(nn.Module):
        @nn.compact
        def __call__(self, x, positions):
            y = RMSNorm(c.rms_norm_eps, c.dtype, c.norm_impl,
                        name="attn_norm")(x)
            Index(name="index")(y)
            x = x + WideAttention(name="attn")(y, positions)
            return x + Experts(name="mlp")(
                RMSNorm(c.rms_norm_eps, c.dtype, c.norm_impl,
                        name="mlp_norm")(x))

    def block(kind, name):
        return (LocalBlock(name=name) if kind == "local"
                else DecoderBlock(one, name=name))

    class TwoKind(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            embed = self.param(
                "embed", _logical(normal, "vocab", "embed"),
                (one.vocab_size, one.hidden_size), one.param_dtype)
            x = embed_lookup(embed, tokens, one)
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[-1]),
                                         tokens.shape)
            for k, kind in enumerate(cfg["layer_types"]):
                x = block(kind, f"layer_{k}")(x, positions)
            x = RMSNorm(one.rms_norm_eps, one.dtype, one.norm_impl,
                        name="final_norm")(x)
            head = self.param(
                "lm_head", _logical(normal, "embed", "vocab"),
                (one.hidden_size, one.vocab_size), one.param_dtype)
            return jnp.dot(x, head.astype(one.dtype)).astype(jnp.float32)

    tx = optax.chain(optax.scale_by_factored_rms(),
                     optax.scale(-cfg["optimizer"]["learning_rate"]))
    return TwoKind(), tx, cross_entropy_loss
