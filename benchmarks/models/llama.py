"""Model class ``llama``: the program's ``dlrover_tpu.models.llama.Llama``
built from a configuration file's published keys, and what the comparison
needs to read out of the program's training state.

A new model class is new files beside these: ``<key>.py`` with the same
functions (``harness.MODEL_CLASS``; stdlib at import, the parent reads the
counts without JAX) and ``<key>_reference.py``, its plain reference;
``harness.py`` finds both by the configuration's ``model`` key.
"""

from __future__ import annotations


def tiny(cfg: dict, traffic: dict) -> tuple:
    """The rehearsal's sizes: every width shrunk, so nothing it prints can be
    taken for a measurement."""
    cfg = dict(cfg, hidden_size=128, intermediate_size=256,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=256)
    traffic = dict(traffic, seq_len=64, rows=512)
    if traffic.get("checkpoint"):
        traffic["checkpoint"] = dict(traffic["checkpoint"], min_free_bytes=0)
    return cfg, traffic


# -- what a step and the attention kernels need, from the sizes alone --------
# Copied in idea from ``dlrover_tpu/obs/mfu.py`` (6 x matmul parameters plus
# the causal attention term, a gather embedding credited with nothing) so
# that a later PR can change the program's own accounting without moving the
# benchmark's.


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


def attention_layers(cfg: dict) -> list:
    """One entry a layer, as ``kernel_needs`` takes them: every layer the
    same, causal to the start (``window`` None)."""
    return [{"heads": cfg["num_attention_heads"],
             "kv_heads": cfg["num_key_value_heads"],
             "head_dim": head_dim(cfg), "window": None}
            for _ in range(cfg["num_hidden_layers"])]


# -- the program, and what the comparison reads out of its state -------------


def build(cfg: dict, traffic: dict):
    """(model, optimizer, loss function) as the program runs them."""
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models.llama import (
        Llama,
        LlamaConfig,
        cross_entropy_loss,
    )

    heads = cfg["num_attention_heads"]
    if cfg.get("head_dim", cfg["hidden_size"] // heads) * heads != cfg[
            "hidden_size"]:
        raise ValueError("the program's Llama has head_dim = hidden/heads")
    config = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        max_seq_len=traffic["seq_len"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        attn_impl=cfg["attn_impl"], norm_impl=cfg["norm_impl"],
        embed_impl=cfg["embed_impl"], remat=cfg["remat"],
        tie_embeddings=cfg["tie_word_embeddings"])
    opt = cfg["optimizer"]
    if opt["name"] != "factored_rms":
        raise ValueError(f"no optimizer {opt['name']!r} in this model class")
    tx = optax.chain(optax.scale_by_factored_rms(),
                     optax.scale(-opt["learning_rate"]))
    return Llama(config), tx, cross_entropy_loss


def _named(tree) -> dict:
    import jax

    def name(path):
        return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                        for k in path)

    return {name(path): leaf for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


def first_grad_norms(state) -> dict:
    """Each leaf's gradient norm as the optimizer got it in the first update,
    worked out from the factored-RMS state after that one step: its decay is
    0 there, so ``v_row`` is the mean over the longest axis of ``g^2 + eps``
    (``v`` is ``g^2 + eps`` itself where the leaf is not factored)."""
    import jax
    import jax.numpy as jnp

    sizes = {name: p.size for name, p in _named(state.params).items()}
    shapes = {name: p.shape for name, p in _named(state.params).items()}

    @jax.jit
    def norms(factored):
        v_row, v = _named(factored.v_row), _named(factored.v)
        out = {}
        for name, size in sizes.items():
            if v[name].shape == shapes[name]:      # not factored
                total = jnp.sum(v[name])
            else:
                total = jnp.sum(v_row[name]) * (size // v_row[name].size)
            out[name] = jnp.sqrt(jnp.maximum(total - size * 1e-30, 0.0))
        return out

    return {k: float(v) for k, v in
            jax.device_get(norms(state.opt_state[0])).items()}


def change_norms_fn(trainer):
    """A jitted ``(rng, params) -> {leaf: |params - init(rng)|}``: the
    program's own initialiser runs again inside the program and each leaf is
    reduced as it is made, so no second copy of the parameters is kept."""
    import jax
    import jax.numpy as jnp

    def change(rng, params):
        initial = trainer.init_fn(rng).params
        return jax.tree.map(
            lambda now, was: jnp.sqrt(jnp.sum(jnp.square(
                now.astype(jnp.float32) - was.astype(jnp.float32)))),
            params, initial)

    return jax.jit(change)


def change_norms(change_fn, rng, state) -> dict:
    return {k: float(v) for k, v in _named(change_fn(rng, state.params)).items()}
