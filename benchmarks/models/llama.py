"""Model class ``llama``: the program's ``dlrover_tpu.models.llama.Llama``
built from a configuration file's published keys, and what the comparison
needs to read out of the program's training state.

A new model class is a new file beside this one with the same four
functions; ``worker.py`` finds it by the configuration's ``model`` key.
"""

from __future__ import annotations


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
