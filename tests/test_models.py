"""Model family tests: shapes, determinism, loss decreases with training,
flash == reference attention inside the full model."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.llama import (
    Llama,
    LlamaConfig,
    cross_entropy_loss,
)


def _data(batch, seq, vocab, seed=0):
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(key, (batch, seq), 0, vocab)
    return tokens


class TestLlama:
    def test_forward_shape_and_param_count(self):
        cfg = LlamaConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        model = Llama(cfg)
        tokens = _data(2, 16, cfg.vocab_size)
        params = model.init(jax.random.PRNGKey(0), tokens)
        logits = model.apply(params, tokens)
        assert logits.shape == (2, 16, cfg.vocab_size)
        actual = sum(x.size for x in jax.tree.leaves(params))
        assert actual == cfg.param_count()

    def test_flash_matches_reference_in_model(self):
        cfg_ref = LlamaConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        cfg_flash = LlamaConfig.tiny(attn_impl="flash", dtype=jnp.float32)
        tokens = _data(1, 64, cfg_ref.vocab_size)
        params = Llama(cfg_ref).init(jax.random.PRNGKey(0), tokens)
        out_ref = Llama(cfg_ref).apply(params, tokens)
        out_flash = Llama(cfg_flash).apply(params, tokens)
        np.testing.assert_allclose(out_ref, out_flash, atol=2e-4, rtol=2e-4)

    def test_loss_decreases(self):
        cfg = LlamaConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        model = Llama(cfg)
        tokens = _data(4, 32, cfg.vocab_size)
        targets = jnp.roll(tokens, -1, axis=-1)
        params = model.init(jax.random.PRNGKey(0), tokens)
        tx = optax.adam(1e-3)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state):
            def loss_fn(p):
                return cross_entropy_loss(model.apply(p, tokens), targets)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        losses = []
        for _ in range(10):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.9, losses

    def test_remat_same_output(self):
        cfg = LlamaConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        cfg_remat = LlamaConfig.tiny(attn_impl="reference",
                                     dtype=jnp.float32, remat=True)
        tokens = _data(1, 16, cfg.vocab_size)
        params = Llama(cfg).init(jax.random.PRNGKey(0), tokens)
        out = Llama(cfg).apply(params, tokens)
        out_remat = Llama(cfg_remat).apply(params, tokens)
        np.testing.assert_allclose(out, out_remat, atol=1e-6)

    def test_config_families(self):
        assert LlamaConfig.llama_7b().param_count() > 6.5e9
        assert 0.9e9 < LlamaConfig.llama_1b().param_count() < 1.6e9
        assert 3e8 < LlamaConfig.llama_410m().param_count() < 6e8


class TestGPT:
    def test_forward_and_train(self):
        cfg = GPTConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        model = GPT(cfg)
        tokens = _data(2, 32, cfg.vocab_size)
        params = model.init(jax.random.PRNGKey(0), tokens)
        logits = model.apply(params, tokens)
        assert logits.shape == (2, 32, cfg.vocab_size)

        targets = jnp.roll(tokens, -1, axis=-1)
        tx = optax.adam(1e-3)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state):
            def loss_fn(p):
                return cross_entropy_loss(model.apply(p, tokens), targets)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        first = last = None
        for i in range(8):
            params, opt_state, loss = step(params, opt_state)
            first = first if first is not None else float(loss)
            last = float(loss)
        assert last < first

    def test_logical_axes_present(self):
        import flax.linen as nn

        cfg = GPTConfig.tiny(attn_impl="reference")
        tokens = _data(1, 8, cfg.vocab_size)
        variables = GPT(cfg).init(jax.random.PRNGKey(0), tokens)
        # with_partitioning wraps params in nn.Partitioned carrying names
        partitioned = [
            x for x in jax.tree.leaves(
                variables, is_leaf=lambda x: isinstance(x, nn.Partitioned))
            if isinstance(x, nn.Partitioned)
        ]
        assert partitioned, "expected logical axis annotations"


class TestBert:
    def test_mlm_forward_and_train(self):
        from dlrover_tpu.models.bert import Bert, BertConfig, mlm_loss

        cfg = BertConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        model = Bert(cfg)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                             jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)
        logits = model.apply(params, tokens)
        assert logits.shape == (2, 32, cfg.vocab_size)
        assert logits.dtype == jnp.float32

        # mask 15% of positions, predict the originals
        mask_positions = jnp.asarray(
            rng.random((2, 32)) < 0.15, jnp.float32)
        mask_id = cfg.vocab_size - 1
        corrupted = jnp.where(mask_positions.astype(bool), mask_id,
                              tokens)
        tx = optax.adam(1e-3)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state):
            def loss_fn(p):
                return mlm_loss(model.apply(p, corrupted), tokens,
                                mask_positions)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        first = last = None
        for _ in range(8):
            params, opt_state, loss = step(params, opt_state)
            first = first if first is not None else float(loss)
            last = float(loss)
        assert last < first

    def test_bidirectional_not_causal(self):
        """Flipping a FUTURE token must change a past position's logits
        (encoders attend both ways; a causal model would be invariant)."""
        from dlrover_tpu.models.bert import Bert, BertConfig

        cfg = BertConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        model = Bert(cfg)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 16)),
            jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)
        base = model.apply(params, tokens)
        flipped = tokens.at[0, 12].set((int(tokens[0, 12]) + 1)
                                       % cfg.vocab_size)
        out = model.apply(params, flipped)
        assert not np.allclose(np.asarray(base[0, 3]),
                               np.asarray(out[0, 3]))

    def test_flash_matches_reference_in_model(self):
        from dlrover_tpu.models.bert import Bert, BertConfig

        tokens = _data(1, 128, 128)
        out = {}
        for impl in ("reference", "flash"):
            cfg = BertConfig.tiny(attn_impl=impl, dtype=jnp.float32,
                                  max_seq_len=128)
            model = Bert(cfg)
            params = model.init(jax.random.PRNGKey(0), tokens)
            out[impl] = np.asarray(model.apply(params, tokens))
        np.testing.assert_allclose(out["flash"], out["reference"],
                                   atol=2e-2, rtol=2e-2)

    def test_token_types_and_masked_loss_ignores_padding(self):
        from dlrover_tpu.models.bert import Bert, BertConfig, mlm_loss

        cfg = BertConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        model = Bert(cfg)
        tokens = _data(2, 16, cfg.vocab_size)
        types = jnp.concatenate(
            [jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8), jnp.int32)],
            axis=1)
        params = model.init(jax.random.PRNGKey(0), tokens, types)
        logits = model.apply(params, tokens, types)
        # zero-weight positions contribute nothing
        w = jnp.zeros((2, 16)).at[:, :4].set(1.0)
        full = mlm_loss(logits, tokens)
        masked = mlm_loss(logits, tokens, w)
        assert np.isfinite(float(full)) and np.isfinite(float(masked))
        assert float(mlm_loss(logits, tokens, jnp.zeros((2, 16)))) == 0.0

    def test_sharded_training_on_mesh(self, cpu_devices):
        """The same strategy table applies to encoders: fsdp x tensor
        mesh losses match the single-device oracle."""
        from dlrover_tpu.models.bert import Bert, BertConfig, mlm_loss
        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
        from dlrover_tpu.trainer.train_step import build_trainer

        cfg = BertConfig.tiny(attn_impl="reference", dtype=jnp.float32,
                              embed_impl="onehot")
        tokens = np.asarray(_data(8, 16, cfg.vocab_size))

        def run(mesh):
            trainer = build_trainer(
                Bert(cfg), optax.adam(1e-3), mesh,
                jnp.zeros((8, 16), jnp.int32),
                lambda logits, tgt: mlm_loss(logits, tgt),
                accum_steps=1, micro_batch=8)
            state = trainer.init(jax.random.PRNGKey(0))
            losses = []
            for _ in range(3):
                tok, tgt = trainer.shard_batch(tokens, tokens)
                state, metrics = trainer.step(state, tok, tgt)
                losses.append(float(metrics["loss"]))
            return losses

        base = run(create_mesh(MeshSpec(data=1), cpu_devices[:1]))
        sharded = run(create_mesh(MeshSpec(fsdp=2, tensor=2),
                                  cpu_devices[:4]))
        np.testing.assert_allclose(sharded, base, atol=1e-4, rtol=1e-4)
        assert base[-1] < base[0]
