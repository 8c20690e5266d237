"""Model family tests: shapes, determinism, loss decreases with training,
flash == reference attention inside the full model."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models import llama
from dlrover_tpu.models.llama import (
    Llama,
    LlamaConfig,
    cross_entropy_loss,
    tie_weight_grads,
    tied_dot,
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


def _same_bits(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(want.astype(jnp.float32)))


def _plain_dot(x, w, tie):
    """The formula before PR 36, kept as the reference."""
    return jnp.dot(x, w)


class TestTiedDot:
    """`tied_dot` is `jnp.dot` with its weight gradient tied into the
    module's backward: nothing of the arithmetic may differ from the
    formula it stands for."""

    @pytest.mark.parametrize("x_shape", [(24, 16), (3, 8, 16)],
                             ids=["2d", "3d"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_value_and_both_gradients_are_jnp_dots_to_the_bit(
            self, dtype, x_shape):
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(keys[0], x_shape, dtype)
        w = jax.random.normal(keys[1], (16, 40), dtype)
        g = jax.random.normal(keys[2], x_shape[:-1] + (40,), dtype)

        def value_and_grads(dot):
            def run(x, w, g):
                def through_the_tie(x, w):
                    x, tie = tie_weight_grads(x)
                    return dot(x, w, tie)
                out, vjp = jax.vjp(through_the_tie, x, w)
                return out, vjp(g)
            return run

        # like with like: a jitted product against a jitted `jnp.dot`
        for wrap in (lambda f: f, jax.jit):
            got, grads = wrap(value_and_grads(tied_dot))(x, w, g)
            want, want_grads = wrap(value_and_grads(_plain_dot))(x, w, g)
            _same_bits(got, want)
            for a, b in zip(grads, want_grads):
                _same_bits(a, b)

    def test_the_inputs_cotangent_waits_for_the_weight_gradient(self):
        """The mechanism: the cotangent `tie` carries is read off dW, and
        the module input's cotangent is made from it."""
        x, w = jnp.ones((4, 8)), jnp.ones((8, 2))

        def through_the_tie(x, w):
            x, tie = tie_weight_grads(x)
            return tied_dot(x, w, tie)

        # x enters dW = x^T g and not dx = g w^T: an x that is not finite
        # shows in the tied dx (0 x inf is NaN) and not in the plain one
        x = x.at[0, 0].set(jnp.inf)
        dx, dw = jax.vjp(through_the_tie, x, w)[1](jnp.ones((4, 2)))
        plain_dx, plain_dw = jax.vjp(jnp.dot, x, w)[1](jnp.ones((4, 2)))
        assert np.isnan(np.asarray(dx)).all()
        assert np.isfinite(np.asarray(plain_dx)).all()
        np.testing.assert_array_equal(np.asarray(dw), np.asarray(plain_dw))

    @staticmethod
    def _loss_and_grads(cfg, params, tokens, targets, jit=False):
        def loss(p):
            return cross_entropy_loss(Llama(cfg).apply(p, tokens), targets)

        fn = jax.value_and_grad(loss)
        if jit:
            # the state donated, as the trainer's step donates it
            fn = jax.jit(fn, donate_argnums=0)
            params = jax.tree.map(jnp.copy, params)
        return fn(params)

    @pytest.mark.parametrize("how", ["plain", "remat", "jit_donated"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_two_layer_llama_equals_the_plain_formula(
            self, monkeypatch, dtype, how):
        """Loss and the whole gradient tree against the same model built
        on `jnp.dot` (the formula before PR 36, kept here)."""
        cfg = LlamaConfig.tiny(attn_impl="reference", dtype=dtype,
                               remat=how == "remat")
        tokens = _data(2, 16, cfg.vocab_size)
        targets = jnp.roll(tokens, -1, axis=-1)
        params = Llama(cfg).init(jax.random.PRNGKey(0), tokens)
        jit = how == "jit_donated"
        loss, grads = self._loss_and_grads(cfg, params, tokens, targets, jit)
        monkeypatch.setattr(llama, "tied_dot", _plain_dot)
        want_loss, want = self._loss_and_grads(
            cfg, params, tokens, targets, jit)
        assert jax.tree.structure(grads) == jax.tree.structure(want)
        if jit and dtype == jnp.bfloat16:
            # under jit XLA fuses the tie's x 1 into its neighbours and
            # keeps float32 between them where the plain program rounded
            # to bf16 (8 bits): a few roundings at each leaf's scale
            def close(got, want):
                want = np.asarray(want.astype(jnp.float32))
                np.testing.assert_allclose(
                    np.asarray(got.astype(jnp.float32)), want, rtol=0,
                    atol=4 * 2.0 ** -8 * np.abs(want).max())
            close(loss, want_loss)
            jax.tree.map(close, grads, want)
        else:
            _same_bits(loss, want_loss)
            jax.tree.map(_same_bits, grads, want)

    def test_llama_moe_still_traces(self):
        """`LlamaMoE` takes `Attention`, and with it the tied product."""
        from dlrover_tpu.models.llama_moe import LlamaMoE, LlamaMoEConfig

        cfg = LlamaMoEConfig.mixtral_tiny(attn_impl="reference")
        tokens = _data(2, 16, cfg.vocab_size)
        model = LlamaMoE(cfg)
        variables = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens))

        def loss(params):
            logits, _ = model.apply(
                {"params": params}, tokens, mutable=["losses"],
                rngs={"gating": jax.random.PRNGKey(1)})
            return jnp.sum(logits.astype(jnp.float32))

        grads = jax.eval_shape(jax.grad(loss), variables["params"])
        assert (jax.tree.structure(grads)
                == jax.tree.structure(variables["params"]))
        assert nn.unbox(grads)["layer_0"]["attn"]["q_proj"][
            "kernel"].shape == (cfg.hidden_size,
                                cfg.num_heads * cfg.head_dim)


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
