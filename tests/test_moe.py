"""MoE / expert-parallel tests (parity: atorch tests moe_test.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.common.constants import MeshAxis
from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
from dlrover_tpu.parallel.moe import (
    ExpertMLP,
    MoEConfig,
    MoELayer,
    moe_aux_loss,
    top_k_gating,
)
from dlrover_tpu.parallel.sharding import mesh_shardings


class TestGating:
    def test_dispatch_respects_capacity(self):
        rng = np.random.default_rng(0)
        logits = jnp.asarray(
            rng.standard_normal((2, 16, 4), dtype=np.float32))
        dispatch, combine, aux = top_k_gating(logits, top_k=2, capacity=3)
        # each expert's slots hold at most one token each
        per_slot = np.asarray(dispatch).sum(axis=1)   # (G, E, C)
        assert per_slot.max() <= 1
        # each token uses at most top_k expert slots
        per_token = np.asarray(dispatch).sum(axis=(2, 3))
        assert per_token.max() <= 2
        assert np.isfinite(float(aux))

    def test_combine_weights_normalized(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(
            rng.standard_normal((1, 8, 4), dtype=np.float32))
        dispatch, combine, _ = top_k_gating(logits, top_k=2, capacity=8)
        sums = np.asarray(combine).sum(axis=(2, 3))
        routed = np.asarray(dispatch).sum(axis=(2, 3)) > 0
        np.testing.assert_allclose(sums[routed], 1.0, atol=1e-5)

    def test_uniform_router_aux_loss_is_one(self):
        logits = jnp.zeros((1, 64, 8))
        _, _, aux = top_k_gating(logits, top_k=1, capacity=64)
        np.testing.assert_allclose(float(aux), 1.0, atol=1e-5)

    def test_overflow_tokens_dropped(self):
        # all tokens want expert 0; capacity 2 ⇒ only 2 dispatched/round
        logits = jnp.zeros((1, 8, 4)).at[:, :, 0].set(10.0)
        dispatch, _, _ = top_k_gating(logits, top_k=1, capacity=2)
        assert int(np.asarray(dispatch)[:, :, 0].sum()) == 2


class TestMoELayer:
    def test_single_expert_full_capacity_equals_dense(self):
        cfg = MoEConfig(num_experts=1, top_k=1, hidden_size=16,
                        expert_intermediate=32, capacity_factor=1e9,
                        eval_capacity_factor=1e9)
        layer = MoELayer(cfg)
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (2, 8, 16), dtype=np.float32))
        variables = layer.init(jax.random.PRNGKey(0), x)
        out, _ = layer.apply(variables, x, mutable=["losses"])
        # dense path: the same expert applied to every token
        params = variables["params"]
        expert = ExpertMLP(cfg)
        dense = expert.apply(
            {"params": jax.tree.map(
                lambda p: p, params["ExpertMLP_0"])},
            x.reshape(1, -1, 16).repeat(1, axis=0))
        np.testing.assert_allclose(
            np.asarray(out).reshape(-1, 16),
            np.asarray(dense).reshape(-1, 16), atol=1e-5, rtol=1e-5)

    def test_forward_backward_finite(self):
        cfg = MoEConfig(num_experts=4, top_k=2, hidden_size=16,
                        expert_intermediate=32)
        import flax.linen as nn

        layer = MoELayer(cfg)
        x = jnp.asarray(np.random.default_rng(1).standard_normal(
            (2, 32, 16), dtype=np.float32))
        variables = nn.unbox(layer.init(jax.random.PRNGKey(0), x))

        def loss(params):
            out, mutables = layer.apply(
                {"params": params}, x, mutable=["losses"])
            return jnp.sum(out ** 2) + moe_aux_loss(mutables)

        value, grads = jax.value_and_grad(loss)(variables["params"])
        assert np.isfinite(float(value))
        for leaf in jax.tree.leaves(grads):
            assert np.isfinite(np.asarray(leaf)).all()
        # router must receive gradient (combine weights depend on it)
        assert float(jnp.abs(grads["router"]).sum()) > 0

    def test_expert_parallel_sharding(self):
        devices = jax.devices("cpu")[:8]
        mesh = create_mesh(MeshSpec(data=2, expert=4), devices)
        cfg = MoEConfig(num_experts=8, top_k=2, hidden_size=16,
                        expert_intermediate=32)
        layer = MoELayer(cfg)
        x = jnp.asarray(np.random.default_rng(2).standard_normal(
            (4, 32, 16), dtype=np.float32))
        abstract = jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(0), x))
        shardings = mesh_shardings(abstract, mesh)
        wi = shardings["params"]["ExpertMLP_0"]["wi"]
        assert wi.spec[0] == MeshAxis.EXPERT
        variables = jax.jit(
            lambda: layer.init(jax.random.PRNGKey(0), x),
            out_shardings=shardings)()
        import flax.linen as nn

        out, _ = jax.jit(
            lambda v, x: layer.apply(v, x, mutable=["losses"]),
        )(nn.unbox(variables) | {}, x)
        assert out.shape == x.shape
        assert np.isfinite(np.asarray(out)).all()


class TestMoEProductPath:
    """LlamaMoE through the STANDARD trainer surface (build_trainer /
    auto_accelerate lowering) — the router aux loss must ride along via
    the mutable 'losses' collection, and expert-mesh training must match
    the single-device oracle."""

    def _setup(self):
        import optax

        from dlrover_tpu.models.llama_moe import (
            LlamaMoE,
            LlamaMoEConfig,
            moe_cross_entropy_loss,
        )
        from dlrover_tpu.models.llama import cross_entropy_loss

        cfg = LlamaMoEConfig.mixtral_tiny(attn_impl="reference",
                                          dtype=jnp.float32)
        rng = np.random.default_rng(11)
        tokens = rng.integers(0, 250, (8, 16)).astype(np.int32)
        return (cfg, LlamaMoE, moe_cross_entropy_loss,
                cross_entropy_loss, optax, tokens)

    def _run(self, cfg, LlamaMoE, cross_entropy_loss, optax, tokens,
             mesh, steps=3):
        from dlrover_tpu.trainer.train_step import build_trainer

        trainer = build_trainer(
            LlamaMoE(cfg), optax.adam(1e-3), mesh,
            jnp.zeros((8, 16), jnp.int32), cross_entropy_loss,
            accum_steps=1, micro_batch=8)
        state = trainer.init(jax.random.PRNGKey(0))
        losses = []
        for _ in range(steps):
            tok, tgt = trainer.shard_batch(tokens, tokens)
            state, metrics = trainer.step(state, tok, tgt)
            losses.append(float(metrics["loss"]))
        return trainer, state, losses

    def test_aux_loss_included_in_standard_trainer(self, cpu_devices):
        """The trainer's reported loss equals token CE + router aux (the
        bespoke moe_cross_entropy_loss) — sown losses are NOT silently
        dropped."""
        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh

        (cfg, LlamaMoE, moe_ce, ce, optax, tokens) = self._setup()
        mesh = create_mesh(MeshSpec(data=1), cpu_devices[:1])
        trainer, _, losses = self._run(cfg, LlamaMoE, ce, optax, tokens,
                                       mesh, steps=1)
        state0 = trainer.init(jax.random.PRNGKey(0))
        import flax.linen as nn

        model = LlamaMoE(cfg)
        expected = float(moe_ce(model, jax.device_get(state0.params),
                                tokens, tokens))
        np.testing.assert_allclose(losses[0], expected, rtol=1e-5)
        # and the aux term is genuinely nonzero
        plain = float(ce(model.apply({"params": state0.params}, tokens),
                         tokens))
        assert abs(expected - plain) > 1e-8

    def test_expert_mesh_matches_single_device(self, cpu_devices):
        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh

        (cfg, LlamaMoE, _, ce, optax, tokens) = self._setup()
        base_mesh = create_mesh(MeshSpec(data=1), cpu_devices[:1])
        _, _, base = self._run(cfg, LlamaMoE, ce, optax, tokens,
                               base_mesh)
        mesh = create_mesh(MeshSpec(expert=2, data=2), cpu_devices[:4])
        _, state, sharded = self._run(cfg, LlamaMoE, ce, optax, tokens,
                                      mesh)
        np.testing.assert_allclose(sharded, base, atol=1e-4, rtol=1e-4)
        assert base[-1] < base[0]

    def test_train_mode_with_jitter_through_standard_trainer(
            self, cpu_devices):
        """The DOCUMENTED training configuration (deterministic=False,
        jitter_noise > 0) needs a 'gating' rng; the trainer supplies
        deterministic per-step/per-microbatch streams, so this must
        train, converge, and replay identically given the same state."""
        import dataclasses as dc

        import optax

        from dlrover_tpu.models.llama import cross_entropy_loss
        from dlrover_tpu.models.llama_moe import LlamaMoE, LlamaMoEConfig
        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
        from dlrover_tpu.trainer.train_step import build_trainer

        cfg = dc.replace(
            LlamaMoEConfig.mixtral_tiny(attn_impl="reference",
                                        dtype=jnp.float32),
            jitter_noise=0.1)
        rng = np.random.default_rng(11)
        tokens = rng.integers(0, 250, (8, 16)).astype(np.int32)
        mesh = create_mesh(MeshSpec(expert=2), cpu_devices[:2])
        trainer = build_trainer(
            LlamaMoE(cfg, deterministic=False), optax.adam(1e-3), mesh,
            jnp.zeros((8, 16), jnp.int32), cross_entropy_loss,
            accum_steps=1, micro_batch=8)
        state = trainer.init(jax.random.PRNGKey(0))
        tok, tgt = trainer.shard_batch(tokens, tokens)
        losses = []
        for _ in range(5):
            state, metrics = trainer.step(state, tok, tgt)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
        # same (state, step) -> same rng stream -> identical replay
        # (fresh init: the trainer donates stepped-state buffers)
        state2 = trainer.init(jax.random.PRNGKey(0))
        _, m_again = trainer.step(state2, tok, tgt)
        np.testing.assert_allclose(float(m_again["loss"]), losses[0],
                                   rtol=1e-6)

    def test_moe_through_pipeline_matches_dense_path(self, cpu_devices):
        """MoE × pipeline: lower an MoE config onto a
        pipe × expert mesh and check the pipelined loss equals the
        single-device dense-path objective (ce + aux) on identical
        params — experts sharded INSIDE stages, router aux losses carried
        through the pipeline's aux accumulator."""
        import optax

        from dlrover_tpu.models.llama import cross_entropy_loss
        from dlrover_tpu.models.llama_moe import (
            LlamaMoE,
            LlamaMoEConfig,
            moe_cross_entropy_loss,
        )
        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
        from dlrover_tpu.trainer.pipeline_trainer import (
            build_pipeline_trainer,
        )

        cfg = LlamaMoEConfig.mixtral_tiny(attn_impl="reference",
                                          dtype=jnp.float32)
        mesh = create_mesh(MeshSpec(pipe=2, expert=2), cpu_devices[:4])
        tx = optax.sgd(0.0)  # loss comparison only
        trainer = build_pipeline_trainer(
            cfg, tx, mesh, num_microbatches=4, micro_batch=2,
            seq_len=16, loss_fn=cross_entropy_loss)
        state = trainer.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32)
        targets = rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32)
        tok, tgt = trainer.shard_batch(tokens, targets)
        _, metrics = trainer.step(state, tok, tgt)
        piped_loss = float(metrics["loss"])

        # dense-path oracle with the SAME stacked params, deterministic
        # routing (the PP spec routes deterministically)
        params = jax.device_get(trainer.init(
            jax.random.PRNGKey(0)).params)
        model = LlamaMoE(cfg, deterministic=True)
        # rebuild the flax param tree: layer ℓ = chunks[(ℓ // per) dims]
        per = trainer.layers_per_chunk
        flat = {}
        for layer in range(cfg.num_layers):
            r, rem = divmod(layer, trainer.num_stages * per)
            s, j = divmod(rem, per)
            flat[f"layer_{layer}"] = jax.tree.map(
                lambda leaf: leaf[r, s, j], params["chunks"])
        dense_params = {
            "embed": params["shared"]["embed"],
            "final_norm": {"weight": params["shared"]["final_norm"]},
            "lm_head": params["shared"]["lm_head"],
            **flat,
        }
        oracle = float(moe_cross_entropy_loss(
            model, dense_params, jnp.asarray(tokens),
            jnp.asarray(targets)))
        np.testing.assert_allclose(piped_loss, oracle, rtol=2e-4)
