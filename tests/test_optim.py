"""Optimizer family tests (parity: atorch optim/optimizers tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.optim import (
    agd,
    bf16_master,
    row_sparse_adagrad,
    wsam_value_and_grad,
)


def quadratic(params):
    return jnp.sum((params - 1.5) ** 2)


def run_opt(tx, params, loss_fn, steps=100, value_and_grad=None):
    opt_state = tx.init(params)
    vag = value_and_grad or jax.value_and_grad(loss_fn)

    @jax.jit
    def step(params, opt_state):
        loss, grads = vag(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state)
    return params, float(loss)


class TestAGD:
    def test_converges_on_quadratic(self):
        params = jnp.zeros(4)
        params, loss = run_opt(agd(1e-1), params, quadratic)
        assert loss < 1e-3
        np.testing.assert_allclose(np.asarray(params), 1.5, atol=0.05)

    def test_weight_decay_pulls_below_optimum(self):
        params = jnp.ones(4)
        tx = agd(1e-1, weight_decay=10.0)
        params, _ = run_opt(tx, params, quadratic, steps=200)
        # heavy decay keeps params well below the unregularized optimum 1.5
        assert float(jnp.abs(params).max()) < 1.2

    def test_preconditioner_uses_moment_difference(self):
        """nu accumulates the squared diff of bias-corrected first moments
        (atorch agd.py: exp_avg/bc1_t - exp_avg_old/bc1_{t-1}); on step 1
        the diff degenerates to the raw gradient."""
        tx = agd(1e-2, b1=0.9, b2=0.999)
        params = jnp.zeros(2)
        state = tx.init(params)
        g1 = jnp.array([1.0, 2.0])
        _, state = tx.update(g1, state, params)
        s1 = state[0]
        # step 1: mu_hat = g1, diff = g1 - 0
        np.testing.assert_allclose(np.asarray(s1.nu),
                                   0.001 * np.asarray(g1) ** 2, rtol=1e-5)
        g2 = jnp.array([1.0, 2.0])  # identical gradient
        _, state = tx.update(g2, state, params)
        s2 = state[0]
        # constant gradient => bias-corrected moment is constant => diff 0
        np.testing.assert_allclose(np.asarray(s2.nu),
                                   0.999 * np.asarray(s1.nu), rtol=1e-5)


class TestWSAM:
    def test_gamma_zero_equals_plain_grad(self):
        vag = wsam_value_and_grad(quadratic, rho=0.1, gamma=0.0)
        params = jnp.array([0.0, 3.0])
        loss, grads = vag(params)
        _, plain = jax.value_and_grad(quadratic)(params)
        np.testing.assert_allclose(np.asarray(grads), np.asarray(plain),
                                   rtol=1e-6)

    def test_converges_and_prefers_flat_minimum(self):
        vag = wsam_value_and_grad(quadratic, rho=0.05, gamma=0.5)
        params = jnp.zeros(4)
        params, loss = run_opt(optax.sgd(0.1), params, quadratic,
                               value_and_grad=vag)
        assert loss < 1e-3

    def test_invalid_gamma_rejected(self):
        with pytest.raises(ValueError, match="gamma"):
            wsam_value_and_grad(quadratic, gamma=1.0)


class TestBF16Master:
    def test_small_updates_accumulate_via_master(self):
        # step small enough to vanish in bf16 rounding must still make
        # progress through the fp32 master copy
        params = jnp.ones(256, jnp.bfloat16) * 100.0
        tx = bf16_master(optax.sgd(1e-4))
        state = tx.init(params)
        grads = jnp.ones_like(params)

        @jax.jit
        def step(params, state):
            updates, state = tx.update(grads, state, params)
            return optax.apply_updates(params, updates), state

        for _ in range(200):
            params, state = step(params, state)
        master = state.master
        # fp32 master moved by exactly 200 * 1e-4
        np.testing.assert_allclose(np.asarray(master), 100.0 - 0.02,
                                   rtol=1e-5)
        assert params.dtype == jnp.bfloat16

    def test_params_track_master_image(self):
        params = jnp.ones(8, jnp.bfloat16)
        tx = bf16_master(optax.sgd(0.5))
        state = tx.init(params)
        updates, state = tx.update(jnp.ones_like(params), state, params)
        new_params = optax.apply_updates(params, updates)
        np.testing.assert_allclose(
            np.asarray(new_params, dtype=np.float32),
            np.asarray(state.master.astype(jnp.bfloat16),
                       dtype=np.float32))


class TestRowSparseAdagrad:
    def test_untouched_rows_bit_identical(self):
        table = jnp.ones((8, 4))
        tx = row_sparse_adagrad(0.1)
        state = tx.init(table)
        grads = jnp.zeros((8, 4)).at[2].set(1.0).at[5].set(-1.0)
        updates, new_state = tx.update(grads, state)
        new_table = optax.apply_updates(table, updates)
        touched = [2, 5]
        for row in range(8):
            if row in touched:
                assert not np.allclose(np.asarray(new_table[row]), 1.0)
                assert not np.allclose(
                    np.asarray(new_state.accumulator[row]), 0.1)
            else:
                np.testing.assert_array_equal(
                    np.asarray(new_table[row]), np.float32(1.0))
                np.testing.assert_array_equal(
                    np.asarray(new_state.accumulator[row]),
                    np.float32(0.1))

    def test_embedding_convergence(self):
        rng = np.random.default_rng(0)
        table = jnp.asarray(rng.standard_normal((16, 4),
                                                dtype=np.float32))
        target = jnp.zeros((16, 4))
        tx = row_sparse_adagrad(0.5)
        state = tx.init(table)

        @jax.jit
        def step(table, state, rows):
            def loss(t):
                return jnp.sum((t[rows] - target[rows]) ** 2)

            grads = jax.grad(loss)(table)
            updates, state = tx.update(grads, state)
            return optax.apply_updates(table, updates), state

        for i in range(300):
            rows = jnp.asarray(rng.integers(0, 16, (4,)))
            table, state = step(table, state, rows)
        assert float(jnp.abs(table).max()) < 0.2


class TestOffloadOptimizer:
    def test_opt_state_shardings_carry_host_memory_kind(self, cpu_devices):
        """offload_optimizer routes Adam moments to pinned_host shardings
        (reference capability: atorch adam_offload). Execution of mixed
        memory kinds is a TPU feature — XLA's CPU backend rejects them
        under SPMD — so on CPU this asserts the lowering plumbing and the
        full train run is exercised on real TPU only."""
        import numpy as np
        import optax

        from dlrover_tpu.models.llama import (
            Llama,
            LlamaConfig,
            cross_entropy_loss,
        )
        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
        from dlrover_tpu.trainer.train_step import build_trainer

        mesh = create_mesh(MeshSpec(fsdp=4), cpu_devices[:4])
        trainer = build_trainer(
            Llama(LlamaConfig.tiny(attn_impl="reference",
                                   dtype=jnp.float32)),
            optax.adamw(1e-3), mesh,
            jnp.zeros((4, 16), jnp.int32), cross_entropy_loss,
            accum_steps=1, micro_batch=4, offload_opt_state=True,
        )
        shardings = trainer.state_shardings
        moment_kinds = {
            s.memory_kind
            for s, leaf in zip(
                jax.tree.leaves(shardings.opt_state),
                jax.tree.leaves(jax.eval_shape(trainer.init_fn,
                                               jax.random.PRNGKey(0))
                                .opt_state))
            if leaf.ndim > 0
        }
        assert moment_kinds == {"pinned_host"}
        # scalars (step counters) and params stay in the device's default
        # memory
        assert all(s.memory_kind == "device"
                   for s in jax.tree.leaves(shardings.params))

        if jax.default_backend() != "tpu":
            pytest.skip("mixed memory-kind execution needs TPU")
        state = trainer.init(jax.random.PRNGKey(0))
        tokens = np.zeros((4, 16), np.int32)
        tok, tgt = trainer.shard_batch(tokens, tokens)
        state, metrics = trainer.step(state, tok, tgt)
        assert np.isfinite(float(metrics["loss"]))

    def test_offload_pass_sets_plan(self):
        from dlrover_tpu.auto import ModelContext, OptimizationLibrary
        from dlrover_tpu.auto.accelerate import apply_strategy
        from dlrover_tpu.models.llama import Llama, LlamaConfig

        context = ModelContext(
            Llama(LlamaConfig.tiny()),
            sample_batch=__import__("numpy").zeros((2, 16), "int32"))
        lib = OptimizationLibrary()
        assert "offload_optimizer" in lib and "adam_offload" in lib
        apply_strategy(context, [("offload_optimizer", {})], lib)
        assert context.plan.offload_optimizer


class TestRowSparseFamily:
    """Untouched embedding rows stay bit-identical — params AND optimizer
    state (the semantics sparse optimizers give embeddings)."""

    @pytest.mark.parametrize("make", ["adam", "sgd"])
    def test_untouched_rows_frozen(self, make):
        from dlrover_tpu.optim.sparse import (
            row_sparse_adam,
            row_sparse_sgd,
        )

        tx = (row_sparse_adam(1e-2) if make == "adam"
              else row_sparse_sgd(1e-2))
        params = {"table": jnp.ones((6, 4))}
        state = tx.init(params)
        grads = {"table": jnp.zeros((6, 4)).at[1].set(0.5).at[4].set(-1.0)}
        for _ in range(3):
            updates, state = tx.update(grads, state, params)
            params = optax.apply_updates(params, updates)
        table = np.asarray(params["table"])
        # touched rows moved, untouched rows bit-identical
        assert not np.allclose(table[1], 1.0)
        assert not np.allclose(table[4], 1.0)
        for row in (0, 2, 3, 5):
            np.testing.assert_array_equal(table[row], np.ones(4))
        for leaf in jax.tree.leaves(state):
            arr = np.asarray(leaf)
            if arr.ndim >= 2:
                for row in (0, 2, 3, 5):
                    np.testing.assert_array_equal(
                        arr[row], np.zeros_like(arr[row]))

    def test_adam_bias_correction_per_row(self):
        """A row first touched at step 3 gets step-1 bias correction —
        the same magnitude a fresh dense Adam would give it."""
        from dlrover_tpu.optim.sparse import row_sparse_adam

        tx = row_sparse_adam(1e-2)
        params = {"t": jnp.zeros((2, 2))}
        state = tx.init(params)
        g_row0 = {"t": jnp.zeros((2, 2)).at[0].set(1.0)}
        for _ in range(2):
            updates, state = tx.update(g_row0, state, params)
        # row 1 touched for the first time now
        g_row1 = {"t": jnp.zeros((2, 2)).at[1].set(1.0)}
        updates, state = tx.update(g_row1, state, params)
        dense = optax.adam(1e-2)
        dstate = dense.init({"t": jnp.zeros((1, 2))})
        dupdates, _ = dense.update({"t": jnp.ones((1, 2))}, dstate)
        np.testing.assert_allclose(
            np.asarray(updates["t"][1]),
            np.asarray(dupdates["t"][0]), rtol=1e-5)
