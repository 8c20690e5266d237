"""Pipeline-parallel tests (parity: atorch pipeline_test.py, 532 LoC of
PiPPy driver tests — here: SPMD pipeline == sequential oracle, fwd+bwd)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
from dlrover_tpu.parallel.pipeline import (
    pipeline_apply,
    sequential_oracle,
    stack_stage_params,
)


def mlp_stage(params, x):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def make_stages(num_stages, hidden=8, seed=0):
    rng = np.random.default_rng(seed)
    stages = []
    for _ in range(num_stages):
        stages.append({
            "w1": jnp.asarray(
                rng.standard_normal((hidden, hidden), dtype=np.float32)
                / np.sqrt(hidden)),
            "b1": jnp.zeros((hidden,), jnp.float32),
            "w2": jnp.asarray(
                rng.standard_normal((hidden, hidden), dtype=np.float32)
                / np.sqrt(hidden)),
            "b2": jnp.zeros((hidden,), jnp.float32),
        })
    return stages


@pytest.fixture(scope="module")
def pipe_mesh():
    return create_mesh(MeshSpec(data=2, pipe=4), jax.devices("cpu")[:8])


class TestPipeline:
    @pytest.mark.parametrize("num_micro", [4, 7])
    def test_matches_sequential(self, pipe_mesh, num_micro):
        stages = make_stages(4)
        stacked = stack_stage_params(stages)
        rng = np.random.default_rng(1)
        inputs = jnp.asarray(
            rng.standard_normal((num_micro, 2, 8), dtype=np.float32))
        expected = sequential_oracle(mlp_stage, stages, inputs)
        got = pipeline_apply(pipe_mesh, mlp_stage, stacked, inputs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("remat", [False, True])
    def test_gradients_match_sequential(self, pipe_mesh, remat):
        stages = make_stages(4, seed=2)
        stacked = stack_stage_params(stages)
        rng = np.random.default_rng(3)
        inputs = jnp.asarray(
            rng.standard_normal((4, 2, 8), dtype=np.float32))

        def loss_pipe(stacked):
            out = pipeline_apply(pipe_mesh, mlp_stage, stacked, inputs,
                                 remat=remat)
            return jnp.sum(out ** 2)

        def loss_seq(stacked):
            stages = [jax.tree.map(lambda p: p[i], stacked)
                      for i in range(4)]
            return jnp.sum(
                sequential_oracle(mlp_stage, stages, inputs) ** 2)

        g_pipe = jax.grad(loss_pipe)(stacked)
        g_seq = jax.grad(loss_seq)(stacked)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4),
            g_pipe, g_seq)

    def test_jit_compiles_once_and_trains(self, pipe_mesh):
        stages = make_stages(4, seed=4)
        stacked = stack_stage_params(stages)
        rng = np.random.default_rng(5)
        inputs = jnp.asarray(
            rng.standard_normal((4, 2, 8), dtype=np.float32))
        target = jnp.zeros_like(inputs)

        @jax.jit
        def train_step(stacked):
            def loss(p):
                out = pipeline_apply(pipe_mesh, mlp_stage, p, inputs)
                return jnp.mean((out - target) ** 2)

            value, grads = jax.value_and_grad(loss)(stacked)
            return value, jax.tree.map(lambda p, g: p - 0.1 * g, stacked,
                                       grads)

        loss0, stacked = train_step(stacked)
        for _ in range(5):
            loss_val, stacked = train_step(stacked)
        assert float(loss_val) < float(loss0)
