"""Named recomputation policies (`ops/remat.py`) and what they keep of a
`models/minicpm_sala.py` block: under `matmul_and_kernel_outputs`, its
class's default, the backward pass launches none of the block's
projections a second time and computes the same loss and gradients as
under `kernel_outputs`. On the CPU at tiny sizes, float32, plain XLA
forms of both mixers."""

import collections
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.keye import KeyeConfig
from dlrover_tpu.models.llama import LlamaConfig
from dlrover_tpu.models.minicpm_sala import MiniCPMSala, SalaConfig
from dlrover_tpu.ops.block_sparse_attention import Sparsity
from dlrover_tpu.ops.remat import resolve_remat_policy

SEQ = 256
# the projections of a layer of either kind: q, k, v, the output's gate and
# `o_proj`, the MLP's gate and up (`down_proj`'s output is the block's
# branch and is read by nothing the backward pass recomputes)
PROJECTIONS = ("q_proj", "k_proj", "v_proj", "o_gate", "o_proj",
               "gate_proj", "up_proj")


@pytest.mark.parametrize("name", [
    "", "full", "nothing_saveable", "dots", "dots_saveable",
    "dots_with_no_batch_dims", "kernel_outputs",
    "matmul_and_kernel_outputs"])
def test_every_named_policy_resolves(name):
    assert callable(resolve_remat_policy(name))


def test_an_unknown_policy_names_the_known_ones():
    with pytest.raises(ValueError, match="matmul_and_kernel_outputs"):
        resolve_remat_policy("every_matmul")


def test_each_class_names_its_own_policy():
    assert SalaConfig().remat_policy == "matmul_and_kernel_outputs"
    assert KeyeConfig().remat_policy == "kernel_outputs"
    assert LlamaConfig().remat_policy == "nothing_saveable"


def _sala(policy: str):
    """Two layers, a sparse one (sparse past 64 tokens: blocks of 16, a
    query's 4) and a lightning one, recomputed by block under `policy`."""
    cfg = SalaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, attn_head_dim=32, max_seq_len=SEQ,
        rms_norm_eps=1e-6, dtype=jnp.float32, norm_impl="reference",
        embed_impl="gather", remat=True, remat_policy=policy,
        embed_scale=12.0, mixer_types=("minicpm4", "lightning-attn"),
        lightning_heads=2, lightning_head_dim=32,
        sparsity=Sparsity(block=16, topk=4, kernel=8, stride=4,
                          init_blocks=1, window=32, dense_len=64))
    return MiniCPMSala(cfg)


def _loss(model, tokens):
    def loss(params):
        logits = model.apply({"params": params}, tokens,
                             mutable=["counters"])[0]
        return jnp.mean(logits ** 2)
    return loss


@pytest.fixture(scope="module")
def tokens_and_params():
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, SEQ), 0, 256)
    params = nn.unbox(_sala("kernel_outputs").init(
        jax.random.PRNGKey(1), tokens))["params"]
    return tokens, params


def test_keeping_the_projections_computes_the_same_gradients(
        tokens_and_params):
    """What is kept is what the forward computed, so the loss and every
    gradient leaf are those of the block recomputed with its projections."""
    tokens, params = tokens_and_params
    loss, grads = jax.value_and_grad(_loss(_sala("kernel_outputs"), tokens))(
        params)
    loss_kept, grads_kept = jax.value_and_grad(
        _loss(_sala("matmul_and_kernel_outputs"), tokens))(params)
    np.testing.assert_allclose(loss_kept, loss, rtol=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree.leaves(grads_kept))
    for (path, theirs), mine in zip(flat, jax.tree.leaves(grads_kept)):
        np.testing.assert_allclose(mine, theirs, rtol=1e-5, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("policy, again", [
    ("kernel_outputs", 2 * len(PROJECTIONS)),
    ("matmul_and_kernel_outputs", 0)])
def test_the_recomputed_forward_launches_no_projection(tokens_and_params,
                                                       policy, again):
    """The gradient's compiled CPU program: every projection of both
    layers stands again under `rematted_computation` where only the
    kernels' outputs are kept, none where the projections' are too."""
    tokens, params = tokens_and_params
    text = jax.jit(jax.grad(_loss(_sala(policy), tokens))).lower(
        params).compile().as_text()
    recomputed = collections.Counter(
        re.search(r"/(\w+)/dot_general$", op_name)[1]
        for op_name in re.findall(r' dot\([^\n]*op_name="([^"]*)"', text)
        if "rematted_computation" in op_name)
    assert sum(recomputed.values()) == again, recomputed
    assert set(recomputed) == (set(PROJECTIONS) if again else set())
