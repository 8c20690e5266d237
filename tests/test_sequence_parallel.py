"""Sequence parallelism end-to-end: the product path reaches ring/Ulysses
attention, and training on a seq-sharded mesh matches the single-device
oracle (reference: atorch DistributedSelfAttention wired into transformer
blocks, modules/distributed_transformer/distributed_attention.py:21-115)."""

import jax
import numpy as np
import optax
import pytest

from dlrover_tpu.auto.accelerate import auto_accelerate
from dlrover_tpu.common.constants import MeshAxis
from dlrover_tpu.models.llama import Llama, LlamaConfig, cross_entropy_loss

BATCH, SEQ, STEPS = 4, 32, 2


def _data(cfg):
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, cfg.vocab_size, (BATCH, SEQ), dtype=np.int32)
    targets = rng.integers(0, cfg.vocab_size, (BATCH, SEQ), dtype=np.int32)
    return tokens, targets


def _train_losses(result, tokens, targets, steps=STEPS):
    trainer = result.trainer
    state = trainer.init(jax.random.PRNGKey(0))
    tok, tgt = trainer.shard_batch(tokens, targets)
    losses = []
    for _ in range(steps):
        state, metrics = trainer.step(state, tok, tgt)
        losses.append(float(metrics["loss"]))
    return losses


def _accelerate(cfg_kwargs, strategy, devices):
    cfg = LlamaConfig.tiny(norm_impl="reference", **cfg_kwargs)
    return auto_accelerate(
        Llama(cfg),
        optim_factory=lambda: optax.adamw(1e-3),
        loss_fn=cross_entropy_loss,
        sample_batch=np.zeros((BATCH, SEQ), np.int32),
        strategy=strategy,
        micro_batch=BATCH,
        devices=devices,
    )


@pytest.fixture(scope="module")
def oracle_losses(cpu_devices_module):
    result = _accelerate({"attn_impl": "reference"}, [], cpu_devices_module[:1])
    tokens, targets = _data(LlamaConfig.tiny())
    return _train_losses(result, tokens, targets)


@pytest.fixture(scope="module")
def cpu_devices_module():
    devices = jax.devices("cpu")
    assert len(devices) >= 8
    return devices[:8]


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sp_through_auto_accelerate_matches_oracle(
        impl, oracle_losses, cpu_devices_module):
    """Loss trajectory on a (data=2, sequence=2) mesh through the
    sequence_parallel pass matches the single-device oracle: forward AND
    grads (step 2's loss depends on step 1's update) are correct."""
    result = _accelerate(
        {}, [("sequence_parallel", {"size": 2, "impl": impl}),
             ("parallel_mode", {"data": 2})],
        cpu_devices_module[:4],
    )
    assert result.mesh.shape[MeshAxis.SEQUENCE] == 2
    # The pass must actually rewrite the model's attention impl.
    assert result.context.model_config().attn_impl == impl
    tokens, targets = _data(LlamaConfig.tiny())
    losses = _train_losses(result, tokens, targets)
    np.testing.assert_allclose(losses, oracle_losses, rtol=2e-3)


def test_sp_composes_with_fsdp(cpu_devices_module, oracle_losses):
    """sequence=2 under fsdp=2: rules + ring shard_map compose."""
    result = _accelerate(
        {}, [("sequence_parallel", {"size": 2}), ("fsdp", {"size": 2})],
        cpu_devices_module[:4],
    )
    tokens, targets = _data(LlamaConfig.tiny())
    losses = _train_losses(result, tokens, targets)
    np.testing.assert_allclose(losses, oracle_losses, rtol=2e-3)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sp_composes_with_tensor_parallel(
        impl, cpu_devices_module, oracle_losses):
    """sequence=2 × tensor=2: heads shard over tensor INSIDE the SP
    shard_map (GQA kv heads ride the ICI unreplicated)."""
    result = _accelerate(
        {}, [("sequence_parallel", {"size": 2, "impl": impl}),
             ("tensor_parallel", {"size": 2})],
        cpu_devices_module[:4],
    )
    assert result.mesh.shape[MeshAxis.TENSOR] == 2
    tokens, targets = _data(LlamaConfig.tiny())
    losses = _train_losses(result, tokens, targets)
    np.testing.assert_allclose(losses, oracle_losses, rtol=2e-3)


def test_sp_product_path_with_flash_blocks(cpu_devices_module,
                                           oracle_losses, monkeypatch):
    """The FULL product path (LlamaConfig.attn_impl="ring" through
    auto_accelerate) with the ring-FLASH block kernel — what real TPU
    runs execute — matches the oracle (interpret mode here)."""
    monkeypatch.setenv("DLROVER_TPU_SP_BLOCK_IMPL", "flash")
    result = _accelerate(
        {}, [("sequence_parallel", {"size": 2}),
             ("parallel_mode", {"data": 2})],
        cpu_devices_module[:4],
    )
    tokens, targets = _data(LlamaConfig.tiny())
    losses = _train_losses(result, tokens, targets)
    np.testing.assert_allclose(losses, oracle_losses, rtol=2e-3)


def test_ring_attn_impl_off_mesh_falls_back(cpu_devices_module):
    """attn_impl="ring" on a sequence=1 mesh must still train (falls back
    to plain attention instead of crashing)."""
    result = _accelerate({"attn_impl": "ring"}, [], cpu_devices_module[:1])
    tokens, targets = _data(LlamaConfig.tiny())
    losses = _train_losses(result, tokens, targets, steps=1)
    assert np.isfinite(losses).all()


@pytest.mark.parametrize("family", ["gpt", "bert"])
def test_sp_reaches_all_model_families(cpu_devices_module, family):
    """attn_impl="ring" is not Llama-only: GPT (causal) and BERT
    (bidirectional) run the same ring dispatch on a sequence-sharded
    mesh and match their own single-device reference oracle."""
    import jax.numpy as jnp

    from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
    from dlrover_tpu.trainer.train_step import build_trainer

    if family == "gpt":
        from dlrover_tpu.models.gpt import GPT, GPTConfig

        def make(impl):
            return GPT(GPTConfig.tiny(attn_impl=impl, dtype=jnp.float32))

        vocab = GPTConfig.tiny().vocab_size
        loss_fn = cross_entropy_loss
    else:
        from dlrover_tpu.models.bert import Bert, BertConfig, mlm_loss

        def make(impl):
            return Bert(BertConfig.tiny(attn_impl=impl,
                                        dtype=jnp.float32))

        vocab = BertConfig.tiny().vocab_size
        loss_fn = lambda logits, tgt: mlm_loss(logits, tgt)  # noqa: E731

    rng = np.random.default_rng(3)
    tokens = rng.integers(0, vocab, (BATCH, SEQ), dtype=np.int32)

    def run(model, mesh):
        trainer = build_trainer(
            model, optax.adam(1e-3), mesh,
            np.zeros((BATCH, SEQ), np.int32), loss_fn,
            accum_steps=1, micro_batch=BATCH)
        state = trainer.init(jax.random.PRNGKey(0))
        losses = []
        for _ in range(2):
            tok, tgt = trainer.shard_batch(tokens, tokens)
            state, metrics = trainer.step(state, tok, tgt)
            losses.append(float(metrics["loss"]))
        return losses

    base = run(make("reference"),
               create_mesh(MeshSpec(data=1), cpu_devices_module[:1]))
    ringed = run(make("ring"),
                 create_mesh(MeshSpec(sequence=4),
                             cpu_devices_module[:4]))
    np.testing.assert_allclose(ringed, base, atol=1e-4, rtol=1e-4)
