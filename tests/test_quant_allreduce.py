"""Quantized gradient all-reduce: collective correctness + training
impact vs the exact fp32 reduce (reference quant_reduce.cu analog)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax

from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.models.llama import (
    Llama,
    LlamaConfig,
    cross_entropy_loss,
)
from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
from dlrover_tpu.parallel.quant_collectives import (
    quantized_pmean,
    quantized_pmean_leaf,
)
from dlrover_tpu.trainer.train_step import build_trainer


def _data_mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), ("data",))


@pytest.mark.parametrize("mode", ["gather", "scatter"])
@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_pmean_matches_exact(mode, bits):
    n = 8
    mesh = _data_mesh(n)
    rng = np.random.default_rng(0)
    # per-member gradients, gaussian like real grads; 4096 elems, ragged
    # trailing shape to exercise the pad path
    x = rng.normal(size=(n, 63, 65)).astype(np.float32)

    fn = jax.shard_map(
        functools.partial(quantized_pmean_leaf, axis_name="data", n=n,
                          bits=bits, mode=mode),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        axis_names=frozenset({"data"}), check_vma=False,
    )
    got = np.asarray(fn(jnp.asarray(x.reshape(n * 63, 65))))
    want = x.mean(axis=0)
    got0 = got.reshape(n, 63, 65)[0]
    # every member must hold the same reduced value
    for i in range(1, n):
        np.testing.assert_array_equal(got.reshape(n, 63, 65)[i], got0)
    # groupwise-symmetric error bound: |err| <= group_absmax/(2*qmax)
    # per quantization pass (x2 for scatter's requantize)
    qmax = 127 if bits == 8 else 7
    passes = 2 if mode == "scatter" else 1
    bound = passes * np.abs(x).max() / qmax
    assert np.abs(got0 - want).max() <= bound
    # and it must be a real approximation, not garbage
    corr = np.corrcoef(got0.ravel(), want.ravel())[0, 1]
    assert corr > 0.999 if bits == 8 else corr > 0.97


def test_small_and_int_leaves_reduce_exactly():
    n = 8
    mesh = _data_mesh(n)
    x = jnp.arange(n * 8, dtype=jnp.float32).reshape(n * 8 // 8, 8)

    fn = jax.shard_map(
        functools.partial(quantized_pmean_leaf, axis_name="data", n=n,
                          bits=8),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        axis_names=frozenset({"data"}), check_vma=False,
    )
    got = np.asarray(fn(x))   # 8 elems/member < MIN_QUANT_SIZE -> pmean
    want = np.asarray(x).reshape(n, -1).mean(axis=0)
    np.testing.assert_allclose(got[0], want, rtol=1e-6)


def test_quantized_pmean_rejects_bad_bits():
    with pytest.raises(ValueError, match="bits"):
        quantized_pmean({"g": jnp.zeros(4096)}, "data", 2, bits=3)


def _tiny_cfg():
    return LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=16,
        attn_impl="reference", norm_impl="reference",
        embed_impl="gather", dtype=jnp.float32, param_dtype=jnp.float32)


def _run_training(grad_reduce_bits, steps=6):
    cfg = _tiny_cfg()
    mesh = create_mesh(MeshSpec(data=4, fsdp=2), jax.devices()[:8])
    micro, seq = 8, 16
    tx = optax.chain(optax.scale_by_factored_rms(), optax.scale(-1e-2))
    sample = jnp.zeros((micro, seq), jnp.int32)
    trainer = build_trainer(
        Llama(cfg), tx, mesh, sample, cross_entropy_loss,
        accum_steps=1, micro_batch=micro,
        grad_reduce_bits=grad_reduce_bits)
    state = trainer.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    losses = []
    for _ in range(steps):
        tokens = rng.integers(0, cfg.vocab_size, (micro, seq), np.int32)
        tok, tgt = trainer.shard_batch(tokens, tokens)
        state, metrics = trainer.step(state, tok, tgt)
        losses.append(float(metrics["loss"]))
    return losses


class _GranuleDevice:
    """Real CPU device with a faked DCN granule (process) identity."""

    def __init__(self, device, process_index):
        self._device = device
        self.process_index = process_index

    def __getattr__(self, name):
        return getattr(self._device, name)


def test_planner_emits_quant_allreduce_on_multi_slice():
    import optax

    from dlrover_tpu.auto.engine.planner import plan_candidates
    from dlrover_tpu.auto.model_context import ModelContext

    cfg = _tiny_cfg()
    devices = [_GranuleDevice(d, i // 4)
               for i, d in enumerate(jax.devices()[:8])]
    context = ModelContext(
        Llama(cfg),
        optim_factory=lambda lr=1e-3: optax.adamw(lr),
        loss_fn=cross_entropy_loss,
        sample_batch=np.zeros((2, 16), np.int32),
        devices=devices,
    )
    candidates = plan_candidates(context, max_candidates=16)
    assert any("quant_allreduce" in [n for n, _ in s]
               for s in candidates), candidates
    # single-granule: not planned
    context_one = ModelContext(
        Llama(cfg),
        optim_factory=lambda lr=1e-3: optax.adamw(lr),
        loss_fn=cross_entropy_loss,
        sample_batch=np.zeros((2, 16), np.int32),
        devices=jax.devices()[:8],
    )
    assert not any(
        "quant_allreduce" in [n for n, _ in s]
        for s in plan_candidates(context_one, max_candidates=16))


def test_auto_accelerate_explicit_quant_allreduce():
    from dlrover_tpu.auto.accelerate import auto_accelerate

    cfg = _tiny_cfg()
    result = auto_accelerate(
        Llama(cfg),
        loss_fn=cross_entropy_loss,
        sample_batch=np.zeros((8, 16), np.int32),
        strategy=[("parallel_mode", {"data": 8}),
                  ("quant_allreduce", {"bits": 8})],
        devices=jax.devices()[:8],
    )
    trainer = result.trainer
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, cfg.vocab_size, (8, 16), np.int32)
    tok, tgt = trainer.shard_batch(tokens, tokens)
    state = trainer.init(jax.random.PRNGKey(0))
    losses = []
    for _ in range(4):
        state, metrics = trainer.step(state, tok, tgt)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_trainer_with_quantized_reduce_tracks_exact():
    """Training-impact check: int8 gradient reduce must track the exact
    reduce's loss curve (same seed, same data) closely."""
    exact = _run_training(0)
    quant = _run_training(8)
    assert quant[-1] < quant[0], "quantized run failed to descend"
    # curves agree step-by-step within a small relative band
    for e, q in zip(exact, quant):
        assert abs(e - q) / max(abs(e), 1e-6) < 0.05, (exact, quant)
