"""Pipelined trainer: PP(+DP/FSDP/TP) training end to end on the virtual
mesh, incl. the circular (interleaved) schedule, the GPT family, and the
auto_accelerate path."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.common.constants import MeshAxis
from dlrover_tpu.models.gpt import GPTConfig
from dlrover_tpu.models.llama import LlamaConfig, cross_entropy_loss
from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
from dlrover_tpu.trainer.pipeline_trainer import build_pipeline_trainer


def flat_loss(logits, targets):
    return cross_entropy_loss(logits, targets)


def _run(cfg, mesh, steps=3, num_rounds=1, seed=0):
    trainer = build_pipeline_trainer(
        cfg, optax.adam(1e-3), mesh, num_microbatches=4,
        micro_batch=4, seq_len=16, loss_fn=flat_loss,
        num_rounds=num_rounds)
    state = trainer.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 120, (16, 16), dtype=np.int32)
    losses = []
    for _ in range(steps):
        tok, tgt = trainer.shard_batch(tokens, tokens)
        state, metrics = trainer.step(state, tok, tgt)
        losses.append(float(metrics["loss"]))
    return trainer, state, losses


@pytest.fixture(scope="module")
def llama_cfg():
    return LlamaConfig.tiny(attn_impl="reference", dtype=jnp.float32)


@pytest.fixture(scope="module")
def llama_oracle(llama_cfg):
    devices = jax.devices("cpu")
    mesh1 = create_mesh(MeshSpec(data=1), devices[:1])
    _, _, losses = _run(llama_cfg, mesh1)
    return losses


class TestPipelinedTrainer:
    def test_pp_dp_training_reduces_loss(self, cpu_devices, llama_cfg):
        # tiny has 2 layers -> 2 stages; remaining 4 devices do DP
        mesh = create_mesh(MeshSpec(data=4, pipe=2), cpu_devices[:8])
        trainer, state, losses = _run(llama_cfg, mesh, steps=6)
        # chunk params AND their optimizer moments sharded over pipe
        chunk_leaf = jax.tree.leaves(state.params["chunks"])[0]
        assert chunk_leaf.sharding.spec[1] == MeshAxis.PIPE
        opt_chunk_leaves = [
            leaf for leaf in jax.tree.leaves(state.opt_state)
            if leaf.ndim >= 3 and leaf.shape[1] == 2
        ]
        assert any(len(leaf.sharding.spec) > 1
                   and leaf.sharding.spec[1] == MeshAxis.PIPE
                   for leaf in opt_chunk_leaves)
        assert losses[-1] < losses[0]

    def test_pp_fsdp_stage_params_sharded_and_match_oracle(
            self, cpu_devices, llama_cfg, llama_oracle):
        """PP × DP × FSDP composition: chunk params shard over BOTH pipe
        and fsdp, and the losses match a single-device run exactly — the
        stage-internal sharding changes layout, not math."""
        mesh = create_mesh(MeshSpec(data=2, fsdp=2, pipe=2),
                           cpu_devices[:8])
        trainer, state, losses = _run(llama_cfg, mesh)

        # q_proj kernel: (rounds, stage, per_chunk, embed->fsdp, heads)
        qk = state.params["chunks"]["attn"]["q_proj"]["kernel"]
        assert qk.sharding.spec[1] == MeshAxis.PIPE
        assert MeshAxis.FSDP in jax.tree.leaves(tuple(qk.sharding.spec))
        shard = qk.sharding.shard_shape(qk.shape)
        assert shard[1] == qk.shape[1] // 2      # pipe
        assert shard[3] == qk.shape[3] // 2      # fsdp on embed dim
        # optimizer moments shard identically to their params
        mu_qk = state.opt_state[0].mu["chunks"]["attn"]["q_proj"]["kernel"]
        assert mu_qk.sharding.shard_shape(mu_qk.shape) == shard

        np.testing.assert_allclose(losses, llama_oracle, atol=1e-4,
                                   rtol=1e-4)

    def test_pp_tensor_parallel_matches_oracle(self, cpu_devices,
                                               llama_cfg, llama_oracle):
        """PP × TP: tensor=2 under the pipe
        shard_map — column/row-parallel chunk weights compose with the
        pipeline and the losses stay exact."""
        mesh = create_mesh(MeshSpec(tensor=2, pipe=2), cpu_devices[:4])
        trainer, state, losses = _run(llama_cfg, mesh)
        qk = state.params["chunks"]["attn"]["q_proj"]["kernel"]
        # heads (output) dim sharded over tensor
        assert MeshAxis.TENSOR in jax.tree.leaves(tuple(qk.sharding.spec))
        shard = qk.sharding.shard_shape(qk.shape)
        assert shard[-1] == qk.shape[-1] // 2
        np.testing.assert_allclose(losses, llama_oracle, atol=1e-4,
                                   rtol=1e-4)

    def test_circular_schedule_matches_oracle(self, cpu_devices):
        """num_rounds=2 (interleaved/circular schedule, bubble ÷ 2):
        4-layer GPT on 2 stages × 2 rounds matches the sequential run."""
        cfg = GPTConfig.nano(attn_impl="reference", dtype=jnp.float32)
        mesh1 = create_mesh(MeshSpec(data=1), cpu_devices[:1])
        _, _, base = _run(cfg, mesh1)
        mesh = create_mesh(MeshSpec(data=2, pipe=2), cpu_devices[:4])
        trainer, state, losses = _run(cfg, mesh, num_rounds=2)
        assert trainer.num_chunks == 4
        # chunk leaves: (rounds=2, stages=2, per_chunk=1, ...)
        leaf = jax.tree.leaves(state.params["chunks"])[0]
        assert leaf.shape[:3] == (2, 2, 1)
        np.testing.assert_allclose(losses, base, atol=1e-4, rtol=1e-4)

    def test_gpt_pipeline_matches_oracle(self, cpu_devices):
        """Pipeline lowering is not Llama-only: the GPT family pipelines via its own spec."""
        cfg = GPTConfig.nano(attn_impl="reference", dtype=jnp.float32)
        mesh1 = create_mesh(MeshSpec(data=1), cpu_devices[:1])
        _, _, base = _run(cfg, mesh1)
        mesh = create_mesh(MeshSpec(data=2, pipe=2), cpu_devices[:4])
        _, _, losses = _run(cfg, mesh)
        np.testing.assert_allclose(losses, base, atol=1e-4, rtol=1e-4)

    def test_auto_accelerate_pipe_with_fsdp_strategy(self, cpu_devices):
        """pipeline_parallel + fsdp through auto_accelerate composes for
        real (no replicated chunk weights)."""
        from dlrover_tpu.auto import auto_accelerate
        from dlrover_tpu.models.llama import Llama

        result = auto_accelerate(
            Llama(LlamaConfig.tiny(attn_impl="reference",
                                   dtype=jnp.float32)),
            optim_factory=lambda: optax.adam(1e-3),
            loss_fn=flat_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            strategy=[("pipeline_parallel", {"size": 2}),
                      ("fsdp", {"size": 2})],
            devices=cpu_devices[:8],
        )
        trainer = result.trainer
        state = trainer.init(jax.random.PRNGKey(0))
        qk = state.params["chunks"]["attn"]["q_proj"]["kernel"]
        shard = qk.sharding.shard_shape(qk.shape)
        assert shard[1] == qk.shape[1] // 2      # pipe
        assert shard[3] == qk.shape[3] // 2      # fsdp
        rng = np.random.default_rng(1)
        total = trainer.num_microbatches * trainer.micro_batch
        tokens = rng.integers(0, 250, (total, 16), dtype=np.int32)
        tok, tgt = trainer.shard_batch(tokens, tokens)
        state, metrics = trainer.step(state, tok, tgt)
        assert np.isfinite(float(metrics["loss"]))

    def test_auto_accelerate_gpt_pipeline(self, cpu_devices):
        """GPT through the pipeline_parallel strategy (generalized
        lowering), including the rounds config knob."""
        from dlrover_tpu.auto import auto_accelerate
        from dlrover_tpu.models.gpt import GPT

        result = auto_accelerate(
            GPT(GPTConfig.nano(attn_impl="reference", dtype=jnp.float32)),
            optim_factory=lambda: optax.adam(1e-3),
            loss_fn=flat_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            strategy=[("pipeline_parallel", {"size": 2, "rounds": 2})],
            devices=cpu_devices[:8],
        )
        trainer = result.trainer
        assert trainer.num_rounds == 2
        state = trainer.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        total = trainer.num_microbatches * trainer.micro_batch
        tokens = rng.integers(0, 250, (total, 16), dtype=np.int32)
        tok, tgt = trainer.shard_batch(tokens, tokens)
        state, metrics = trainer.step(state, tok, tgt)
        assert np.isfinite(float(metrics["loss"]))

    def test_auto_accelerate_pipeline_respects_global_batch(self,
                                                            cpu_devices):
        from dlrover_tpu.auto import auto_accelerate
        from dlrover_tpu.models.llama import Llama

        result = auto_accelerate(
            Llama(LlamaConfig.tiny(attn_impl="reference",
                                   dtype=jnp.float32)),
            loss_fn=flat_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            strategy=[("pipeline_parallel", {"size": 2})],
            global_batch=32, micro_batch=8,
            devices=cpu_devices[:8],
        )
        trainer = result.trainer
        assert trainer.num_microbatches * trainer.micro_batch == 32
        # a 32-row batch (the contract) reshapes cleanly
        tokens = np.zeros((32, 16), np.int32)
        trainer.shard_batch(tokens, tokens)

    def test_pipeline_with_flash_attn_traces(self, cpu_devices):
        """attn_impl='flash' inside the pipe-manual shard_map: the
        mesh_flash_attention wrapper must step aside (its nested
        shard_map cannot trace there) and the kernel must run on the
        per-stage blocks."""
        cfg = LlamaConfig.tiny(attn_impl="flash", dtype=jnp.float32)
        mesh = create_mesh(MeshSpec(data=2, pipe=2), cpu_devices[:4])
        _, _, losses = _run(cfg, mesh, steps=1)
        assert np.isfinite(losses).all()

    def test_clean_spmd_lowering_pipeline(self, cpu_devices, capfd):
        """The pipeline lowering on a (data, fsdp, pipe) mesh must not hit
        XLA's 'Involuntary full rematerialization' fallback (the dense
        trainer has the same regression guard in test_parallel.py)."""
        cfg = LlamaConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        mesh = create_mesh(MeshSpec(data=2, fsdp=2, pipe=2),
                           cpu_devices[:8])
        # unique seq length so the XLA compile cache can't satisfy this
        # compile without partitioning (warnings fire at partition time)
        trainer = build_pipeline_trainer(
            cfg, optax.adam(1e-3), mesh, num_microbatches=4,
            micro_batch=4, seq_len=24, loss_fn=flat_loss)
        state = trainer.init(jax.random.PRNGKey(0))
        tokens = np.zeros((16, 24), np.int32)
        tok, tgt = trainer.shard_batch(tokens, tokens)
        trainer.step(state, tok, tgt)
        captured = capfd.readouterr()
        assert "Involuntary full rematerialization" not in captured.err

    def test_bert_pipeline_matches_dense(self, cpu_devices):
        """Encoder (BERT) pipeline spec: the MLM
        objective through the pipeline equals the dense Bert forward on
        identical params."""
        from dlrover_tpu.models.bert import Bert, BertConfig, mlm_loss

        cfg = BertConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        mesh = create_mesh(MeshSpec(data=2, pipe=2), cpu_devices[:4])
        trainer = build_pipeline_trainer(
            cfg, optax.sgd(0.0), mesh, num_microbatches=4,
            micro_batch=2, seq_len=16, loss_fn=mlm_loss)
        state = trainer.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        tokens = rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32)
        targets = rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32)
        tok, tgt = trainer.shard_batch(tokens, targets)
        _, metrics = trainer.step(state, tok, tgt)
        piped = float(metrics["loss"])

        params = jax.device_get(trainer.init(jax.random.PRNGKey(0)).params)
        per = trainer.layers_per_chunk
        flat = {}
        for layer in range(cfg.num_layers):
            r, rem = divmod(layer, trainer.num_stages * per)
            s, j = divmod(rem, per)
            flat[f"layer_{layer}"] = jax.tree.map(
                lambda leaf: leaf[r, s, j], params["chunks"])
        dense_params = {
            **params["shared"], **flat,
            # the segment table is a fine-tuning feature the pipeline
            # spec omits; zeros = the token_types=None path regardless
            "type_embed": np.zeros(
                (cfg.type_vocab_size, cfg.hidden_size), np.float32),
        }
        logits = Bert(cfg).apply({"params": dense_params},
                                 jnp.asarray(tokens))
        oracle = float(mlm_loss(logits, jnp.asarray(targets)))
        np.testing.assert_allclose(piped, oracle, rtol=2e-4)

    def test_offload_opt_state_shardings(self, cpu_devices):
        """offload_optimizer × pipeline: optimizer
        moments carry pinned_host shardings; scalars and params stay in
        device memory. (Mixed-memory-kind EXECUTION is TPU-only, same
        contract as the dense trainer's offload test.)"""
        cfg = LlamaConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        mesh = create_mesh(MeshSpec(data=2, pipe=2), cpu_devices[:4])
        trainer = build_pipeline_trainer(
            cfg, optax.adam(1e-3), mesh, num_microbatches=4,
            micro_batch=2, seq_len=16, loss_fn=flat_loss,
            offload_opt_state=True)
        trainer._ensure_shardings(jax.random.PRNGKey(0))
        shardings = trainer.state_shardings
        abstract = jax.eval_shape(trainer._make_state,
                                  jax.random.PRNGKey(0))
        kinds = {
            s.memory_kind
            for s, leaf in zip(jax.tree.leaves(shardings.opt_state),
                               jax.tree.leaves(abstract.opt_state))
            if leaf.ndim > 0
        }
        assert kinds == {"pinned_host"}
        assert all(s.memory_kind == "device"
                   for s in jax.tree.leaves(shardings.params))

    def test_indivisible_layers_rejected(self, cpu_devices):
        mesh = create_mesh(MeshSpec(pipe=4), cpu_devices[:4])
        cfg = LlamaConfig.tiny()  # 2 layers, 4 stages
        trainer = build_pipeline_trainer(
            cfg, optax.adam(1e-3), mesh, num_microbatches=4,
            micro_batch=2, seq_len=16, loss_fn=flat_loss)
        with pytest.raises(ValueError, match="not divisible"):
            trainer.init(jax.random.PRNGKey(0))


class TestBf16Pipeline:
    """The bf16 pipeline program must compile and train on the CPU
    backend: the blanket fp32 forcing is gone;
    shared params cross the pipe shard_map in fp32 (pvary'd before the
    compute-dtype cast) so their grad psum dodges the XLA-CPU
    half-precision promotion bug while compute stays bf16."""

    def test_bf16_dense_pipeline_trains(self, cpu_devices):
        cfg = LlamaConfig.tiny(attn_impl="reference",
                               dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16)
        mesh = create_mesh(MeshSpec(data=2, pipe=2), cpu_devices[:4])
        trainer, state, losses = _run(cfg, mesh, steps=4)
        # the REAL dtypes survived — no silent fp32 forcing
        embed = state.params["shared"]["embed"]
        assert embed.dtype == jnp.bfloat16
        chunk_leaf = jax.tree.leaves(state.params["chunks"])[0]
        assert chunk_leaf.dtype == jnp.bfloat16
        assert losses[-1] < losses[0]

    def test_bf16_moe_pipeline_forces_fp32_on_cpu_only(self, cpu_devices):
        # MoE chunks put the expert axis auto inside the pipe-manual
        # region; GSPMD's bf16 expert collectives still hit the CPU bug,
        # so ONLY those configs force fp32 on cpu (documented residue)
        from dlrover_tpu.models.llama_moe import LlamaMoEConfig

        cfg = LlamaMoEConfig(
            vocab_size=120, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=16,
            attn_impl="reference", norm_impl="reference",
            embed_impl="gather", dtype=jnp.bfloat16,
            param_dtype=jnp.bfloat16, num_experts=4, top_k=2)
        mesh = create_mesh(MeshSpec(pipe=2, expert=2),
                           cpu_devices[:4])
        trainer = build_pipeline_trainer(
            cfg, optax.adam(1e-3), mesh, num_microbatches=4,
            micro_batch=4, seq_len=16, loss_fn=flat_loss)
        state = trainer.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 120, (16, 16), dtype=np.int32)
        tok, tgt = trainer.shard_batch(tokens, tokens)
        state, metrics = trainer.step(state, tok, tgt)
        assert np.isfinite(float(metrics["loss"]))
        assert state.params["shared"]["embed"].dtype == jnp.float32


class TestBoundedActivations:
    """1F1B-style memory profile: with
    bound_activations the step scan is checkpointed in windows of
    num_stages steps, so live linearization residuals are bound to ~one
    window (~num_stages microbatches) instead of O(num_microbatches) —
    same schedule, same math, one extra forward of recompute."""

    def _temp_bytes(self, num_micro, bound, cpu_devices):
        cfg = LlamaConfig.tiny(attn_impl="reference", dtype=jnp.float32)
        mesh = create_mesh(MeshSpec(pipe=2), cpu_devices[:2])
        trainer = build_pipeline_trainer(
            cfg, optax.sgd(1e-2), mesh, num_microbatches=num_micro,
            micro_batch=2, seq_len=16, loss_fn=flat_loss,
            bound_activations=bound)
        state = trainer.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 120, (num_micro * 2, 16), np.int32)
        tok, tgt = trainer.shard_batch(tokens, tokens)
        state2, metrics = trainer.step(state, tok, tgt)
        stats = trainer._step.lower(state2, tok, tgt).compile(
        ).memory_analysis()
        return stats.temp_size_in_bytes, float(metrics["loss"])

    def test_bounded_memory_flat_in_microbatches(self, cpu_devices):
        free8, loss_free8 = self._temp_bytes(8, False, cpu_devices)
        bound8, loss_bound8 = self._temp_bytes(8, True, cpu_devices)
        bound32, _ = self._temp_bytes(32, True, cpu_devices)
        free32, _ = self._temp_bytes(32, False, cpu_devices)
        # same math (remat changes memory, not values)
        np.testing.assert_allclose(loss_bound8, loss_free8, rtol=1e-5)
        # bounded uses materially less temp memory at depth...
        assert bound32 < free32 * 0.6, (bound32, free32)
        # ...and grows sublinearly in M where the free schedule grows
        # ~linearly (4x M: free ~4x, bounded well under 2.5x)
        assert free32 > free8 * 2.5, (free8, free32)
        assert bound32 < bound8 * 2.5, (bound8, bound32)
