"""auto_accelerate / opt_lib / engine tests (reference parity:
atorch auto_accelerate_test.py + semi_auto_acc_test.py) — on the 8-device
virtual CPU mesh from conftest."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.auto import (
    ModelContext,
    OptimizationLibrary,
    auto_accelerate,
    load_strategy,
    save_strategy,
)
from dlrover_tpu.auto.accelerate import apply_strategy, default_strategy
from dlrover_tpu.auto.engine.analyser import analyse
from dlrover_tpu.auto.engine.dry_runner import dry_run
from dlrover_tpu.auto.engine.planner import plan_candidates
from dlrover_tpu.common.constants import MeshAxis
from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.llama import Llama, LlamaConfig, cross_entropy_loss


def tiny_model():
    return Llama(LlamaConfig.tiny(attn_impl="reference"))


def make_context(devices=None, optim_factory=None):
    return ModelContext(
        tiny_model(),
        optim_factory=optim_factory or (lambda lr=1e-3: optax.adamw(lr)),
        loss_fn=cross_entropy_loss,
        sample_batch=np.zeros((2, 16), np.int32),
        devices=devices,
    )


class TestOptLib:
    def test_registry_has_reference_names(self):
        lib = OptimizationLibrary()
        for name in ("parallel_mode", "zero1", "zero2", "fsdp", "amp",
                     "amp_native", "half", "checkpoint", "module_replace",
                     "tensor_parallel", "pipeline_parallel",
                     "mixed_parallel", "3d_parallel", "sequence_parallel",
                     "expert_parallel"):
            assert name in lib, name

    def test_mutual_exclusion(self):
        lib = OptimizationLibrary()
        with pytest.raises(ValueError, match="mutually exclusive"):
            lib.validate_strategy([("zero1", {}), ("fsdp", {})])

    def test_passes_edit_plan(self):
        context = make_context()
        apply_strategy(context, [
            ("half", {}), ("checkpoint", {"policy": "dots"}),
            ("module_replace", {}),
            ("mixed_parallel", {"dims": [["fsdp", 2], ["tensor", 2]]}),
        ])
        plan = context.plan
        assert plan.compute_dtype == jnp.bfloat16
        assert plan.remat and plan.remat_policy == "dots"
        assert plan.flash_attention
        assert plan.mesh_dims == {"fsdp": 2, "tensor": 2}
        assert plan.fsdp and plan.tensor_parallel


class TestAutoAccelerate:
    def test_explicit_strategy_trains(self, cpu_devices):
        result = auto_accelerate(
            tiny_model(),
            optim_factory=lambda: optax.adamw(1e-3),
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            strategy=[("half", {}),
                      ("mixed_parallel",
                       {"dims": [["fsdp", 2], ["tensor", 2]]})],
            devices=cpu_devices,
        )
        assert result.mesh.shape[MeshAxis.FSDP] == 2
        assert result.mesh.shape[MeshAxis.TENSOR] == 2
        assert result.mesh.shape[MeshAxis.DATA] == 2
        state = result.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = result.trainer.accum_steps * result.trainer.micro_batch
        tokens = rng.integers(0, 250, (batch, 16), dtype=np.int32)
        tok, tgt = result.trainer.shard_batch(tokens, tokens)
        loss0 = None
        for _ in range(3):
            state, metrics = result.step(state, tok, tgt)
            loss0 = loss0 or float(metrics["loss"])
        assert float(metrics["loss"]) < loss0

    def test_default_strategy_single_device(self):
        devices = jax.devices("cpu")[:1]
        result = auto_accelerate(
            tiny_model(),
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((1, 16), np.int32),
            devices=devices,
        )
        names = [name for name, _ in result.strategy]
        assert "half" in names and "fsdp" not in names

    def test_default_strategy_multi_device_adds_fsdp(self):
        assert [n for n, _ in default_strategy(8)] == [
            "half", "module_replace", "fsdp"]

    def test_strategy_save_load_roundtrip(self, tmp_path, cpu_devices):
        path = str(tmp_path / "strategy.json")
        result = auto_accelerate(
            tiny_model(),
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            strategy=["half", ("fsdp", {"size": 4})],
            save_strategy_to_file=path,
            devices=cpu_devices,
        )
        loaded = load_strategy(path)
        assert loaded == result.strategy
        # reload-and-train via load_strategy_file
        result2 = auto_accelerate(
            tiny_model(),
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            load_strategy_file=path,
            devices=cpu_devices,
        )
        assert result2.mesh.shape[MeshAxis.FSDP] == 4

    def test_global_batch_accumulation(self, cpu_devices):
        result = auto_accelerate(
            tiny_model(),
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            strategy=["half"],
            global_batch=32,
            micro_batch=8,   # cap per-step micro → forces accumulation
            devices=cpu_devices,
        )
        trainer = result.trainer
        assert trainer.accum_steps * trainer.micro_batch == 32

    def test_plain_flax_model_works_without_cfg_edits(self, cpu_devices):
        import flax.linen as nn

        class Mlp(nn.Module):
            @nn.compact
            def __call__(self, x):
                x = nn.Embed(64, 32)(x)
                x = nn.Dense(64)(x)
                return x

        def loss_fn(logits, targets):
            one_hot = jax.nn.one_hot(targets, 64)
            return optax.softmax_cross_entropy(logits, one_hot).mean()

        result = auto_accelerate(
            Mlp(),
            loss_fn=loss_fn,
            sample_batch=np.zeros((2, 8), np.int32),
            strategy=["half"],   # cfg edit silently skipped
            devices=cpu_devices,
        )
        state = result.init(jax.random.PRNGKey(0))
        batch = result.trainer.accum_steps * result.trainer.micro_batch
        tokens = np.ones((batch, 8), np.int32)
        tok, tgt = result.trainer.shard_batch(tokens, tokens)
        state, metrics = result.step(state, tok, tgt)
        assert np.isfinite(float(metrics["loss"]))

    def test_partial_cfg_support_applies_supported_subset(self,
                                                          cpu_devices):
        """A config missing one field (e.g. no `remat`) must still get
        the edits it DOES support — dtype here — instead of losing the
        whole batch (the old all-or-nothing behavior silently dropped
        half/checkpoint/SP edits for any non-Llama family)."""
        import dataclasses

        import flax.linen as nn

        from dlrover_tpu.auto.model_context import ModelContext

        @dataclasses.dataclass(frozen=True)
        class MiniCfg:
            dtype: object = jnp.float32

        class Mini(nn.Module):
            config: MiniCfg

            @nn.compact
            def __call__(self, x):
                return nn.Dense(8, dtype=self.config.dtype)(x)

        context = ModelContext(
            Mini(MiniCfg()), sample_batch=np.zeros((1, 4), np.float32),
            devices=cpu_devices[:1])
        skipped = context.replace_model_config(
            dtype=jnp.bfloat16, remat=True)
        assert skipped == ["remat"]
        assert context.model_config().dtype == jnp.bfloat16
        # no dataclass config at all -> None
        context2 = ModelContext(
            nn.Dense(4), sample_batch=np.zeros((1, 4), np.float32),
            devices=cpu_devices[:1])
        assert context2.replace_model_config(dtype=jnp.bfloat16) is None


class TestEngine:
    def test_analyse_reports_size(self):
        info = analyse(make_context())
        cfg = LlamaConfig.tiny()
        assert info["param_count"] == cfg.param_count()
        assert info["n_devices"] >= 1
        # fp32 params + transient grads + fp32 grad accumulator +
        # measured adamw moments (mu+nu fp32) ≈ 20 B/param, plus the
        # optimizer's scalar bookkeeping
        assert (info["train_state_bytes"]
                >= info["param_count"] * 20) and (
            info["train_state_bytes"] < info["param_count"] * 20 + 1024)

    def test_analyse_measures_actual_optimizer_state(self):
        """An adafactor user must not be sized as if they carried fp32
        Adam moments — the analyser eval_shapes tx.init for the real
        bytes (factored stats are ~100x leaner)."""
        import optax

        lean = analyse(make_context(optim_factory=lambda: optax.adafactor(
            1e-3, min_dim_size_to_factor=8)))  # tiny dims must factor too
        fat = analyse(make_context())
        assert lean["train_state_bytes"] < fat["train_state_bytes"] * 0.7

    def test_planner_prunes_by_devices(self):
        single = plan_candidates(make_context(jax.devices("cpu")[:1]))
        for strategy in single:
            names = [n for n, _ in strategy]
            assert "fsdp" not in names and "tensor_parallel" not in names
        multi = plan_candidates(make_context(jax.devices("cpu")[:8]))
        assert any("fsdp" in [n for n, _ in s] for s in multi)

    def test_size_axes_fsdp_from_hbm_fit(self):
        """fsdp = smallest divisor of n_devices whose state shard fits
        60% of HBM (mip_tp_planner.py:30 role, closed form)."""
        from dlrover_tpu.auto.engine.analyser import size_axes

        gib = 1 << 30
        info = {"n_devices": 8, "device_hbm_bytes": 16 * gib,
                "train_state_bytes": 36 * gib, "activation_bytes": 0,
                "num_heads": 16, "num_kv_heads": 16}
        sizing = size_axes(info)
        # 36/2=18 > 9.6, 36/4=9 <= 9.6 -> fsdp 4, data absorbs the rest
        assert sizing == {"fsdp": 4, "tensor": 1, "sequence": 1,
                          "expert": 1, "data": 2, "remat": False}

    def test_size_axes_remat_and_tensor_from_activations(self):
        from dlrover_tpu.auto.engine.analyser import size_axes

        gib = 1 << 30
        info = {"n_devices": 8, "device_hbm_bytes": 16 * gib,
                "train_state_bytes": 9 * gib,
                # huge activations: remat alone insufficient -> tensor
                "activation_bytes": 400 * gib,
                "num_heads": 4, "num_kv_heads": 2}
        sizing = size_axes(info)
        assert sizing["fsdp"] == 1           # state fits one device
        assert sizing["remat"] is True
        # act_eff = 400/7 ≈ 57 GiB; budget ≈ 0.8·(16−9) = 5.6 GiB →
        # tensor capped by kv-head divisibility (kv=2): tensor == 2
        assert sizing["tensor"] == 2
        assert sizing["data"] == 4

    def test_size_axes_sequence_for_long_context(self):
        """When activations blow the budget even after remat AND the
        head-divisibility-capped tensor split, the sequence axis takes
        the rest (ring attention keeps the math exact) — the
        long-context escape hatch."""
        from dlrover_tpu.auto.engine.analyser import size_axes

        gib = 1 << 30
        info = {"n_devices": 8, "device_hbm_bytes": 16 * gib,
                "train_state_bytes": 9 * gib,
                "activation_bytes": 1600 * gib,   # seq 256k-class
                "num_heads": 4, "num_kv_heads": 2, "seq_len": 1 << 18}
        sizing = size_axes(info)
        assert sizing["remat"] is True
        assert sizing["tensor"] == 2          # capped by kv heads
        # act_eff ≈ 228 GiB; /tensor 2 = 114 > 5.6 GiB budget -> the
        # remaining 4 devices go to sequence
        assert sizing["sequence"] == 4
        assert sizing["data"] == 1

    def test_size_axes_unknown_hbm_is_noop(self):
        from dlrover_tpu.auto.engine.analyser import size_axes

        assert size_axes({"n_devices": 8, "device_hbm_bytes": 0,
                          "train_state_bytes": 1}) == {
            "fsdp": 1, "tensor": 1, "sequence": 1, "expert": 1,
            "data": 8, "remat": False}

    def test_size_axes_expert_for_moe(self):
        """num_experts > 1 sizes the expert axis: largest divisor of the
        free devices that divides the expert count — even when HBM is
        unknown (the axis choice is model-shaped, not memory-shaped)."""
        from dlrover_tpu.auto.engine.analyser import size_axes

        sizing = size_axes({"n_devices": 8, "device_hbm_bytes": 0,
                            "train_state_bytes": 1, "num_experts": 4})
        assert sizing["expert"] == 4 and sizing["data"] == 2
        gib = 1 << 30
        sizing = size_axes({"n_devices": 8, "device_hbm_bytes": 16 * gib,
                            "train_state_bytes": 36 * gib,
                            "activation_bytes": 0, "num_heads": 16,
                            "num_kv_heads": 16, "num_experts": 8})
        # fsdp 4 leaves 2 devices; 2 divides 8 experts -> expert 2
        assert sizing["fsdp"] == 4 and sizing["expert"] == 2
        assert sizing["data"] == 1

    def test_auto_picks_sized_fsdp_strategy(self, monkeypatch,
                                            cpu_devices):
        """Auto on an 8-device mesh
        picks a SIZED non-default strategy for a model that needs
        fsdp=4."""
        cfg = LlamaConfig.tiny()
        # HBM such that the tiny model's train state needs exactly fsdp=4:
        # state/4 <= 0.6·hbm < state/2
        state = cfg.param_count() * 16
        monkeypatch.setenv("DLROVER_TPU_HBM_BYTES",
                           str(int(state / 4 / 0.6) + 1))
        monkeypatch.setenv("DLROVER_TPU_SEARCH_MAX_CANDIDATES", "2")
        result = auto_accelerate(
            tiny_model(),
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            strategy="auto",
            devices=cpu_devices[:8],
        )
        # the sized best guess is fsdp=4; its one profiled neighbor is
        # fsdp=8, and on a loaded CPU the dry-run speed race between the
        # two is noise — either way auto must land on a SIZED non-default
        # fsdp strategy (the actual done-bar)
        fsdp_sizes = [conf.get("size") for name, conf in result.strategy
                      if name == "fsdp"]
        assert fsdp_sizes and fsdp_sizes[0] in (4, 8)
        assert result.mesh.shape[MeshAxis.FSDP] == fsdp_sizes[0]
        state0 = result.init(jax.random.PRNGKey(0))
        batch = result.trainer.accum_steps * result.trainer.micro_batch
        tokens = np.ones((batch, 16), np.int32)
        tok, tgt = result.trainer.shard_batch(tokens, tokens)
        _, metrics = result.step(state0, tok, tgt)
        assert np.isfinite(float(metrics["loss"]))

    def test_auto_on_moe_picks_expert_axis(self, monkeypatch,
                                           cpu_devices):
        """Auto on an MoE model must
        pick the expert axis (every candidate carries expert_parallel, so
        no dry-run race can lose it)."""
        from dlrover_tpu.models.llama_moe import LlamaMoE, LlamaMoEConfig

        cfg = LlamaMoEConfig.mixtral_tiny(attn_impl="reference")
        monkeypatch.setenv("DLROVER_TPU_SEARCH_MAX_CANDIDATES", "2")
        result = auto_accelerate(
            LlamaMoE(cfg),
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            strategy="auto",
            devices=cpu_devices[:8],
        )
        expert_sizes = [conf.get("size") for name, conf in result.strategy
                        if name == "expert_parallel"]
        assert expert_sizes and expert_sizes[0] == cfg.num_experts == 4
        assert result.mesh.shape[MeshAxis.EXPERT] == 4
        state = result.init(jax.random.PRNGKey(0))
        batch = result.trainer.accum_steps * result.trainer.micro_batch
        tokens = np.ones((batch, 16), np.int32)
        tok, tgt = result.trainer.shard_batch(tokens, tokens)
        _, metrics = result.step(state, tok, tgt)
        assert np.isfinite(float(metrics["loss"]))

    def test_deep_model_gets_sized_pipeline_candidate(self, monkeypatch,
                                                      cpu_devices):
        """A deep model that
        doesn't fit one device gets a SIZED pipeline_parallel candidate
        in the plan, and the dry-run can score it."""
        cfg = dataclasses.replace(
            LlamaConfig.tiny(attn_impl="reference"), num_layers=4)
        state = cfg.param_count() * 20
        # state doesn't fit one device but fsdp=2 fits
        monkeypatch.setenv("DLROVER_TPU_HBM_BYTES",
                           str(int(state / 2 / 0.6) + 1))
        context = ModelContext(
            Llama(cfg), optim_factory=lambda lr=1e-3: optax.adamw(lr),
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            devices=cpu_devices[:8],
        )
        candidates = plan_candidates(context, max_candidates=16)
        pp = [s for s in candidates
              if any(n == "pipeline_parallel" for n, _ in s)]
        assert pp, f"no pipeline candidate in {candidates}"
        size = next(conf["size"] for n, conf in pp[0]
                    if n == "pipeline_parallel")
        assert size in (2, 4) and cfg.num_layers % size == 0
        speed, err = dry_run(context, pp[0], warmup=1, steps=1)
        assert err == "" and speed > 0

    def test_moe_deep_model_gets_expert_pipe_candidate(self, monkeypatch,
                                                       cpu_devices):
        """A deep MoE model that doesn't fit one device plans an
        expert × pipeline composition (experts sharded INSIDE stages —
        the reference's 3D story) and the dry-run can score it."""
        from dlrover_tpu.models.llama_moe import LlamaMoE, LlamaMoEConfig

        cfg = dataclasses.replace(
            LlamaMoEConfig.mixtral_tiny(attn_impl="reference"),
            num_layers=4)
        state = cfg.param_count() * 20
        monkeypatch.setenv("DLROVER_TPU_HBM_BYTES",
                           str(int(state / 2 / 0.6) + 1))
        context = ModelContext(
            LlamaMoE(cfg), optim_factory=lambda lr=1e-3: optax.adamw(lr),
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            devices=cpu_devices[:8],
        )
        candidates = plan_candidates(context, max_candidates=16)
        combo = [s for s in candidates
                 if any(n == "pipeline_parallel" for n, _ in s)
                 and any(n == "expert_parallel" for n, _ in s)]
        assert combo, candidates
        sizes = dict((n, c.get("size")) for n, c in combo[0])
        assert (sizes["expert_parallel"] * sizes["pipeline_parallel"]
                <= 8)
        speed, err = dry_run(context, combo[0], warmup=1, steps=1)
        assert err == "" and speed > 0

    def test_dry_run_scores_and_survives_bad_strategy(self):
        context = make_context(jax.devices("cpu")[:2])
        speed, err = dry_run(context, [("half", {})], warmup=1, steps=2)
        assert speed > 0 and err == ""
        # a strategy that cannot lower on 2 devices
        speed, err = dry_run(
            context, [("tensor_parallel", {"size": 64})], warmup=1,
            steps=1)
        assert speed == float("-inf") and err

    def test_auto_search_end_to_end(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_SEARCH_MAX_CANDIDATES", "3")
        result = auto_accelerate(
            tiny_model(),
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            strategy="auto",
            devices=jax.devices("cpu")[:2],
        )
        state = result.init(jax.random.PRNGKey(0))
        batch = result.trainer.accum_steps * result.trainer.micro_batch
        tokens = np.ones((batch, 16), np.int32)
        tok, tgt = result.trainer.shard_batch(tokens, tokens)
        state, metrics = result.step(state, tok, tgt)
        assert np.isfinite(float(metrics["loss"]))


class TestStreamingWiring:
    """The streaming per-layer trainer (trainer/streaming.py) through the
    product surface: an explicit `streaming` strategy lowers via
    auto_accelerate, and the planner proposes it for a single-device
    model whose gradient tree overflows HBM (reference capability:
    zero_optimization.py:215 + adam_offload.py — the >memory training
    path)."""

    @staticmethod
    def _per_leaf_factory(lr=1e-3):
        return optax.chain(optax.scale_by_factored_rms(),
                           optax.scale(-lr))

    def test_streaming_strategy_lowers_and_steps(self, cpu_devices):
        result = auto_accelerate(
            tiny_model(),
            optim_factory=self._per_leaf_factory,
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            strategy=[("streaming", {})],
            devices=cpu_devices[:1],
        )
        state = result.init(jax.random.PRNGKey(0))
        # the streaming step donates its input state — snapshot a leaf
        # to host BEFORE stepping
        before = np.asarray(jax.tree.leaves(state.block_params)[0])
        tokens = np.ones((2, 16), np.int32)
        tok, tgt = result.trainer.shard_batch(tokens, tokens)
        state2, metrics = result.step(state, tok, tgt)
        assert np.isfinite(float(metrics["loss"]))
        assert int(state2.step) == 1
        # the update actually moved the stacked block params
        after = np.asarray(jax.tree.leaves(state2.block_params)[0])
        assert np.abs(after - before).sum() > 0.0

    def test_streaming_rejects_grad_accumulation(self, cpu_devices):
        with pytest.raises(ValueError, match="accumulate"):
            auto_accelerate(
                tiny_model(),
                optim_factory=self._per_leaf_factory,
                loss_fn=cross_entropy_loss,
                sample_batch=np.zeros((2, 16), np.int32),
                strategy=[("streaming", {})],
                global_batch=8, micro_batch=2,
                devices=cpu_devices[:1],
            )

    def test_streaming_rejects_multi_device(self, cpu_devices):
        with pytest.raises(ValueError, match="single-device"):
            auto_accelerate(
                tiny_model(),
                optim_factory=self._per_leaf_factory,
                loss_fn=cross_entropy_loss,
                sample_batch=np.zeros((2, 16), np.int32),
                strategy=[("streaming", {})],
                devices=cpu_devices[:8],
            )

    def test_single_device_overflow_plans_streaming(self, monkeypatch,
                                                    cpu_devices):
        cfg = LlamaConfig.tiny(attn_impl="reference")
        # HBM smaller than the model's training state: nothing fits
        monkeypatch.setenv("DLROVER_TPU_HBM_BYTES",
                           str(cfg.param_count() * 4))
        context = ModelContext(
            Llama(cfg), optim_factory=self._per_leaf_factory,
            loss_fn=cross_entropy_loss,
            sample_batch=np.zeros((2, 16), np.int32),
            devices=cpu_devices[:1],
        )
        candidates = plan_candidates(context, max_candidates=16)
        streaming = [s for s in candidates
                     if any(n == "streaming" for n, _ in s)]
        assert streaming, f"no streaming candidate in {candidates}"
        speed, err = dry_run(context, streaming[0], warmup=1, steps=1)
        assert err == "" and speed > 0
