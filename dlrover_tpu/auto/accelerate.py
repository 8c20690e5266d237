"""auto_accelerate: strategy → lowered sharded trainer.

Capability parity: atorch auto_accelerate (atorch/auto/accelerate.py:391)
and model_transform (:35). Three modes:
- explicit strategy (load_strategy given): apply passes, lower, return —
  the reference's skip-search path;
- semi-auto (strategy="auto"): engine search over SEMIAUTO_STRATEGIES with
  dry-run scoring (engine module);
- default: a sensible TPU baseline (bf16 + flash attention; fsdp when the
  mesh has >1 device).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np

from dlrover_tpu.auto.model_context import ModelContext
from dlrover_tpu.auto.opt_lib import OptimizationLibrary
from dlrover_tpu.auto.strategy import (
    Strategy,
    load_strategy,
    normalize_strategy,
    save_strategy,
)
from dlrover_tpu.common.constants import MeshAxis
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.ops.backend import on_tpu
from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
from dlrover_tpu.parallel.sharding import make_sharding_rules
from dlrover_tpu.trainer.train_step import (
    ShardedTrainer,
    build_trainer,
    choose_accumulation,
)


@dataclasses.dataclass
class AccelerateResult:
    """What auto_accelerate hands back (the reference returns a tuple of
    transformed model/optim/dataloader/loss; here the lowered trainer
    carries them all)."""

    trainer: ShardedTrainer
    mesh: Any
    model: Any
    strategy: Strategy
    context: ModelContext

    # convenience passthroughs
    def init(self, rng):
        return self.trainer.init(rng)

    def step(self, state, tokens, targets):
        return self.trainer.step(state, tokens, targets)


def default_strategy(n_devices: int) -> Strategy:
    strategy: Strategy = [("half", {}), ("module_replace", {})]
    if n_devices > 1:
        strategy.append(("fsdp", {}))
    return strategy


def apply_strategy(context: ModelContext, strategy: Strategy,
                   opt_lib: Optional[OptimizationLibrary] = None
                   ) -> ModelContext:
    """The model_transform analog (accelerate.py:35-66): run each pass."""
    opt_lib = opt_lib or OptimizationLibrary()
    opt_lib.validate_strategy(strategy)
    for name, config in strategy:
        opt_lib[name].apply(context, config)
    return context


def lower(context: ModelContext) -> AccelerateResult:
    """Compile the accumulated plan into a mesh + jitted train step."""
    plan = context.plan
    n_devices = len(context.devices)

    # -- mesh ----------------------------------------------------------
    dims = dict(plan.mesh_dims)
    unknown = sorted(set(dims) - set(MeshAxis.ALL))
    if unknown:
        raise ValueError(
            f"unknown mesh axes {unknown}; valid axes: {MeshAxis.ALL}")
    if plan.fsdp and dims.get(MeshAxis.FSDP, 0) <= 1:
        # fsdp requested without an explicit size: the fsdp axis absorbs
        # every device not claimed by other axes (incl. an explicit data
        # dim; with no data dim, data is pinned to 1 — batch is sharded
        # over (data, fsdp) jointly anyway)
        fixed = 1
        for axis, size in dims.items():
            if axis != MeshAxis.FSDP:
                fixed *= size
        if n_devices % fixed == 0 and n_devices // fixed > 1:
            dims[MeshAxis.FSDP] = n_devices // fixed
            dims.setdefault(MeshAxis.DATA, 1)
    spec = MeshSpec(**dims)
    mesh = create_mesh(spec, context.devices)

    # -- model edits (dataclass-config models) -------------------------
    updates = {}
    if plan.compute_dtype is not None:
        updates["dtype"] = plan.compute_dtype
    if plan.params_dtype is not None:
        updates["param_dtype"] = plan.params_dtype
    if plan.flash_attention:
        updates["attn_impl"] = "flash" if on_tpu() else "reference"
    if plan.sequence_parallel and mesh.shape[MeshAxis.SEQUENCE] > 1:
        # SP replaces the attention kernel: the sequence dim is sharded, so
        # attention must be the ring/all-to-all implementation (wins over a
        # flash_attention request — the Pallas kernel needs the full seq).
        updates["attn_impl"] = plan.sequence_impl
    if plan.remat:
        updates["remat"] = True
        if plan.remat_policy:
            updates["remat_policy"] = plan.remat_policy
    if updates:
        skipped = context.replace_model_config(**updates)
        if skipped is None:
            logger.info(
                "model has no dataclass cfg; edits %s skipped (strategy "
                "still shapes mesh + shardings)", sorted(updates))
        elif skipped:
            # a partially-supported config is a memory-plan hazard: the
            # sizing may have counted on the dropped edit (remat, SP)
            logger.warning(
                "model config does not accept %s; those edits were "
                "dropped (applied: %s)", skipped,
                sorted(set(updates) - set(skipped)))

    # -- sharding rules -------------------------------------------------
    rules = make_sharding_rules(
        fsdp=plan.fsdp and mesh.shape[MeshAxis.FSDP] > 1,
        tensor=plan.tensor_parallel and mesh.shape[MeshAxis.TENSOR] > 1,
        extra=plan.rule_overrides,
    )

    # -- batch geometry --------------------------------------------------
    from dlrover_tpu.parallel.mesh import dp_size as mesh_dp_size

    dp = mesh_dp_size(mesh)
    if plan.global_batch:
        accum, micro_global = choose_accumulation(
            plan.global_batch, dp,
            max_micro_per_replica=plan.micro_batch or 64)
        micro = micro_global
    else:
        accum = plan.accum_steps
        micro = plan.micro_batch or dp
    sample = context.infer_sample_batch(micro)

    if plan.streaming:
        from dlrover_tpu.models.llama import (
            LlamaConfig,
            cross_entropy_loss,
        )
        from dlrover_tpu.trainer.streaming import build_streaming_trainer

        if n_devices > 1 or plan.pipeline_stages > 1:
            raise ValueError(
                "streaming is the single-device >HBM escape hatch; on "
                f"{n_devices} devices use fsdp / pipeline_parallel "
                "instead (they shard the gradient tree across chips)")
        if accum > 1:
            raise ValueError(
                f"streaming cannot gradient-accumulate (accum={accum}): "
                "holding the accumulated full-tree gradients is exactly "
                "the >HBM cost streaming exists to avoid — raise "
                "micro_batch (or drop global_batch) so accum == 1")
        cfg = context.model_config()
        if not isinstance(cfg, LlamaConfig):
            raise NotImplementedError(
                "streaming lowering needs the scan-shaped Llama stack "
                "(LlamaConfig); for custom models call "
                "dlrover_tpu.trainer.streaming.build_streaming_trainer "
                "with a compatible per-layer model directly")
        if context.loss_fn not in (None, cross_entropy_loss):
            logger.warning(
                "streaming computes its own chunked cross-entropy head "
                "loss; the provided loss_fn is ignored")
        trainer = build_streaming_trainer(
            cfg, context.make_optimizer(),
            micro_batch=micro,
            seq_len=int(np.asarray(sample).shape[-1]),
            devices=context.devices,
        )
        return AccelerateResult(trainer=trainer, mesh=trainer.mesh,
                                model=context.model, strategy=[],
                                context=context)

    if plan.pipeline_stages > 1:
        from dlrover_tpu.models.bert import BertConfig
        from dlrover_tpu.models.gpt import GPTConfig
        from dlrover_tpu.models.llama import LlamaConfig
        from dlrover_tpu.trainer.pipeline_trainer import (
            build_pipeline_trainer,
        )

        cfg = context.model_config()
        if not isinstance(cfg, (LlamaConfig, GPTConfig, BertConfig)):
            raise NotImplementedError(
                "pipeline lowering needs a stacked-block model config "
                "(LlamaConfig, GPTConfig, or BertConfig); for custom "
                "models build a PipelineModelSpec and a PipelinedTrainer "
                "directly (dlrover_tpu.trainer.pipeline_trainer)")
        if plan.global_batch:
            # the accumulation geometry IS the microbatch stream: the
            # user's global batch is authoritative (accum × micro rows)
            num_micro = accum
        else:
            num_micro = max(plan.accum_steps, 2 * plan.pipeline_stages)
        if plan.grad_reduce_bits:
            logger.warning(
                "quant_allreduce is not implemented for the pipeline "
                "trainer: the data-axis gradient reduce stays exact "
                "(grad_reduce_bits=%d ignored under "
                "pipeline_parallel)", plan.grad_reduce_bits)
        trainer = build_pipeline_trainer(
            cfg, context.make_optimizer(), mesh,
            num_microbatches=num_micro, micro_batch=micro,
            seq_len=np.asarray(sample).shape[-1],
            loss_fn=context.loss_fn, remat=plan.remat,
            num_rounds=plan.pipeline_rounds,
            rules=rules,
            offload_opt_state=plan.offload_optimizer,
            bound_activations=plan.pipeline_bound_activations,
        )
        return AccelerateResult(trainer=trainer, mesh=mesh,
                                model=context.model, strategy=[],
                                context=context)

    trainer = build_trainer(
        context.model,
        context.make_optimizer(),
        mesh,
        np.asarray(sample),
        context.loss_fn,
        accum_steps=accum,
        micro_batch=micro,
        rules=rules,
        donate_state=plan.donate_state,
        offload_opt_state=plan.offload_optimizer,
        grad_reduce_bits=plan.grad_reduce_bits,
    )
    return AccelerateResult(trainer=trainer, mesh=mesh,
                            model=context.model, strategy=[],
                            context=context)


def auto_accelerate(
    model: Any,
    optim_factory: Optional[Callable] = None,
    dataset: Optional[Any] = None,
    loss_fn: Optional[Callable] = None,
    *,
    sample_batch: Optional[Any] = None,
    strategy: Optional[Any] = None,
    load_strategy_file: str = "",
    save_strategy_to_file: str = "",
    global_batch: int = 0,
    micro_batch: int = 0,
    devices: Optional[Sequence[jax.Device]] = None,
    optim_args: Optional[dict] = None,
) -> AccelerateResult:
    """One-call acceleration (atorch auto_accelerate parity).

    strategy: None → default TPU baseline; "auto" → engine search;
    list → explicit strategy (names or (name, config) pairs).
    """
    context = ModelContext(
        model, optim_factory=optim_factory, dataset=dataset,
        loss_fn=loss_fn, sample_batch=sample_batch,
        optim_args=optim_args, devices=devices,
    )
    context.plan.global_batch = global_batch
    context.plan.micro_batch = micro_batch

    if load_strategy_file:
        chosen = load_strategy(load_strategy_file)
    elif strategy == "auto":
        from dlrover_tpu.auto.engine.acceleration_engine import (
            search_strategy,
        )

        chosen = search_strategy(context)
    elif strategy is not None:
        chosen = normalize_strategy(strategy)
    else:
        chosen = default_strategy(len(context.devices))

    apply_strategy(context, chosen)
    result = lower(context)
    result.strategy = chosen
    if save_strategy_to_file:
        save_strategy(chosen, save_strategy_to_file)
    return result
