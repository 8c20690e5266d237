"""Sharded training step builder with gradient accumulation.

Capability parity: ElasticTrainer's fixed-global-batch gradient accumulation
(dlrover/trainer/torch/elastic/trainer.py:53-139 GradientState/no_sync
machinery) — TPU re-design: microbatches are a `lax.scan` inside ONE jitted
program; the whole state (params + optimizer) is laid out by logical-axis
rules over the mesh, so DP/FSDP/TP are a table change, not a wrapper class.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.common.constants import MeshAxis, TraceScope
from dlrover_tpu.common.log import default_logger
from dlrover_tpu.parallel.mesh import data_axes, dp_size, use_mesh
from dlrover_tpu.parallel.moe import moe_aux_loss, sown_counters
from dlrover_tpu.parallel.sharding import (
    DEFAULT_RULES,
    mesh_shardings,
    sanitize_shardings,
)


def abstract_state_with_shardings(abstract: Any, shardings: Any) -> Any:
    """Attach shardings to an eval_shape'd state tree — the checkpoint
    restore target shared by the dense and pipelined trainers."""
    return jax.tree.map(
        lambda leaf, sharding: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=sharding),
        abstract, shardings)


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>\S+) = (?P<result>.*?) (?P<opcode>[\w\-]+)\(.*"
    r'op_name="(?P<op_name>[^"]*)"', re.M)


def schedule_counts(compiled_text: str, rows_and_seq: Tuple[int, ...]) -> dict:
    """What the compiled step's ENTRY computation, which is printed in
    schedule order, says of two things XLA decides and nobody asks for,
    and of two the block's recomputation policy decides:

    `remat_instructions`: forward matmuls (`jvp(` outside `transpose(`,
    `dot_general`) launched beyond the first of their `op_name` where XLA
    marks one of them `.remat`, i.e. recomputed to fit the memory. Neither
    sign alone will do: XLA may call the only copy `.remat2`, and a mesh
    may split one product into several of one `op_name`.

    `late_weight_grads`: weight-gradient matmuls of the projections
    (`transpose(jvp(` + `_proj/`, with a result that is no activation: it
    does not start with a device's `rows_and_seq`) that stand after the
    backward pass's last activation-gradient matmul, with everything they
    read alive until then; that last projection's own does not count, it
    may stand on either side of its sibling.

    `recomputed_kernels`: kernel name -> launches (`custom-call`s, named
    after their `pl.pallas_call`) under `rematted_computation`, a block's
    forward run again in the backward pass (`remat`). What
    `ops/remat.py:Kept` names is kept and its kernel is not among them.

    `recomputed_matmuls`: matmuls (found as for `remat_instructions`) under
    `rematted_computation`; 0 where the block's policy keeps every
    projection's output (`matmul_and_kernel_outputs`)."""
    entry = compiled_text[compiled_text.rfind("\nENTRY "):]
    activation = "[" + ",".join(str(d) for d in rows_and_seq) + ","
    forward, weight_grads, last_dx = {}, [], (-1, "")
    recomputed: dict = {}
    recomputed_matmuls = 0
    for at, found in enumerate(_INSTRUCTION.finditer(entry)):
        opcode, op_name = found["opcode"], found["op_name"]
        if opcode == "custom-call" and "rematted_computation" in op_name:
            kernel = found["name"].rstrip(".0123456789")
            recomputed[kernel] = recomputed.get(kernel, 0) + 1
        matmul = opcode == "convolution" or (
            opcode == "fusion" and "kind=kOutput" in found[0])
        if not matmul or not op_name.endswith("dot_general"):
            continue
        recomputed_matmuls += "rematted_computation" in op_name
        if "transpose(jvp(" in op_name:
            if "_proj/" not in op_name:
                continue
            if activation in found["result"]:
                last_dx = (at, op_name)
            else:
                weight_grads.append((at, op_name))
        elif "jvp(" in op_name:
            launches, marked = forward.get(op_name, (0, False))
            forward[op_name] = (launches + 1,
                                marked or ".remat" in found["name"])
    return {
        "remat_instructions": sum(
            launches - 1 for launches, marked in forward.values() if marked),
        "late_weight_grads": sum(
            at > last_dx[0] and op_name != last_dx[1]
            for at, op_name in weight_grads),
        "recomputed_kernels": recomputed,
        "recomputed_matmuls": recomputed_matmuls,
    }


@flax.struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any


@dataclasses.dataclass
class ShardedTrainer:
    """A lowered (mesh-specific) training program.

    Rebuild via `build_trainer` after an elastic world resize — compiled
    programs are mesh-shape-specific (SURVEY.md §7 'hard parts').
    """

    mesh: Mesh
    init_fn: Callable[[jax.Array], TrainState]
    step_fn: Callable[..., Tuple[TrainState, dict]]
    state_shardings: Any
    batch_sharding: NamedSharding
    accum_steps: int
    micro_batch: int
    batch_abstract: Optional[jax.ShapeDtypeStruct] = None
    # split-step programs (build_trainer(split_grad_apply=True)): the
    # host-level cross-slice gradient sync (parallel/dcn_sync.py) needs
    # the in-slice-reduced gradient OUT of the program and the fleet-
    # reduced gradient back IN before the optimizer update. None on
    # fused-step trainers.
    grad_fn: Any = dataclasses.field(default=None, repr=False)
    apply_fn: Any = dataclasses.field(default=None, repr=False)
    _compiled_step: Any = dataclasses.field(default=None, repr=False)
    # static per program: "fused" where the model offers its final hidden
    # states and head apart from `__call__` and the loss carries its fused
    # form (models/llama.py:head_cross_entropy), which then takes the
    # sequence in `head_loss_slices` slices; else "logits", whole (1)
    head_loss_path: str = "logits"
    head_loss_slices: int = 1
    precompile_timings: dict = dataclasses.field(default_factory=dict)
    last_used_aot: bool = False

    def init(self, rng: jax.Array) -> TrainState:
        return self.init_fn(rng)

    def abstract_state(self, rng: jax.Array) -> TrainState:
        """Abstract TrainState (shapes + shardings, nothing allocated) —
        the checkpoint-restore target (reshard-on-restore)."""
        return abstract_state_with_shardings(
            jax.eval_shape(self.init_fn, rng), self.state_shardings)

    def precompile(self, rng: Optional[jax.Array] = None) -> None:
        """AOT-compile the train step from abstract inputs (trace +
        lower + XLA compile or persistent-cache load), so a respawned
        worker can overlap compilation with its checkpoint read instead
        of serializing re-jit after it (at scale the compile is the
        longer of the two). Safe to call from a
        background thread; `step` uses the compiled executable when
        present and falls back to the jitted path on any mismatch."""
        if self._compiled_step is not None or self.batch_abstract is None:
            return
        import time as _time

        from dlrover_tpu import obs

        abstract = self.abstract_state(
            jax.random.PRNGKey(0) if rng is None else rng)
        with obs.span("recompile", {
                "phase": "aot", "head_loss_path": self.head_loss_path,
                "head_loss_slices": self.head_loss_slices}) as aot_span:
            with obs.device.compile_cache_reads() as cache:
                t0 = _time.monotonic()
                lowered = self.step_fn.lower(
                    abstract, self.batch_abstract, self.batch_abstract)
                t1 = _time.monotonic()
                compiled = lowered.compile()
                t2 = _time.monotonic()
            for name, value in cache.items():
                aot_span.set_attr(name, value)
            self.precompile_timings = {
                "trace_lower_s": round(t1 - t0, 2),
                "compile_or_cache_load_s": round(t2 - t1, 2),
            }
            aot_span.set_attr("trace_lower_s",
                              self.precompile_timings["trace_lower_s"])
            aot_span.set_attr(
                "compile_or_cache_load_s",
                self.precompile_timings["compile_or_cache_load_s"])
            # a device's (rows, sequence) of one micro-batch: how the
            # compiled text shapes an activation
            counts = schedule_counts(
                compiled.as_text(), self.batch_sharding.shard_shape(
                    self.batch_abstract.shape)[1:])
            by_kernel = counts["recomputed_kernels"]    # the span: the total
            counts["recomputed_kernels"] = sum(by_kernel.values())
            counts["schedule_read_s"] = round(_time.monotonic() - t2, 2)
            for name, value in counts.items():
                aot_span.set_attr(name, value)
        default_logger.info(
            "step program: remat_instructions=%d late_weight_grads=%d "
            "recomputed_kernels=%d %s recomputed_matmuls=%d "
            "(read in %.2f s)",
            counts["remat_instructions"], counts["late_weight_grads"],
            counts["recomputed_kernels"], dict(sorted(by_kernel.items())),
            counts["recomputed_matmuls"], counts["schedule_read_s"])
        self._compiled_step = compiled

    def step(self, state: TrainState, tokens, targets):
        if self._compiled_step is not None:
            try:
                out = self._compiled_step(state, tokens, targets)
                self.last_used_aot = True
                return out
            except (TypeError, ValueError) as e:
                # pre-dispatch signature/layout mismatch vs the AOT
                # arguments (raised before buffers are donated): the
                # jitted path recompiles correctly. Runtime errors (OOM,
                # XlaRuntimeError) propagate — state may already be
                # donated, so re-running would only mask the real error.
                default_logger.warning(
                    "AOT-compiled step rejected its arguments (%s); "
                    "falling back to the jitted path", e)
                self._compiled_step = None
        self.last_used_aot = False
        return self.step_fn(state, tokens, targets)

    def grad_step(self, state: TrainState, tokens, targets):
        """Forward+backward only: (slice-mean grads, metrics). The
        caller reduces the grads across slices (host-level DCN sync)
        before `apply_grads`. Only on split-built trainers."""
        if self.grad_fn is None:
            raise RuntimeError("trainer was not built with "
                               "split_grad_apply=True")
        return self.grad_fn(state, tokens, targets)

    def apply_grads(self, state: TrainState, grads):
        """Optimizer update from (fleet-reduced) grads → (new_state,
        metrics)."""
        if self.apply_fn is None:
            raise RuntimeError("trainer was not built with "
                               "split_grad_apply=True")
        return self.apply_fn(state, grads)

    def shard_batch(self, tokens, targets):
        """Host numpy (global_batch, seq) → device arrays shaped
        (accum, micro, seq) with the micro axis over (data, fsdp)."""
        accum, micro = self.accum_steps, self.micro_batch
        tokens = tokens.reshape(accum, micro, *tokens.shape[1:])
        targets = targets.reshape(accum, micro, *targets.shape[1:])
        put = lambda x: jax.device_put(x, self.batch_sharding)
        return put(tokens), put(targets)


def build_trainer(
    model: nn.Module,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    sample_batch: jax.Array,
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    accum_steps: int = 1,
    micro_batch: int = 1,
    rules: Optional[Sequence] = None,
    donate_state: bool = True,
    offload_opt_state: bool = False,
    grad_reduce_bits: int = 0,
    split_grad_apply: bool = False,
) -> ShardedTrainer:
    """Lower (model, optimizer, mesh) into init/step programs.

    sample_batch: one microbatch of tokens, shape (micro_batch, seq) — used
    only for shape inference.

    offload_opt_state: keep the optimizer state in HOST memory
    (pinned_host memory kind) — the TPU-native equivalent of the
    reference's CPU-offloaded Adam (atorch/optim/adam_offload.py): the
    moments' shardings carry the host memory kind and XLA inserts the
    host↔HBM transfers around the update, freeing ~2/3 of the train
    state's HBM at the cost of PCIe/DMA traffic per step.

    grad_reduce_bits: 8/4 = the gradient mean over the reduce axis
    runs through the quantized collective
    (parallel/quant_collectives.py, the reference quant_reduce.cu
    analog) instead of XLA's implicit fp psum: the whole step is wrapped
    in a shard_map manual over that one axis, every other axis stays
    auto. 0 = exact reduce (default).

    The reduce axis resolves hierarchically — the ``dcn`` axis when the
    mesh spans slices (dcn > 1), else ``data``. A dcn reduce
    makes the gradient sync explicitly two-level: the in-slice mean
    rides XLA's implicit psum over the (data, fsdp) axes inside each
    slice block, then the cross-slice mean (all-)reduces over the
    manual dcn axis — quantized when ``grad_reduce_bits`` asks for it,
    exact pmean otherwise.

    split_grad_apply: additionally build ``grad_fn``/``apply_fn`` —
    the two halves of the step around a HOST-level cross-slice
    gradient sync (parallel/dcn_sync.py): grad_fn returns the
    in-slice-reduced grads, the host exchanges them over DCN
    (tolerating an absent slice), apply_fn applies the fleet mean.
    """
    rules = list(rules if rules is not None else DEFAULT_RULES)
    # Head and loss as one function (models/llama.py:head_cross_entropy)
    # where the inputs say they can be: the model by offering
    # `hidden_and_head`, the loss by carrying `from_hidden`. A mesh that
    # shards the sequence keeps whole logits: the slices cut that axis.
    fused_loss = getattr(loss_fn, "from_hidden", None)
    fused = (fused_loss is not None
             and callable(getattr(model, "hidden_and_head", None))
             and mesh.shape.get(MeshAxis.SEQUENCE, 1) == 1)
    head_loss_path = "fused" if fused else "logits"

    def _boxed_state(variables):
        params = variables["params"]
        # optax maps over the boxed tree, so optimizer moments inherit the
        # logical axis annotations (→ FSDP shards them like the params)
        opt_state = tx.init(params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt_state)

    def _init_boxed(rng):
        return _boxed_state(model.init(rng, sample_batch))

    def _abstract_init(rng):
        """The state and, on the fused path, what head and loss will see
        (hidden states, head), from ONE abstract pass over the model."""
        if not fused:
            return _init_boxed(rng), None
        hidden_and_head, variables = model.init_with_output(
            rng, sample_batch, method="hidden_and_head")
        return _boxed_state(variables), hidden_and_head

    # The mesh context is entered INSIDE every traced function so model
    # code can reach the concrete mesh at trace time (current_mesh() —
    # ring/Ulysses attention build an inner shard_map from it), including
    # re-traces from eval_shape in the checkpoint-restore path.
    with use_mesh(mesh):
        abstract_boxed, hidden_and_head = jax.eval_shape(
            _abstract_init, jax.random.key(0)
        )
    head_slices = 1
    if fused:
        from dlrover_tpu.models.llama import head_loss_slices

        hidden, head = hidden_and_head
        # a device holds its share of the micro-batch's rows
        head_slices = head_loss_slices(
            max(1, micro_batch // dp_size(mesh)), hidden.shape[1],
            head.shape[-1], hidden.dtype.itemsize)
    default_logger.info("head and loss: path=%s slices=%d",
                        head_loss_path, head_slices)
    state_shardings = mesh_shardings(abstract_boxed, mesh, rules)
    # factored optimizers (adafactor) produce state leaves whose rank
    # differs from the param that named their axes — replicate those
    state_shardings = sanitize_shardings(
        state_shardings, nn.unbox(abstract_boxed), mesh)
    if offload_opt_state:
        abstract_opt = nn.unbox(abstract_boxed).opt_state
        state_shardings = state_shardings.replace(
            opt_state=jax.tree.map(
                # scalars (step counters) stay on device: XLA's SPMD
                # partitioner rejects memory-kind annotations on them
                lambda s, a: s if a.ndim == 0 else NamedSharding(
                    mesh, s.spec, memory_kind="pinned_host"),
                state_shardings.opt_state, abstract_opt,
            ))
    # Batch (accum, micro, seq): micro over the joint dp axes (dcn +
    # data + fsdp — cross-slice replicas outermost), seq over the
    # sequence axis (a no-op at sequence=1; shards inputs for SP runs).
    batch_shard = NamedSharding(
        mesh, P(None, data_axes(mesh), MeshAxis.SEQUENCE)
    )

    def _init(rng):
        with use_mesh(mesh):
            return nn.unbox(_init_boxed(rng))

    init_fn = jax.jit(_init, out_shardings=state_shardings)

    def _train_step(state: TrainState, tokens, targets):
        # activation logical-constraints in the models resolve through
        # these rules (no-ops without this context); with-block so a
        # trace-time exception never leaks flax's global rules stack
        with use_mesh(mesh), nn.logical_axis_rules(rules):
            return _train_step_body(state, tokens, targets)

    def _accumulate(state: TrainState, tokens, targets):
        """The microbatch scan: (loss_sum, f32 grad_sum) before any
        explicit cross-axis reduce or the optimizer update — the shared
        core of the fused step and the split grad_fn."""
        params = state.params
        # Deterministic per-step rng streams for stochastic model paths
        # (MoE gating jitter, dropout): folded from the step counter so
        # every restart replays identically, and identical across
        # replicas as SPMD single-program semantics require.
        step_key = jax.random.fold_in(jax.random.PRNGKey(0), state.step)

        def micro_step(carry, micro):
            loss_acc, grad_acc = carry
            tok, tgt, idx = micro
            micro_key = jax.random.fold_in(step_key, idx)
            rngs = {"gating": jax.random.fold_in(micro_key, 0),
                    "dropout": jax.random.fold_in(micro_key, 1)}

            def compute_loss(p):
                # mutable "losses": models sow auxiliary losses there
                # (MoE router balancing, parallel/moe.py:172); for models
                # that never sow, the collection is empty and the sum is
                # 0 — one generic path covers both
                # `counters`: what a model counts of a step (an expert
                # layer's load) and wants among the step's metrics, each
                # name averaged over whoever sowed it; empty likewise
                if fused:
                    (hidden, head), mutables = model.apply(
                        {"params": p}, tok, mutable=["losses", "counters"],
                        rngs=rngs, method="hidden_and_head")
                    loss = fused_loss(hidden, head, tgt, head_slices)
                else:
                    logits, mutables = model.apply(
                        {"params": p}, tok, mutable=["losses", "counters"],
                        rngs=rngs)
                    # the model's final norm and head open the same scope
                    # (models/llama.py): head + loss read as one in a trace
                    with jax.named_scope(TraceScope.HEAD_LOSS):
                        loss = loss_fn(logits, tgt)
                return loss + moe_aux_loss(mutables), sown_counters(mutables)

            (loss, counted), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(params)
            grad_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grad_acc, grads
            )
            return (loss_acc + loss, grad_acc), counted

        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        with jax.named_scope(TraceScope.GRAD_ACCUM):
            (loss_sum, grad_sum), counted = jax.lax.scan(
                micro_step, (jnp.zeros((), jnp.float32), zero_grads),
                (tokens, targets, jnp.arange(accum_steps)),
            )
        return loss_sum, grad_sum, jax.tree.map(jnp.mean, counted)

    def _apply_body(state: TrainState, grads):
        """Optimizer update from already-reduced grads (param dtype):
        (new_state, grad_norm)."""
        with jax.named_scope(TraceScope.OPTIMIZER):
            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               opt_state=new_opt)
        return new_state, optax.global_norm(grads)

    def _train_step_body(state: TrainState, tokens, targets,
                         grad_reduce=None):
        loss_sum, grad_sum, counted = _accumulate(state, tokens, targets)
        if grad_reduce is not None:
            # explicit (possibly quantized) mean over the manual reduce
            # axis — the cross-slice half of the hierarchical sync; the
            # in-slice half already happened through XLA's implicit
            # psum over the auto (data, fsdp) axes. The loss metric
            # reduces exactly (it's a scalar).
            grad_sum = grad_reduce(grad_sum)
            loss_sum = jax.lax.pmean(loss_sum, grad_reduce_axis)
        grads = jax.tree.map(
            lambda g, p: (g / accum_steps).astype(p.dtype), grad_sum,
            state.params
        )
        new_state, grad_norm = _apply_body(state, grads)
        metrics = {
            "loss": loss_sum / accum_steps,
            "grad_norm": grad_norm,
            **counted,
        }
        return new_state, metrics

    # hierarchical: a mesh spanning slices reduces over the dcn axis
    # (in-slice implicit + cross-slice explicit)
    grad_reduce_axis = (MeshAxis.DCN
                        if mesh.shape.get(MeshAxis.DCN, 1) > 1
                        else MeshAxis.DATA)
    n_reduce = mesh.shape.get(grad_reduce_axis, 1)
    # the dcn axis always reduces explicitly (the hierarchical
    # contract), quantized or not; other axes only when quantized
    wrap_reduce = n_reduce > 1 and (
        bool(grad_reduce_bits) or grad_reduce_axis == MeshAxis.DCN)
    if wrap_reduce:
        from jax.sharding import PartitionSpec

        from dlrover_tpu.parallel.quant_collectives import quantized_pmean

        # Manual ONLY over the reduce axis: every other axis (fsdp/tp/…)
        # stays auto so XLA keeps intra-slice sharding + collectives.
        # Activation rules must not name the manual axis — strip it.
        def _strip(axes):
            if axes is None:
                return None
            if isinstance(axes, str):
                return None if axes == grad_reduce_axis else axes
            kept = tuple(a for a in axes if a != grad_reduce_axis)
            return kept or None

        rules_local = [(name, _strip(axes)) for name, axes in rules]

        def _reduce(tree):
            return quantized_pmean(tree, grad_reduce_axis, n_reduce,
                                   bits=grad_reduce_bits)

        def _body_local(state, tokens, targets):
            with use_mesh(mesh), nn.logical_axis_rules(rules_local):
                return _train_step_body(state, tokens, targets,
                                        grad_reduce=_reduce)

        state_manual_spec = jax.tree.map(lambda _: PartitionSpec(),
                                         state_shardings)
        batch_manual_spec = PartitionSpec(None, grad_reduce_axis)
        wrapped = jax.shard_map(
            _body_local,
            mesh=mesh,
            in_specs=(state_manual_spec, batch_manual_spec,
                      batch_manual_spec),
            out_specs=(state_manual_spec, PartitionSpec()),
            axis_names=frozenset({grad_reduce_axis}),
            # the updated state IS invariant over the reduce axis (it is
            # computed from the reduced grads), but all_gather-derived
            # values type as varying — the static check can't see this
            check_vma=False,
        )
        step_impl = wrapped
    else:
        step_impl = _train_step

    step_fn = jax.jit(
        step_impl,
        in_shardings=(state_shardings, batch_shard, batch_shard),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate_state else (),
    )

    grad_fn = apply_fn = None
    if split_grad_apply:
        # the two halves around a host-level cross-slice sync: grad_fn's
        # output is the in-slice mean (XLA's implicit psum over the auto
        # dp axes of THIS program's world — one slice in the elastic
        # multi-world mode), apply_fn takes the fleet-reduced mean back.
        # grad_fn must NOT donate the state: apply_fn still reads it.
        def _grad_only(state, tokens, targets):
            with use_mesh(mesh), nn.logical_axis_rules(rules):
                loss_sum, grad_sum, _ = _accumulate(state, tokens,
                                                    targets)
                grads = jax.tree.map(
                    lambda g, p: (g / accum_steps).astype(p.dtype),
                    grad_sum, state.params)
                return grads, {"loss": loss_sum / accum_steps}

        def _apply_only(state, grads):
            with use_mesh(mesh), nn.logical_axis_rules(rules):
                new_state, grad_norm = _apply_body(state, grads)
                return new_state, {"grad_norm": grad_norm}

        grads_shardings = state_shardings.params
        # NO donation on grad_fn by design: the same state is re-read
        # by apply_fn after the host-level cross-slice exchange
        grad_fn = jax.jit(  # graftlint: disable=GL104
            _grad_only,
            in_shardings=(state_shardings, batch_shard, batch_shard),
            out_shardings=(grads_shardings, None),
        )
        apply_fn = jax.jit(
            _apply_only,
            in_shardings=(state_shardings, grads_shardings),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,) if donate_state else (),
        )

    return ShardedTrainer(
        mesh=mesh,
        init_fn=init_fn,
        step_fn=step_fn,
        state_shardings=state_shardings,
        batch_sharding=batch_shard,
        accum_steps=accum_steps,
        micro_batch=micro_batch,
        grad_fn=grad_fn,
        apply_fn=apply_fn,
        head_loss_path=head_loss_path,
        head_loss_slices=head_slices,
        batch_abstract=jax.ShapeDtypeStruct(
            (accum_steps, micro_batch, *sample_batch.shape[1:]),
            jnp.int32, sharding=batch_shard),
    )


def choose_accumulation(global_batch: int, dp_size: int,
                        max_micro_per_replica: int) -> Tuple[int, int]:
    """Pick (accum_steps, micro_batch_global) holding the global batch fixed
    as the world resizes (reference: ElasticTrainer trainer.py:225 —
    acc = max_workers / cur_workers).

    micro_batch_global = global_batch / accum must divide by dp_size and fit
    per-replica memory (micro/dp ≤ max_micro_per_replica).
    """
    if global_batch % dp_size:
        raise ValueError(
            f"global batch {global_batch} not divisible by dp size {dp_size}"
        )
    per_replica_total = global_batch // dp_size
    accum = 1
    while (per_replica_total % accum
           or per_replica_total // accum > max_micro_per_replica):
        accum += 1
        if accum > per_replica_total:
            accum = per_replica_total
            break
    return accum, global_batch // accum
