"""Pipeline parallelism: stage-sharded SPMD pipelining over the `pipe` axis.

Capability parity: atorch's PiPPy path (modules/distributed_modules/
compilers/pipe_compiler/distributed_pippy_compiler.py:378 — fx-trace,
split into stages, RPC driver, GPipe/interleaved schedules) and the
DeepSpeed 3D alternative (opt_lib/ds_3d_parallel_optimization.py:53).

TPU re-design: there is no RPC; all stages run the SAME jitted SPMD
program under a shard_map that is MANUAL only over the `pipe` axis
(jax.shard_map `axis_names`): every other mesh axis (data/fsdp/tensor/…)
stays "auto", so stage-internal parameters keep their fsdp/tensor
shardings and XLA inserts the intra-stage collectives — PP composes with
FSDP/TP the way the reference's 3D path does (ds_3d_parallel topology).

Microbatch streaming is O(M/S) per stage, not O(M): the stream is stored
round-robin across stages (microbatch m lives on stage m % S) and moves
through two single-microbatch ring buffers — an input ring rotating toward
stage 0 (each stage injects its next stored microbatch every S steps) and
an output ring rotating away from the last stage (each stage deposits the
microbatches it owns as they pass by). Per-step bandwidth is three
microbatch-sized ppermutes (activation, input ring, output ring),
independent of M. The GPipe schedule runs M + 2(S-1) steps: M + S - 1 for
the pipeline itself plus up to S - 1 more for the output ring to deliver
the last microbatch to its owner.

Autodiff through scan+ppermute yields the backward pipeline;
`jax.checkpoint` on the stage fn gives per-stage remat.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.common.constants import MeshAxis


def _pipeline_local(stage_params, in_store, *, stage_fn, axis_name: str,
                    num_stages: int, stored_micro: int):
    """Per-device body (manual over the pipe axis only).

    stage_params: this stage's params (leading pipe dim of size 1 already
    squeezed). in_store: (1, stored_micro, micro, ...) — this stage's
    round-robin share of the stream; in_store[0, j] is microbatch
    j * S + stage.
    """
    stage = lax.axis_index(axis_name)
    in_store = in_store[0]
    num_micro = stored_micro * num_stages
    # Since the stream is padded to a multiple of S, the final microbatch's
    # owner is stage S-1 (deposit at t = M+S-2) and the latest deposit
    # overall is u = M-2 at owner S-2 (t = M+2S-4), so M + 2S - 3 steps
    # suffice; S == 1 degenerates to plain sequential execution.
    steps = num_micro + max(2 * num_stages - 3, 0)

    micro_shape = in_store.shape[1:]
    # carries hold per-stage values: mark them varying over the pipe axis
    # so the vma check accepts the ppermute outputs fed back into the scan
    zeros = _varying(jnp.zeros(micro_shape, in_store.dtype), axis_name)
    out_store0 = jnp.zeros_like(in_store)  # varying: derived from in_store

    fwd_perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
    bwd_perm = [(i, (i - 1) % num_stages) for i in range(num_stages)]

    def step(carry, t):
        act, in_slot, out_slot, out_store = carry

        # -- input ring: every S steps each stage loads its next stored
        # microbatch into the slot currently at its position; the slot
        # reaches stage 0 exactly when that microbatch is due.
        load_idx = jnp.minimum(t // num_stages, stored_micro - 1)
        in_slot = jnp.where(t % num_stages == 0,
                            in_store[load_idx], in_slot)

        # -- stage 0 ingests microbatch t (garbage after the stream ends;
        # those outputs are never deposited)
        x = jnp.where(stage == 0, in_slot, act)
        y = stage_fn(stage_params, x)

        # -- output ring: the last stage writes its fresh output into the
        # slot at its position, then whichever stage owns the slot's
        # content deposits it. Content u at stage s (after the write):
        #   s == S-1: u = t - (S-1)
        #   else:     u = t - (S-1) - (s+1)
        produced = t - (num_stages - 1)
        out_slot = jnp.where(
            jnp.logical_and(stage == num_stages - 1,
                            jnp.logical_and(produced >= 0,
                                            produced < num_micro)),
            y, out_slot)
        u = jnp.where(stage == num_stages - 1,
                      t - (num_stages - 1),
                      t - num_stages - stage)
        deposit = jnp.logical_and(
            jnp.logical_and(u >= 0, u < num_micro),
            u % num_stages == stage)
        dep_idx = jnp.clip(u // num_stages, 0, stored_micro - 1)
        current = lax.dynamic_index_in_dim(out_store, dep_idx, 0,
                                           keepdims=False)
        out_store = lax.dynamic_update_index_in_dim(
            out_store, jnp.where(deposit, out_slot, current), dep_idx, 0)

        # -- rotate: activations toward higher stages, input ring toward
        # stage 0, output ring away from the last stage
        act = lax.ppermute(y, axis_name, fwd_perm)
        in_slot = lax.ppermute(in_slot, axis_name, bwd_perm)
        out_slot = lax.ppermute(out_slot, axis_name, fwd_perm)
        return (act, in_slot, out_slot, out_store), None

    (_, _, _, out_store), _ = lax.scan(
        step, (zeros, zeros, zeros, out_store0), jnp.arange(steps))
    return out_store[None]


def pipeline_apply(
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    inputs: jax.Array,
    axis: str = MeshAxis.PIPE,
    remat: bool = False,
) -> jax.Array:
    """Run `inputs` (num_microbatches, micro, ...) through the pipeline.

    stacked_params: pytree whose leaves have a leading stage dim of size
    mesh.shape[axis]; stage_fn(params_one_stage, x) -> y with y.shape ==
    x.shape (uniform-stage contract, same as GPipe splits). Leaves may be
    sharded over other mesh axes (fsdp/tensor) on their trailing dims —
    those axes are auto inside the pipe shard_map, so XLA keeps the
    sharding and inserts the intra-stage collectives. The micro (row) dim
    sharding likewise flows through the auto axes — each data replica
    pipelines its own row shard.
    """
    num_stages = mesh.shape[axis]
    num_micro = inputs.shape[0]
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    # round-robin storage layout: padded[j * S + s] -> stage s, slot j
    pad = (-num_micro) % num_stages
    if pad:
        inputs = jnp.concatenate(
            [inputs, jnp.zeros((pad,) + inputs.shape[1:], inputs.dtype)])
    stored = inputs.shape[0] // num_stages
    staged = inputs.reshape((stored, num_stages) + inputs.shape[1:])
    staged = jnp.swapaxes(staged, 0, 1)  # (S, stored, micro, ...)

    def body(params, x):
        squeezed = jax.tree.map(lambda p: p[0], params)
        return _pipeline_local(
            squeezed, x, stage_fn=fn, axis_name=axis,
            num_stages=num_stages, stored_micro=stored)

    params_spec = jax.tree.map(lambda _: P(axis), stacked_params)
    piped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(params_spec, P(axis)),
        out_specs=P(axis),
        axis_names=frozenset({axis}),
    )
    out = piped(stacked_params, staged)   # (S, stored, micro, ...)
    out = jnp.swapaxes(out, 0, 1).reshape(
        (stored * num_stages,) + out.shape[2:])
    return out[:num_micro]


def _varying(x, axis_name):
    """Mark x as varying over the pipe axis (idempotent)."""
    if axis_name in jax.typeof(x).vma:
        return x
    return lax.pcast(x, (axis_name,), to="varying")


def pipeline_train(
    mesh: Mesh,
    chunk_fn: Callable[[Any, jax.Array], jax.Array],
    chunk_params: Any,
    shared_params: Any,
    enter_fn: Callable[[Any, jax.Array], jax.Array],
    exit_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
    tokens: jax.Array,
    targets: jax.Array,
    num_rounds: int = 1,
    axis: str = MeshAxis.PIPE,
    remat: bool = False,
    chunk_has_aux: bool = False,
    activation_groups: int = 0,
) -> jax.Array:
    """Circular (interleaved) pipeline producing the mean microbatch loss.

    chunk_has_aux: chunk_fn returns (act, aux_scalar) — per-chunk
    auxiliary losses (MoE router load-balancing) accumulated over every
    VALID (chunk, microbatch) pair and folded into the returned loss as
    their microbatch mean, matching the dense trainer's
    `ce + moe_aux_loss` objective (models/llama_moe.py
    moe_cross_entropy_loss; each chunk sees each microbatch exactly
    once, so the sum over valid steps is the sum over layers).

    The schedule generalizes GPipe the way Megatron's interleaved 1F1B
    generalizes plain 1F1B (reference: PiPPy schedules consumed at
    distributed_pippy_compiler.py:378): layers split into S×num_rounds
    chunks, chunk g living on stage g % S, so each activation loops the
    ring num_rounds times. Steps = ceil(M/S)·S·C + S − 1 with only the
    S − 1 fill/drain steps idle per chunk — the bubble shrinks by the
    round count C vs GPipe. C = 1 is the plain schedule (M + S − 1 steps).

    TPU-first design decisions vs the round-2 ring-buffer version:
    - The model ENTERS the pipeline at stage 0 (enter_fn: embedding) and
      EXITS at the last stage (exit_fn: norm + head + per-row loss),
      selected by `jnp.where` on the stage index. SPMD uniformity note:
      `lax.cond` on a stage-varying predicate deadlocks — devices taking
      different branches reach the auto-axis collectives in divergent
      orders against the step's global ppermute (observed on the CPU
      backend) — so every device computes both sides and selects. The
      waste is the enter/exit bodies once per step per device: keep
      enter_fn cheap (gather embedding, not the one-hot matmul); the
      exit head matmul costs V/(V + 12·H·layers_per_chunk) of a step's
      FLOPs (~7.5% for Llama-7B at 8 layers/chunk) — the price of
      O(1) per-step comm and no output ring. For C > 1 a lax.cond on a
      stage-INDEPENDENT predicate (which steps can need enter/exit is a
      function of t alone, so every device branches identically — no
      deadlock) executes those bodies on only ~1/C of steps; measured
      full-vs-stubbed-exit wall deltas on the 8-device CPU mesh drop
      from 7-28% at C=1 to noise at C=2
      (tools/measure_pipeline_overhead.py). Uniform execution also
      means shared params may keep fsdp/tensor shardings: their
      collectives run on every device in the same order.
    - exit_fn returns UNREDUCED per-row losses (micro,), accumulated in
      the carry; only the (micro,) loss rows leave the last stage, so
      there is no output ring and no logits materialization; per-step
      comm is ONE activation ppermute. The cross-device reductions (psum
      over pipe, row mean) happen after the scan.
    - tokens/targets (M, micro, seq) ride in replicated over pipe — raw
      int32 microbatches are tiny next to hidden activations, which is
      what made the round-2 input ring necessary (it carried embedded
      activations).

    chunk_params: leaves (C, S, layers_per_chunk, ...) — chunk r·S + s is
    [r, s]; trailing dims may be auto-sharded (fsdp/tensor), composing
    PP × TP × FSDP × DP in one partial-auto shard_map. shared_params
    (embedding/norm/head) replicate over pipe, auto elsewhere.
    enter_fn(shared, tok_micro) -> (micro, seq, H) activation;
    chunk_fn(params[r·S+s], act) -> act;
    exit_fn(shared, act, tgt_micro) -> (micro,) per-row losses, no
    cross-row reduction.
    Returns the scalar mean loss over all microbatch rows.
    """
    num_stages = mesh.shape[axis]
    num_micro = tokens.shape[0]
    num_groups = -(-num_micro // num_stages)     # ceil
    steps = num_groups * num_stages * num_rounds + num_stages - 1
    fn = jax.checkpoint(chunk_fn) if remat else chunk_fn
    # act shape from the REAL dtypes (before any fp32 boundary cast)
    act_shape = jax.eval_shape(enter_fn, shared_params, tokens[0])

    # XLA-CPU workaround: shard_map's transpose psums the SHARED params'
    # gradients over pipe (they enter replicated), and the CPU backend
    # CHECK-fails promoting that half-precision all-reduce ("Invalid
    # binary instruction opcode copy"). Route shared params through an
    # fp32 boundary — the transpose psum then runs fp32 — and cast back
    # to the compute dtype inside, so ALL compute (and the activation
    # ppermute, which the CPU backend handles fine in bf16) keeps the
    # real dtypes. TPU/GPU take the direct path.
    _half = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))
    cast_boundary = (jax.default_backend() == "cpu" and any(
        jnp.dtype(leaf.dtype) in _half
        for leaf in jax.tree.leaves(shared_params)
        if hasattr(leaf, "dtype")))
    if cast_boundary:
        shared_dtypes = jax.tree.map(lambda l: l.dtype, shared_params)
        shared_params = jax.tree.map(
            lambda l: l.astype(jnp.float32)
            if jnp.dtype(l.dtype) in _half else l, shared_params)

        def _restore_shared(shared):
            # order matters: mark the fp32 leaves VARYING first, THEN
            # cast to the compute dtype. The grad psum is inserted at
            # the pvary transpose — done this way it reduces the fp32
            # cotangent; cast-first would put the bf16 all-reduce right
            # back (psum_invariant on the bf16 value, the instruction
            # the CPU compiler CHECK-fails on)
            shared = jax.tree.map(lambda l: _varying(l, axis), shared)
            return jax.tree.map(lambda l, d: l.astype(d), shared,
                                shared_dtypes)
    else:
        def _restore_shared(shared):
            return shared

    micro = tokens.shape[1]
    fwd_perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def body(chunk_params, shared, tokens, targets):
        shared = _restore_shared(shared)
        # chunk leaves arrive (C, 1, layers_per_chunk, ...): drop the
        # sharded stage dim
        local_chunks = jax.tree.map(lambda p: p[:, 0], chunk_params)
        stage = lax.axis_index(axis)
        S, C, M = num_stages, num_rounds, num_micro

        def step(carry, t):
            act, loss_rows, aux_acc = carry
            ts = t - stage
            # the activation arriving here was injected at stage 0 at
            # step ts − r·S; see the schedule proof in the docstring
            r = jnp.clip((ts // S) % C, 0, C - 1)
            m = (ts // (S * C)) * S + ts % S
            valid = jnp.logical_and(ts >= 0, m < M)
            m_safe = jnp.clip(m, 0, M - 1)

            def fresh(_):
                tok = lax.dynamic_index_in_dim(tokens, m_safe, 0,
                                               keepdims=False)
                return _varying(enter_fn(shared, tok).astype(act.dtype),
                                axis)

            # SPMD uniformity allows lax.cond only on stage-INDEPENDENT
            # predicates (every device must take the same branch — see
            # the docstring's deadlock note). Enter is needed only when
            # stage 0's round index (t // S) % C is 0, and that is a
            # function of t alone — so for C > 1 the cond skips the
            # enter body entirely on C−1 of C step-groups, on every
            # device, instead of computing-and-discarding it each step.
            enter_round = ((t // S) % C == 0) if C > 1 else True

            def enter_true(act):
                return jnp.where(jnp.logical_and(stage == 0, r == 0),
                                 fresh(None), act)

            if C > 1:
                x = lax.cond(enter_round, enter_true, lambda a: a, act)
            else:
                x = enter_true(act)
            params_r = jax.tree.map(
                lambda p: lax.dynamic_index_in_dim(p, r, 0,
                                                   keepdims=False),
                local_chunks)
            if chunk_has_aux:
                y, aux = fn(params_r, x)
                aux_acc = aux_acc + jnp.where(
                    valid, aux.astype(jnp.float32), 0.0)
            else:
                y = fn(params_r, x)

            def take_loss(_):
                tgt = lax.dynamic_index_in_dim(targets, m_safe, 0,
                                               keepdims=False)
                return _varying(
                    exit_fn(shared, y, tgt).astype(jnp.float32), axis)

            do_loss = jnp.logical_and(
                jnp.logical_and(stage == S - 1, r == C - 1), valid)

            def exit_true(loss_rows):
                return loss_rows + jnp.where(do_loss, take_loss(None),
                                             0.0)

            # Same uniform-cond trick for the exit: the last stage holds
            # a final-round activation only at steps with
            # ((t−S+1) // S) % C == C−1 — again a function of t alone.
            # For C > 1 this cuts the exit body (norm + head matmul +
            # loss — the waste the docstring prices at
            # V/(V + 12·H·layers_per_chunk) of a step) to 1/C of the
            # steps.
            if C > 1:
                exit_round = jnp.logical_and(
                    t >= S - 1, ((t - (S - 1)) // S) % C == C - 1)
                loss_rows = lax.cond(exit_round, exit_true,
                                     lambda lr: lr, loss_rows)
            else:
                loss_rows = exit_true(loss_rows)
            act = lax.ppermute(y, axis, fwd_perm)
            return (act, loss_rows, aux_acc), None

        act0 = _varying(jnp.zeros(act_shape.shape, act_shape.dtype), axis)
        loss0 = _varying(jnp.zeros((micro,), jnp.float32), axis)
        aux0 = _varying(jnp.zeros((), jnp.float32), axis)
        carry0 = (act0, loss0, aux0)
        if activation_groups and steps > activation_groups:
            # 1F1B-style memory profile WITHOUT changing the schedule
            # (reference analog: PiPPy's 1F1B bounds live microbatch
            # activations to ~num_stages,
            # distributed_pippy_compiler.py:378). The step scan's
            # linearization residuals grow O(steps) ~ O(M); grouping
            # the scan into checkpointed windows of `activation_groups`
            # (= num_stages) steps stores only the carry at group
            # boundaries and recomputes one group at a time in the
            # backward — live residuals bound to one group (~S
            # microbatches in flight), bubble unchanged, at the
            # standard one-extra-forward remat cost.
            pad_steps = (-steps) % activation_groups
            ts = jnp.arange(steps + pad_steps)  # padded tail: valid=False
            groups = ts.reshape(-1, activation_groups)

            @jax.checkpoint
            def group_body(carry, ts_g):
                return lax.scan(step, carry, ts_g)

            (_, loss_rows, aux_acc), _ = lax.scan(group_body, carry0,
                                                  groups)
        else:
            (_, loss_rows, aux_acc), _ = lax.scan(step, carry0,
                                                  jnp.arange(steps))
        # only the last stage accumulated anything; reductions (pipe
        # psum here, row mean outside) stay OUT of the cond branches
        return lax.psum(loss_rows, axis), lax.psum(aux_acc, axis)

    params_spec = jax.tree.map(lambda _: P(None, axis), chunk_params)
    rep = jax.tree.map(lambda _: P(), shared_params)
    piped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(params_spec, rep, P(), P()),
        out_specs=(P(), P()),
        axis_names=frozenset({axis}),
    )
    loss_rows, aux_total = piped(chunk_params, shared_params, tokens,
                                 targets)
    # mean over all M·micro rows; the cross-replica reduce of the row
    # mean happens here, outside the pipeline scan. Aux losses: each
    # (chunk, microbatch) contributed once → microbatch mean matches the
    # dense objective's per-batch aux sum.
    loss = jnp.mean(loss_rows) / num_micro
    if chunk_has_aux:
        loss = loss + aux_total / num_micro
    return loss


def stack_stage_params(per_stage_params) -> Any:
    """[stage0_tree, stage1_tree, ...] → one tree with leading stage dim."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves),
                        *per_stage_params)


def sequential_oracle(stage_fn, per_stage_params, inputs) -> jax.Array:
    """Reference semantics: every microbatch through every stage in
    order (what the pipeline must equal)."""
    outs = []
    for i in range(inputs.shape[0]):
        x = inputs[i]
        for params in per_stage_params:
            x = stage_fn(params, x)
        outs.append(x)
    return jnp.stack(outs)
