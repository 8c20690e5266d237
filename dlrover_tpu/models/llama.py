"""LLaMA-family model in flax.linen, TPU-first.

Capability parity: the reference accelerates LLaMA-style models via atorch
(LlamaAttentionFA atorch/modules/transformer/layers.py:1279; Megatron-style
col/row-parallel projections modules/distributed_modules/layers.py:239-670).
TPU re-design: one set of plain matmul modules whose parameters carry
*logical axis names* (`embed`, `heads`, `kv`, `head_dim`, `mlp`, `vocab`);
tensor/fsdp/sequence parallelism become sharding rules applied at jit time
(dlrover_tpu/parallel/sharding.py) instead of distinct module classes —
XLA inserts the collectives the Megatron classes perform by hand.

Attention runs through the Pallas flash kernel (dlrover_tpu/ops) or a plain
XLA path (`attn_impl="reference"`), selected per config.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.common.constants import TraceScope
from dlrover_tpu.ops.backend import on_tpu
from dlrover_tpu.ops.flash_attention import (
    mesh_flash_attention,
    reference_attention,
)
from dlrover_tpu.ops.norms import mesh_rms_norm, reference_rms_norm
from dlrover_tpu.ops.remat import resolve_remat_policy


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32   # master parameter dtype
    # "flash" (Pallas kernel) | "reference" (XLA) | "ring" (sequence-
    # parallel ppermute KV rotation) | "ulysses" (sequence-parallel
    # all-to-all head dispatch). ring/ulysses shard the sequence dim over
    # the mesh's `sequence` axis (parallel/ring_attention.py) and need the
    # ambient mesh build_trainer provides at trace time.
    attn_impl: str = "flash"
    # "onehot": iota/one-hot matmul lookup — SPMD-partitions as a plain
    # matmul, so the embedding-table gradient never hits the scatter path
    # that forces XLA into involuntary full rematerialization on a
    # (data, fsdp, tensor) mesh. "gather" is cheaper on a single chip.
    embed_impl: str = "onehot"
    norm_impl: str = "fused"         # "fused" (Pallas) | "reference" (XLA)
    remat: bool = False              # rematerialize each block
    # "full"/"nothing_saveable" | "dots"/"dots_saveable" |
    # "dots_with_no_batch_dims" | "kernel_outputs" |
    # "matmul_and_kernel_outputs" (ops/remat.py)
    remat_policy: str = "nothing_saveable"
    tie_embeddings: bool = False
    # a head's width where the model declares one of its own (q is then
    # num_heads x attn_head_dim wide, whatever the hidden size); 0: the
    # usual hidden_size // num_heads
    attn_head_dim: int = 0
    qk_norm: bool = False            # RMSNorm over each head of q and k
    embed_scale: float = 1.0         # the embedding's output times this

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_heads

    # ---- stock sizes -----------------------------------------------------
    @classmethod
    def llama_1b(cls, **kw) -> "LlamaConfig":
        return cls(hidden_size=2048, intermediate_size=5504, num_layers=22,
                   num_heads=16, num_kv_heads=16, **kw)

    @classmethod
    def llama_7b(cls, **kw) -> "LlamaConfig":
        return cls(hidden_size=4096, intermediate_size=11008,
                   num_layers=32, num_heads=32, num_kv_heads=32, **kw)

    @classmethod
    def llama_wide_1b(cls, **kw) -> "LlamaConfig":
        """Gemma-style wide-MLP variant (i/h = 4 instead of Llama's 2.7):
        1.47B parameters, 20 layers; the width ``chip_smoke.py`` drives."""
        return cls(hidden_size=2048, intermediate_size=8192,
                   num_layers=20, num_heads=16, num_kv_heads=16, **kw)

    @classmethod
    def llama_410m(cls, **kw) -> "LlamaConfig":
        return cls(hidden_size=1024, intermediate_size=2816, num_layers=24,
                   num_heads=8, num_kv_heads=8, **kw)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 128)
        return cls(hidden_size=64, intermediate_size=128, num_layers=2,
                   num_heads=4, num_kv_heads=2, rms_norm_eps=1e-5, **kw)

    def param_count(self) -> int:
        h, i, v, L = (self.hidden_size, self.intermediate_size,
                      self.vocab_size, self.num_layers)
        q = self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        per_layer = (
            h * q + 2 * h * kv + q * h      # q, k, v, o projections
            + 3 * h * i                      # gate, up, down
            + 2 * h                          # 2 rmsnorm scales
            + (2 * self.head_dim if self.qk_norm else 0)
        )
        emb = v * h * (1 if self.tie_embeddings else 2)
        return L * per_layer + emb + h


def _logical(init, *axes):
    return nn.with_logical_partitioning(init, axes)


def embed_lookup(embed: jax.Array, tokens: jax.Array, cfg: Any) -> jax.Array:
    """Token embedding lookup; see LlamaConfig.embed_impl. cfg only needs
    embed_impl / vocab_size / dtype (GPTConfig works too)."""
    if cfg.embed_impl == "onehot":
        onehot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cfg.dtype)
        return jnp.dot(onehot, embed.astype(cfg.dtype))
    return embed.astype(cfg.dtype)[tokens]


class RMSNorm(nn.Module):
    eps: float
    dtype: Any
    impl: str = "fused"

    @nn.compact
    def __call__(self, x):
        weight = self.param(
            "weight", _logical(nn.initializers.ones, "norm"), (x.shape[-1],)
        )
        # The fused kernel only on real TPU: off-TPU it would run in
        # Pallas interpret mode — slow, and its interpreter loop breaks
        # the vma typing inside partial-auto shard_map (pipeline stages)
        if self.impl == "fused" and on_tpu():
            return mesh_rms_norm(x, weight.astype(jnp.float32),
                                 self.eps).astype(self.dtype)
        return reference_rms_norm(x, weight.astype(jnp.float32),
                                  self.eps).astype(self.dtype)


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float) -> jax.Array:
    """Rotary embedding on (..., seq, num_heads, head_dim)."""
    head_dim = x.shape[-1]
    freq = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions[..., :, None].astype(jnp.float32) * freq  # (b, s, d/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def _sequence_parallel_mesh():
    """The ambient mesh when it has an active sequence axis, else None
    (→ the caller falls back to plain attention)."""
    from dlrover_tpu.common.constants import MeshAxis
    from dlrover_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.shape.get(MeshAxis.SEQUENCE, 1) == 1:
        return None
    return mesh


def _sequence_parallel_attention(impl, mesh, q, k, v, causal: bool = True):
    """Dispatch to ring/Ulysses attention on (b, seq, heads, dim) arrays;
    k/v carry the (smaller) GQA head count — the kernels replicate heads
    after sharding so only KV-sized bytes ride the ICI.

    Capability parity: atorch DistributedSelfAttention wired into the real
    transformer blocks (distributed_attention.py:21-115, commu_utils.py:6,47)
    — here the model reaches the sequence-parallel kernels directly via
    `attn_impl`, with the mesh taken from the ambient context that
    build_trainer establishes at trace time."""
    from dlrover_tpu.common.constants import MeshAxis
    from dlrover_tpu.parallel.ring_attention import (
        ring_attention,
        ulysses_attention,
    )

    head_axis = (MeshAxis.TENSOR
                 if mesh.shape.get(MeshAxis.TENSOR, 1) > 1 else None)
    if impl == "ulysses":
        return ulysses_attention(q, k, v, mesh, causal=causal,
                                 head_axis=head_axis)
    return ring_attention(q, k, v, mesh, causal=causal,
                          head_axis=head_axis)


def dispatch_attention(impl: str, q, k, v, causal: bool = True):
    """Shared attention dispatch for the model families (GPT, BERT, …):
    (b, seq, heads, dim) in and out, impl = flash | reference | ring |
    ulysses. The SP impls need an ambient mesh with an active `sequence`
    axis (build_trainer establishes it at trace time); off-mesh they fall
    back to the plain path so unit runs stay valid."""
    if impl in ("ring", "ulysses"):
        sp_mesh = _sequence_parallel_mesh()
        if sp_mesh is not None:
            return _sequence_parallel_attention(impl, sp_mesh, q, k, v,
                                                causal)
        impl = "reference"
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    if impl == "flash":
        out = mesh_flash_attention(qt, kt, vt, causal)
    else:
        out = reference_attention(qt, kt, vt, causal)
    return out.transpose(0, 2, 1, 3)


def project_qkv(cfg: LlamaConfig, x, tie, positions, rope: bool = True):
    """q, k, v as (batch, seq, heads, head_dim), q and k normed by head
    where the model says so (`qk_norm`) and rotated unless `rope` is
    False; called inside an attention module's compact `__call__`, whose
    submodules these are."""
    batch, seq, _ = x.shape
    dense = functools_partial_dense(cfg)
    q = dense("q_proj", (cfg.hidden_size, cfg.num_heads * cfg.head_dim),
              ("embed", "heads"))(x, tie)
    k = dense("k_proj", (cfg.hidden_size, cfg.num_kv_heads * cfg.head_dim),
              ("embed", "kv"))(x, tie)
    v = dense("v_proj", (cfg.hidden_size, cfg.num_kv_heads * cfg.head_dim),
              ("embed", "kv"))(x, tie)
    q = q.reshape(batch, seq, cfg.num_heads, cfg.head_dim)
    k = k.reshape(batch, seq, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(batch, seq, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl,
                    name="q_norm")(q)
        k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl,
                    name="k_norm")(k)
    if not rope:
        return q, k, v
    return (apply_rope(q, positions, cfg.rope_theta),
            apply_rope(k, positions, cfg.rope_theta), v)


class Attention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        batch, seq, _ = x.shape
        dense = functools_partial_dense(cfg)
        # the four weight gradients inside this module's backward
        x, tie = tie_weight_grads(x)
        q, k, v = project_qkv(cfg, x, tie, positions)
        impl = cfg.attn_impl
        sp_mesh = None
        if impl in ("ring", "ulysses"):
            sp_mesh = _sequence_parallel_mesh()
            if sp_mesh is None:
                # Off-mesh (unit runs) or no sequence axis: fall back to
                # the plain path below rather than a degenerate shard_map.
                impl = "reference"
        if sp_mesh is not None:
            out = _sequence_parallel_attention(impl, sp_mesh, q, k, v)
            out = out.reshape(batch, seq, -1)
        else:
            # (b, heads, seq, dim) layout for the kernel
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            if impl == "flash":
                out = mesh_flash_attention(q, k, v, True)
            else:
                out = reference_attention(q, k, v, True)
            out = out.transpose(0, 2, 1, 3).reshape(batch, seq, -1)
        return dense("o_proj",
                     (cfg.num_heads * cfg.head_dim, cfg.hidden_size),
                     ("heads", "embed"))(out, tie)


# -- each module's weight gradients inside that module's backward ----------
# Left alone, XLA puts every projection's weight gradient behind the WHOLE
# backward pass (nothing needs it before the optimizer; its scheduler walks
# back from the updated parameters in the order of their tree), so what
# each reads (gate, up, d h, the normed inputs, the attention output and
# its cotangent: ~235 MB a layer at hidden 2048 x 8192, batch 2 x 2048)
# lives until then, and a program that does not fit recomputes forward
# matmuls to make room (18 a step for 24 layers on a 16 GB chip; PERF.md
# section 6, PR 36). A module says "mine before you go on":
#
#     x, tie = tie_weight_grads(x)        # at the module's input
#     y = tied_dot(x, kernel, tie)        # every projection of the module
#
# and the cotangent of `x`, which the backward pass needs to go on to the
# layer before, is not formed until each of those weight gradients is.
# Order only, by a dependence on the data: no gradient is fenced, so a
# weight gradient still fuses with whatever reads it (the optimizer's sums),
# and inside the module the order stays XLA's. Once a projection (with or
# without `optimization_barrier`) is faster where the memory is short and
# 1 % slower where it is not; once a module does not lose there.


def _zero_tie(x):
    tie = jnp.zeros((), jnp.float32)
    # inside a check_vma shard_map (the pipeline's stages) the tie varies
    # over the manual axes as x does, as its cotangent, made of x, will
    manual = tuple(jax.typeof(x).vma)
    return jax.lax.pcast(tie, manual, to="varying") if manual else tie


@jax.custom_vjp
def tie_weight_grads(x: jax.Array):
    """(x, tie): `x` itself and a float32 zero for the module's `tied_dot`s
    to take. The backward rule scales the cotangent of `x` by 1 + the
    cotangent of `tie`, which is 0 times each taker's weight gradient."""
    return x, _zero_tie(x)


def _tie_weight_grads_fwd(x):
    return (x, _zero_tie(x)), None


def _tie_weight_grads_bwd(_, cotangents):
    dx, dtie = cotangents
    return (dx * (1 + dtie).astype(dx.dtype),)


tie_weight_grads.defvjp(_tie_weight_grads_fwd, _tie_weight_grads_bwd)


@jax.custom_vjp
def tied_dot(x: jax.Array, w: jax.Array, tie: jax.Array) -> jax.Array:
    """`jnp.dot(x, w)`, x (..., in) and w (in, out), whose backward rule
    forms both gradients as `jnp.dot`'s own transpose does (same
    contractions, same dtypes, the operands' dtype out of float32
    accumulation) and hands `tie` (from `tie_weight_grads`) a cotangent
    that is 0 and yet read off the weight gradient: 0 x sum(dW^2), the sum
    the optimizer's gradient norm takes anyway."""
    return jnp.dot(x, w)


def _tied_dot_fwd(x, w, tie):
    return jnp.dot(x, w), (x, w)


def _tied_dot_bwd(operands, g):
    x, w = operands
    rows = tuple(range(g.ndim - 1))
    dx = jax.lax.dot_general(g, w, (((g.ndim - 1,), (1,)), ((), ())),
                             preferred_element_type=g.dtype)
    dw = jax.lax.dot_general(g, x, ((rows, rows), ((), ())),
                             preferred_element_type=g.dtype).T
    # not a constant to XLA: 0 x inf is NaN, so the product stays, and a
    # gradient that is not finite has ended the run anyway
    return dx, dw, 0 * jnp.sum(jnp.square(dw.astype(jnp.float32)))


tied_dot.defvjp(_tied_dot_fwd, _tied_dot_bwd)


def functools_partial_dense(cfg: LlamaConfig):
    """A kernel-only linear with named logical axes; called with its
    input and the module's `tie` (`tie_weight_grads`), or with its input
    alone by a module that opens none and gets the plain product."""

    def make(name, shape, axes):
        class _Dense(nn.Module):
            @nn.compact
            def __call__(self, x, tie=None):
                kernel = self.param(
                    "kernel",
                    _logical(nn.initializers.normal(0.02), *axes),
                    shape, cfg.param_dtype,
                )
                if tie is None:
                    return jnp.dot(x, kernel.astype(cfg.dtype))
                return tied_dot(x, kernel.astype(cfg.dtype), tie)

        return _Dense(name=name)

    return make


class MLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = functools_partial_dense(cfg)
        # the three weight gradients inside this module's backward
        x, tie = tie_weight_grads(x)
        gate = dense("gate_proj", (cfg.hidden_size, cfg.intermediate_size),
                     ("embed", "mlp"))(x, tie)
        up = dense("up_proj", (cfg.hidden_size, cfg.intermediate_size),
                   ("embed", "mlp"))(x, tie)
        return dense("down_proj", (cfg.intermediate_size, cfg.hidden_size),
                     ("mlp", "embed"))(nn.silu(gate) * up, tie)


ACT_AXES = ("act_batch", "act_seq", "act_embed")


class DecoderBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        # pin the activation layout so SPMD never round-trips the
        # residual stream between layouts (constraint is a no-op off-mesh)
        x = nn.with_logical_constraint(x, ACT_AXES)
        x = x + Attention(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl, name="attn_norm")(x),
            positions,
        )
        x = nn.with_logical_constraint(x, ACT_AXES)
        x = x + MLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl, name="mlp_norm")(x)
        )
        return nn.with_logical_constraint(x, ACT_AXES)


@functools.lru_cache(maxsize=None)
def _recomputed(block_cls, policy: str):
    """One recomputed class for each block class and policy, so that the
    layers of one kind share it and trace as one."""
    return nn.remat(block_cls, static_argnums=(),
                    policy=resolve_remat_policy(policy))


class Llama(nn.Module):
    """Decoder-only LM. `__call__(tokens) -> logits`; `hidden_and_head`
    gives what the logits are made of, for a loss that never forms them
    whole (`head_cross_entropy`)."""

    config: LlamaConfig
    _BLOCK = DecoderBlock       # a model of other blocks names its own

    def block(self, layer: int) -> nn.Module:
        """Layer `layer`'s block, made inside `hidden_and_head`: `_BLOCK`
        for every layer. A model of layers of more than one kind picks by
        the layer."""
        return self.recomputed(self._BLOCK)(self.config,
                                            name=f"layer_{layer}")

    def recomputed(self, block_cls):
        """`block_cls` recomputed by block in the backward pass where the
        configuration says `remat`, under its `remat_policy`."""
        cfg = self.config
        if not cfg.remat:
            return block_cls
        return _recomputed(block_cls, cfg.remat_policy)

    @nn.compact
    def hidden_and_head(self, tokens: jax.Array):
        """(final hidden states (b, s, hidden), head matrix (hidden,
        vocab)), both in the compute dtype: `__call__` is their product.
        Tied embeddings give the transposed embedding."""
        cfg = self.config
        embed = self.param(
            "embed",
            _logical(nn.initializers.normal(0.02), "vocab", "embed"),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype,
        )
        with jax.named_scope(TraceScope.EMBED):
            x = embed_lookup(embed, tokens, cfg)
            if cfg.embed_scale != 1.0:
                x = x * cfg.embed_scale
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[-1]), tokens.shape)
        for layer in range(cfg.num_layers):
            x = self.block(layer)(x, positions)
        # one scope with head and loss (`__call__` and the losses below
        # open it again): final norm + head matmul + loss are one item
        # in a trace's account of the step
        with jax.named_scope(TraceScope.HEAD_LOSS):
            x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl,
                        name="final_norm")(x)
            if cfg.tie_embeddings:
                return x, embed.astype(cfg.dtype).T
            head = self.param(
                "lm_head",
                _logical(nn.initializers.normal(0.02), "embed", "vocab"),
                (cfg.hidden_size, cfg.vocab_size), cfg.param_dtype,
            )
            return x, head.astype(cfg.dtype)

    def __call__(self, tokens: jax.Array) -> jax.Array:
        x, head = self.hidden_and_head(tokens)
        with jax.named_scope(TraceScope.HEAD_LOSS):
            return jnp.dot(x, head).astype(jnp.float32)


def cross_entropy_loss(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token cross entropy; logits (b, s, v), targets (b, s)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# A slice's logits, in the dtype they are held in, may take this much.
# The chip chose it (PERF.md section 6, PR 34): one slice is fastest, and
# each further one costs a read and a write of the float32 accumulator of
# the head's gradient (the second 3.7 ms of a 28 ms head and loss at
# 2 x 2048 tokens x 92,544, 2.7 of 19 ms at x 32,000). So logits of that
# size (758 and 262 MB) go in one, and a sequence twice as long in two.
HEAD_LOSS_SLICE_BYTES = 1 << 30


def head_loss_slices(rows: int, seq_len: int, vocab: int,
                     itemsize: int) -> int:
    """How many slices of the sequence `head_cross_entropy` takes for
    `rows` sequences a device: the fewest that divide `seq_len` and keep a
    slice's logits within `HEAD_LOSS_SLICE_BYTES`."""
    for slices in range(1, seq_len):
        if seq_len % slices == 0 and (
                rows * (seq_len // slices) * vocab * itemsize
                <= HEAD_LOSS_SLICE_BYTES):
            return slices
    return seq_len


def _slice_loss_and_grads(x, head, targets, scale):
    """One slice: (summed nll, d loss / d x, d loss / d head in float32),
    the gradients already times `scale` (1 / tokens of the whole batch)."""
    logits = jnp.einsum("bsh,hv->bsv", x, head).astype(jnp.float32)
    top = jnp.max(logits, axis=-1, keepdims=True)
    unnormalised = jnp.exp(logits - top)
    total = jnp.sum(unnormalised, axis=-1, keepdims=True)
    # the target's column by comparison, not by gather: it fuses into the
    # passes that are made anyway (a gather kept 1.4 GB more on the chip)
    hit = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
           == targets[..., None])
    picked = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    nll = jnp.sum(top[..., 0] + jnp.log(total[..., 0]) - picked)
    dlogits = ((unnormalised / total - hit.astype(jnp.float32))
               * scale).astype(x.dtype)
    dx = jnp.einsum("bsv,hv->bsh", dlogits, head)
    dhead = jnp.einsum("bsh,bsv->hv", x, dlogits,
                       preferred_element_type=jnp.float32)
    return nll, dx, dhead


def _head_loss_and_grads(x, head, targets, slices):
    batch, seq_len, hidden = x.shape
    scale = 1.0 / (batch * seq_len)
    with jax.named_scope(TraceScope.HEAD_LOSS):
        if slices == 1:
            nll, dx, dhead = _slice_loss_and_grads(x, head, targets, scale)
        else:
            # slices of the SEQUENCE, so a batch sharded over data / fsdp
            # stays sharded; one traced body whatever their number
            def cut(a):
                return jnp.swapaxes(a.reshape(
                    batch, slices, seq_len // slices, *a.shape[2:]), 0, 1)

            def body(carry, slice_):
                nll, dx, dhead = _slice_loss_and_grads(
                    slice_[0], head, slice_[1], scale)
                return (carry[0] + nll, carry[1] + dhead), dx

            (nll, dhead), dx = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32),
                       jnp.zeros(head.shape, jnp.float32)),
                (cut(x), cut(targets)))
            dx = jnp.swapaxes(dx, 0, 1).reshape(batch, seq_len, hidden)
        # rounded once, where the logits path's weight-gradient matmul
        # rounds: after every slice is summed in float32
        return nll * scale, dx, dhead.astype(head.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def head_cross_entropy(x: jax.Array, head: jax.Array, targets: jax.Array,
                       slices: int = 1) -> jax.Array:
    """`cross_entropy_loss(jnp.dot(x, head), targets)` as ONE function
    that forms its gradients on the way forward: x (b, s, hidden) final
    hidden states, head (hidden, vocab), targets (b, s).

    The logits are computed a slice of the sequence at a time (`slices`
    divides s), in the operands' dtype with float32 accumulation as
    `jnp.dot` gives them; log-sum-exp and loss in float32. The same pass
    forms softmax - onehot and from it both gradients, so head-sized
    matmuls are three a step, not four, and what is kept for the backward
    is the two gradients: no (b, s, vocab) array outlives its slice. The
    backward rule only scales them by the incoming cotangent."""
    return _head_loss_and_grads(x, head, targets, slices)[0]


def _head_cross_entropy_fwd(x, head, targets, slices):
    loss, dx, dhead = _head_loss_and_grads(x, head, targets, slices)
    # Both gradients before anything that reads either. Left alone, XLA
    # puts off the head's (nothing needs it before the optimizer), lets
    # go of the logits meanwhile and runs their matmul a second time for
    # it (`fusion.N.remat`; PERF.md section 6, PR 34): the fourth
    # head-sized matmul this function exists to remove.
    dx, dhead = jax.lax.optimization_barrier((dx, dhead))
    return loss, (dx, dhead)


def _head_cross_entropy_bwd(slices, grads, cotangent):
    # traced apart from the scope around the call: open it again
    with jax.named_scope(TraceScope.HEAD_LOSS):
        dx, dhead = grads
        return (dx * cotangent.astype(dx.dtype),
                dhead * cotangent.astype(dhead.dtype), None)


head_cross_entropy.defvjp(_head_cross_entropy_fwd, _head_cross_entropy_bwd)
# how a trainer recognises softmax cross-entropy and reaches its fused
# form (trainer/train_step.py:build_trainer); a wrapped or other loss
# carries none and gets whole logits
cross_entropy_loss.from_hidden = head_cross_entropy
