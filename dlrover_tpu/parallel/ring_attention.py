"""Sequence-parallel attention: ring attention + Ulysses all-to-all.

Capability parity: atorch DistributedSelfAttention
(atorch/modules/distributed_transformer/distributed_attention.py:21-115 —
seq-sharded K/V, micro-chunked Q all-gather, distributed online softmax via
global max/sum all-reduce, reduce-scatter of context, dual-stream overlap).

TPU re-design: the sequence dim is a mesh axis under `shard_map`.
- `ring_attention`: K/V blocks rotate around the ring with `ppermute`
  while each device keeps its Q shard; softmax is accumulated online
  (running max/sum) — numerically identical to blockwise/flash attention.
  Communication rides the ICI ring; compute of block i overlaps the
  permute of block i+1 because XLA schedules the independent DMA and
  matmul concurrently (the role of the reference's dual CUDA streams).
- `ulysses_attention`: `all_to_all` re-shards sequence→heads so every
  device runs dense attention on full sequences for its head group, then
  re-shards back (head-parallel SP; absent in the reference snapshot —
  noted in SURVEY.md §2.4).

The einsum paths are pure jax.lax collectives (autodiff derives the
backward; ppermute/all_to_all have transpose rules). The TPU-default
flash paths are NOT: the ring's is a custom VJP over Pallas kernels
(forward-mode AD unsupported there), and Ulysses calls the flash
kernel's own custom VJP.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.common.constants import MeshAxis
from dlrover_tpu.ops.backend import on_tpu

_NEG_INF = -1e30


def _use_flash_blocks(block_impl: str) -> bool:
    """Per-device attention kernel dispatch shared by ring and Ulysses:
    "auto" = flash kernel on TPU, einsum elsewhere.
    $DLROVER_TPU_SP_BLOCK_IMPL overrides "auto" (tests force the flash
    path through the model-level product dispatch in interpret mode)."""
    import os

    if block_impl == "auto":
        # deliberate trace-time read: kernel dispatch is a per-lowering
        # decision and must re-resolve on every elastic re-trace
        env = "DLROVER_TPU_SP_BLOCK_IMPL"
        block_impl = os.environ.get(env, "auto")  # graftlint: disable=GL102
    block_impl = block_impl.strip().lower()
    if block_impl not in ("auto", "flash", "einsum"):
        raise ValueError(
            f"unknown SP block impl {block_impl!r}: "
            "expected auto | flash | einsum")
    return block_impl == "flash" or (
        block_impl == "auto" and on_tpu())


def _block_attn(q, k, v, scale, mask):
    """One Q-shard × KV-block: returns (unnorm_out, block_max, block_sum).

    q: (B, Lq, H, D), k/v: (B, Lk, H, D) (GQA callers repeat KV heads to H
    before this), mask: (Lq, Lk) additive or None.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = s + mask[None, None, :, :]
    m = jnp.max(s, axis=-1)                          # (B, H, Lq)
    # guard fully-masked rows (causal first block): exp(-inf - -inf)
    m_safe = jnp.maximum(m, _NEG_INF / 2)
    p = jnp.exp(s - m_safe[..., None])               # (B, H, Lq, Lk)
    l = jnp.sum(p, axis=-1)                          # (B, H, Lq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m_safe, l


def _online_merge(o, m, l, o_new, m_new, l_new):
    """Merge a new block into the running (o, m, l) accumulators."""
    m_next = jnp.maximum(m, m_new)
    alpha = jnp.exp(m - m_next)          # rescale old
    beta = jnp.exp(m_new - m_next)       # rescale new
    l_next = l * alpha + l_new * beta
    o_next = (o * alpha[..., None].transpose(0, 2, 1, 3)
              + o_new * beta[..., None].transpose(0, 2, 1, 3))
    return o_next, m_next, l_next


def _ring_attn_local(q, k, v, *, axis_name: str, causal: bool,
                     scale: float, block_impl: str = "auto"):
    """Per-device body under shard_map. q: (B, L_local, H, D); k/v may
    carry fewer (GQA) heads — only the small KV shards rotate around the
    ring; the head replication happens locally per block (einsum path)
    or inside the kernel's GQA index maps (flash path), so ppermute
    traffic is not multiplied by the group count.

    block_impl: "auto" (flash kernel on TPU, einsum elsewhere) |
    "flash" | "einsum"."""
    if _use_flash_blocks(block_impl):
        # kernel layout (B, H, L, D); custom-VJP ring-flash path
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        out = _ring_flash_local(qt, kt, vt, axis_name, causal, scale)
        return out.transpose(0, 2, 1, 3)
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    batch, l_local, heads, dim = q.shape
    groups = heads // k.shape[2]

    q32 = q.astype(jnp.float32)

    diag_mask = jnp.where(
        jnp.arange(l_local)[None, :] > jnp.arange(l_local)[:, None],
        _NEG_INF, 0.0).astype(jnp.float32)

    def step(carry, i):
        o, m, l, k_blk, v_blk = carry
        kv_idx = (my_idx - i) % axis_size
        if groups > 1:
            k_rep = jnp.repeat(k_blk, groups, axis=2)
            v_rep = jnp.repeat(v_blk, groups, axis=2)
        else:
            k_rep, v_rep = k_blk, v_blk

        def merge(mask):
            o_new, m_new, l_new = _block_attn(q32, k_rep, v_rep, scale,
                                              mask)
            return _online_merge(o, m, l, o_new, m_new, l_new)

        if causal:
            # Three block kinds per step: diagonal (causal mask), fully
            # visible past block (no mask), fully masked future block
            # (skipped — its softmax weight is exactly zero). The switch
            # predicate varies per device, which is fine here: this
            # shard_map is fully manual, so the branches are pure local
            # compute with no collectives to diverge on. Skipping future
            # blocks halves the causal ring's compute.
            branch = jnp.where(kv_idx == my_idx, 0,
                               jnp.where(kv_idx < my_idx, 1, 2))
            o, m, l = lax.switch(branch, [
                lambda _: merge(diag_mask),
                lambda _: merge(None),
                lambda _: (o, m, l),
            ], None)
        else:
            o, m, l = merge(None)
        # rotate K/V to the next device; the permute of step i+1 overlaps
        # this step's matmuls (independent DMA)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (o, m, l, k_blk, v_blk), None

    o0 = jnp.zeros((batch, l_local, heads, dim), jnp.float32)
    m0 = jnp.full((batch, heads, l_local), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((batch, heads, l_local), jnp.float32)
    (o, m, l, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(axis_size))
    denominator = l[..., None].transpose(0, 2, 1, 3)
    out = o / jnp.maximum(denominator, 1e-20)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Ring attention on the flash kernel (MXU-rate blocks, O(L_local) memory)
# ---------------------------------------------------------------------------
#
# The einsum ring above materializes (L_local × L_local) block scores; the
# flash path runs the Pallas kernel per visiting KV block and merges the
# NORMALIZED per-block outputs via their logsumexp — the standard
# ring-flash construction. Autodiff cannot see through pallas_call, so the
# backward is a custom VJP: a second ring pass where each visiting KV
# block's (dk, dv) accumulator travels around the ring WITH the block and
# arrives home after S rotations; per-block grads come from the flash
# backward kernels evaluated with the FINAL global lse (which makes each
# block's softmax weights exact).


def _merge_normalized(o, lse, o_b, lse_b):
    """Merge (normalized out, lse) accumulators; -inf lse = empty."""
    lse_n = jnp.logaddexp(lse, lse_b)
    w_old = jnp.where(jnp.isfinite(lse), jnp.exp(lse - lse_n), 0.0)
    w_new = jnp.where(jnp.isfinite(lse_b), jnp.exp(lse_b - lse_n), 0.0)
    return o * w_old + o_b * w_new, lse_n


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale):
    """q (B,H,L,D), k/v (B,KV,L,D) kernel layout; returns (out, lse)."""
    from dlrover_tpu.ops.flash_attention import _flash_fwd

    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    fwd_perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    batch, heads, l_local, dim = q.shape

    def block(flag):
        def run(kv):
            from dlrover_tpu.ops.flash_attention import (
                DEFAULT_BLOCK_K,
                DEFAULT_BLOCK_Q,
            )

            o_b, lse_b = _flash_fwd(q, kv[0], kv[1], scale, flag,
                                    DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
            return o_b.astype(jnp.float32), lse_b

        return run

    def step(carry, i):
        o, lse, kb, vb = carry
        kv_idx = (my_idx - i) % axis_size
        if causal:
            branch = jnp.where(kv_idx == my_idx, 0,
                               jnp.where(kv_idx < my_idx, 1, 2))
            o_b, lse_b = lax.switch(branch, [
                block(True),            # diagonal: causal mask
                block(False),           # fully visible past block
                lambda kv: (jnp.zeros(q.shape, jnp.float32),
                            jnp.full((batch, heads, l_local, 1),
                                     -jnp.inf, jnp.float32)),
            ], (kb, vb))
        else:
            o_b, lse_b = block(False)((kb, vb))
        o, lse = _merge_normalized(o, lse, o_b, lse_b)
        kb = lax.ppermute(kb, axis_name, fwd_perm)
        vb = lax.ppermute(vb, axis_name, fwd_perm)
        return (o, lse, kb, vb), None

    o0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full((batch, heads, l_local, 1), -jnp.inf, jnp.float32)
    (o, lse, _, _), _ = lax.scan(step, (o0, lse0, k, v),
                                 jnp.arange(axis_size))
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash_local(q, k, v, axis_name, causal, scale):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, scale):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, res, g):
    from dlrover_tpu.ops.flash_attention import _flash_bwd

    q, k, v, out, lse = res
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    fwd_perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    # step-invariant: rowsum(dO·O), computed once for the whole ring
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    def block(flag):
        def run(kv):
            from dlrover_tpu.ops.flash_attention import (
                DEFAULT_BLOCK_K,
                DEFAULT_BLOCK_Q,
            )

            dq_b, dk_b, dv_b = _flash_bwd(
                (q, kv[0], kv[1], out, lse), g, sm_scale=scale,
                causal=flag, block_q=DEFAULT_BLOCK_Q,
                block_k=DEFAULT_BLOCK_K, delta=delta)
            return (dq_b.astype(jnp.float32), dk_b.astype(jnp.float32),
                    dv_b.astype(jnp.float32))

        return run

    def zeros(kv):
        return (jnp.zeros(q.shape, jnp.float32),
                jnp.zeros(k.shape, jnp.float32),
                jnp.zeros(v.shape, jnp.float32))

    def step(carry, i):
        dq, kb, vb, dkb, dvb = carry
        kv_idx = (my_idx - i) % axis_size
        if causal:
            branch = jnp.where(kv_idx == my_idx, 0,
                               jnp.where(kv_idx < my_idx, 1, 2))
            dq_b, dk_b, dv_b = lax.switch(
                branch, [block(True), block(False), zeros], (kb, vb))
        else:
            dq_b, dk_b, dv_b = block(False)((kb, vb))
        dq = dq + dq_b
        dkb = dkb + dk_b
        dvb = dvb + dv_b
        # the (dk, dv) accumulators travel WITH their kv block; after
        # axis_size rotations both are back at the block's owner
        kb = lax.ppermute(kb, axis_name, fwd_perm)
        vb = lax.ppermute(vb, axis_name, fwd_perm)
        dkb = lax.ppermute(dkb, axis_name, fwd_perm)
        dvb = lax.ppermute(dvb, axis_name, fwd_perm)
        return (dq, kb, vb, dkb, dvb), None

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v, dk0, dv0), jnp.arange(axis_size))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash_local.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = MeshAxis.SEQUENCE,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    batch_axes=(MeshAxis.DATA, MeshAxis.FSDP),
    head_axis: Optional[str] = MeshAxis.TENSOR,
    block_impl: str = "auto",
) -> jax.Array:
    """Full-array API: q (B, S, H, D), k/v (B, S, KV, D) with KV ≤ H (GQA),
    all sharded S over `axis`; returns the attention output with q's
    sharding. Composes with tensor parallelism (heads over `head_axis`)
    in one shard_map. block_impl selects the per-block kernel ("auto":
    flash on TPU, einsum elsewhere)."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    spec = P(batch_axes, axis, head_axis, None)
    fn = jax.shard_map(
        functools.partial(_ring_attn_local, axis_name=axis, causal=causal,
                          scale=scale, block_impl=block_impl),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all head parallelism)
# ---------------------------------------------------------------------------


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, scale: float,
                   block_impl: str = "auto"):
    """Per-device body: (B, L_local, H, D) → all_to_all → full-seq
    attention on H/axis_size heads → all_to_all back.

    GQA: when the KV head count divides the axis size, the SMALL k/v
    arrays ride the all_to_all and heads are replicated after (ICI moves
    KV-sized bytes, not H-sized); otherwise KV is replicated up front.

    The per-device attention is the Pallas flash kernel on TPU (O(L)
    memory, MXU-rate blocks; GQA handled by the kernel's head grouping)
    and the plain blockwise einsum elsewhere — `block_impl` forces one
    ("flash" | "einsum") for tests."""
    from dlrover_tpu.ops.flash_attention import flash_attention

    use_flash = _use_flash_blocks(block_impl)
    axis_size = lax.psum(1, axis_name)

    def seq_to_heads(x):
        # (B, L_local, H, D) → (B, L_full, H_local, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    if k.shape[2] % axis_size:
        groups = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    q_full = seq_to_heads(q)
    k_full = seq_to_heads(k)
    v_full = seq_to_heads(v)
    if use_flash:
        # (B, L, H, D) → kernel layout (B, H, L, D); GQA head grouping
        # happens inside the kernel's index maps — local q head j maps
        # to local kv head j // rep, matching the einsum path's repeat
        qt, kt, vt = (t.transpose(0, 2, 1, 3)
                      for t in (q_full, k_full, v_full))
        out = flash_attention(qt, kt, vt, causal, sm_scale=scale)
        return heads_to_seq(out.transpose(0, 2, 1, 3))
    rep = q_full.shape[2] // k_full.shape[2]
    if rep > 1:
        # local q heads j map to local kv head j // rep — the same
        # assignment as a global pre-split repeat, since contiguous head
        # blocks land on each device
        k_full = jnp.repeat(k_full, rep, axis=2)
        v_full = jnp.repeat(v_full, rep, axis=2)
    l_full = q_full.shape[1]
    mask = None
    if causal:
        pos = jnp.arange(l_full)
        mask = jnp.where(pos[None, :] > pos[:, None], _NEG_INF,
                         0.0).astype(jnp.float32)
    o, m, l = _block_attn(q_full.astype(jnp.float32), k_full, v_full,
                          scale, mask)
    out = o / jnp.maximum(l[..., None].transpose(0, 2, 1, 3), 1e-20)
    return heads_to_seq(out.astype(q.dtype))


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = MeshAxis.SEQUENCE,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    batch_axes=(MeshAxis.DATA, MeshAxis.FSDP),
    head_axis: Optional[str] = None,
    block_impl: str = "auto",
) -> jax.Array:
    """All-to-all sequence parallelism. q (B, S, H, D), k/v may carry
    fewer (GQA) heads. Lower latency than the ring for moderate sequence
    lengths: 2 all-to-alls instead of axis_size permutes. With
    `head_axis` (tensor parallelism) the per-device head group is divided
    again by the sequence axis, composing SP × TP in one shard_map.
    block_impl: per-device attention kernel — "auto" (flash on TPU,
    einsum elsewhere) | "flash" | "einsum"."""
    heads = q.shape[2]
    axis_size = mesh.shape[axis]
    tensor_size = mesh.shape[head_axis] if head_axis else 1
    if heads % (axis_size * tensor_size):
        raise ValueError(
            f"{heads} heads not divisible by sequence axis {axis_size}"
            + (f" × tensor axis {tensor_size}" if tensor_size > 1 else ""))
    if head_axis and k.shape[2] % tensor_size:
        raise ValueError(
            f"{k.shape[2]} kv heads not divisible by tensor axis "
            f"{tensor_size}")
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    spec = P(batch_axes, axis, head_axis, None)
    fn = jax.shard_map(
        functools.partial(_ulysses_local, axis_name=axis, causal=causal,
                          scale=scale, block_impl=block_impl),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
