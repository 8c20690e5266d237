"""Block-sparse attention after InfLLM-v2 (MiniCPM4 report, arXiv:2506.07900):
each query of each kv group attends the key blocks its group's heads point
to through compressed keys. No weights of its own, no objective: the
selection passes no gradient.

Selection (`select_blocks`, plain XLA under the `block_select` scope), for
query t and kv group g, with `Sparsity`'s constants:

- compressed keys c_i = mean of k over [stride i, stride i + kernel), those
  whose span ends at or before t;
- r[t, g, i] = sum over the query heads h of g of softmax_i(q[t, h] . c_i /
  sqrt(d));
- a block's score is the largest r over the c_i that overlap it;
- the `topk` blocks of highest score, the first `init_blocks` and the last
  `window // block` (the one holding t among them) forced in, none after t;
  ties to the lower block. While t has no more than `topk` causal blocks
  every one is chosen, and a sequence shorter than `dense_len` is attended
  whole.

The result is a (b, G, S, S / block) int8 block mask: 8.4 MB at 2 groups and
16,384 rows of 64-key blocks.

Attention (`attend`) is causal softmax attention over the chosen blocks' keys.
Two forms (`impl`):

- ``"xla"``: whole (S, S) scores under the mask expanded to keys. The test
  oracle and the path off the TPU.
- ``"kernel"``: the flash kernels of `ops/flash_attention.py` given the
  mask as `Blocks` (a word of bits a row and kv tile, and a table of the
  block pairs some row selected), under the names `block_sparse_attn_fwd`,
  `_dq` and `_dkv`: a tile expands its rows' bits to keys in VMEM and a kv
  tile no row of the q tile selected is skipped. Within the tiles it visits
  it computes every causal pair and masks what was not chosen.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.common.constants import TraceScope
from dlrover_tpu.ops.backend import on_tpu
from dlrover_tpu.ops.flash_attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    NEG_INF,
    Blocks,
    _flash_bwd,
    _flash_fwd,
    fit_block,
)
from dlrover_tpu.ops.remat import Kept
from dlrover_tpu.ops.sparse_attention_kernels import _keep_rows

SELECT_ROWS = 1024      # queries whose scores against every c_i sit at once
MAX_BITS = 24           # blocks a kv tile: a row's word is exact in float32


@dataclasses.dataclass(frozen=True)
class Sparsity:
    """InfLLM-v2's constants (MiniCPM4's published `sparse_config`)."""
    block: int = 64          # keys a block
    topk: int = 64           # blocks a query attends
    kernel: int = 32         # keys a compressed key averages ...
    stride: int = 16         # ... every `stride` keys
    init_blocks: int = 1     # the first blocks, always chosen
    window: int = 2048       # the last keys' blocks, always chosen
    dense_len: int = 8192    # a shorter sequence is attended whole

    def __post_init__(self):
        if self.block % self.stride or self.kernel % self.stride:
            raise ValueError("block and kernel must be multiples of stride")


def resolve_impl(impl: str) -> str:
    """``auto``: the kernels on the TPU, plain XLA elsewhere."""
    if impl == "auto":
        return "kernel" if on_tpu() else "xla"
    if impl not in ("kernel", "xla"):
        raise ValueError(f"unknown block-sparse attention impl {impl!r}")
    return impl


# ===========================================================================
# Selection
# ===========================================================================


def compressed_keys(k, sp: Sparsity):
    """(b, G, n, d) float32: the mean of each `kernel` keys every `stride`,
    from k (b, G, S, d); S a multiple of `stride`."""
    b, groups, seq, d = k.shape
    pieces = jnp.mean(k.astype(jnp.float32).reshape(
        b, groups, seq // sp.stride, sp.stride, d), axis=3)
    span = sp.kernel // sp.stride
    n = seq // sp.stride - span + 1
    return sum(pieces[:, :, j:j + n] for j in range(span)) / span


def _block_scores(r, sp: Sparsity, blocks: int):
    """(..., blocks): the largest of the r over the compressed keys that
    overlap each block; r (..., n) >= 0, 0 where no key counts."""
    ratio = sp.block // sp.stride
    first = -((sp.kernel - 1) // sp.stride)     # c_i from ratio j + first ...
    last = (sp.block - 1) // sp.stride          # ... to ratio j + last
    width = ratio * (blocks - 1) + last - first + 1
    pad = [(0, 0)] * (r.ndim - 1) + [(-first, max(0, width + first
                                                   - r.shape[-1]))]
    r = jnp.pad(r, pad)
    return functools.reduce(jnp.maximum, (
        r[..., off - first::ratio][..., :blocks]
        for off in range(first, last + 1)))


def _top_blocks(score, topk: int):
    """bool: the `topk` largest of `score` on its last axis, ties to the
    lower index (the keys above the topk-th value that `lax.top_k` finds,
    and of those equal to it the lowest-placed that fill the set)."""
    least = jax.lax.top_k(score, topk)[0][..., -1:]
    above, level = score > least, score == least
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return above | (level & (jnp.cumsum(level, axis=-1) <= room))


def _select_rows(q, ck, first, sp: Sparsity, blocks: int):
    """(b, G, rows, blocks) bool for the queries first ... of q (b, G, R,
    rows, d) against the compressed keys ck (b, G, n, d)."""
    rows, d = q.shape[3], q.shape[4]
    t = first + jnp.arange(rows)[:, None]                  # (rows, 1)
    exact = q.dtype == jnp.float32
    s = jnp.einsum("bgrqd,bgcd->bgrqc", q, ck.astype(q.dtype),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST if exact else None)
    counts = (sp.stride * jnp.arange(ck.shape[2]) + sp.kernel - 1) <= t
    s = jnp.where(counts, s * d ** -0.5, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    score = _block_scores(jnp.sum(p, axis=2), sp, blocks)
    j = jnp.arange(blocks)
    own = t // sp.block
    causal = j <= own
    forced = (j < sp.init_blocks) | (j > own - sp.window // sp.block)
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(causal, score, -jnp.inf)
    return _top_blocks(score, min(sp.topk, blocks)) & causal


def select_blocks(q, k, sp: Sparsity):
    """(b, G, S, S / block) int8, 1 where the row's kv group chose the
    block; q (b, H, S, d), k (b, G, S, d), S a multiple of `block`."""
    b, heads, seq, d = q.shape
    groups = k.shape[1]
    blocks = seq // sp.block
    causal = (jnp.arange(blocks)[None, :]
              <= jnp.arange(seq)[:, None] // sp.block)
    if seq < sp.dense_len or blocks <= sp.topk:
        return jnp.broadcast_to(causal, (b, groups, seq, blocks)).astype(
            jnp.int8)
    ck = compressed_keys(k, sp)
    q = q.reshape(b, groups, heads // groups, seq, d)
    rows = fit_block(seq, SELECT_ROWS)
    if rows == seq:
        return _select_rows(q, ck, 0, sp, blocks).astype(jnp.int8)
    cut = jnp.moveaxis(q.reshape(b, groups, heads // groups, seq // rows,
                                 rows, d), 3, 0)
    chosen = jax.lax.map(
        lambda c: _select_rows(c[1], ck, c[0], sp, blocks).astype(jnp.int8),
        (jnp.arange(0, seq, rows), cut))
    return jnp.moveaxis(chosen, 0, 2).reshape(b, groups, seq, blocks)


# ===========================================================================
# Attention over the chosen blocks
# ===========================================================================


def _tiles(seq: int, size: int) -> tuple:
    """(q tile, kv tile) of the kernels at this sequence length."""
    block_k = fit_block(seq, DEFAULT_BLOCK_K)
    if block_k % size or block_k // size > MAX_BITS:
        raise ValueError(f"a kv tile of {block_k} keys holds no whole "
                         f"number of {size}-key blocks within {MAX_BITS}")
    return fit_block(seq, DEFAULT_BLOCK_Q), block_k


def kernel_operands(mask, size: int) -> Blocks:
    """`Blocks` of the block mask (b, G, S, S / size) for the kernels'
    tiles."""
    b, groups, seq, blocks = mask.shape
    block_q, block_k = _tiles(seq, size)
    per = block_k // size
    shifts = jnp.arange(per, dtype=jnp.int32)
    bits = jnp.sum(mask.reshape(b, groups, seq, blocks // per, per).astype(
        jnp.int32) << shifts, axis=-1)
    visit = jnp.any(bits.reshape(b, groups, seq // block_q, block_q, -1)
                    != 0, axis=3)
    return Blocks(bits, visit.astype(jnp.int32).reshape(-1), size)


def tiles_visited_share(mask, size: int):
    """The kernels' (q tile, kv tile) pairs visited over the causal pairs:
    a float32 scalar, from the block mask alone."""
    seq = mask.shape[2]
    block_q, block_k = _tiles(seq, size)
    visited = jnp.sum(kernel_operands(mask, size).visit)
    causal = sum(min(((q + 1) * block_q - 1) // block_k + 1, seq // block_k)
                 for q in range(seq // block_q))
    return visited.astype(jnp.float32) / (mask.shape[0] * mask.shape[1]
                                          * causal)


def _xla_attend(q, k, v, mask, size: int, sm_scale: float):
    b, heads, seq, d = q.shape
    groups = k.shape[1]
    keys = jnp.repeat(mask != 0, size, axis=-1)            # (b, G, S, S)
    keys = keys & jnp.tril(jnp.ones((seq, seq), bool))
    exact = q.dtype == jnp.float32
    qg = q.reshape(b, groups, heads // groups, seq, d)
    s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST if exact else None)
    s = jnp.where(keys[:, :, None], s * sm_scale, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST if exact else None)
    return checkpoint_name(out.reshape(b, heads, seq, d).astype(q.dtype),
                           Kept.BLOCK_SPARSE)


def _forward(q, k, v, bits, visit, sm_scale: float, size: int):
    return _flash_fwd(q, k, v, sm_scale, True, DEFAULT_BLOCK_Q,
                      DEFAULT_BLOCK_K, blocks=Blocks(bits, visit, size))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel_attend(q, k, v, bits, visit, sm_scale: float, size: int):
    return _forward(q, k, v, bits, visit, sm_scale, size)[0]


def _kernel_attend_fwd(q, k, v, bits, visit, sm_scale, size):
    # tagged here, where they are outputs and residuals at once: a block's
    # recomputation that keeps them (`ops/remat.py`) drops the kernel
    out, lse = _forward(q, k, v, bits, visit, sm_scale, size)
    out = checkpoint_name(out, Kept.BLOCK_SPARSE)
    lse = _keep_rows(lse, Kept.BLOCK_SPARSE)
    return out, (q, k, v, out, lse, bits, visit)


def _kernel_attend_bwd(sm_scale, size, res, g):
    *res, bits, visit = res
    # traced apart from the scope around the call: open it again
    with jax.named_scope(TraceScope.BLOCK_SPARSE_ATTN):
        dq, dk, dv = _flash_bwd(
            tuple(res), g, sm_scale=sm_scale, causal=True,
            block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
            blocks=Blocks(bits, visit, size))
    return dq, dk, dv, None, None


_kernel_attend.defvjp(_kernel_attend_fwd, _kernel_attend_bwd)


def attend(q, k, v, mask, size: int, sm_scale=None, impl: str = "auto"):
    """Causal attention of q (b, H, S, d) over the keys of k, v (b, G, S, d)
    in the `size`-key blocks `mask` (b, G, S, S / size) chose for each row
    of each kv group."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if resolve_impl(impl) == "xla":
        return _xla_attend(q, k, v, mask, size, sm_scale)
    blocks = kernel_operands(mask, size)
    return _kernel_attend(q, k, v, blocks.bits, blocks.visit, sm_scale, size)


def block_sparse_attention(q, k, v, sp: Sparsity, impl: str = "auto"):
    """(out (b, H, S, d), the share of the kernels' causal tile pairs the
    selection visits): InfLLM-v2's selection from q and k, detached, then
    attention over the chosen blocks. Both under scopes of their own; the
    block mask is tagged for a block's recomputation to keep."""
    detach = jax.lax.stop_gradient
    with jax.named_scope(TraceScope.BLOCK_SELECT):
        mask = checkpoint_name(select_blocks(detach(q), detach(k), sp),
                               Kept.BLOCKS)
        share = tiles_visited_share(mask, sp.block)
    with jax.named_scope(TraceScope.BLOCK_SPARSE_ATTN):
        out = attend(q, k, v, mask, sp.block, impl=impl)
    return out, share
