"""Pallas TPU flash attention with custom VJP.

TPU-native equivalent of the reference's flash-attention integration
(atorch/atorch/modules/transformer/layers.py:740-1279 binds CUDA flash-attn
into BERT/LLaMA/GLM blocks) — re-designed as a blockwise online-softmax
kernel for the MXU instead of a CUDA binding:

- forward: grid (batch, heads, q_blocks, kv_blocks); the kv axis is the
  innermost (sequential on TPU), accumulating (acc, row-max m, row-sum l) in
  VMEM scratch.
- causal: blocks above the diagonal are skipped whole (their DMA too, by
  the index maps); inside a block ON the diagonal the kernels compute only
  the sub-tiles at or below it, at a grain of 128 rows (`causal_plan`): the
  block is cut into strips of q rows, strip j against k columns
  [0, (j+1)*128) alone. At seq 2048 that is 2.125 of 4 blocks' worth of
  scores instead of 3; nothing above a strip's last 128 columns is
  computed, then masked, any more.
- block sizes default to 1024x1024 (v5e-tuned: 92 TF/s fwd vs 11 at
  128x128; capped by seq len so small shapes still work).
- backward: two kernels — dq accumulates over kv blocks; dk/dv accumulate
  over q blocks — using the saved logsumexp and delta = rowsum(dO*O).
- GQA: kv heads are indexed as h // (num_q_heads // num_kv_heads) directly
  in the BlockSpec index maps; no materialized head broadcast.
- each kernel is traced and lowered once per signature, not once per call
  site: `_flash_fwd_call` / `_flash_bwd_call` are jitted, so a 24-layer
  step program's text holds 3 flash kernels, not 72.

MXU matmuls run in the input dtype (bf16 at full rate) with fp32
accumulation via `preferred_element_type` — FlashAttention-2 numerics; the
softmax statistics are always fp32. On non-TPU backends the kernels run in
Pallas interpret mode, so tests validate the same code path on the virtual
CPU platform.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.backend import on_tpu

NEG_INF = -1e30

# exp2-domain softmax: fold log2(e) into the score scale so every
# transcendental in the kernels is a bare exp2 (TPU lowers exp via exp2
# anyway; doing it explicitly saves the per-element argument multiply).
# The SAVED logsumexp stays in natural-log units — ring attention
# (parallel/ring_attention.py) merges lse across ring steps with
# natural exp/log.
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453

# v5e-tuned default block sizes (92 TF/s fwd vs 11 at 128×128); capped by
# the actual sequence length via fit_block. Shared with the ring-flash
# path (parallel/ring_attention.py).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024

# Sub-tile causal skipping inside a block on the diagonal: the grain is a
# multiple of the lane width (a slice of scores must be), and a block is
# cut into at most MAX_STRIPS strips, each one more body in the kernel's
# text (what every launch traces, lowers and compiles). PIPE_DEPTH: how
# many strips' score matmuls are issued ahead of a strip's softmax
# (`_pipelined`). All three chosen on the v5e (PERF.md, PR 30).
SUBTILE = 128
MAX_STRIPS = 8
PIPE_DEPTH = 2

# Grid axes (batch, heads, outer-block) are independent; the innermost
# axis carries the VMEM accumulators and must stay sequential.
_DIM_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))

# Kernel names: each becomes its custom-call's HLO instruction name
# (`%flash_attn_fwd.3 = ... custom-call(...)`) and so the start of the
# profiler's event name. The trace readers (benchmarks/metrics/
# kernels.flash_attn_*_roofline.py) find the kernels by these prefixes:
# a contract (docs/observability.md), not a label to reword.
KERNEL_FWD = "flash_attn_fwd"
KERNEL_DQ = "flash_attn_dq"
KERNEL_DKV = "flash_attn_dkv"
# the same three kernels given a selection mask (`mask=`): attention over
# the keys an indexer selected (ops/sparse_attention_kernels.py)
KERNEL_SPARSE_FWD = "sparse_attn_fwd"
KERNEL_SPARSE_DQ = "sparse_attn_dq"
KERNEL_SPARSE_DKV = "sparse_attn_dkv"
# ... and given a selection of key blocks for each row (`blocks=`):
# attention over the blocks InfLLM-v2 chose (ops/block_sparse_attention.py)
KERNEL_BLOCK_FWD = "block_sparse_attn_fwd"
KERNEL_BLOCK_DQ = "block_sparse_attn_dq"
KERNEL_BLOCK_DKV = "block_sparse_attn_dkv"


def _vma(*arrays) -> frozenset:
    """Union of the inputs' varying-manual-axes: under a check_vma
    shard_map (e.g. the pipeline's manual `pipe` axis) pallas_call
    outputs must declare how they vary."""
    u: frozenset = frozenset()
    for a in arrays:
        u = u | jax.typeof(a).vma
    return u


def in_manual_region(*arrays) -> bool:
    """True when tracing inside a shard_map already (the pipeline's
    pipe-manual stages, the train step's manual grad-reduce axis): the
    operands are the caller's per-shard blocks, and a nested full-mesh
    shard_map cannot be traced there. The abstract mesh's manual axes
    cover regions traced with check_vma=False, whose avals carry no
    vma."""
    return bool(_vma(*arrays)
                or jax.sharding.get_abstract_mesh().manual_axes)


def _sds(shape, dtype, vma):
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def fit_block(n: int, block: int) -> int:
    """Largest divisor of n that is <= block.

    Pallas pads out-of-bounds block rows with undefined data on real TPU
    (interpret mode zero-pads, so CPU tests can't catch it); requiring the
    block to divide the dimension keeps every block fully in-bounds.
    Prefers multiples of 128 (lane width) when one divides n.
    """
    block = min(block, n)
    aligned = (block // 128) * 128
    while aligned >= 128:
        if n % aligned == 0:
            return aligned
        aligned -= 128
    for b in range(block, 0, -1):
        if n % b == 0:
            return b
    return n


# ===========================================================================
# Causal skipping: whole blocks above the diagonal, and sub-tiles above it
# inside a block that straddles it
# ===========================================================================


class CausalPlan(NamedTuple):
    """What a causal call does, from its shapes alone (`causal_plan`)."""
    block_q: int            # fitted blocks
    block_k: int
    grain: int              # sub-tile grain of a straddling block; 0 = whole
    computed_share: float   # share of the seq_q x seq_k scores computed


def _grain(block_q: int, block_k: int) -> int:
    """Grain of the strips a straddling block is cut into: the finest
    multiple of the lane width that splits a square block into 2 to
    MAX_STRIPS strips; 0 (no sub-tiling) for unequal or short blocks."""
    if block_q != block_k:
        return 0
    for g in range(SUBTILE, block_q // 2 + 1, SUBTILE):
        if block_q % g == 0 and block_q // g <= MAX_STRIPS:
            return g
    return 0


class _Tile(NamedTuple):
    """q rows [r0, r0 + rn) of a (q, k) block against its k columns
    [c0, c0 + cn); a block's tiling is a tuple of these."""
    r0: int
    rn: int
    c0: int
    cn: int
    mask: int

    @property
    def rows(self) -> slice:
        return slice(self.r0, self.r0 + self.rn)

    @property
    def cols(self) -> slice:
        return slice(self.c0, self.c0 + self.cn)


_UNMASKED = 0
_BY_POSITION = 1    # mask by the block's place in the sequence (dynamic)
_ON_DIAGONAL = 2    # the block's own diagonal is the sequence's (static)


def _whole(block_q: int, block_k: int, mask: int) -> tuple:
    return (_Tile(0, block_q, 0, block_k, mask),)


def _straddling(block_q: int, block_k: int) -> tuple:
    """Tiling of a block that straddles the diagonal. With a grain
    (`_grain`): strips of q rows, strip j, rows [j*g, (j+1)*g), against
    the k columns [0, (j+1)*g) alone; equal blocks straddle the diagonal
    only where q_start == k_start, so the block's own diagonal is the
    sequence's and the mask is static. All three kernels cut the block
    this way (dK/dV adds a strip's share into the first (j+1)*g rows of
    its accumulators). One tile a strip: on the chip a strip split into
    an unmasked part and the g x g square on the diagonal was slower than
    one matmul over both, masked; largest strip first is the order the
    forward runs fastest in (PERF.md, PR 30). Without a grain: the whole
    block, masked by position, as before."""
    grain = _grain(block_q, block_k)
    if not grain:
        return _whole(block_q, block_k, _BY_POSITION)
    return tuple(_Tile(lo, grain, 0, lo + grain, _ON_DIAGONAL)
                 for lo in range(block_q - grain, -1, -grain))


def causal_plan(seq_q: int, seq_k: int,
                block_q: int = DEFAULT_BLOCK_Q,
                block_k: int = DEFAULT_BLOCK_K) -> CausalPlan:
    """The path a causal call takes and the share of the score area its
    kernels compute (the rest is skipped, not masked), counted over the
    tilings the kernels run. A pure function of the shapes: the
    mechanism's engagement counter, static per shape. At seq 2048 with
    1024-blocks and grain 128: (1 + 2 * 36/64) / 4."""
    block_q = fit_block(seq_q, block_q)
    block_k = fit_block(seq_k, block_k)
    straddling = sum(t.rn * t.cn for t in _straddling(block_q, block_k))
    computed = 0
    for q_start in range(0, seq_q, block_q):
        for k_start in range(0, seq_k, block_k):
            needed, full = _needed_full(q_start, k_start, block_q, block_k)
            if needed:
                computed += block_q * block_k if full else straddling
    return CausalPlan(block_q, block_k, _grain(block_q, block_k),
                      computed / (seq_q * seq_k))


def _needed_full(q_start, k_start, block_q: int, block_k: int):
    """Where a (q, k) block pair lies: (not entirely above the diagonal,
    entirely at or below it). Python ints or traced grid positions."""
    return (k_start <= q_start + block_q - 1,
            k_start + block_k - 1 <= q_start)


def _dispatch(scores, update, causal: bool, q_start, k_start,
              block_q: int, block_k: int, live=None) -> None:
    """Run a kernel's two stages (`_pipelined`) over one (q, k) block
    pair. Causal: skip blocks entirely above the diagonal, run blocks
    entirely below it whole and unmasked, and in a block that straddles
    it compute only the strips' tiles (`_straddling`): what lies above
    the diagonal beyond a strip's last g columns is never computed.
    Static per-block skip is impossible (q_start/k_start are dynamic over
    the grid), so dispatch with pl.when. Shared by the forward and both
    backward kernels so the boundary conditions cannot drift apart.
    `live` (a traced bool, causal calls only): the block pair holds a
    selected key of some row; where it is false the pair is skipped too."""
    whole = _whole(block_q, block_k, _UNMASKED)
    if not causal:
        _pipelined(whole, scores, update)
        return
    needed, full = _needed_full(q_start, k_start, block_q, block_k)
    if live is not None:
        needed = jnp.logical_and(needed, live)
    pl.when(jnp.logical_and(needed, full))(
        lambda: _pipelined(whole, scores, update))
    pl.when(jnp.logical_and(needed, jnp.logical_not(full)))(
        lambda: _pipelined(_straddling(block_q, block_k), scores, update))


def _pipelined(tiling, scores, update) -> None:
    """Each tile in two stages, `update(tile, scores(tile))`, issued as a
    software pipeline: the score matmuls of the next PIPE_DEPTH tiles come
    before a tile's update in program order. The MXU takes matmuls in
    program order, so without this it waits out every strip's softmax
    before the next strip's first matmul (on the chip the forward was
    slower in 8 strips than whole; PERF.md, PR 30). A whole block is one
    tile: stage one, then stage two, as before."""
    pending = []
    for tile in tiling:
        pending.append((tile, scores(tile)))
        if len(pending) > PIPE_DEPTH:
            update(*pending.pop(0))
    for item in pending:
        update(*item)


def _masked(s, tile: _Tile, q_start, k_start):
    """A tile's scores with those above the diagonal at NEG_INF."""
    if tile.mask == _UNMASKED:
        return s
    r0, c0 = tile.r0, tile.c0
    if tile.mask == _BY_POSITION:
        r0, c0 = q_start + r0, k_start + c0
    q_idx = r0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_idx = c0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_idx >= k_idx, s, NEG_INF)


# A selection applies in every computed tile, beside the causal mask: the
# kernels take it as `select(s, tile)`, which sets the scores of the keys it
# leaves out to NEG_INF, and a block pair's `live` for `_dispatch`.


def _with_mask(kernel, operands: int):
    """`kernel` for a call whose operand after the first `operands` is a
    (1, block_q, block_k) int8 block of the selection mask, nonzero =
    attended."""
    def masked(*refs, **static):
        mask_ref = refs[operands]

        def select(s, tile: _Tile):
            return jnp.where(mask_ref[0, tile.rows, tile.cols] != 0, s,
                             NEG_INF)

        return kernel(*refs[:operands], *refs[operands + 1:],
                      select=select, **static)
    return masked


class Blocks(NamedTuple):
    """A selection of key blocks for each row, as the kernels take it
    (`ops/block_sparse_attention.py:kernel_operands` makes it): `bits`
    (batch, groups, seq_q, seq_k // block_k) int32, bit j of word [b, g,
    t, c] set where row t of kv group g selected the j-th `size`-key block
    of kv tile c; `visit` (batch x groups x q tiles x kv tiles,) int32,
    nonzero where some row of the q tile selected a block of the kv tile
    (scalar-prefetched: the kernels skip the pair where it is 0)."""
    bits: jax.Array
    visit: jax.Array
    size: int


def _with_blocks(kernel, operands: int, *, groups: int, group: int,
                 num_q_blocks: int, num_k_blocks: int, size: int,
                 kv_major: bool):
    """`kernel` for a call whose first operand is `Blocks.visit`
    (scalar-prefetched) and whose operand after the next `operands` is the
    (1, 1, block_q, kv tiles) block of `Blocks.bits`. `kv_major`: the
    grid runs (b, h, kv tile, q tile), as dK/dV's does."""
    def blocked(visit_ref, *refs, **static):
        bits_ref = refs[operands]
        b, h = pl.program_id(0), pl.program_id(1)
        qi, ki = pl.program_id(2), pl.program_id(3)
        if kv_major:
            qi, ki = ki, qi
        live = visit_ref[((b * groups + h // group) * num_q_blocks + qi)
                         * num_k_blocks + ki] != 0

        def select(s, tile: _Tile):
            words = bits_ref[0, 0, tile.rows, :]
            lane = jax.lax.broadcasted_iota(jnp.int32, words.shape, 1)
            # the kv tile's word of each row; below 2^24, exact in float32
            word = jnp.sum(jnp.where(lane == ki, words, 0).astype(
                jnp.float32), axis=1, keepdims=True).astype(jnp.int32)
            block = (tile.c0 + jax.lax.broadcasted_iota(
                jnp.int32, (1, tile.cn), 1)) // size
            chosen = jnp.right_shift(word, block) & 1
            return jnp.where(chosen != 0, s, NEG_INF)

        return kernel(*refs[:operands], *refs[operands + 1:],
                      select=select, live=live, **static)
    return blocked


def _pallas(kernel, *, grid, in_specs, out_specs, out_shape, scratch_shapes,
            name: str, interpret: bool, prefetch=None):
    """`pl.pallas_call` with the flash kernels' grid semantics; with
    `prefetch`, an int32 array scalar-prefetched ahead of the operands
    (every index map then takes it after the grid's indices)."""
    if prefetch is None:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch_shapes,
            compiler_params=_DIM_SEMANTICS, interpret=interpret, name=name)
    call = pl.pallas_call(
        kernel, grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        out_shape=out_shape, compiler_params=_DIM_SEMANTICS,
        interpret=interpret, name=name)
    return lambda *operands: call(prefetch, *operands)


# ===========================================================================
# Forward kernel
# ===========================================================================


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, sm_scale: float, causal: bool,
                block_q: int, block_k: int, num_k_blocks: int,
                select=None, live=None):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    qi = pl.program_id(2)
    q_start = qi * block_q
    k_start = ki * block_k

    # Inputs stay in their native dtype (bf16) so the MXU runs at full
    # rate; accumulation is fp32 via preferred_element_type (the
    # FlashAttention-2 numerics). fp32 operands pass through unchanged.
    def _scores(tile):
        q = q_ref[0, 0, tile.rows]
        k = k_ref[0, 0, tile.cols]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * (
            sm_scale * LOG2E)
        s = _masked(s, tile, q_start, k_start)
        return s if select is None else select(s, tile)

    def _update(tile, s):
        # one online-softmax update of the tile's rows
        rows = tile.rows
        v = v_ref[0, 0, tile.cols]
        m_prev = m_ref[rows]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp2(s - m_new)
        if select is not None:
            # a row may have no selected key yet (under the causal mask
            # alone key 0 serves every row): NEG_INF - NEG_INF is 0, not
            # -inf, so its masked scores must not count as exp2(0)
            p = jnp.where(s > 0.5 * NEG_INF, p, 0.0)
        alpha = jnp.exp2(m_prev - m_new)
        l_ref[rows] = l_ref[rows] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[rows] = m_new

    # Causal: blocks below the diagonal run whole and unmasked; a block on
    # it (2 of the 3 computed at seq 2048; 8 of 36 at 8k) computes only
    # the sub-tiles its strips meet, 36 of 64 at grain 128.
    _dispatch(_scores, _update, causal, q_start, k_start, block_q, block_k,
              live)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[:] + jnp.log2(l_safe)) * LN2


def _flash_fwd(q, k, v, sm_scale: float, causal: bool,
               block_q: int, block_k: int, mask=None, blocks=None):
    """(out, lse) of one forward launch; blocks are fitted here, so that
    every request for the same fitted blocks shares one traced kernel.
    `mask` (batch, seq_q, seq_k) int8, nonzero = attended: the softmax
    runs over those keys alone (every head the same), under the kernels'
    `sparse_attn_*` names. `blocks` (`Blocks`, made for the same fitted
    blocks): over the key blocks each row of each kv group selected, under
    the `block_sparse_attn_*` names; causal calls only."""
    return _flash_fwd_call(
        q, k, v, mask, _arrays_of(blocks), sm_scale=sm_scale, causal=causal,
        size=0 if blocks is None else blocks.size,
        block_q=fit_block(q.shape[2], block_q),
        block_k=fit_block(k.shape[2], block_k),
        interpret=not on_tpu())


def _arrays_of(blocks):
    """`Blocks` as a jitted call takes it: its arrays (its size goes
    apart, static)."""
    return None if blocks is None else (blocks.bits, blocks.visit)


def _kernel_of(kernel, operands: int, mask, blocks, size: int,
               names: tuple, *, kv_major: bool, q_shape, k_shape,
               block_q: int, block_k: int):
    """(kernel, its name, the prefetched operand) of a call with no
    selection, a mask or blocks: `names` in that order."""
    if mask is not None:
        return _with_mask(kernel, operands), names[1], None
    if blocks is None:
        return kernel, names[0], None
    return _with_blocks(
        kernel, operands, groups=k_shape[1], group=q_shape[1] // k_shape[1],
        num_q_blocks=_cdiv(q_shape[2], block_q),
        num_k_blocks=_cdiv(k_shape[2], block_k), size=size,
        kv_major=kv_major), names[2], blocks[1]


# Under jit with everything but the arrays static: a model calls this once
# per layer, and JAX traces and lowers a jitted function once per
# signature, so the step program's text holds one forward kernel, not one
# per layer (the backward pair likewise). `interpret` is an argument, not
# read inside, because the trace is cached across backends.
@functools.partial(jax.jit, static_argnames=(
    "size", "sm_scale", "causal", "block_q", "block_k", "interpret"))
def _flash_fwd_call(q, k, v, mask=None, blocks=None, *, sm_scale: float,
                    causal: bool, block_q: int, block_k: int,
                    interpret: bool, size: int = 0):
    batch, num_heads, seq_q, head_dim = q.shape
    _, num_kv_heads, seq_k, _ = k.shape
    group = num_heads // num_kv_heads
    num_q_blocks = _cdiv(seq_q, block_q)
    num_k_blocks = _cdiv(seq_k, block_k)

    grid = (batch, num_heads, num_q_blocks, num_k_blocks)

    # every index map takes `*_`: the prefetched operand, where there is one
    def q_map(b, h, qi, ki, *_):
        return (b, h, qi, 0)

    def kv_map(b, h, qi, ki, *_):
        if causal:
            # Blocks above the diagonal are skipped by the kernel; map
            # their kv index to the last needed block so consecutive
            # grid steps see the same index and Pallas elides the DMA.
            ki = jnp.minimum(ki, ((qi + 1) * block_q - 1) // block_k)
        return (b, h // group, ki, 0)

    def o_map(b, h, qi, ki, *_):
        return (b, h, qi, 0)

    kernel, name, prefetch = _kernel_of(
        _fwd_kernel, 3, mask, blocks, size,
        (KERNEL_FWD, KERNEL_SPARSE_FWD, KERNEL_BLOCK_FWD), kv_major=False,
        q_shape=q.shape, k_shape=k.shape, block_q=block_q, block_k=block_k)
    kernel = functools.partial(
        kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k_blocks=num_k_blocks,
    )
    operands, masked = (q, k, v), []
    if mask is not None:
        operands += (mask,)
        masked = [pl.BlockSpec(
            (1, block_q, block_k),
            lambda b, h, qi, ki, *_: (b, qi, kv_map(b, h, qi, ki)[2]))]
    if blocks is not None:
        operands += (blocks[0],)
        masked = [pl.BlockSpec(
            (1, 1, block_q, blocks[0].shape[-1]),
            lambda b, h, qi, ki, *_: (b, h // group, qi, 0))]
    out, lse = _pallas(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim), q_map),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_map),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_map),
        ] + masked,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim), o_map),
            pl.BlockSpec((1, 1, block_q, 1), o_map),
        ],
        out_shape=[
            _sds(q.shape, q.dtype, _vma(q, k, v)),
            _sds((batch, num_heads, seq_q, 1), jnp.float32, _vma(q, k, v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        name=name, interpret=interpret, prefetch=prefetch,
    )(*operands)
    return out, lse


# ===========================================================================
# Backward kernels
# ===========================================================================


def _bwd_scores(tile, *, q_ref, k_ref, v_ref, do_ref, sm_scale: float,
                q_start, k_start, select=None):
    """Stage one of both backward kernels: a tile's scores (exp2 domain,
    masked) and dP = dO V^T, the matmuls that wait on nothing."""
    q = q_ref[0, 0, tile.rows]
    do = do_ref[0, 0, tile.rows]
    k = k_ref[0, 0, tile.cols]
    v = v_ref[0, 0, tile.cols]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * (
        sm_scale * LOG2E)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    s = _masked(s, tile, q_start, k_start)
    return (s if select is None else select(s, tile)), dp


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc_ref,
                   *, sm_scale: float, causal: bool,
                   block_q: int, block_k: int, num_k_blocks: int,
                   select=None, live=None):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    qi = pl.program_id(2)
    q_start = qi * block_q
    k_start = ki * block_k

    _scores = functools.partial(
        _bwd_scores, q_ref=q_ref, k_ref=k_ref, v_ref=v_ref, do_ref=do_ref,
        sm_scale=sm_scale, q_start=q_start, k_start=k_start,
        select=select)

    def _update(tile, scores):
        rows = tile.rows
        s, dp = scores
        k = k_ref[0, 0, tile.cols]
        lse = lse_ref[0, 0, rows] * LOG2E   # nat -> exp2 domain (per row)
        delta = delta_ref[0, 0, rows]
        p = jnp.exp2(s - lse)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        dq_acc_ref[rows] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    _dispatch(_scores, _update, causal, q_start, k_start, block_q, block_k,
              live)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, sm_scale: float, causal: bool,
                    block_q: int, block_k: int, num_q_blocks: int,
                    select=None, live=None):
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    ki = pl.program_id(2)
    q_start = qi * block_q
    k_start = ki * block_k

    _scores = functools.partial(
        _bwd_scores, q_ref=q_ref, k_ref=k_ref, v_ref=v_ref, do_ref=do_ref,
        sm_scale=sm_scale, q_start=q_start, k_start=k_start,
        select=select)

    def _update(tile, scores):
        rows, cols = tile.rows, tile.cols
        s, dp = scores
        q = q_ref[0, 0, rows]
        do = do_ref[0, 0, rows]
        lse = lse_ref[0, 0, rows] * LOG2E   # nat -> exp2 domain (per row)
        delta = delta_ref[0, 0, rows]
        p = jnp.exp2(s - lse)
        dv_acc_ref[cols] += jnp.dot(p.astype(do.dtype).T, do,
                                    preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_acc_ref[cols] += jnp.dot(ds.T, q,
                                    preferred_element_type=jnp.float32)

    # Causal: for a kv block, only q blocks at or below the diagonal
    # contribute; blocks strictly below it need no mask.
    _dispatch(_scores, _update, causal, q_start, k_start, block_q, block_k,
              live)

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _flash_bwd(res, g, *, sm_scale: float, causal: bool,
               block_q: int, block_k: int, delta=None, mask=None,
               blocks=None):
    """(dq, dk, dv) of one backward launch pair. delta = rowsum(dO·O) may
    be passed precomputed — ring callers invoke this once per visiting KV
    block with step-invariant dO/O. `mask`, `blocks`: the forward's
    (`_flash_fwd`)."""
    q, k = res[0], res[1]
    return _flash_bwd_call(
        res, g, delta, mask, _arrays_of(blocks), sm_scale=sm_scale,
        causal=causal, size=0 if blocks is None else blocks.size,
        block_q=fit_block(q.shape[2], block_q),
        block_k=fit_block(k.shape[2], block_k),
        interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=(
    "size", "sm_scale", "causal", "block_q", "block_k", "interpret"))
def _flash_bwd_call(res, do, delta, mask=None, blocks=None, *,
                    sm_scale: float, causal: bool, block_q: int,
                    block_k: int, interpret: bool, size: int = 0):
    q, k, v, out, lse = res
    batch, num_heads, seq_q, head_dim = q.shape
    _, num_kv_heads, seq_k, _ = k.shape
    group = num_heads // num_kv_heads
    num_q_blocks = _cdiv(seq_q, block_q)
    num_k_blocks = _cdiv(seq_k, block_k)

    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)  # (b, h, seq_q, 1)

    # every index map takes `*_`: the prefetched operand, where there is one
    def q_map(b, h, qi, ki, *_):
        return (b, h, qi, 0)

    def kv_map(b, h, qi, ki, *_):
        if causal:
            # dedupe the DMA of kv blocks above the diagonal (skipped by
            # the kernel): same trick as the forward's kv_map
            ki = jnp.minimum(ki, ((qi + 1) * block_q - 1) // block_k)
        return (b, h // group, ki, 0)

    def row_map(b, h, qi, ki, *_):
        return (b, h, qi, 0)

    def kernel_of(kernel, names, kv_major):
        return _kernel_of(kernel, 6, mask, blocks, size, names,
                          kv_major=kv_major, q_shape=q.shape,
                          k_shape=k.shape, block_q=block_q, block_k=block_k)

    # ---- dq: iterate kv blocks innermost -----------------------------
    dq_kernel, name, prefetch = kernel_of(
        _bwd_dq_kernel, (KERNEL_DQ, KERNEL_SPARSE_DQ, KERNEL_BLOCK_DQ),
        False)
    dq_kernel = functools.partial(
        dq_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k_blocks=num_k_blocks,
    )
    operands, masked = (q, k, v, do, lse, delta), []
    if mask is not None:
        operands += (mask,)
        masked = [pl.BlockSpec(
            (1, block_q, block_k),
            lambda b, h, qi, ki, *_: (b, qi, kv_map(b, h, qi, ki)[2]))]
    if blocks is not None:
        operands += (blocks[0],)
        masked = [pl.BlockSpec(
            (1, 1, block_q, blocks[0].shape[-1]),
            lambda b, h, qi, ki, *_: (b, h // group, qi, 0))]
    dq = _pallas(
        dq_kernel,
        grid=(batch, num_heads, num_q_blocks, num_k_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim), q_map),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_map),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_map),
            pl.BlockSpec((1, 1, block_q, head_dim), q_map),
            pl.BlockSpec((1, 1, block_q, 1), row_map),
            pl.BlockSpec((1, 1, block_q, 1), row_map),
        ] + masked,
        out_specs=pl.BlockSpec((1, 1, block_q, head_dim), q_map),
        out_shape=_sds(q.shape, q.dtype, _vma(q, k, v, do)),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        name=name, interpret=interpret, prefetch=prefetch,
    )(*operands)

    # ---- dk/dv: per q-head contributions, iterate q blocks innermost --
    # Grid runs over *query* heads so GQA contributions are disjoint per
    # (kv-head, group member); sum over the group afterwards.
    def kv_out_map(b, h, ki, qi, *_):
        return (b, h, ki, 0)

    if causal:
        # dedupe the DMA of q/do/lse/delta blocks strictly above the
        # diagonal (skipped by the kernel): clamp to the first
        # contributing q block for this kv block. The upper clamp keeps
        # the index in range when seq_k > seq_q (trailing kv blocks have
        # no contributing q block at all — the kernel skips them, but
        # the index map must still be in bounds: on real TPU an OOB
        # block DMAs undefined memory).
        def _qi_eff(ki, qi):
            return jnp.minimum(
                jnp.maximum(qi, (ki * block_k) // block_q),
                num_q_blocks - 1)
    else:
        def _qi_eff(ki, qi):
            return qi

    def q_map2(b, h, ki, qi, *_):
        return (b, h, _qi_eff(ki, qi), 0)

    def kv_map2(b, h, ki, qi, *_):
        return (b, h // group, ki, 0)

    def row_map2(b, h, ki, qi, *_):
        return (b, h, _qi_eff(ki, qi), 0)

    dkv_kernel, name, prefetch = kernel_of(
        _bwd_dkv_kernel, (KERNEL_DKV, KERNEL_SPARSE_DKV, KERNEL_BLOCK_DKV),
        True)
    dkv_kernel = functools.partial(
        dkv_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_q_blocks=num_q_blocks,
    )
    if mask is not None:
        masked = [pl.BlockSpec(
            (1, block_q, block_k),
            lambda b, h, ki, qi, *_: (b, _qi_eff(ki, qi), ki))]
    if blocks is not None:
        masked = [pl.BlockSpec(
            (1, 1, block_q, blocks[0].shape[-1]),
            lambda b, h, ki, qi, *_: (b, h // group, _qi_eff(ki, qi), 0))]
    dk_per_qh, dv_per_qh = _pallas(
        dkv_kernel,
        grid=(batch, num_heads, num_k_blocks, num_q_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim), q_map2),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_map2),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_map2),
            pl.BlockSpec((1, 1, block_q, head_dim), q_map2),
            pl.BlockSpec((1, 1, block_q, 1), row_map2),
            pl.BlockSpec((1, 1, block_q, 1), row_map2),
        ] + masked,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, head_dim), kv_out_map),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_out_map),
        ],
        out_shape=[
            _sds((batch, num_heads, seq_k, head_dim), q.dtype,
                 _vma(q, k, v, do)),
            _sds((batch, num_heads, seq_k, head_dim), q.dtype,
                 _vma(q, k, v, do)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
        ],
        name=name, interpret=interpret, prefetch=prefetch,
    )(*operands)

    if group > 1:
        dk = dk_per_qh.reshape(
            batch, num_kv_heads, group, seq_k, head_dim
        ).sum(axis=2).astype(k.dtype)
        dv = dv_per_qh.reshape(
            batch, num_kv_heads, group, seq_k, head_dim
        ).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_per_qh, dv_per_qh
    return dq, dk, dv


# ===========================================================================
# Public API
# ===========================================================================


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """Blockwise attention: softmax(q k^T / sqrt(d)) v.

    Args:
      q: (batch, num_heads, seq_q, head_dim)
      k/v: (batch, num_kv_heads, seq_k, head_dim); num_heads must be a
        multiple of num_kv_heads (GQA/MQA).
    """
    out, _ = _flash_fwd(q, k, v, _scale(sm_scale, q), causal,
                        block_q, block_k)
    return out


def _scale(sm_scale: Optional[float], q: jax.Array) -> float:
    return sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])


def _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, _scale(sm_scale, q), causal,
                          block_q, block_k)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, sm_scale, block_q, block_k, res, g):
    q = res[0]
    dq, dk, dv = _flash_bwd(res, g, sm_scale=_scale(sm_scale, q),
                            causal=causal, block_q=block_q, block_k=block_k)
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def reference_attention(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None) -> jax.Array:
    """Plain-XLA attention with identical semantics (test oracle and
    small-shape fallback)."""
    scale = _scale(sm_scale, q)
    num_heads, num_kv_heads = q.shape[1], k.shape[1]
    if num_kv_heads != num_heads:
        reps = num_heads // num_kv_heads
        k = jnp.repeat(k, reps, axis=1)
        v = jnp.repeat(v, reps, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        seq_q, seq_k = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((seq_q, seq_k), dtype=bool))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def mesh_flash_attention(q, k, v, causal: bool = True,
                         sm_scale: Optional[float] = None) -> jax.Array:
    """flash_attention partitioned over the ambient mesh.

    A Pallas kernel is a custom call the SPMD partitioner cannot split on
    real TPU, so under a multi-device mesh it must run inside a shard_map
    that makes the batch/head axes manual: batch over (data, fsdp), heads
    over tensor — each device runs the kernel on its local block. Falls
    back to the plain call when there is no ambient mesh (single chip),
    when no relevant axis is >1, or when the shapes don't divide (XLA
    then reports the partitioning failure loudly rather than silently
    replicating)."""
    from jax.sharding import PartitionSpec as P

    from dlrover_tpu.common.constants import MeshAxis
    from dlrover_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return flash_attention(q, k, v, causal, sm_scale)
    if in_manual_region(q, k, v):
        return flash_attention(q, k, v, causal, sm_scale)
    # foreign ambient meshes (no data/fsdp/tensor axes) fall through to
    # the plain call via the dp == tp == 1 check
    dp = (mesh.shape.get(MeshAxis.DATA, 1)
          * mesh.shape.get(MeshAxis.FSDP, 1))
    tp = mesh.shape.get(MeshAxis.TENSOR, 1)
    if dp == 1 and tp == 1:
        return flash_attention(q, k, v, causal, sm_scale)
    if (q.shape[0] % dp or q.shape[1] % tp or k.shape[1] % tp):
        return flash_attention(q, k, v, causal, sm_scale)
    spec = P((MeshAxis.DATA, MeshAxis.FSDP), MeshAxis.TENSOR, None, None)
    fn = jax.shard_map(
        lambda a, b, c: flash_attention(a, b, c, causal, sm_scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
