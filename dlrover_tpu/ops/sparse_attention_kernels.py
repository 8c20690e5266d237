"""The Pallas form of `ops/sparse_attention.py`: selection by a learned
indexer, attention over the selected keys, the indexer's KL objective and
its gradient. The module docstring there says what each kernel does; the
names below are a contract with the trace readers (docs/observability.md).

Layouts: q (b, H, S, d), k and v (b, G, S, d) as the flash kernels take
them; the indexer's qi (b, J, S, D) by head, ki (b, S, D), w (b, S, J)
float32. The mask is (b, S, S) int8 and holds the causal limit too.

Exactness of the selection. A row's scores become int32 keys whose signed
order is the floats' (`_to_key`); the topk-th largest key T is built bit by
bit from the top, one compare-and-count pass a bit: the largest T with
count(key >= T) >= topk. Keys above T are selected; of the keys equal to T
the `topk - count(key > T)` of lowest position, by a second bisection on the
position (what `jax.lax.top_k` does with ties).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common.constants import TraceScope
from dlrover_tpu.ops.backend import on_tpu
from dlrover_tpu.ops.flash_attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    LOG2E,
    NEG_INF,
    _flash_bwd,
    _flash_fwd,
    _sds,
    _vma,
    fit_block,
)
from dlrover_tpu.ops.remat import Kept

KERNEL_SELECT = "indexer_select"
KERNEL_KL = "indexer_kl"

INT_MIN = -(2 ** 31)
FLIP = 0x7FFFFFFF

SELECT_BLOCK_Q = 128      # rows whose scores sit in VMEM at once (x seq x 4 B)
SELECT_BLOCK_K = 2048     # keys a pass takes at a time
KL_BLOCK = 1024
KL_ROWS = 128             # a block's rows whose index scores VMEM keeps at once
VMEM_LIMIT = 100 * 1024 * 1024


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _to_key(scores):
    """float32 -> int32 whose signed order is the floats'."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits >= 0, bits, bits ^ FLIP)


def _from_key(key):
    return jax.lax.bitcast_convert_type(
        jnp.where(key >= 0, key, key ^ FLIP), jnp.float32)


def _dot(a, b, dims):
    """`dot_general` with float32 accumulation, in the operands' own
    precision: bfloat16 operands take the MXU's one pass, float32 ones (a
    float32 model's) are not rounded to it on the way in."""
    exact = a.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if exact else None)


def _scores_of(q, k):
    """q k^T for q (rows, D), k (cols, D)."""
    return _dot(q, k, ((1,), (1,)))


def _index_block(q_of, w, k, heads: int, scores=None):
    """I[rows, cols] = sum_j w[:, j] relu(qi_j k^T): `q_of(j)` (rows, D) and
    k (cols, D) in the indexer's dtype, float32 accumulation; w (rows, J).
    `scores`, a (J, rows, cols) float32 ref, keeps each head's qi_j k^T."""
    acc = None
    for j in range(heads):
        s = _scores_of(q_of(j), k)
        if scores is not None:
            scores[j] = s
        term = w[:, j:j + 1] * jnp.maximum(s, 0.0)
        acc = term if acc is None else acc + term
    return acc


# ===========================================================================
# indexer_select: scores, exact top-k threshold, mask and log-sum-exp
# ===========================================================================


def _select_kernel(qi_ref, w_ref, ki_ref, mask_ref, lse_ref,
                   key_ref, thr_ref, pos_ref,
                   *, topk: int, block_q: int, block_k: int, heads: int,
                   seq: int, pos_bits: int):
    row0 = pl.program_id(1) * block_q
    # key blocks that hold a key at or before the block's last row
    live = (row0 + block_q + block_k - 1) // block_k
    w = w_ref[0]
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def cols_of(kb):
        return kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)

    def at(kb):
        return pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)

    def score(kb, carry):
        k = ki_ref[0, at(kb), :]
        index = _index_block(lambda j: qi_ref[0, j], w, k, heads)
        key_ref[:, at(kb)] = jnp.where(cols_of(kb) <= rows, _to_key(index),
                                       INT_MIN)
        return carry

    jax.lax.fori_loop(0, live, score, 0)

    def count(test):
        """Per row, how many live keys pass `test(key, cols)`."""
        def body(kb, total):
            hit = test(key_ref[:, at(kb)], cols_of(kb))
            return total + jnp.sum(hit.astype(jnp.int32), axis=1,
                                   keepdims=True)
        return jax.lax.fori_loop(0, live, body,
                                 jnp.zeros((block_q, 1), jnp.int32))

    thr_ref[:] = jnp.full((block_q, 1), INT_MIN, jnp.int32)
    pos_ref[:] = jnp.full((block_q, 1), seq, jnp.int32)

    # a block whose rows all have at most topk keys takes every causal key
    @pl.when(row0 + block_q > topk)
    def _threshold():
        def value_bit(i, prefix):
            # the prefix is the key with its sign bit flipped: unsigned order
            grown = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
            enough = count(lambda key, _: key >= (grown ^ INT_MIN)) >= topk
            return jnp.where(enough, grown, prefix)

        prefix = jax.lax.fori_loop(0, 32, value_bit,
                                   jnp.zeros((block_q, 1), jnp.int32))
        thr = prefix ^ INT_MIN
        thr_ref[:] = thr
        need = topk - count(lambda key, _: key > thr)
        tied = count(lambda key, _: key == thr)

        # the tie rule: of the keys equal to the threshold, the `need` of
        # lowest position
        def position_bit(i, below):
            grown = below | jnp.left_shift(jnp.int32(1), pos_bits - 1 - i)
            fewer = count(lambda key, cols: (key == thr) & (cols < grown))
            return jnp.where(fewer < need, grown, below)

        last = jax.lax.fori_loop(0, pos_bits, position_bit,
                                 jnp.zeros((block_q, 1), jnp.int32))
        pos_ref[:] = jnp.where(tied > need, last, seq)

    thr, last = thr_ref[:], pos_ref[:]

    def write(kb, carry):
        top, total = carry
        key, cols = key_ref[:, at(kb)], cols_of(kb)
        chosen = (cols <= rows) & (
            (key > thr) | ((key == thr) & (cols <= last)))
        mask_ref[0, :, at(kb)] = chosen.astype(jnp.int8)
        index = jnp.where(chosen, _from_key(key), NEG_INF)
        new_top = jnp.maximum(top, jnp.max(index, axis=1, keepdims=True))
        total = total * jnp.exp(top - new_top) + jnp.sum(
            jnp.where(chosen, jnp.exp(index - new_top), 0.0), axis=1,
            keepdims=True)
        return new_top, total

    top, total = jax.lax.fori_loop(
        0, seq // block_k, write,
        (jnp.full((block_q, 1), NEG_INF, jnp.float32),
         jnp.zeros((block_q, 1), jnp.float32)))
    lse_ref[0] = top + jnp.log(total)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select_call(qi, ki, w, *, topk: int, interpret: bool):
    batch, heads, seq, dim = qi.shape
    block_q = fit_block(seq, SELECT_BLOCK_Q)
    block_k = fit_block(seq, SELECT_BLOCK_K)
    kernel = functools.partial(
        _select_kernel, topk=topk, block_q=block_q, block_k=block_k,
        heads=heads, seq=seq, pos_bits=max(1, (seq - 1).bit_length()))
    vma = _vma(qi, ki, w)
    return pl.pallas_call(
        kernel,
        grid=(batch, seq // block_q),
        in_specs=[
            pl.BlockSpec((1, heads, block_q, dim), lambda b, r: (b, 0, r, 0)),
            pl.BlockSpec((1, block_q, heads), lambda b, r: (b, r, 0)),
            pl.BlockSpec((1, seq, dim), lambda b, r: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, seq), lambda b, r: (b, r, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, r: (b, r, 0)),
        ],
        out_shape=[_sds((batch, seq, seq), jnp.int8, vma),
                   _sds((batch, seq, 1), jnp.float32, vma)],
        scratch_shapes=[pltpu.VMEM((block_q, seq), jnp.int32),
                        pltpu.VMEM((block_q, 1), jnp.int32),
                        pltpu.VMEM((block_q, 1), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
        name=KERNEL_SELECT,
    )(qi, w, ki)


def indexer_select(qi, ki, w, topk: int):
    """(mask (b, S, S) int8, the log-sum-exp of I over each row's selected
    keys (b, S, 1)) from qi (b, S, J, D), ki (b, S, D), w (b, S, J)."""
    return _select_call(qi.transpose(0, 2, 1, 3), ki,
                        w.astype(jnp.float32), topk=topk,
                        interpret=not on_tpu())


# ===========================================================================
# Attention over the selected keys: the flash kernels under the mask
# ===========================================================================


def _keep_rows(rows, name: str):
    """`checkpoint_name` on a (..., S, 1) row statistic without its last
    axis: on the chip a float32 array whose minor dimension is 1 is padded
    to 128 lanes, and what a block's recomputation keeps (`ops/remat.py`)
    it keeps through the whole forward pass (268 MB a layer for the
    attention's log-sum-exp at 32 heads x 16,384 rows, 2 MB so)."""
    return checkpoint_name(rows[..., 0], name)[..., None]


def _masked_forward(q, k, v, mask, sm_scale: float):
    return _flash_fwd(q, k, v, sm_scale, True, DEFAULT_BLOCK_Q,
                      DEFAULT_BLOCK_K, mask=mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def masked_attention(q, k, v, mask, sm_scale: float):
    """(out, lse) of softmax attention over the keys `mask` selects."""
    return _masked_forward(q, k, v, mask, sm_scale)


def _masked_attention_fwd(q, k, v, mask, sm_scale):
    # tagged here, where they are outputs and residuals at once: a block's
    # recomputation that keeps them (`ops/remat.py`) drops the kernel
    out, lse = _masked_forward(q, k, v, mask, sm_scale)
    out = checkpoint_name(out, Kept.ATTENTION)
    lse = _keep_rows(lse, Kept.ATTENTION)
    return (out, lse), (q, k, v, out, lse, mask)


def _masked_attention_bwd(sm_scale, res, cotangents):
    *res, mask = res
    # traced apart from the scope around the call: open it again
    with jax.named_scope(TraceScope.SPARSE_ATTN):
        dq, dk, dv = _flash_bwd(
            tuple(res), cotangents[0], sm_scale=sm_scale, causal=True,
            block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K, mask=mask)
    return dq, dk, dv, None


masked_attention.defvjp(_masked_attention_fwd, _masked_attention_bwd)


# ===========================================================================
# indexer_kl: head-summed probabilities, the KL rows, and d KL / d I taken
# back to qi, w and ki in the same pass
# ===========================================================================


def _weights_of(grad, w, s, j: int):
    """One head's part of d I: (d w[:, j] rows, the matmuls' left operand
    `grad x w[:, j]` where the head's score is positive)."""
    live = jnp.where(s > 0.0, grad, 0.0)
    return jnp.sum(live * s, axis=1, keepdims=True), live * w[:, j:j + 1]


def _kl_kernel(q_ref, k_ref, lse_ref, mask_ref, qi_ref, w_ref, ki_ref,
               lsei_ref, kl_ref, *rest, sm_scale: float, block: int,
               heads: int, index_heads: int, grads: bool, rows: int):
    if grads:
        dqi_ref, dw_ref, dki_ref, sum_ref, scores_ref = rest
    else:
        (sum_ref,), scores_ref = rest, None
    qb, kb, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    needed = kb <= qb       # equal square blocks: at or below the diagonal
    last_head = h == heads - 1

    @pl.when(h == 0)
    def _init():
        sum_ref[:] = jnp.zeros_like(sum_ref)

    @pl.when(jnp.logical_and(last_head, kb == 0))
    def _init_rows():
        kl_ref[0] = jnp.zeros(kl_ref.shape[1:], jnp.float32)
        if grads:
            dqi_ref[0] = jnp.zeros(dqi_ref.shape[1:], jnp.float32)
            dw_ref[0] = jnp.zeros(dw_ref.shape[1:], jnp.float32)

            @pl.when(qb == 0)
            def _init_keys():
                dki_ref[0] = jnp.zeros(dki_ref.shape[1:], jnp.float32)

    @pl.when(needed)
    def _head():
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (sm_scale * LOG2E)
        p = jnp.exp2(s - lse_ref[0, 0] * LOG2E)
        sum_ref[:] += jnp.where(mask_ref[0] != 0, p, 0.0)

    @pl.when(jnp.logical_and(last_head, needed))
    def _rows():
        k = ki_ref[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, index_heads), 1)
        dk = None
        for r0 in range(0, block, rows):
            at = pl.ds(r0, rows)
            chosen = mask_ref[0, at, :] != 0
            w = w_ref[0, at, :]
            index = _index_block(lambda j: qi_ref[0, j, at, :], w, k,
                                 index_heads, scores_ref)
            log_q = index - lsei_ref[0, at, :]
            target = sum_ref[at, :] * (1.0 / heads)
            there = jnp.logical_and(chosen, target > 0)
            gap = jnp.where(there, target * (
                jnp.log(jnp.where(there, target, 1.0)) - log_q), 0.0)
            kl_ref[0, at, :] += jnp.sum(gap, axis=1, keepdims=True)
            if not grads:
                continue
            # d KL / d I, float32 until the matmuls' operands are cast
            grad = jnp.where(chosen, jnp.exp(log_q), 0.0) - target
            dw = jnp.zeros((rows, index_heads), jnp.float32)
            for j in range(index_heads):
                q = qi_ref[0, j, at, :]
                dw_j, left = _weights_of(grad, w, scores_ref[j], j)
                dw = dw + jnp.where(lane == j, dw_j, 0.0)
                left = left.astype(k.dtype)
                dqi_ref[0, j, at, :] += _dot(left, k, ((1,), (0,)))
                part = _dot(q, left, ((0,), (0,)))
                dk = part if dk is None else dk + part
            dw_ref[0, at, :] += dw
        if grads:
            keys = pl.ds(pl.multiple_of(kb * block, block), block)
            dki_ref[0, :, keys] += dk


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "grads", "interpret"))
def _kl_call(q, k, lse, mask, qi, w, ki, lse_i, *, sm_scale: float,
             grads: bool, interpret: bool):
    """The KL term's rows (b, S, 1) and, with `grads`, its unscaled
    gradients with respect to qi, w and ki, float32."""
    batch, heads, seq, d = q.shape
    group = heads // k.shape[1]
    index_heads, dim = qi.shape[1], qi.shape[3]
    block = fit_block(seq, KL_BLOCK)
    blocks = seq // block
    rows = fit_block(block, KL_ROWS)
    kernel = functools.partial(
        _kl_kernel, sm_scale=sm_scale, block=block, heads=heads,
        index_heads=index_heads, grads=grads, rows=rows)

    def low(qb, kb):        # a block above the diagonal repeats the last
        return jnp.minimum(kb, qb)

    def of_rows(b, qb, kb, h):
        return b, qb, 0

    vma = _vma(q, k, qi, ki, w)
    out_specs = [pl.BlockSpec((1, block, 1), of_rows)]
    out_shape = [_sds((batch, seq, 1), jnp.float32, vma)]
    scratch = [pltpu.VMEM((block, block), jnp.float32)]
    if grads:
        # dqi and dw a row block's, written when its last key block is
        # done; dki the whole sequence's, written once at the end, so the
        # row blocks run in order; each index head's scores of a slice of
        # rows, formed for I and read again for d I
        out_specs += [
            pl.BlockSpec((1, index_heads, block, dim),
                         lambda b, qb, kb, h: (b, 0, qb, 0)),
            pl.BlockSpec((1, block, index_heads), of_rows),
            pl.BlockSpec((1, dim, seq), lambda b, qb, kb, h: (b, 0, 0)),
        ]
        out_shape += [_sds(qi.shape, jnp.float32, vma),
                      _sds(w.shape, jnp.float32, vma),
                      _sds((batch, dim, seq), jnp.float32, vma)]
        scratch.append(pltpu.VMEM((index_heads, rows, block), jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=(batch, blocks, blocks, heads),
        in_specs=[
            pl.BlockSpec((1, 1, block, d), lambda b, qb, kb, h: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, block, d),
                         lambda b, qb, kb, h: (b, h // group, low(qb, kb), 0)),
            pl.BlockSpec((1, 1, block, 1), lambda b, qb, kb, h: (b, h, qb, 0)),
            pl.BlockSpec((1, block, block),
                         lambda b, qb, kb, h: (b, qb, low(qb, kb))),
            pl.BlockSpec((1, index_heads, block, dim),
                         lambda b, qb, kb, h: (b, 0, qb, 0)),
            pl.BlockSpec((1, block, index_heads), of_rows),
            pl.BlockSpec((1, block, dim),
                         lambda b, qb, kb, h: (b, low(qb, kb), 0)),
            pl.BlockSpec((1, block, 1), of_rows),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_params("parallel",
                                "arbitrary" if grads else "parallel",
                                "arbitrary", "arbitrary"),
        interpret=interpret,
        name=KERNEL_KL,
    )(q, k, lse, mask, qi, w, ki, lse_i)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def indexer_kl(qi, ki, w, mask, lse_i, q, k, lse, sm_scale: float):
    """mean over batch and queries of KL(p_t || softmax_{S_t} I[t, :]), a
    function of qi (b, J, S, D), ki and w alone: the mask, the main
    attention's q, k and log-sum-exp are constants of it."""
    (rows,) = _kl_call(q, k, lse, mask, qi, w, ki, lse_i, sm_scale=sm_scale,
                       grads=False, interpret=not on_tpu())
    return jnp.mean(rows)


def _indexer_kl_fwd(qi, ki, w, mask, lse_i, q, k, lse, sm_scale):
    rows, dqi, dw, dki = _kl_call(q, k, lse, mask, qi, w, ki, lse_i,
                                  sm_scale=sm_scale, grads=True,
                                  interpret=not on_tpu())
    dki = dki.transpose(0, 2, 1)
    scale = 1.0 / rows.size
    # kept in the dtypes their cotangents go back in; the rule's only
    # residuals: a block's recomputation that keeps them runs no kernel
    return jnp.mean(rows), tuple(
        checkpoint_name(g, Kept.KL_GRADS)
        for g in ((dqi * scale).astype(qi.dtype),
                  (dki * scale).astype(ki.dtype), dw * scale))


def _indexer_kl_bwd(sm_scale, res, cotangent):
    # traced apart from the scope around the call: open it again
    with jax.named_scope(TraceScope.INDEXER):
        return tuple((g * cotangent).astype(g.dtype) for g in res) + (
            None,) * 5


indexer_kl.defvjp(_indexer_kl_fwd, _indexer_kl_bwd)


# ===========================================================================
# The whole: what ops/sparse_attention.py dispatches to
# ===========================================================================


def sparse_attention(q, k, v, qi, ki, w, topk: int, sm_scale: float):
    detach = jax.lax.stop_gradient
    w = w.astype(jnp.float32)
    with jax.named_scope(TraceScope.INDEXER):
        mask, lse_i = indexer_select(detach(qi), detach(ki), detach(w), topk)
        mask = checkpoint_name(mask, Kept.SELECTION)
        lse_i = _keep_rows(lse_i, Kept.SELECTION)
    qi = qi.transpose(0, 2, 1, 3)
    with jax.named_scope(TraceScope.SPARSE_ATTN):
        out, lse = masked_attention(q, k, v, mask, sm_scale)
    with jax.named_scope(TraceScope.INDEXER):
        kl = indexer_kl(qi, ki, w, mask, lse_i, detach(q), detach(k),
                        detach(lse), sm_scale)
    return out, kl
