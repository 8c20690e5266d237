"""Attention over a set of keys that a learned indexer selects.

For each query t an indexer scores every key s <= t,

    I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s]),

(`w` and `qi` carry their scalings already), and the main attention's
softmax runs over S_t alone: the `topk` keys of largest I[t, :] among
s <= t, all of them while t < topk, ties to the lower s (what
`jax.lax.top_k` does). The indexer learns from the main attention: its
objective is mean_t KL(p_t || softmax_{s in S_t} I[t, s]), p_t the main
attention's probabilities summed over the heads and normalised on S_t,
detached. Selection passes no gradient: the attention's loss reaches q, k
and v only, the KL term qi, ki and w only.

Two forms of the same mathematics (`impl`):

- ``"xla"``: whole (seq, seq) arrays in plain XLA. The test oracle and the
  path off the TPU; its memory is quadratic in the sequence.
- ``"kernel"``: Pallas kernels under names of their own, a contract with
  the trace readers (docs/observability.md): `indexer_select` scores a
  block of query rows against every key at or before it in VMEM, finds each
  row's topk-th largest score exactly by bisection on the bit pattern (32
  compare-and-count passes, then the tie rule by bisection on the
  position) and writes the selection as an int8 mask with the row's
  log-sum-exp over the selected scores; `sparse_attn_fwd`, `sparse_attn_dq`
  and `sparse_attn_dkv` are the dense flash kernels
  (`ops/flash_attention.py`) with that mask as an operand, applied in every
  computed block, the causal block skipping kept; `indexer_kl` recomputes
  the main attention's scores a head at a time, sums the probabilities over
  the heads in VMEM and forms the KL term's rows and, under
  differentiation, d KL / d I, which it takes back to qi, w and ki in the
  same pass. The int8 mask is the only (seq, seq) array written: d KL / d I
  stays in VMEM, float32 until the products' operands are cast.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.common.constants import TraceScope
from dlrover_tpu.ops.backend import on_tpu
from dlrover_tpu.ops.remat import Kept

NEG_INF = -1e30


def resolve_impl(impl: str) -> str:
    """``auto``: the kernels on the TPU, plain XLA elsewhere."""
    if impl == "auto":
        return "kernel" if on_tpu() else "xla"
    if impl not in ("kernel", "xla"):
        raise ValueError(f"unknown sparse attention impl {impl!r}")
    return impl


# ===========================================================================
# Plain XLA: whole (seq, seq) arrays
# ===========================================================================


def index_scores(qi, ki, w):
    """I (b, S, S) float32 from qi (b, S, J, D), ki (b, S, D) in the compute
    dtype (float32 accumulation) and w (b, S, J) float32."""
    exact = qi.dtype == jnp.float32     # float32 operands stay float32
    s = jnp.einsum("bqjd,bkd->bjqk", qi, ki,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST if exact else None)
    return jnp.sum(jax.nn.relu(s) * jnp.moveaxis(
        w.astype(jnp.float32), -1, 1)[..., None], axis=1)


def select_keys(scores, topk: int):
    """(b, S, S) bool: S_t by `jax.lax.top_k` over the causal scores; every
    key s <= t while t < topk."""
    seq = scores.shape[-1]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    if topk >= seq:
        return jnp.broadcast_to(causal, scores.shape)
    _, picked = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    rows = jnp.arange(seq)[None, :, None]
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None, None], rows, picked].set(True)
    return chosen & causal      # a row with fewer than topk keys takes all


def _xla_attention(q, k, v, mask, sm_scale):
    """(out (b, H, S, d), probabilities summed over the heads (b, S, S)
    float32) of softmax attention over the selected keys."""
    b, heads, seq, d = q.shape
    group = heads // k.shape[1]
    qg = q.reshape(b, k.shape[1], group, seq, d)
    s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return (out.reshape(b, heads, seq, d).astype(q.dtype),
            jnp.sum(p, axis=(1, 2)))


def _xla_forward(q, k, v, qi, ki, w, topk, sm_scale):
    # tagged where the kernel form tags (`ops/remat.py:Kept`): a block's
    # recomputation keeps the same three things in either form
    detach = jax.lax.stop_gradient
    with jax.named_scope(TraceScope.INDEXER):
        scores = index_scores(qi, ki, w)
        mask = checkpoint_name(select_keys(detach(scores), topk),
                               Kept.SELECTION)
    with jax.named_scope(TraceScope.SPARSE_ATTN):
        out, p_sum = _xla_attention(q, k, v, mask, sm_scale)
        out = checkpoint_name(out, Kept.ATTENTION)
    with jax.named_scope(TraceScope.INDEXER):
        target = checkpoint_name(detach(p_sum) / q.shape[1], Kept.KL_GRADS)
        log_q = checkpoint_name(jax.nn.log_softmax(
            jnp.where(mask, scores, NEG_INF), axis=-1), Kept.KL_GRADS)
        kl = jnp.sum(jnp.where(target > 0, target * (
            jnp.log(jnp.where(target > 0, target, 1.0)) - log_q), 0.0),
            axis=-1)
    return out, jnp.mean(kl)


# ===========================================================================
# Public entry
# ===========================================================================


def sparse_attention(q, k, v, qi, ki, w, topk: int, sm_scale=None,
                     impl: str = "auto"):
    """(out, kl): attention of q (b, H, S, d) over the selected keys of
    k, v (b, G, S, d), and the indexer's objective, the mean over the batch
    and the queries of KL(p_t || softmax_{S_t} I[t, :]).

    qi (b, S, J, D) and ki (b, S, D) in the compute dtype, w (b, S, J)
    float32: the indexer's query heads, its one key head and its head
    weights, scalings folded in (float32 qi and ki, a float32 model's, are
    scored in float32). The caller detaches what the indexer reads
    of the model; here `out`'s gradient reaches q, k, v alone and `kl`'s
    qi, ki, w alone."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if resolve_impl(impl) == "xla":
        return _xla_forward(q, k, v, qi, ki, w, topk, sm_scale)
    from dlrover_tpu.ops import sparse_attention_kernels as kernels

    return kernels.sparse_attention(q, k, v, qi, ki, w, topk, sm_scale)


def selection(qi, ki, w, topk: int, impl: str = "auto"):
    """The selection alone, (b, S, S) bool: what the tests hold the two
    forms to."""
    if resolve_impl(impl) == "xla":
        return select_keys(index_scores(qi, ki, w), topk)
    from dlrover_tpu.ops import sparse_attention_kernels as kernels

    return kernels.indexer_select(qi, ki, w, topk)[0] != 0
