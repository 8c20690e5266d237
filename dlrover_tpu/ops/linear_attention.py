"""Lightning attention: causal linear attention with a decay per head
(Lightning Attention, Qin et al., arXiv:2401.04658; MiniMax-01,
arXiv:2501.08313).

For one head, q and k (S, d), v (S, e) and a decay lam = exp(-rate) in
(0, 1], the recurrence

    S_t = lam S_{t-1} + k_t^T v_t,      o_t = scale q_t S_t,

that is o_t = scale sum_{s <= t} lam^(t - s) (q_t . k_s) v_s. No feature map,
no normaliser: what the model puts around it (norms, gate) is the model's.

The chunked form both implementations compute. A chunk of C rows, position
i in it, S_prev the state after the chunk before:

    O   = scale [((Q K^T) * D) V + (Q * lam^(i+1)) S_prev],
          D[i, j] = lam^(i-j) where j <= i, else 0
    S   = lam^C S_prev + (K * lam^(C-1-j))^T V

and the backward, G the gradient of the state after this chunk, carried
from the last chunk to the first:

    dQ  = scale [((dO V^T) * D) K + (dO * lam^(i+1)) S_prev^T]
    dK  = scale ((dO V^T) * D)^T Q + (V G^T) * lam^(C-1-j)
    dV  = scale ((Q K^T) * D)^T dO + (K G) * lam^(C-1-j)
    G  <- lam^C G + scale (Q * lam^(i+1))^T dO

Two forms (`impl`):

- ``"xla"``: the chunked form in plain JAX, a `lax.scan` over chunks that
  JAX differentiates. The test oracle and the path off the TPU.
- ``"kernel"``: Pallas kernels under names of their own, a contract with the
  trace readers (docs/observability.md): `lightning_fwd` walks a head's
  chunks in order with the state in VMEM and writes, beside O, the state
  before each chunk (float32, b x H x S/C x d x e: 134 MB a layer at 32 heads
  of 128 and 16,384 rows in chunks of 256); `lightning_bwd` walks them from
  the last, the state's gradient in VMEM, and writes dQ, dK and dV. The
  decay is a constant of the model: it takes no gradient.

Matrix products take the operands' dtype with float32 accumulation (float32
operands at full precision); decays, states and the masks D are float32.
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
from dlrover_tpu.ops.flash_attention import _sds, _vma, fit_block
from dlrover_tpu.ops.remat import Kept

KERNEL_FWD = "lightning_fwd"
KERNEL_BWD = "lightning_bwd"

CHUNK = 256     # rows a chunk: (C, C) float32 masks, C x 128 operands


def resolve_impl(impl: str) -> str:
    """``auto``: the kernels on the TPU, plain XLA elsewhere."""
    if impl == "auto":
        return "kernel" if on_tpu() else "xla"
    if impl not in ("kernel", "xla"):
        raise ValueError(f"unknown linear attention impl {impl!r}")
    return impl


def _dot(a, b, dims=((1,), (0,))):
    """A 2-D product, float32 accumulation, in the operands' precision."""
    exact = a.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if exact else None)


# ===========================================================================
# Plain XLA: a scan over chunks
# ===========================================================================


def _decays(rate, chunk: int):
    """(D (H, C, C), lam^(i+1) (H, C, 1), lam^(C-1-j) (H, C, 1),
    lam^C (H, 1, 1)), float32, from rate (H,)."""
    rate = rate.astype(jnp.float32)[:, None, None]
    i = jnp.arange(chunk, dtype=jnp.float32)
    gap = i[:, None] - i[None, :]
    mask = jnp.where(gap >= 0, jnp.exp(-rate * jnp.maximum(gap, 0.0)), 0.0)
    into = jnp.exp(-rate * (i + 1.0)[:, None])
    out_of = jnp.exp(-rate * (chunk - 1.0 - i)[:, None])
    return mask, into, out_of, jnp.exp(-rate * chunk)


def _xla_forward(q, k, v, rate, scale: float, chunk: int):
    b, heads, seq, d = q.shape
    e = v.shape[-1]
    n = seq // chunk
    exact = q.dtype == jnp.float32

    def dot(spec, x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST
                          if exact else None)

    mask, into, out_of, whole = _decays(rate, chunk)

    def cut(x):         # (b, H, S, .) -> (n, b, H, C, .)
        return jnp.moveaxis(x.reshape(b, heads, n, chunk, x.shape[-1]), 2, 0)

    def body(state, rows):
        qc, kc, vc = rows
        scores = dot("bhid,bhjd->bhij", qc, kc) * mask
        out = dot("bhij,bhje->bhie", scores.astype(vc.dtype), vc)
        out = out + dot("bhid,bhde->bhie", (qc * into).astype(qc.dtype),
                        state.astype(qc.dtype))
        state = whole * state + dot("bhjd,bhje->bhde",
                                    (kc * out_of).astype(kc.dtype), vc)
        return state, out * scale

    with jax.named_scope(TraceScope.LIGHTNING_ATTN):
        _, out = jax.lax.scan(
            body, jnp.zeros((b, heads, d, e), jnp.float32),
            (cut(q), cut(k), cut(v)))
    # tagged as the kernel form's output is: a block's recomputation keeps
    # the same thing in either form
    return checkpoint_name(jnp.moveaxis(out, 0, 2).reshape(
        b, heads, seq, e).astype(q.dtype), Kept.LIGHTNING)


# ===========================================================================
# Pallas: one kernel forward, one backward
# ===========================================================================


def _chunk_decays(rate_ref, chunk: int):
    """The kernel's `_decays` for its head, from a (1, 1) float32 block."""
    rate = rate_ref[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    gap = (rows - cols).astype(jnp.float32)
    mask = jnp.where(gap >= 0.0, jnp.exp(-rate * jnp.maximum(gap, 0.0)), 0.0)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0).astype(
        jnp.float32)
    into = jnp.exp(-rate * (i + 1.0))
    out_of = jnp.exp(-rate * (chunk - 1.0 - i))
    return mask, into, out_of, jnp.exp(-rate * float(chunk))


def _fwd_kernel(rate_ref, q_ref, k_ref, v_ref, o_ref, states_ref, state_ref,
                *, scale: float, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[:] = jnp.zeros_like(state_ref)

    mask, into, out_of, whole = _chunk_decays(rate_ref, chunk)
    q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
    state = state_ref[:]
    states_ref[0, 0, 0] = state
    scores = _dot(q, k, ((1,), (1,))) * mask
    out = _dot(scores.astype(v.dtype), v)
    out = out + _dot((q * into).astype(q.dtype), state.astype(q.dtype))
    o_ref[0, 0] = (out * scale).astype(o_ref.dtype)
    state_ref[:] = whole * state + _dot((k * out_of).astype(k.dtype).T, v)


def _bwd_kernel(rate_ref, q_ref, k_ref, v_ref, do_ref, states_ref,
                dq_ref, dk_ref, dv_ref, grad_ref, *, scale: float,
                chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        grad_ref[:] = jnp.zeros_like(grad_ref)

    mask, into, out_of, whole = _chunk_decays(rate_ref, chunk)
    q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
    dtype = q.dtype
    before, grad = states_ref[0, 0, 0], grad_ref[:]
    scores = (_dot(q, k, ((1,), (1,))) * mask).astype(dtype)
    dscores = (_dot(do, v, ((1,), (1,))) * mask).astype(dtype)
    dq = _dot(dscores, k) + _dot((do * into).astype(dtype),
                                 before.astype(dtype).T)
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)
    dk = _dot(dscores.T, q) * scale + _dot(v, grad.astype(dtype).T) * out_of
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv = _dot(scores.T, do) * scale + _dot(k, grad.astype(dtype)) * out_of
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    grad_ref[:] = whole * grad + scale * _dot(
        (q * into).astype(dtype).T, do)


def _rate_spec():
    return pl.BlockSpec((1, 1, 1), lambda b, h, c: (h, 0, 0))


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret"))
def _fwd_call(q, k, v, rate, *, scale: float, chunk: int, interpret: bool):
    b, heads, seq, d = q.shape
    e = v.shape[-1]
    n = seq // chunk
    vma = _vma(q, k, v)

    def rows(width):
        return pl.BlockSpec((1, 1, chunk, width), lambda b, h, c: (b, h, c, 0))

    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, chunk=chunk),
        grid=(b, heads, n),
        in_specs=[_rate_spec(), rows(d), rows(d), rows(e)],
        out_specs=[rows(e), pl.BlockSpec(
            (1, 1, 1, d, e), lambda b, h, c: (b, h, c, 0, 0))],
        out_shape=[_sds(v.shape, v.dtype, vma),
                   _sds((b, heads, n, d, e), jnp.float32, vma)],
        scratch_shapes=[pltpu.VMEM((d, e), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_FWD,
    )(rate.astype(jnp.float32).reshape(heads, 1, 1), q, k, v)


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret"))
def _bwd_call(q, k, v, do, rate, states, *, scale: float, chunk: int,
              interpret: bool):
    b, heads, seq, d = q.shape
    e = v.shape[-1]
    n = seq // chunk
    vma = _vma(q, k, v, do)

    def rows(width):    # the chunks from the last
        return pl.BlockSpec((1, 1, chunk, width),
                            lambda b, h, c: (b, h, n - 1 - c, 0))

    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, chunk=chunk),
        grid=(b, heads, n),
        in_specs=[_rate_spec(), rows(d), rows(d), rows(e), rows(e),
                  pl.BlockSpec((1, 1, 1, d, e),
                               lambda b, h, c: (b, h, n - 1 - c, 0, 0))],
        out_specs=[rows(d), rows(d), rows(e)],
        out_shape=[_sds(q.shape, q.dtype, vma), _sds(k.shape, k.dtype, vma),
                   _sds(v.shape, v.dtype, vma)],
        scratch_shapes=[pltpu.VMEM((d, e), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_BWD,
    )(rate.astype(jnp.float32).reshape(heads, 1, 1), q, k, v, do, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _lightning(q, k, v, rate, scale: float, chunk: int):
    return _fwd_call(q, k, v, rate, scale=scale, chunk=chunk,
                     interpret=not on_tpu())[0]


def _lightning_fwd(q, k, v, rate, scale, chunk):
    # tagged here, where they are outputs and residuals at once: a block's
    # recomputation that keeps them (`ops/remat.py`) drops the kernel
    out, states = _fwd_call(q, k, v, rate, scale=scale, chunk=chunk,
                            interpret=not on_tpu())
    out = checkpoint_name(out, Kept.LIGHTNING)
    states = checkpoint_name(states, Kept.LIGHTNING)
    return out, (q, k, v, rate, states)


def _lightning_bwd(scale, chunk, res, do):
    q, k, v, rate, states = res
    # traced apart from the scope around the call: open it again
    with jax.named_scope(TraceScope.LIGHTNING_ATTN):
        dq, dk, dv = _bwd_call(q, k, v, do, rate, states, scale=scale,
                               chunk=chunk, interpret=not on_tpu())
    return dq, dk, dv, jnp.zeros_like(rate)


_lightning.defvjp(_lightning_fwd, _lightning_bwd)


# ===========================================================================
# Public entry
# ===========================================================================


def linear_attention(q, k, v, rate, scale: float, chunk: int = CHUNK,
                     impl: str = "auto"):
    """o (b, H, S, e) of the decayed causal recurrence over q, k (b, H, S, d)
    and v (b, H, S, e); `rate` (H,) float32 gives each head's decay
    exp(-rate), a constant (no gradient). `chunk` is fitted to divide S."""
    chunk = fit_block(q.shape[2], chunk)
    rate = jax.lax.stop_gradient(rate)
    if resolve_impl(impl) == "xla":
        return _xla_forward(q, k, v, rate, scale, chunk)
    with jax.named_scope(TraceScope.LIGHTNING_ATTN):
        return _lightning(q, k, v, rate, scale, chunk)


def recurrence(q, k, v, rate, scale: float):
    """The recurrence step by step, float32 at full precision: what the
    tests hold both forms to."""
    lam = jnp.exp(-rate.astype(jnp.float32))[None, :, None, None]
    f32 = jnp.float32

    def step(state, rows):
        qt, kt, vt = rows           # (b, H, d), (b, H, d), (b, H, e)
        state = lam * state + kt[..., :, None] * vt[..., None, :]
        return state, scale * jnp.einsum(
            "bhd,bhde->bhe", qt, state, precision=jax.lax.Precision.HIGHEST)

    b, heads, _, d = q.shape
    _, out = jax.lax.scan(
        step, jnp.zeros((b, heads, d, v.shape[-1]), f32),
        tuple(jnp.moveaxis(x.astype(f32), 2, 0) for x in (q, k, v)))
    return jnp.moveaxis(out, 0, 2)
