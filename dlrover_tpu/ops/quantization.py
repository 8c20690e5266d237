"""Quantization kernel suite: int8/int4 groupwise quantize / dequantize /
swizzled layouts / quantized reduction.

Capability parity: the reference's CUDA quantization library
(atorch/atorch/ops/csrc/: quantize.cu:150, dequantize.cu:67,
swizzled_quantize.cu:194, quant_reduce.cu:248, pt_binding.cpp:178 and the
vectorized memory_access/conversion/reduction headers). TPU re-design:
- groupwise symmetric quantization as a Pallas kernel (VMEM-resident
  rows, fp32 scale math) with an XLA reference path;
- "swizzle" = the partner-major tile re-layout used before chunked
  collectives (the CUDA version reorders for coalesced NVLink pushes;
  here the permutation is a cheap XLA reshape/transpose the compiler
  fuses into the collective's copy);
- quant_reduce = dequantize-accumulate-requantize across chunks, the
  compressed-gradient all-reduce building block.

int4 values are carried two-per-int8 (packed low/high nibble), matching
the CUDA suite's storage.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from dlrover_tpu.ops.backend import on_tpu

# Kernel names (HLO instruction names, so the start of the profiler's
# event names): a contract for trace readers (docs/observability.md).
KERNEL_QUANTIZE = "quantize_groupwise"
KERNEL_DEQUANTIZE = "dequantize_groupwise"


def _qmax(bits: int) -> int:
    if bits == 8:
        return 127
    if bits == 4:
        return 7
    raise ValueError(f"bits must be 4 or 8, got {bits}")


# ---------------------------------------------------------------------------
# Pallas kernels (int8 path; int4 packs outside the kernel)
# ---------------------------------------------------------------------------


def _quantize_kernel(x_ref, q_ref, scale_ref, *, qmax: int):
    x = x_ref[:].astype(jnp.float32)          # (rows_block, group)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = absmax / qmax
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(x * inv), -qmax, qmax)
    q_ref[:] = q.astype(jnp.int8)
    scale_ref[:] = scale


def _dequantize_kernel(q_ref, scale_ref, o_ref):
    o_ref[:] = (q_ref[:].astype(jnp.float32)
                * scale_ref[:]).astype(o_ref.dtype)


def _rows_block(rows: int) -> int:
    return min(rows, 512)


def quantize(x: jax.Array, bits: int = 8, group_size: int = 128
             ) -> Tuple[jax.Array, jax.Array]:
    """Groupwise symmetric quantization over the last dim.

    Returns (q, scales): q int8 — for bits=4, two nibbles packed per int8,
    so the last dim halves; scales fp32 with shape x.shape[:-1] +
    (groups,).
    """
    qmax = _qmax(bits)
    orig_shape = x.shape
    if orig_shape[-1] % group_size:
        raise ValueError(
            f"last dim {orig_shape[-1]} not divisible by group "
            f"{group_size}")
    groups = orig_shape[-1] // group_size
    x2 = x.reshape(-1, group_size)            # (rows, group)
    rows = x2.shape[0]
    block = _rows_block(rows)
    grid = ((rows + block - 1) // block,)
    q, scales = pl.pallas_call(
        functools.partial(_quantize_kernel, qmax=qmax),
        grid=grid,
        in_specs=[pl.BlockSpec((block, group_size), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block, group_size), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, group_size), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=not on_tpu(),
        name=KERNEL_QUANTIZE,
    )(x2)
    scales = scales.reshape(orig_shape[:-1] + (groups,))
    q = q.reshape(orig_shape)
    if bits == 4:
        q = pack_int4(q)
    return q, scales


def dequantize(q: jax.Array, scales: jax.Array, bits: int = 8,
               dtype=jnp.float32) -> jax.Array:
    """Inverse of `quantize`."""
    if bits == 4:
        q = unpack_int4(q)
    orig_shape = q.shape
    groups = scales.shape[-1]
    group_size = orig_shape[-1] // groups
    q2 = q.reshape(-1, group_size)
    s2 = scales.reshape(-1, 1)
    rows = q2.shape[0]
    block = _rows_block(rows)
    grid = ((rows + block - 1) // block,)
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, group_size), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, group_size), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, group_size), dtype),
        interpret=not on_tpu(),
        name=KERNEL_DEQUANTIZE,
    )(q2, s2)
    return out.reshape(orig_shape)


# ---------------------------------------------------------------------------
# int4 packing
# ---------------------------------------------------------------------------


def pack_int4(q: jax.Array) -> jax.Array:
    """int8 values in [-7, 7] → packed nibbles, last dim halves."""
    lo = q[..., 0::2] & 0x0F
    hi = (q[..., 1::2] & 0x0F) << 4
    return (lo | hi).astype(jnp.int8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Packed nibbles → int8 values (sign-extended), last dim doubles."""
    lo = (packed & 0x0F).astype(jnp.int8)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = ((packed.astype(jnp.int32) >> 4) & 0x0F).astype(jnp.int8)
    hi = jnp.where(hi > 7, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(packed.shape[:-1] + (packed.shape[-1] * 2,))


# ---------------------------------------------------------------------------
# Swizzled quantize + quantized reduction
# ---------------------------------------------------------------------------


def swizzled_quantize(x: jax.Array, partners: int, bits: int = 8,
                      group_size: int = 128
                      ) -> Tuple[jax.Array, jax.Array]:
    """Quantize then re-layout partner-major for chunked collectives.

    x flat length must divide by partners×group_size. Output q has shape
    (partners, chunk): partner p's chunk is contiguous, so a
    reduce-scatter/all-to-all sends one dense slice per peer (the CUDA
    swizzled_quantize.cu serves the same purpose for NVLink pushes).
    """
    flat = x.reshape(-1)
    if flat.shape[0] % (partners * group_size):
        raise ValueError("size not divisible by partners*group_size")
    chunk = flat.shape[0] // partners
    # interleaved → partner-major: element i goes to partner i % partners
    swizzled = flat.reshape(chunk, partners).T.reshape(partners, chunk)
    q, scales = quantize(swizzled, bits=bits, group_size=group_size)
    return q, scales


def unswizzle_dequantize(q: jax.Array, scales: jax.Array, shape,
                         bits: int = 8, dtype=jnp.float32) -> jax.Array:
    partners = q.shape[0]
    deq = dequantize(q, scales, bits=bits, dtype=dtype)
    flat = deq.reshape(partners, -1).T.reshape(-1)
    return flat.reshape(shape)


def quant_reduce(qs: jax.Array, scales: jax.Array, bits: int = 8,
                 group_size: int = 128) -> Tuple[jax.Array, jax.Array]:
    """Reduce N quantized chunks → one re-quantized chunk.

    qs: (N, ...) packed ints; scales: (N, ..., groups). Dequantize each,
    accumulate in fp32, requantize (the CUDA quant_reduce.cu pipeline for
    hierarchical compressed all-reduce).
    """
    deq = jax.vmap(lambda q, s: dequantize(q, s, bits=bits))(qs, scales)
    total = jnp.sum(deq, axis=0)
    return quantize(total, bits=bits, group_size=group_size)


# ---------------------------------------------------------------------------
# XLA reference (test oracle)
# ---------------------------------------------------------------------------


def reference_quantize(x: jax.Array, bits: int = 8, group_size: int = 128
                       ) -> Tuple[jax.Array, jax.Array]:
    qmax = _qmax(bits)
    orig = x.shape
    x2 = x.reshape(-1, group_size).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x2), axis=-1, keepdims=True)
    scale = absmax / qmax
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(x2 * inv), -qmax, qmax).astype(jnp.int8)
    q = q.reshape(orig)
    scales = scale.reshape(orig[:-1] + (orig[-1] // group_size,))
    if bits == 4:
        q = pack_int4(q)
    return q, scales
