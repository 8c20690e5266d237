"""Fused RMSNorm / LayerNorm Pallas kernels.

TPU-native equivalent of the reference's fused normalization
(atorch/atorch/normalization/layernorm.py:157-237, an apex-CUDA-backed
autograd function): one VMEM-resident kernel per (rows-block), fp32 math,
custom VJP with a fused backward.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from dlrover_tpu.ops.backend import on_tpu
from dlrover_tpu.ops.flash_attention import _sds, _vma, in_manual_region

# Kernel names (HLO instruction names, so the start of the profiler's
# event names): the contract benchmarks/metrics/kernels.rms_norm_roofline.py
# holds the program to (docs/observability.md).
KERNEL_FWD = "rms_norm_fwd"
KERNEL_BWD = "rms_norm_bwd"


def _rms_fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    o_ref[:] = (x * rstd * w).astype(o_ref.dtype)
    rstd_ref[:] = rstd


def _rms_bwd_kernel(x_ref, w_ref, rstd_ref, g_ref, dx_ref, dwp_ref,
                    *, eps: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dwp_ref[:] = jnp.zeros_like(dwp_ref)

    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = x * rstd
    wg = g * w
    # dx = rstd * (wg - xhat * mean(wg * xhat))
    mean_term = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (wg - xhat * mean_term)).astype(dx_ref.dtype)
    # dw accumulates into a single (8, dim) block across the sequential
    # grid; the partial is split evenly over 8 sublanes (exact: /8) and the
    # caller sums the rows.
    partial = jnp.sum(g * xhat, axis=0, keepdims=True) * 0.125
    dwp_ref[:] += jnp.broadcast_to(partial, dwp_ref.shape)


def _rows_block(n_rows: int, dim: int, bytes_per_elem: int) -> int:
    """Row-block size: a divisor of n_rows (Pallas pads out-of-bounds
    rows with undefined data on real TPU, and the backward's dw
    accumulation would silently fold that garbage into the weight
    gradient), capped so the block's fp32 working set fits scoped VMEM.
    bytes_per_elem estimates the live per-element footprint — ~12 B for
    the forward (x, out, fp32 copy), ~32 B for the backward (x, g, dx,
    xhat, wg and products); 10 MB of the 16 MB scoped limit leaves
    headroom for the weight row and rstd column."""
    from dlrover_tpu.ops.flash_attention import fit_block

    cap = max(8, (10 * 1024 * 1024) // (dim * bytes_per_elem))
    return fit_block(n_rows, min(256, cap))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fused_rms_norm(x: jax.Array, weight: jax.Array,
                   eps: float = 1e-6) -> jax.Array:
    """RMSNorm over the last dim: x * rsqrt(mean(x^2) + eps) * weight.

    Accepts any leading shape; rows are processed in VMEM blocks.
    """
    out, _ = _rms_fwd(x, weight, eps)
    return out


def _rms_fwd(x, weight, eps):
    orig_shape = x.shape
    dim = orig_shape[-1]
    x2 = x.reshape(-1, dim)
    rows = x2.shape[0]
    block = _rows_block(rows, dim, bytes_per_elem=12)
    grid = ((rows + block - 1) // block,)
    out, rstd = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, dim), lambda i: (i, 0)),
            pl.BlockSpec((dim,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block, dim), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            _sds(x2.shape, x.dtype, _vma(x2, weight)),
            _sds((rows, 1), jnp.float32, _vma(x2, weight)),
        ],
        interpret=not on_tpu(),
        name=KERNEL_FWD,
    )(x2, weight)
    return out.reshape(orig_shape), (x2, weight, rstd, orig_shape)


def _rms_fwd_vjp(x, weight, eps):
    return _rms_fwd(x, weight, eps)


def _rms_bwd_vjp(eps, res, g):
    x2, weight, rstd, orig_shape = res
    dim = x2.shape[1]
    rows = x2.shape[0]
    g2 = g.reshape(-1, dim)
    block = _rows_block(rows, dim, bytes_per_elem=32)
    n_blocks = (rows + block - 1) // block
    dx, dw_partial = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, eps=eps),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block, dim), lambda i: (i, 0)),
            pl.BlockSpec((dim,), lambda i: (0,)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((block, dim), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, dim), lambda i: (i, 0)),
            pl.BlockSpec((8, dim), lambda i: (0, 0)),
        ],
        out_shape=[
            _sds(x2.shape, x2.dtype, _vma(x2, weight, g2)),
            _sds((8, dim), jnp.float32, _vma(x2, weight, g2)),
        ],
        interpret=not on_tpu(),
        name=KERNEL_BWD,
    )(x2, weight, rstd, g2)
    dw = dw_partial.sum(axis=0).astype(weight.dtype)
    return dx.reshape(orig_shape), dw


fused_rms_norm.defvjp(_rms_fwd_vjp, _rms_bwd_vjp)


def mesh_rms_norm(x: jax.Array, weight: jax.Array,
                  eps: float = 1e-6) -> jax.Array:
    """fused_rms_norm partitioned over the ambient mesh.

    A Pallas kernel is a custom call the SPMD partitioner cannot split
    on real TPU ("Mosaic kernels cannot be automatically partitioned"),
    so under a multi-device mesh it runs inside a full-mesh shard_map:
    rows follow the activation layout (dim 0 over the joint dp axes,
    dim 1 over `sequence`; a dim the axes do not divide stays whole and
    is computed redundantly), the hidden dim and the weight are whole on
    every device. The custom VJP is INSIDE the shard_map, so its
    transpose psums each shard's partial weight gradient over the whole
    mesh — `_rms_bwd_kernel` only ever sums the rows of its own call.
    Plain call when there is no ambient mesh, on one device, or inside
    an already-manual region (see `in_manual_region`)."""
    from jax.sharding import PartitionSpec as P

    from dlrover_tpu.common.constants import MeshAxis
    from dlrover_tpu.parallel.mesh import current_mesh, data_axes

    mesh = current_mesh()
    if mesh is None or mesh.size == 1 or in_manual_region(x, weight):
        return fused_rms_norm(x, weight, eps)

    def fit(dim: int, axes):
        axes = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
        ways = math.prod(mesh.shape[a] for a in axes)
        return axes if axes and x.shape[dim] % ways == 0 else None

    rows = [fit(0, data_axes(mesh))] if x.ndim >= 2 else []
    if x.ndim >= 3:
        rows.append(fit(1, (MeshAxis.SEQUENCE,)))
    spec = P(*rows)
    fn = jax.shard_map(
        lambda a, w: fused_rms_norm(a, w, eps),
        mesh=mesh,
        in_specs=(spec, P()),
        out_specs=spec,
        check_vma=False,
    )
    return fn(x, weight)


def reference_rms_norm(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight).astype(x.dtype)
