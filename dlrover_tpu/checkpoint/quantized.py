"""Quantized checkpoint codec: int8/int4 state trees for flash checkpoints.

Capability parity: the reference ships a CUDA quantization library whose
flagship consumer is communication/storage compression
(atorch/atorch/ops/csrc/quantization/quant_reduce.cu:248); here the same
groupwise-symmetric scheme (ops/quantization.py) compresses the
checkpoint itself — int8 cuts restore bytes ~4x vs fp32 (~2x vs bf16),
which is exactly the term that dominates kill→first-step recovery time
at multi-GB scale.

Design: a pure codec over pytrees, composed by FlashCheckpointer.

- ``encode_tree(state)``: every *eligible* float leaf (ndim >= 1, last
  dim divisible by the group size) becomes ``{"__quant__", "q", "s"}``
  — int8 codes + fp32 groupwise scales; everything else (int counters,
  scalars, ragged tails) rides along raw. The transform is jittable and
  runs on device, so a sharded train state quantizes shard-locally with
  no gather.
- ``abstract_encoded(abstract_state)``: the matching abstract target for
  Orbax's reshard-on-restore — ``q`` keeps the leaf's partitioning on
  every dim but the (group-quantized) last one, so multi-GB restores
  still stream shard-parallel from disk; scales are tiny and land
  replicated.
- ``decode_tree(encoded, abstract_state)``: dequantize + cast back,
  jitted with the target shardings (the reshard happens inside XLA).

Eligibility is a pure function of the abstract state, so the save and
restore sides always agree on the tree structure.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.ops.quantization import pack_int4, unpack_int4

_TAG = "__quant__"
DEFAULT_GROUP = 128


def _mode(leaf: Any, group_size: int) -> str:
    """row: groupwise over the (divisible) last dim, layout preserved —
    big matmul weights keep their partitioning, so multi-GB restores
    stream shard-parallel. flat: flatten + zero-pad to the group size —
    catches ragged/small-last-dim leaves (embeddings, odd heads) at the
    cost of a replicated restore. raw: not worth compressing."""
    dtype = jnp.dtype(leaf.dtype)
    if not (jnp.issubdtype(dtype, jnp.floating)
            and getattr(leaf, "ndim", 0) >= 1):
        return "raw"
    if leaf.shape[-1] % group_size == 0 and leaf.shape[-1] > 0:
        return "row"
    size = int(np.prod(leaf.shape))
    if size >= group_size:
        return "flat"
    return "raw"


def _is_encoded(node: Any) -> bool:
    return isinstance(node, dict) and _TAG in node


def _quantize_groups(x2: jax.Array, qmax: int) -> tuple:
    absmax = jnp.max(jnp.abs(x2), axis=-1, keepdims=True)
    scale = absmax / qmax
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(x2 * inv), -qmax, qmax).astype(jnp.int8)
    return q, scale


def _quantize_leaf(x: jax.Array, bits: int, group_size: int,
                   mode: str) -> dict:
    qmax = 127 if bits == 8 else 7
    if mode == "flat":
        flat = x.reshape(-1).astype(jnp.float32)
        pad = (-flat.shape[0]) % group_size
        if pad:
            flat = jnp.pad(flat, (0, pad))
        x2 = flat.reshape(-1, group_size)
    else:
        x2 = x.reshape(-1, group_size).astype(jnp.float32)
    q, scale = _quantize_groups(x2, qmax)
    if mode == "flat":
        q = q.reshape(-1)
        scales = scale.reshape(-1)
    else:
        q = q.reshape(x.shape)
        scales = scale.reshape(x.shape[:-1] + (x.shape[-1] // group_size,))
    if bits == 4:
        q = pack_int4(q)
    return {_TAG: jnp.asarray(bits, jnp.int32), "q": q, "s": scales}


def _dequantize_leaf(node: dict, target: Any, bits: int,
                     group_size: int, mode: str) -> jax.Array:
    q = node["q"]
    if bits == 4:
        q = unpack_int4(q)
    if mode == "flat":
        q2 = q.reshape(-1, group_size)
        s2 = node["s"].reshape(-1, 1)
        out = (q2.astype(jnp.float32) * s2).reshape(-1)
        size = int(np.prod(target.shape))
        return out[:size].astype(target.dtype).reshape(target.shape)
    groups = node["s"].shape[-1]
    q2 = q.reshape(-1, q.shape[-1] // groups)
    s2 = node["s"].reshape(-1, 1)
    out = (q2.astype(jnp.float32) * s2).astype(target.dtype)
    return out.reshape(target.shape)


@functools.lru_cache(maxsize=None)
def _jitted_quantizer(bits: int, group_size: int, mode: str):
    return jax.jit(functools.partial(
        _quantize_leaf, bits=bits, group_size=group_size, mode=mode))


@functools.lru_cache(maxsize=None)
def _jitted_dequantizer(bits: int, group_size: int, mode: str,
                        shape, dtype):
    target = jax.ShapeDtypeStruct(shape, dtype)
    return jax.jit(lambda q, s: _dequantize_leaf(
        {_TAG: bits, "q": q, "s": s}, target, bits, group_size, mode))


def encode_tree(state: Any, bits: int = 8,
                group_size: int = DEFAULT_GROUP) -> Any:
    """Quantize eligible leaves on device, one small jitted program per
    unique (shape, mode) — NOT one whole-tree program: a mega-program
    with hundreds of big-tensor outputs is one long compile per state
    layout, while the per-leaf programs hit jit's cache across leaves
    and saves."""
    if bits not in (8, 4):
        raise ValueError(f"checkpoint quantization bits must be 8 or 4, "
                         f"got {bits}")

    def _leaf(leaf):
        mode = _mode(leaf, group_size)
        if mode == "raw":
            return leaf
        return _jitted_quantizer(bits, group_size, mode)(leaf)

    return jax.tree.map(_leaf, state)


def abstract_encoded(abstract_state: Any, bits: int = 8,
                     group_size: int = DEFAULT_GROUP) -> Any:
    """Abstract (ShapeDtypeStruct) target matching encode_tree's output,
    carrying restore shardings derived from the abstract state's."""

    def _leaf(leaf):
        mode = _mode(leaf, group_size)
        if mode == "raw":
            return leaf
        sharding = getattr(leaf, "sharding", None)
        q_sharding = s_sharding = r_sharding = None
        if isinstance(sharding, NamedSharding):
            s_sharding = NamedSharding(sharding.mesh, P())
            r_sharding = s_sharding
            if mode == "row":
                # keep every partitioned dim but the last (its groups may
                # not divide by the axis); scales/tag are tiny → replicated
                spec = list(sharding.spec) + [None] * (
                    leaf.ndim - len(sharding.spec))
                spec[-1] = None
                q_sharding = NamedSharding(sharding.mesh, P(*spec))
            else:
                q_sharding = s_sharding
        if mode == "flat":
            size = int(np.prod(leaf.shape))
            padded = size + (-size) % group_size
            q_shape = (padded // (2 if bits == 4 else 1),)
            s_shape = (padded // group_size,)
        else:
            q_shape = leaf.shape[:-1] + (
                leaf.shape[-1] // (2 if bits == 4 else 1),)
            s_shape = leaf.shape[:-1] + (leaf.shape[-1] // group_size,)
        return {
            _TAG: jax.ShapeDtypeStruct((), jnp.int32,
                                       sharding=r_sharding),
            "q": jax.ShapeDtypeStruct(q_shape, jnp.int8,
                                      sharding=q_sharding),
            "s": jax.ShapeDtypeStruct(s_shape, jnp.float32,
                                      sharding=s_sharding),
        }

    return jax.tree.map(_leaf, abstract_state)


def decode_tree(encoded: Any, abstract_state: Any, bits: int = 8,
                group_size: int = DEFAULT_GROUP) -> Any:
    """Dequantize back into the abstract state's dtypes + shardings —
    per-leaf jitted programs (see encode_tree), with each result
    device_put into the target's sharding when one is given."""
    enc_leaves = jax.tree.leaves(encoded, is_leaf=_is_encoded)
    targets, treedef = jax.tree.flatten(abstract_state)
    assert len(enc_leaves) == len(targets), (
        f"encoded tree has {len(enc_leaves)} leaves, target "
        f"{len(targets)} — quantization eligibility drifted between "
        f"save and restore")

    out = []
    for node, target in zip(enc_leaves, targets):
        if _is_encoded(node):
            mode = _mode(target, group_size)
            fn = _jitted_dequantizer(bits, group_size, mode,
                                     tuple(target.shape),
                                     jnp.dtype(target.dtype))
            leaf = fn(node["q"], node["s"])
        else:
            leaf = jnp.asarray(node, target.dtype)
        sharding = getattr(target, "sharding", None)
        if isinstance(sharding, NamedSharding):
            leaf = jax.device_put(leaf, sharding)
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def encoded_nbytes(encoded: Any) -> int:
    """Serialized payload bytes of an (abstract or concrete) tree."""
    return sum(
        int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(encoded)
        if hasattr(leaf, "shape"))
