"""Standalone kernel benchmark: flash-attention TF/s at bench shapes.

The headline MFU wall is the attention kernel — the MLP matmul runs
near peak, so the next MFU points live here. This measures the Pallas kernel's effective TF/s (fwd and fwd+bwd) against
the XLA reference at the shapes the headline bench uses, so kernel
surgery has a number to move. Prints one JSON line per config.

FLOP accounting: causal attention does 2*s*s*d FLOPs per (batch, head)
for QK^T and the same for PV, halved by causality -> fwd
2*b*h*s*s*d. Backward recomputes fwd block products and adds dQ/dK/dV
products: ~2.5x fwd FLOPs (standard flash accounting).

Run on the chip: `python bench_kernels.py`. Off the chip it exits
non-zero: an interpreter's time is not a kernel's.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _force(out) -> float:
    """Force execution with a host transfer of one scalar: device
    execution is in-order, so forcing the last step's output proves all
    prior steps finished."""
    leaf = jax.tree.leaves(out)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def bench_one(fn, args, reps=20, timed_calls=3):
    """Time `fn` amortized over `reps` sequential calls INSIDE one jitted
    program (a scan whose carry perturbs q each iteration, so calls can't
    be CSE'd) — per-call dispatch would swamp a ~1 ms kernel when timed
    call-by-call; inside the model's jitted step the kernel pays no such
    cost."""
    q0, *rest = args

    @jax.jit
    def many(q, *rest):
        def body(c, _):
            o = fn(c, *rest)
            lead = jax.tree.leaves(o)[0]
            return c + 1e-6 * lead.astype(c.dtype), None

        c, _ = jax.lax.scan(body, q, None, length=reps)
        return c

    out = many(q0, *rest)          # compile + warm
    _force(out)
    t0 = time.perf_counter()
    for _ in range(timed_calls):
        out = many(q0, *rest)
    _force(out)
    return (time.perf_counter() - t0) / (timed_calls * reps)


def main() -> None:
    from dlrover_tpu.models.llama import reference_attention
    from dlrover_tpu.ops.backend import on_tpu
    from dlrover_tpu.ops.flash_attention import flash_attention

    if not on_tpu():
        raise SystemExit(
            f"bench_kernels.py measures on a TPU; jax found "
            f"{jax.default_backend()!r}")
    device = jax.devices()[0]
    # headline bench shape (llama_wide_1b at micro 2, seq 2048) and a
    # 7B-shaped config
    configs = [
        ("bench_1b", 2, 16, 2048, 128),
        ("llama7b", 1, 32, 2048, 128),
        ("long_8k", 1, 16, 8192, 128),
    ]
    variants = [("flash", dict(block_q=1024, block_k=1024)),
                ("flash_512", dict(block_q=512, block_k=512)),
                ("xla_ref", None)]

    rng = np.random.default_rng(0)
    for name, b, h, s, d in configs:
        q, k, v = (jnp.asarray(rng.normal(size=(b, h, s, d)),
                               jnp.bfloat16) for _ in range(3))
        fwd_flops = 2 * 2 * b * h * s * s * d / 2   # causal half
        for vname, kwargs in variants:
            if kwargs is None:
                f = jax.jit(lambda q, k, v: reference_attention(
                    q, k, v, True))
            else:
                kw = dict(kwargs)
                f = jax.jit(lambda q, k, v, _kw=kw: flash_attention(
                    q, k, v, True, **_kw))
            try:
                dt_f = bench_one(f, (q, k, v))

                def loss(q, k, v, _f=f):
                    return jnp.sum(_f(q, k, v).astype(jnp.float32))

                g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                dt_b = bench_one(g, (q, k, v))
                print(json.dumps({
                    "device_kind": device.device_kind,
                    "config": name, "variant": vname,
                    "fwd_ms": round(dt_f * 1e3, 3),
                    "fwd_tflops": round(fwd_flops / dt_f / 1e12, 1),
                    "fwdbwd_ms": round(dt_b * 1e3, 3),
                    "fwdbwd_tflops": round(
                        3.5 * fwd_flops / dt_b / 1e12, 1),
                }))
            except Exception as e:
                print(json.dumps({"config": name, "variant": vname,
                                  "error": str(e)[:200]}))


if __name__ == "__main__":
    main()
