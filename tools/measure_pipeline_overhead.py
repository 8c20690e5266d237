"""Measure the pipeline's enter/exit overhead.

The circular schedule computes the enter (embedding) and exit
(norm + head + loss) bodies under selection on every device, so part of
every step is architectural waste. Two measurements:

1. **Per-step FLOP share** from the COMPILED program: XLA's cost
   analysis counts a scan body once, so the FLOP delta between the real
   program and one whose exit_fn is stubbed to ~zero cost is the
   per-step exit overhead — the compiled-program version of the
   docstring's analytic ~V/(12·H·layers_per_chunk) estimate.
2. **Wall-clock share** on the 8-virtual-device CPU mesh (indicative,
   not TPU time): same full-vs-stubbed pair, timed.

With num_rounds C > 1 the uniform-predicate lax.cond in pipeline_train
executes the enter/exit bodies on only ~1/C of steps; the wall-clock
pair captures that saving (the FLOP count may not — cost analysis sums
both cond branches).

Prints one JSON line per (S, C, M) config.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_platforms", "cpu")

from jax.sharding import Mesh  # noqa: E402

from dlrover_tpu.parallel.pipeline import pipeline_train  # noqa: E402


def build(S, C, M, micro, seq, hidden, vocab, layers_per_chunk, stub):
    rng = np.random.default_rng(0)

    def mk(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.02, jnp.float32)

    chunk_params = {
        "w1": mk(C, S, layers_per_chunk, hidden, 4 * hidden),
        "w2": mk(C, S, layers_per_chunk, 4 * hidden, hidden),
    }
    shared = {"embed": mk(vocab, hidden), "head": mk(hidden, vocab)}

    def chunk_fn(p, x):
        def layer(x, wl):
            w1, w2 = wl
            return x + jnp.tanh(x @ w1) @ w2, None

        x, _ = jax.lax.scan(layer, x, (p["w1"], p["w2"]))
        return x

    def enter_fn(shared, tok):
        return shared["embed"][tok]

    if stub:
        def exit_fn(shared, act, tgt):
            # ~zero-cost exit with the same output shape: isolates the
            # head-matmul + softmax share of the step
            return jnp.mean(act, axis=(-1, -2))
    else:
        def exit_fn(shared, act, tgt):
            logits = act @ shared["head"]
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, tgt[..., None], axis=-1)[..., 0]
            return jnp.mean(nll, axis=-1)

    tokens = jnp.asarray(
        rng.integers(0, vocab, (M, micro, seq)), jnp.int32)
    targets = jnp.asarray(
        rng.integers(0, vocab, (M, micro, seq)), jnp.int32)

    devices = np.array(jax.devices("cpu")[:S]).reshape(S)
    mesh = Mesh(devices, ("pipe",))

    def loss_fn(chunk_params, shared, tokens, targets):
        return pipeline_train(
            mesh, chunk_fn, chunk_params, shared, enter_fn, exit_fn,
            tokens, targets, num_rounds=C)

    compiled = (jax.jit(loss_fn)
                .lower(chunk_params, shared, tokens, targets).compile())
    return compiled, (chunk_params, shared, tokens, targets)


def timed(compiled, args, n=5):
    out = compiled(*args)
    float(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = compiled(*args)
    float(out)
    return (time.perf_counter() - t0) / n * 1e3


def measure(S=4, C=2, M=8, micro=1, seq=128, hidden=512, vocab=2048,
            layers_per_chunk=4):
    """Default shapes keep Llama-7B's exit-to-chunk FLOP RATIO
    (V/(V + 8·H·lpc): 32000/(32000+8·4096·8) = 0.109 at 7B;
    2048/(2048+8·512·4) = 0.111 here) at CPU-mesh-runnable sizes — the
    share is shape-determined, so the measured number transfers."""
    shapes = (S, C, M, micro, seq, hidden, vocab, layers_per_chunk)
    full, args = build(*shapes, stub=False)
    stubbed, sargs = build(*shapes, stub=True)
    f_full = float(full.cost_analysis().get("flops", -1.0))
    f_stub = float(stubbed.cost_analysis().get("flops", -1.0))
    w_full = timed(full, args)
    w_stub = timed(stubbed, sargs)
    analytic = vocab / (vocab + 8 * hidden * layers_per_chunk)
    print(json.dumps({
        "S": S, "C": C, "M": M,
        "per_step_flops_g": round(f_full / 1e9, 3),
        "exit_flop_share_per_step": round(1 - f_stub / f_full, 4),
        "analytic_share": round(analytic, 4),
        "wall_full_ms": round(w_full, 1),
        "wall_stub_ms": round(w_stub, 1),
        "exit_wall_share": round(1 - w_stub / w_full, 4),
    }))


if __name__ == "__main__":
    for cfg in (dict(S=4, C=1, M=8), dict(S=4, C=2, M=8),
                dict(S=8, C=2, M=16)):
        measure(**cfg)
