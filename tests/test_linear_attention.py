"""Lightning attention (`ops/linear_attention.py`) on the CPU: the chunked
form in plain XLA and the Pallas kernels (interpret mode) against the
recurrence written step by step, forward and gradients, and the kernels
under a block's recomputation.

Tolerances: every form here runs in float32 at full precision, so the
chunked sums differ from the step-by-step ones by reassociation alone
(measured ~1e-7 of the largest value); 1e-5 leaves room for that and fails
any term of the chunked form left out or mis-scaled (a wrong decay power
moves the output by percents)."""

from __future__ import annotations

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import linear_attention as la
from dlrover_tpu.ops.remat import Kept, resolve_remat_policy

TOL = 1e-5
# a fast, a middling and a slow decay: exp(-0.9) per step down to ~1
RATES = jnp.array([0.9, 0.05, 0.004], jnp.float32)


def _operands(seq=64, d=16, e=8, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k = (jax.random.normal(key, (2, 3, seq, d)) * 0.5 for key in keys[:2])
    v = jax.random.normal(keys[2], (2, 3, seq, e))
    weight = jax.random.normal(keys[3], (2, 3, seq, e))
    return q, k, v, weight


def _close(mine, truth):
    scale = float(jnp.max(jnp.abs(truth)))
    np.testing.assert_allclose(np.asarray(mine), np.asarray(truth),
                               atol=TOL * scale, rtol=0)


@pytest.mark.parametrize("impl", ["xla", "kernel"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_form_is_the_recurrence_forward_and_backward(impl, chunk):
    """One chunk or four, both forms: the output and all three gradients of
    a weighted sum are the step-by-step recurrence's."""
    q, k, v, weight = _operands()
    scale = q.shape[-1] ** -0.5

    def loss(form):
        return lambda q, k, v: jnp.sum(form(q, k, v) * weight)

    def truth(q, k, v):
        return la.recurrence(q, k, v, RATES, scale)

    def mine(q, k, v):
        return la.linear_attention(q, k, v, RATES, scale, chunk=chunk,
                                   impl=impl)

    _close(mine(q, k, v), truth(q, k, v))
    every = (0, 1, 2)
    for got, want in zip(jax.grad(loss(mine), every)(q, k, v),
                         jax.grad(loss(truth), every)(q, k, v)):
        _close(got, want)


def test_a_decay_of_one_is_plain_causal_linear_attention():
    """rate 0 (lam = 1): o_t = scale q_t sum_{s <= t} k_s^T v_s."""
    q, k, v, _ = _operands(seq=32)
    rate = jnp.zeros((3,), jnp.float32)
    kv = jnp.cumsum(k[..., :, None] * v[..., None, :], axis=2)
    plain = 0.5 * jnp.einsum("bhsd,bhsde->bhse", q, kv,
                             precision=jax.lax.Precision.HIGHEST)
    for impl in ("xla", "kernel"):
        _close(la.linear_attention(q, k, v, rate, 0.5, chunk=8, impl=impl),
               plain)


def test_the_decay_takes_no_gradient():
    q, k, v, weight = _operands(seq=32)
    grad = jax.grad(lambda r: jnp.sum(la.linear_attention(
        q, k, v, r, 0.25, chunk=8, impl="kernel") * weight))(RATES)
    assert not np.any(np.asarray(grad))


@pytest.mark.parametrize("policy, forward_launches", [
    ("kernel_outputs", 1), ("nothing_saveable", 2)])
def test_the_forward_kernel_runs_once_under_a_policy_that_keeps_it(
        policy, forward_launches):
    """Under `jax.checkpoint` with `Kept`'s names kept, the forward kernel's
    output and the chunks' states are kept and it stands once in the
    gradient's program (twice where nothing is kept); the backward kernel
    once either way; the gradients are the plain ones to the last digit."""
    q, k, v, weight = _operands(seq=32)

    def objective(q, k, v):
        return jnp.sum(la.linear_attention(q, k, v, RATES, 0.25, chunk=8,
                                           impl="kernel") * weight)

    every = (0, 1, 2)
    kept = jax.grad(jax.checkpoint(
        objective, policy=resolve_remat_policy(policy)), argnums=every)
    launches = collections.Counter(re.findall(
        r"\bname=(\w+)", str(jax.make_jaxpr(kept)(q, k, v))))
    assert launches[la.KERNEL_FWD] == forward_launches
    assert launches[la.KERNEL_BWD] == 1
    assert launches[Kept.LIGHTNING] == 2        # the output and the states
    for mine, plain in zip(kept(q, k, v),
                           jax.grad(objective, argnums=every)(q, k, v)):
        np.testing.assert_array_equal(mine, plain)
