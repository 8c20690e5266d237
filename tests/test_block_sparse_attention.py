"""InfLLM-v2 block-sparse attention (`ops/block_sparse_attention.py`) on the
CPU: the selection's rules, the flash kernels given the block selection
(interpret mode) against whole-array attention under the mask expanded to
keys, the skipped tiles, and a block's recomputation reusing the kept
selection.

Tolerances: float32 operands at full precision in every form, so the
kernels' blockwise softmax differs from the whole-row one by reassociation
alone (~1e-7 of the largest value); 1e-5 fails a key let in or left out,
which moves its row by percents."""

from __future__ import annotations

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import block_sparse_attention as bs
from dlrover_tpu.ops.flash_attention import reference_attention
from dlrover_tpu.ops.remat import Kept, resolve_remat_policy

TOL = 1e-5
# 256 keys in 16 blocks of 16; a query takes 4: block 0, the two holding
# the last 32 keys, and the best other one
SPARSE = bs.Sparsity(block=16, topk=4, kernel=8, stride=4, init_blocks=1,
                     window=32, dense_len=64)


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles at which a 256-long sequence takes 8 q tiles and 4 kv tiles of
    4 blocks each (read when a call is traced)."""
    jax.clear_caches()
    monkeypatch.setattr(bs, "DEFAULT_BLOCK_Q", 32)
    monkeypatch.setattr(bs, "DEFAULT_BLOCK_K", 64)
    yield
    jax.clear_caches()


def _qkv(seq=256, heads=4, groups=2, d=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (1, heads, seq, d))
    k = jax.random.normal(keys[1], (1, groups, seq, d))
    v = jax.random.normal(keys[2], (1, groups, seq, d))
    weight = jax.random.normal(keys[3], (1, heads, seq, d))
    return q, k, v, weight


def _block_scores_by_hand(q, k, sp: bs.Sparsity) -> np.ndarray:
    """(b, G, S, blocks): the selection's scores written as loops."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    b, heads, seq, d = q.shape
    groups = k.shape[1]
    starts = range(0, seq - sp.kernel + 1, sp.stride)
    compressed = np.stack([k[:, :, s:s + sp.kernel].mean(2) for s in starts],
                          axis=2)
    out = np.zeros((b, groups, seq, seq // sp.block))
    for t in range(seq):
        counted = [i for i, s in enumerate(starts) if s + sp.kernel - 1 <= t]
        if not counted:
            continue
        for g in range(groups):
            r = 0.0
            for h in range(g * heads // groups, (g + 1) * heads // groups):
                s = np.einsum("bd,bnd->bn", q[:, h, t],
                              compressed[:, g, counted]) / np.sqrt(d)
                s = np.exp(s - s.max(-1, keepdims=True))
                r = r + s / s.sum(-1, keepdims=True)
            for j in range(seq // sp.block):
                over = [n for n, i in enumerate(counted)
                        if starts[i] < (j + 1) * sp.block
                        and starts[i] + sp.kernel > j * sp.block]
                out[:, g, t, j] = r[:, over].max(-1) if over else 0.0
    return out


def test_compressed_keys_are_the_means_of_their_spans():
    _, k, _, _ = _qkv(seq=64)
    got = np.asarray(bs.compressed_keys(k, SPARSE))
    for i in range(got.shape[2]):
        span = np.asarray(k[:, :, 4 * i:4 * i + 8]).mean(2)
        np.testing.assert_allclose(got[:, :, i], span, rtol=1e-6, atol=1e-6)
    assert got.shape[2] == (64 - 8) // 4 + 1


def test_the_selection_keeps_its_rules():
    """Causal; block 0 and the window's two blocks in every row; as many
    blocks as a row has up to topk, all of them while it has no more; and
    of the rest the best: no block left out scores above one taken by
    score (the scores written out by hand)."""
    q, k, _, _ = _qkv()
    chosen = np.asarray(bs.select_blocks(q, k, SPARSE)) != 0
    seq, blocks = 256, 16
    own = np.arange(seq)[:, None] // 16
    j = np.arange(blocks)[None, :]
    assert not (chosen & (j > own)).any()
    forced = (j == 0) | ((j > own - 2) & (j <= own))
    assert (chosen | ~forced).all()
    assert (chosen.sum(-1) == np.minimum(own[:, 0] + 1, 4)).all()
    everything = own[:, 0] < 4
    assert (chosen[..., everything, :] == (j <= own)[everything]).all()
    scores = _block_scores_by_hand(q, k, SPARSE)
    taken = np.where(chosen & ~forced, scores, np.inf).min(-1)
    left = np.where(~chosen & (j <= own), scores, -np.inf).max(-1)
    assert (left <= taken + 1e-6).all()
    assert (chosen & ~forced).any()             # the scores chose some


def test_ties_go_to_the_lower_block():
    """Keys all alike: every counted compressed key scores the same, so
    every block with one scores the same, and the one block chosen by score
    is the lowest not forced in."""
    q, _, _, _ = _qkv()
    k = jnp.ones((1, 2, 256, 16))
    chosen = np.asarray(bs.select_blocks(q, k, SPARSE)) != 0
    for t in range(4 * 16, 256):
        own = t // 16
        assert list(np.flatnonzero(chosen[0, 0, t])) == [0, 1, own - 1, own]


def test_a_sequence_below_dense_len_is_attended_whole():
    q, k, _, _ = _qkv(seq=48)
    chosen = np.asarray(bs.select_blocks(q, k, SPARSE)) != 0
    assert (chosen == (np.arange(3)[None, :]
                       <= np.arange(48)[:, None] // 16)).all()


def _hand_mask(seq=256, size=16, seed=1):
    """Row t takes block 0, its own block and, as the seed draws, the block
    before its own: the kv tiles between go unvisited."""
    blocks = seq // size
    rng = np.random.default_rng(seed)
    own = np.arange(seq)[:, None] // size
    j = np.arange(blocks)[None, :]
    mask = (j == 0) | (j == own) | (
        (j == own - 1) & (rng.random((1, 2, seq, 1)) < 0.5))
    return jnp.asarray(mask & (j <= own), jnp.int8)


def test_the_kernels_are_whole_row_attention_under_the_mask(small_tiles):
    """Forward and all three gradients of a weighted sum, the kernels
    against the plain form, with kv tiles that no row of a q tile chose
    (skipped by the kernels)."""
    q, k, v, weight = _qkv()
    mask = _hand_mask()
    # a (q tile, kv tile) pair is visited where a row of the one chose a
    # block of the other: counted here by hand, of each group's 20 causal
    # pairs
    chose = np.asarray(mask).reshape(1, 2, 8, 32, 4, 4).any(axis=(3, 5))
    visit = np.asarray(bs.kernel_operands(mask, 16).visit)
    np.testing.assert_array_equal(visit, chose.reshape(-1))
    assert 2 * 6 < visit.sum() < 2 * 20
    assert float(bs.tiles_visited_share(mask, 16)) == pytest.approx(
        visit.sum() / 40)

    def loss(impl):
        return lambda q, k, v: jnp.sum(
            bs.attend(q, k, v, mask, 16, impl=impl) * weight)

    every = (0, 1, 2)
    scale = float(jnp.max(jnp.abs(v)))
    np.testing.assert_allclose(
        bs.attend(q, k, v, mask, 16, impl="kernel"),
        bs.attend(q, k, v, mask, 16, impl="xla"), atol=TOL * scale, rtol=0)
    for mine, plain in zip(jax.grad(loss("kernel"), every)(q, k, v),
                           jax.grad(loss("xla"), every)(q, k, v)):
        np.testing.assert_allclose(
            mine, plain, atol=TOL * float(jnp.max(jnp.abs(plain))), rtol=0)


def test_every_causal_block_is_causal_attention(small_tiles):
    """All blocks chosen: the kernels are dense causal attention."""
    q, k, v, _ = _qkv(seq=128)
    mask = jnp.asarray(np.arange(8)[None, :] <= np.arange(128)[:, None] // 16,
                       jnp.int8)
    mask = jnp.broadcast_to(mask, (1, 2, 128, 8))
    dense = reference_attention(q, k, v, True)
    for impl in ("xla", "kernel"):
        # v is standard normal: 4 stands for the largest output's scale
        np.testing.assert_allclose(bs.attend(q, k, v, mask, 16, impl=impl),
                                   dense, atol=TOL * 4, rtol=0)
    assert float(bs.tiles_visited_share(mask, 16)) == 1.0


@pytest.mark.parametrize("policy, forward_launches", [
    ("kernel_outputs", 1), ("nothing_saveable", 2)])
def test_recomputation_reuses_the_kept_selection(policy, forward_launches,
                                                 small_tiles):
    """Under `jax.checkpoint` with `Kept`'s names kept, the selection (its
    one `top_k`) and the forward kernel stand once in the gradient's
    program, twice where nothing is kept; the backward pair once; the
    gradients are the plain ones to the last digit."""
    q, k, v, weight = _qkv()

    def objective(q, k, v):
        out, _ = bs.block_sparse_attention(q, k, v, SPARSE, impl="kernel")
        return jnp.sum(out * weight)

    every = (0, 1, 2)
    kept = jax.grad(jax.checkpoint(
        objective, policy=resolve_remat_policy(policy)), argnums=every)
    text = str(jax.make_jaxpr(kept)(q, k, v))
    launches = collections.Counter(re.findall(r"\bname=(\w+)", text))
    assert launches["block_sparse_attn_fwd"] == forward_launches
    assert launches["block_sparse_attn_dq"] == 1
    assert launches["block_sparse_attn_dkv"] == 1
    assert len(re.findall(r"\btop_k\[", text)) == forward_launches
    assert launches[Kept.BLOCKS] and launches[Kept.BLOCK_SPARSE]
    for mine, plain in zip(kept(q, k, v),
                           jax.grad(objective, argnums=every)(q, k, v)):
        np.testing.assert_array_equal(mine, plain)
