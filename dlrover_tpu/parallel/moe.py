"""Mixture-of-Experts with expert parallelism.

Capability parity: atorch modules/moe/ — `MOELayer` (moe_layer.py:161),
`Experts` (:116), top-k gating (topk_gating.py), switch gating
(switch_gating.py), `_AllToAll` autograd (:87), expert process groups
(:29).

TPU re-design: the classic capacity-based dispatch/combine einsum
formulation (Mesh-TensorFlow / Switch Transformer lineage): the router
builds a dispatch mask (tokens → expert capacity slots) and combine
weights; expert parameters carry an "expert" logical axis mapped to the
`expert` mesh axis, and XLA inserts the all-to-all when the dispatch
einsum crosses the expert sharding — no explicit _AllToAll autograd
function needed (its transpose falls out of autodiff).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    hidden_size: int = 512
    expert_intermediate: int = 1024
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    jitter_noise: float = 0.0       # router input jitter (switch-style)
    aux_loss_weight: float = 0.01
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32


def _capacity(tokens_per_group: int, num_experts: int,
              capacity_factor: float, min_capacity: int) -> int:
    capacity = int(tokens_per_group * capacity_factor / num_experts)
    return max(capacity, min_capacity)


def top_k_gating(
    router_logits: jax.Array,     # (G, S, E) groups × tokens × experts
    top_k: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Capacity-based top-k routing.

    Returns (dispatch_mask (G,S,E,C) bool, combine_weights (G,S,E,C),
    aux_loss). Tokens over an expert's capacity are dropped (the standard
    TPU MoE contract; the residual path keeps them alive).
    """
    groups, seq, num_experts = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)

    # load-balancing aux loss (Switch eq. 4): E * Σ_e f_e · P_e
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, num_experts), axis=1)   # (G, E)
    p = jnp.mean(probs, axis=1)                               # (G, E)
    aux_loss = num_experts * jnp.mean(jnp.sum(f * p, axis=-1))

    # iteratively take the k best experts per token
    dispatch = jnp.zeros((groups, seq, num_experts, capacity),
                         dtype=jnp.bool_)
    combine = jnp.zeros((groups, seq, num_experts, capacity),
                        dtype=jnp.float32)
    remaining = probs
    # slots already used per expert, carried across the k rounds
    fill = jnp.zeros((groups, num_experts), dtype=jnp.int32)
    for _ in range(top_k):
        expert_idx = jnp.argmax(remaining, axis=-1)           # (G, S)
        gate = jnp.take_along_axis(remaining, expert_idx[..., None],
                                   axis=-1)[..., 0]           # (G, S)
        onehot = jax.nn.one_hot(expert_idx, num_experts,
                                dtype=jnp.int32)              # (G, S, E)
        # position of each token in its expert's queue this round
        position = jnp.cumsum(onehot, axis=1) - 1 + fill[:, None, :]
        position = jnp.sum(position * onehot, axis=-1)        # (G, S)
        within = position < capacity
        slot_onehot = (
            jax.nn.one_hot(position, capacity, dtype=jnp.float32)
            * (onehot.sum(-1) * within)[..., None])           # (G, S, C)
        this_dispatch = (onehot[..., None] *
                         slot_onehot[:, :, None, :]).astype(jnp.bool_)
        dispatch = dispatch | this_dispatch
        combine = combine + this_dispatch * gate[..., None, None]
        fill = fill + jnp.sum(onehot * within[..., None].astype(jnp.int32),
                              axis=1)
        remaining = remaining * (1.0 - onehot.astype(remaining.dtype))
    # renormalize combine weights over the selected experts
    denom = jnp.sum(combine, axis=(2, 3), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    return dispatch, combine, aux_loss


class ExpertMLP(nn.Module):
    """E parallel feed-forward experts; params carry the 'expert' logical
    axis so EP shards them (atorch Experts analog)."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        # x: (E, C_total, H)
        cfg = self.cfg
        wi = self.param(
            "wi",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "embed", "mlp")),
            (cfg.num_experts, cfg.hidden_size, cfg.expert_intermediate),
            cfg.param_dtype,
        )
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "mlp", "embed")),
            (cfg.num_experts, cfg.expert_intermediate, cfg.hidden_size),
            cfg.param_dtype,
        )
        x = x.astype(cfg.dtype)
        h = jnp.einsum("ech,ehm->ecm", x, wi.astype(cfg.dtype))
        h = nn.gelu(h)
        return jnp.einsum("ecm,emh->ech", h, wo.astype(cfg.dtype))


class MoELayer(nn.Module):
    """Drop-in MLP replacement: (..., S, H) → (..., S, H) + aux loss via
    `self.sow('losses', 'moe_aux_loss', ...)` (atorch MOELayer analog)."""

    cfg: MoEConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        orig_shape = x.shape
        hidden = orig_shape[-1]
        # flatten leading dims into routing groups
        x = x.reshape((-1,) + orig_shape[-2:])    # (G, S, H)
        groups, seq, _ = x.shape

        router = self.param(
            "router",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "expert")),
            (hidden, cfg.num_experts),
            jnp.float32,
        )
        router_in = x.astype(jnp.float32)
        if cfg.jitter_noise > 0 and not self.deterministic:
            rng = self.make_rng("gating")
            router_in = router_in * jax.random.uniform(
                rng, router_in.shape, minval=1.0 - cfg.jitter_noise,
                maxval=1.0 + cfg.jitter_noise)
        logits = router_in @ router                # (G, S, E)

        capacity = _capacity(seq, cfg.num_experts,
                             cfg.capacity_factor if not self.deterministic
                             else cfg.eval_capacity_factor,
                             cfg.min_capacity)
        capacity = min(capacity, seq)
        dispatch, combine, aux_loss = top_k_gating(
            logits, cfg.top_k, capacity)
        self.sow("losses", "moe_aux_loss", cfg.aux_loss_weight * aux_loss)

        # dispatch: (G,S,E,C) × (G,S,H) → (E, G*C, H); the contraction
        # crossing the expert-sharded dim is where XLA places the
        # all-to-all when E is sharded over the expert mesh axis
        expert_in = jnp.einsum("gsec,gsh->egch",
                               dispatch.astype(x.dtype), x)
        expert_in = expert_in.reshape(cfg.num_experts,
                                      groups * capacity, hidden)
        expert_in = nn.with_logical_constraint(
            expert_in, ("expert", None, "embed"))
        expert_out = ExpertMLP(cfg)(expert_in)
        expert_out = expert_out.reshape(cfg.num_experts, groups, capacity,
                                        hidden)
        out = jnp.einsum("gsec,egch->gsh",
                         combine.astype(expert_out.dtype), expert_out)
        return out.reshape(orig_shape).astype(x.dtype)


def moe_aux_loss(variables) -> jax.Array:
    """Collect sown aux losses from a model's 'losses' collection."""
    losses = variables.get("losses", {})
    total = 0.0
    for leaf in jax.tree.leaves(losses):
        total = total + jnp.sum(leaf)
    return total


def sown_counters(variables) -> dict:
    """name -> mean over whoever sowed it, of a model's `counters`
    collection: what a step counts of itself (an expert layer's load) and
    hands to the step's metrics. Empty for a model that sows none."""
    by_name: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            variables.get("counters", {}))[0]:
        names = [str(getattr(k, "key", k)) for k in path
                 if isinstance(getattr(k, "key", None), str)]
        by_name.setdefault(names[-1], []).append(jnp.mean(leaf))
    return {name: jnp.mean(jnp.stack(values))
            for name, values in by_name.items()}


# ===========================================================================
# Experts held by share: nothing dropped, no capacity
# ===========================================================================
# The layer one chip of an expert-parallel group runs: it is told how many
# experts the model has, how many it holds and which is its first, routes
# every token over ALL of them and sorts the token-expert assignments by
# expert, the held experts' first. It then works through the sorted order a
# chunk of rows at a time (`chunk_rows`: half the even routing's share of
# the assignments): a chunk gathers its token rows, multiplies them by grouped
# matmuls (`jax.lax.ragged_dot`) with its own group sizes and is skipped, by
# a real branch, when it starts past the last held row. So every held row is
# computed under every routing, and the cost follows the rows held to within
# one chunk, not the worst case's tokens x top_k. The layer adds its own
# experts' weighted outputs; what the absent experts would add is left out:
# on a mesh that is the other chips' part, and no code here stands in for
# them or for their exchange. The capacity-based `MoELayer` above stays for
# `models/llama_moe.py` until that model runs on this layer (ROADMAP R8).

@dataclasses.dataclass(frozen=True)
class HeldExpertsConfig:
    num_experts: int = 8            # the model's, the router's width
    experts_held: int = 8           # held here: first_expert ... + held
    first_expert: int = 0
    top_k: int = 2
    hidden_size: int = 512
    expert_intermediate: int = 1024
    norm_topk_prob: bool = True     # the k gates renormalised to sum 1
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32


def route_top_k(router_logits: jax.Array, top_k: int,
                norm_topk_prob: bool) -> Tuple[jax.Array, jax.Array]:
    """(gates (T, k) float32, experts (T, k) int32): softmax over every
    expert in float32, the k largest, renormalised where the model says."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32)


def held_assignments(experts: jax.Array, first_expert: int,
                     experts_held: int):
    """Sort the (T, k) assignments by expert, held experts first in their
    order and every absent expert after them. Returns (held (T k,) bool,
    which assignments are of a held expert; order (T k,), the flat
    assignment at each sorted row; sizes (held,), the rows of each held
    expert)."""
    local = experts.reshape(-1) - first_expert
    held = (local >= 0) & (local < experts_held)
    group = jnp.where(held, local, experts_held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(group[:, None] == jnp.arange(experts_held)[None, :],
                    axis=0, dtype=jnp.int32)
    return held, order, sizes


def chunk_rows(assignments: int, experts_held: int, num_experts: int) -> int:
    """Rows of one chunk of the sorted order: half of the held experts'
    share of the assignments under an even routing, that share first taken
    up to a multiple of 512 (8,192 of 131,072 where an eighth is held: the
    chip multiplies what a routing leaves under half a share at half the
    price, and an even routing's 16,384 and a few more rows in three chunks
    and not in two of 16,384); every assignment, one chunk, where every
    expert is held."""
    even = -(-assignments * experts_held // num_experts)
    if even >= assignments:
        return assignments
    return min(-(-even // 512) * 256, assignments)


def _chunk_experts(rows, gate, stacks, sizes, live):
    """(R, H) -> (R, H) float32: `gate * w2(silu(w1 x) * w3 x)` on one
    chunk's sorted rows, `sizes` rows to each held expert from the first
    row on."""
    def grouped(lhs, stack):
        # a row past the held ones is never written, forward or backward
        # (the chip leaves what was there: not a zero, maybe not a number),
        # so it is selected out on both sides and its cotangent is a zero
        # in either direction
        return jnp.where(live, jax.lax.ragged_dot(
            jnp.where(live, lhs, 0), stack, sizes), 0)

    w1, w3, w2 = stacks
    out = grouped(nn.silu(grouped(rows, w1)) * grouped(rows, w3), w2)
    return out.astype(jnp.float32) * gate[:, None]


def _over_chunks(per_chunk, tokens, weights, order, sizes, run, carry):
    """`carry` through `run(carry, (token of each row, gate of each row,
    group sizes, which rows are held ones))` for every chunk of `per_chunk`
    sorted rows that holds a held expert's row, in order; a chunk that
    starts past the last held row costs a branch. Also returns what each
    chunk's `run` gave beside the carry, zeros for a chunk skipped."""
    k = order.shape[0] // tokens.shape[0]
    count = -(-order.shape[0] // per_chunk)
    spare = count * per_chunk - order.shape[0]
    order = jnp.pad(order, (0, spare))
    # a row of an absent expert has no weight: `weights` is 0 there
    gate = jnp.pad(weights[order], (0, spare))
    cum = jnp.concatenate([jnp.zeros((1,), sizes.dtype), jnp.cumsum(sizes)])

    def chunk(carry, c):
        lo = c * per_chunk
        at = jax.lax.dynamic_slice_in_dim(order, lo, per_chunk)
        # an expert whose rows straddle two chunks is in both
        edges = jnp.clip(cum, lo, lo + per_chunk)
        live = (jnp.arange(per_chunk) < cum[-1] - lo)[:, None]
        return run(carry, (at // k,
                           jax.lax.dynamic_slice_in_dim(gate, lo, per_chunk),
                           edges[1:] - edges[:-1], live))

    skipped = jax.tree.map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
        jax.eval_shape(chunk, carry, 0)[1])

    def body(carry, c):
        return jax.lax.cond(c * per_chunk < cum[-1], chunk,
                            lambda carry, c: (carry, skipped), carry, c)

    return jax.lax.scan(body, carry, jnp.arange(count))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def held_rows(per_chunk: int, tokens, weights, order, sizes, stacks):
    """(T, H) float32: each token's held experts' outputs, weighted and
    summed. `tokens` (T, H); `weights` (T k,) float32, an assignment's gate
    or 0 for an absent expert's; `order`, `sizes` as `held_assignments`
    gives them; `stacks` (w1, w3, w2).

    Forward and backward each work through the sorted rows a chunk at a
    time: gather the chunk's token rows, multiply, and add its weighted
    rows into the tokens' (the cotangent's rows likewise): at most
    `per_chunk` rows added a chunk, where a gather the other way round
    would read all T k, and on the chip the scatter-add is the faster of
    the two (`PERF.md` section 6, PR 39). The rule keeps its inputs only:
    the backward forms a chunk's forward anew, and a recomputed forward
    pass (`remat`) whose output nobody reads costs the router and the sort
    alone."""
    return _held_rows_fwd(per_chunk, tokens, weights, order, sizes,
                          stacks)[0]


def _held_rows_fwd(per_chunk, tokens, weights, order, sizes, stacks):
    def run(mixed, chunk):
        tok, gate, groups, live = chunk
        return mixed.at[tok].add(_chunk_experts(
            tokens[tok], gate, stacks, groups, live)), None

    mixed, _ = _over_chunks(per_chunk, tokens, weights, order, sizes, run,
                            jnp.zeros(tokens.shape, jnp.float32))
    return mixed, (tokens, weights, order, sizes, stacks)


def _held_rows_bwd(per_chunk, res, g):
    tokens, weights, order, sizes, stacks = res

    def run(carry, chunk):
        tok, gate, groups, live = chunk
        d_rows, d_gate, d_stacks = jax.vjp(
            lambda rows, gate, stacks: _chunk_experts(
                rows, gate, stacks, groups, live),
            tokens[tok], gate, stacks)[1](g[tok])
        d_tokens, acc = carry
        # the stacks' cotangents add up in the stacks' own dtype, as the
        # grouped product's transpose gives them
        return (d_tokens.at[tok].add(d_rows.astype(jnp.float32)),
                jax.tree.map(jnp.add, acc, d_stacks)), d_gate

    (d_tokens, d_stacks), d_gate = _over_chunks(
        per_chunk, tokens, weights, order, sizes, run,
        (jnp.zeros(tokens.shape, jnp.float32),
         jax.tree.map(jnp.zeros_like, stacks)))
    # each assignment's gate cotangent, from its sorted row
    d_weights = d_gate.reshape(-1)[jnp.argsort(order)]
    return d_tokens.astype(tokens.dtype), d_weights, None, None, d_stacks


held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


class HeldExpertsLayer(nn.Module):
    """(..., S, H) -> (..., S, H): the held experts' part of a top-k
    mixture of SwiGLU experts, `w2(silu(w1 x) * w3 x)`. Leaves: `router`
    (H, E) float32 and three stacks of three axes, `w1`, `w3` (held, H, I)
    and `w2` (held, I, H). Sows three counters into `counters`:
    `moe_load_max_over_mean` (the fullest held expert's rows over the mean),
    `moe_held_rows_share` (the rows of the held experts over every
    assignment, tokens x k: `experts_held / num_experts` under an even
    routing) and `moe_chunks_run` (how many chunks of `chunk_rows` sorted
    rows the layer worked through, of `ceil(tokens x k / chunk_rows)`: what
    the grouped products' work follows)."""

    cfg: HeldExpertsConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        hidden, held_n, k = cfg.hidden_size, cfg.experts_held, cfg.top_k
        normal = nn.initializers.normal(0.02)
        router = self.param(
            "router", nn.with_logical_partitioning(normal, ("embed", None)),
            (hidden, cfg.num_experts), jnp.float32)
        stacks = {}
        for name, shape, axes in (
                ("w1", (held_n, hidden, cfg.expert_intermediate),
                 ("expert", "embed", "mlp")),
                ("w3", (held_n, hidden, cfg.expert_intermediate),
                 ("expert", "embed", "mlp")),
                ("w2", (held_n, cfg.expert_intermediate, hidden),
                 ("expert", "mlp", "embed"))):
            stacks[name] = self.param(
                name, nn.with_logical_partitioning(normal, axes), shape,
                cfg.param_dtype).astype(cfg.dtype)
        tokens = x.reshape(-1, hidden)
        # the router in float32 whatever the backend's default for it
        logits = jnp.dot(tokens.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        gates, experts = route_top_k(logits, k, cfg.norm_topk_prob)
        held, order, sizes = held_assignments(experts, cfg.first_expert,
                                              held_n)
        per_chunk = chunk_rows(held.shape[0], held_n, cfg.num_experts)
        held_rows_n = jnp.sum(sizes)
        self.sow("counters", "moe_load_max_over_mean",
                 jnp.max(sizes) * held_n / jnp.maximum(held_rows_n, 1))
        self.sow("counters", "moe_held_rows_share",
                 held_rows_n / held.shape[0])
        self.sow("counters", "moe_chunks_run",
                 (-(-held_rows_n // per_chunk)).astype(jnp.float32))
        mixed = held_rows(
            per_chunk, tokens.astype(cfg.dtype),
            jnp.where(held, gates.reshape(-1), 0.0), order, sizes,
            (stacks["w1"], stacks["w3"], stacks["w2"]))
        return mixed.reshape(x.shape).astype(x.dtype)
