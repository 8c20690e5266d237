"""Model class ``keye``, its plain reference: the leaves of Keye-VL-2.0's
language model as this chip holds them and one block's forward with the
indexer's objective, in straightforward ``jax.numpy``. Nothing of
``dlrover_tpu`` is imported; ``benchmarks/reference.py`` has the rest.

The equations (the configuration's ``assumed`` lists what the public config
does not settle). Pre-norm residual block on ``x`` (batch, seq, hidden):

- attention: ``q = h Wq`` (heads of ``head_dim``; q is wider than the
  hidden size), ``k, v = h Wk, h Wv`` on fewer heads, RMSNorm over each head
  of q and of k, RoPE over the whole head, scale ``head_dim^-0.5``; the
  softmax of query t runs over its selected keys ``S_t`` only;
- indexer, on the normed input ``h`` DETACHED: ``qI = RoPE(h W_qI)`` (J
  heads of D), ``kI = RoPE(norm(h W_kI))`` (one head), ``w = J^-0.5 h W_w``,
  ``I[t, s] = sum_j w[t, j] relu(D^-0.5 qI[t, j] . kI[s])`` for ``s <= t``;
  ``S_t`` = the ``topk`` keys of largest ``I[t, :]`` by ``jax.lax.top_k``
  (ties to the lower ``s``; written as the keys above its ``topk``-th value
  and the lowest-placed of those equal to it, which is the same set and
  needs no scatter), every ``s <= t`` while ``t < topk``;
- the layer's second objective, returned as ``extra``: ``index_loss_weight
  x mean_t KL(p_t || softmax_{s in S_t} I[t, s])``, ``p_t`` the attention's
  probabilities summed over the heads and divided by their number (each
  head's sum to one on ``S_t``), detached;
- experts: router ``z Wr`` over ALL ``num_experts``, softmax, the
  ``num_experts_per_tok`` largest renormalised to sum 1; of those, the
  experts this chip holds (``first_expert`` ... ``+ num_local_experts``),
  each ``W2(silu(W1 z) * W3 z)``, as a loop over the held experts with a
  0/1 assignment mask over every token: no sort, no grouped product, no
  capacity. What the absent experts would add is left out.

Departures from writing each of these as one expression, all for memory at
seq 16,384 and none in the mathematics: the attention, the selection and the
KL rows are computed over chunks of ``q_chunk_size`` queries (each against
every key) under ``jax.lax.map`` with ``jax.checkpoint`` on the chunk's
body, so that neither pass holds heads x seq x seq scores; a sequence the
chunk does not divide, or no longer than it, goes in one piece. ``mode``
reaches every matrix product, the router's and the indexer's included; the
weighted sum over the indexer's heads is elementwise float32 in every mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import Leaf, linear, product, rms_norm

INIT_STDDEV = 0.02


def leaves(cfg: dict) -> dict:
    """name -> ``Leaf`` of every parameter, named as the program's tree is;
    the count is the order flax makes a scope's parameters in."""
    h, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    sa, held = cfg["sa_config"], cfg["num_local_experts"]
    i, di = cfg["moe_intermediate_size"], sa["indexer_head_dim"]
    out = {"embed": Leaf((v, h), (), 1, INIT_STDDEV)}
    for layer in range(cfg["num_hidden_layers"]):
        name = f"layer_{layer}"
        for norm in ("attn_norm", "mlp_norm"):
            out[f"{name}/{norm}/weight"] = Leaf((h,), (name, norm), 1, None)
        for norm in ("q_norm", "k_norm"):
            out[f"{name}/attn/{norm}/weight"] = Leaf(
                (d,), (name, "attn", norm), 1, None)
        for proj, shape in (("q_proj", (h, q)), ("k_proj", (h, kv)),
                            ("v_proj", (h, kv)), ("o_proj", (q, h))):
            out[f"{name}/attn/{proj}/kernel"] = Leaf(
                shape, (name, "attn", proj), 1, INIT_STDDEV)
        for proj, shape in (("q_proj", (h, sa["indexer_num_heads"] * di)),
                            ("k_proj", (h, di)),
                            ("w_proj", (h, sa["indexer_num_heads"]))):
            out[f"{name}/attn/indexer/{proj}/kernel"] = Leaf(
                shape, (name, "attn", "indexer", proj), 1, INIT_STDDEV)
        out[f"{name}/attn/indexer/k_norm/weight"] = Leaf(
            (di,), (name, "attn", "indexer", "k_norm"), 1, None)
        for count, (leaf, shape) in enumerate((
                ("router", (h, cfg["num_experts"])), ("w1", (held, h, i)),
                ("w3", (held, h, i)), ("w2", (held, i, h))), start=1):
            out[f"{name}/moe/{leaf}"] = Leaf(shape, (name, "moe"), count,
                                             INIT_STDDEV)
    out["final_norm/weight"] = Leaf((h,), ("final_norm",), 1, None)
    out["lm_head"] = Leaf((h, v), (), 2, INIT_STDDEV)
    return out


def layer_prefix(layer: int) -> str:
    return f"layer_{layer}/"


def layer_kind(cfg: dict, layer: int):
    """Every layer is of the one kind (``decoder_sparse_step`` 1,
    ``mlp_only_layers`` empty)."""
    return "sparse_moe"


def rope(x, theta: float):
    """(batch, seq, heads, d): the halves of a head turned against each
    other by the position's angle, over the whole head."""
    half = x.shape[-1] // 2
    frequency = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * frequency
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def selected(index, first: int, topk: int):
    """(batch, chunk, seq) bool: ``S_t`` of the chunk's queries, which
    stand at ``first`` ... in the sequence, from their index scores: the
    keys above the ``topk``-th largest score that ``jax.lax.top_k`` finds
    and, of those equal to it, as many of the lowest positions as fill the
    set (``top_k`` puts the lower position first among equals)."""
    chunk, seq = index.shape[1:]
    seen = (first + jnp.arange(chunk))[:, None] >= jnp.arange(seq)[None, :]
    if topk >= seq:
        return jnp.broadcast_to(seen, index.shape)
    index = jnp.where(seen, index, -jnp.inf)
    least = jax.lax.top_k(index, topk)[0][..., -1:]
    above, level = index > least, index == least
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    chosen = above | (level & (jnp.cumsum(level, axis=-1) <= room))
    return chosen & seen        # a query with fewer than topk keys: all


def _chunk(first, q, qi, w, k, v, ki, cfg: dict, mode: str, dense: bool):
    """One chunk of queries against every key: (attention output
    (b, chunk, heads x d), the chunk's KL rows (b, chunk)). ``dense``
    plants a fault: the selection switched off."""
    b, chunk, heads, d = q.shape
    kv_heads, sa = k.shape[2], cfg["sa_config"]
    scored = product("bqjd,bkd->bjqk", qi, ki, mode, -1, -1)
    index = jnp.sum(jax.nn.relu(scored * sa["indexer_head_dim"] ** -0.5)
                    * jnp.moveaxis(w, -1, 1)[..., None], axis=1)
    keep = selected(jax.lax.stop_gradient(index), first,
                    k.shape[1] if dense else sa["topk"])
    # query head g * (heads / kv_heads) + r reads k and v of head g
    grouped = q.reshape(b, chunk, kv_heads, heads // kv_heads, d)
    scores = product("bqgrd,bkgd->bgrqk", grouped, k, mode, -1, -1) * d ** -0.5
    probs = jax.nn.softmax(jnp.where(keep[:, None, None], scores, -jnp.inf),
                           axis=-1)
    mixed = product("bgrqk,bkgd->bqgrd", probs, v, mode, -1, 1)
    target = jax.lax.stop_gradient(jnp.sum(probs, axis=(1, 2))) / heads
    log_q = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), axis=-1)
    gap = jnp.where(target > 0, target * (
        jnp.log(jnp.where(target > 0, target, 1.0))
        - jnp.where(keep, log_q, 0.0)), 0.0)
    return mixed.reshape(b, chunk, heads * d), jnp.sum(gap, axis=-1)


def attention(y, p: dict, cfg: dict, mode: str, dense: bool = False):
    """(attention's output before ``o_proj``, the mean KL of the layer)."""
    b, s, _ = y.shape
    d, eps, theta = cfg["head_dim"], cfg["rms_norm_eps"], cfg["rope_theta"]
    sa = cfg["sa_config"]
    q = linear(y, p["attn/q_proj/kernel"], mode).reshape(b, s, -1, d)
    k = linear(y, p["attn/k_proj/kernel"], mode).reshape(b, s, -1, d)
    v = linear(y, p["attn/v_proj/kernel"], mode).reshape(b, s, -1, d)
    q = rope(rms_norm(q, p["attn/q_norm/weight"], eps), theta)
    k = rope(rms_norm(k, p["attn/k_norm/weight"], eps), theta)
    h = jax.lax.stop_gradient(y)
    heads_i = sa["indexer_num_heads"]
    qi = rope(linear(h, p["attn/indexer/q_proj/kernel"], mode).reshape(
        b, s, heads_i, sa["indexer_head_dim"]), theta)
    ki = rope(rms_norm(linear(h, p["attn/indexer/k_proj/kernel"], mode),
                       p["attn/indexer/k_norm/weight"], eps)[:, :, None, :],
              theta)[:, :, 0, :]
    w = linear(h, p["attn/indexer/w_proj/kernel"], mode) * heads_i ** -0.5
    chunk = sa["q_chunk_size"]
    if s <= chunk or s % chunk:
        out, rows = _chunk(0, q, qi, w, k, v, ki, cfg, mode, dense)
        return out, jnp.mean(rows)

    def cut(a):     # (b, s, ...) -> (chunks, b, chunk, ...)
        return jnp.moveaxis(a.reshape(b, s // chunk, chunk, *a.shape[2:]),
                            1, 0)

    body = jax.checkpoint(
        lambda c: _chunk(c[0], c[1], c[2], c[3], k, v, ki, cfg, mode, dense))
    out, rows = jax.lax.map(body, (jnp.arange(0, s, chunk), cut(q), cut(qi),
                                   cut(w)))
    return (jnp.moveaxis(out, 0, 1).reshape(b, s, -1),
            jnp.mean(jnp.moveaxis(rows, 0, 1)))


def route(z, router, cfg: dict, mode: str, renormalise: bool = True):
    """(gates (b, s, k), experts (b, s, k)) over every expert."""
    share = jax.nn.softmax(linear(z, router, mode), axis=-1)
    gates, experts = jax.lax.top_k(share, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"] and renormalise:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts


def experts(z, p: dict, cfg: dict, mode: str, renormalise: bool = True,
            capacity=None):
    """What the held experts add. ``renormalise`` False and ``capacity``
    (an expert takes its first so many assignments in the order of the
    tokens and drops the rest) plant faults."""
    gates, chosen = route(z, p["moe/router"], cfg, mode, renormalise)
    out = jnp.zeros_like(z)
    for held in range(cfg["num_local_experts"]):
        assigned = chosen == cfg["first_expert"] + held     # the 0/1 mask
        if capacity is not None:
            place = jnp.cumsum(jnp.any(assigned, -1).reshape(-1)).reshape(
                assigned.shape[:-1])
            assigned = assigned & (place <= capacity)[..., None]
        weight = jnp.sum(jnp.where(assigned, gates, 0.0), axis=-1)
        act = (jax.nn.silu(linear(z, p["moe/w1"][held], mode))
               * linear(z, p["moe/w3"][held], mode))
        out = out + weight[..., None] * linear(act, p["moe/w2"][held], mode)
    return out


def block(x, p: dict, cfg: dict, layer: int, mode: str):
    """One block on (batch, seq, hidden): (x, the layer's weighted KL)."""
    eps = cfg["rms_norm_eps"]
    mixed, gap = attention(rms_norm(x, p["attn_norm/weight"], eps), p, cfg,
                           mode)
    x = x + linear(mixed, p["attn/o_proj/kernel"], mode)
    x = x + experts(rms_norm(x, p["mlp_norm/weight"], eps), p, cfg, mode)
    return x, cfg["index_loss_weight"] * gap
