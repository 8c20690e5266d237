"""Model class ``minicpm_sala``, its plain reference: the leaves of a stage of
MiniCPM-SALA's layers and one block's forward, in straightforward
``jax.numpy``. Nothing of ``dlrover_tpu`` is imported;
``benchmarks/reference.py`` has the rest.

The equations (the configuration's ``assumed`` lists what the public config
does not settle). Each layer, on ``x`` (batch, seq, hidden):
``x + a Mixer(norm(x))``, then ``x + a MLP(norm(x))``, ``a = scale_depth /
sqrt(published depth)``; the first layer takes the embedding times
``scale_emb`` (the shared reference hands it the plain embedding). The mixer
is the layer's ``mixer_types`` entry:

- ``lightning-attn``: per head ``q = RoPE(norm(h Wq))``, ``k = RoPE(norm(h
  Wk))``, ``v = h Wv``, ``o[t] = d^-1/2 sum_{s <= t} lam^(t-s) (q[t] . k[s])
  v[s]``, written as the masked quadratic form, ``lam = exp(-slope (1 -
  layer / (depth - 1) + 1e-5))``, ``slope = 2^(-8 (h + 1) / heads)``; the
  output ``W_o(sigmoid(h W_g) * norm(o))``, the norm over the whole width;
- ``minicpm4``: ``q = norm(h Wq)`` on 32 heads, ``k = norm(h Wk)``, ``v =
  h Wv`` on 2, no RoPE; query t of kv group g attends the keys of the blocks
  it chooses: compressed keys ``c_i`` = the mean of k over ``[stride i,
  stride i + kernel)`` for spans that end at or before t, ``r[i]`` = the sum
  over the group's heads of ``softmax_i(q . c_i / sqrt(d))``, a block's score
  the largest ``r`` over the ``c_i`` that overlap it, the ``topk`` blocks of
  highest score with the first ``init_blocks`` and the last ``window /
  block`` forced in and none after t (ties to the lower block), every causal
  key below ``dense_len`` tokens; the output ``W_o(sigmoid(h W_g) * attn)``.

Departures from writing each of these as one expression, for memory at seq
16,384 and none in the mathematics: both mixers run over chunks of
``QUERY_CHUNK`` queries (each against every key) under ``jax.lax.map`` with
``jax.checkpoint`` on the chunk's body; a sequence the chunk does not divide,
or no longer than it, goes in one piece. ``mode`` reaches every matrix
product; the decays, the masks and the softmax are float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import Leaf, linear, product, rms_norm

INIT_STDDEV = 0.02
QUERY_CHUNK = 512
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _kinds(cfg: dict) -> list:
    return cfg["mixer_types"][:cfg["num_hidden_layers"]]


def _widths(cfg: dict, kind: str) -> tuple:
    """(heads, kv heads, head width) of a layer of ``kind``."""
    if kind == SPARSE:
        return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    return cfg["lightning_nh"], cfg["lightning_nkv"], cfg["lightning_head_dim"]


def leaves(cfg: dict) -> dict:
    """name -> ``Leaf`` of every parameter, named as the program's tree is;
    the count is the order flax makes a scope's parameters in."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {"embed": Leaf((v, h), (), 1, INIT_STDDEV)}
    for layer, kind in enumerate(_kinds(cfg)):
        name = f"layer_{layer}"
        heads, kv_heads, d = _widths(cfg, kind)
        q, kv = heads * d, kv_heads * d
        for norm in ("attn_norm", "mlp_norm"):
            out[f"{name}/{norm}/weight"] = Leaf((h,), (name, norm), 1, None)
        norms = ("q_norm", "k_norm") + (("o_norm",) if kind == LIGHTNING
                                        else ())
        for norm in norms:
            out[f"{name}/attn/{norm}/weight"] = Leaf(
                (q if norm == "o_norm" else d,), (name, "attn", norm), 1,
                None)
        for proj, shape in (("q_proj", (h, q)), ("k_proj", (h, kv)),
                            ("v_proj", (h, kv)), ("o_gate", (h, q)),
                            ("o_proj", (q, h))):
            out[f"{name}/attn/{proj}/kernel"] = Leaf(
                shape, (name, "attn", proj), 1, INIT_STDDEV)
        for proj, shape in (("gate_proj", (h, i)), ("up_proj", (h, i)),
                            ("down_proj", (i, h))):
            out[f"{name}/mlp/{proj}/kernel"] = Leaf(
                shape, (name, "mlp", proj), 1, INIT_STDDEV)
    out["final_norm/weight"] = Leaf((h,), ("final_norm",), 1, None)
    out["lm_head"] = Leaf((h, v), (), 2, INIT_STDDEV)
    return out


def layer_prefix(layer: int) -> str:
    return f"layer_{layer}/"


def layer_kind(cfg: dict, layer: int):
    """``sparse`` or ``lightning``, and the layer: every layer is a kind of
    its own, because a lightning layer's decay and the first layer's
    embedding scale depend on where it stands."""
    kind = "sparse" if _kinds(cfg)[layer] == SPARSE else "lightning"
    return f"{kind}.{layer}"


def rope(x, theta: float):
    """(batch, seq, heads, d): the halves of a head turned against each
    other by the position's angle."""
    half = x.shape[-1] // 2
    frequency = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * frequency
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def decay_rates(cfg: dict, layer: int, constant: bool = False):
    """(heads,) float32: -log lam of each head in this stage's ``layer``.
    ``constant`` plants a fault: lam = 1."""
    heads = cfg["lightning_nh"]
    depth = cfg["published"]["num_hidden_layers"]
    slopes = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    rates = slopes * (1.0 - layer / (depth - 1) + 1e-5)
    return jnp.asarray(np.zeros(heads) if constant else rates, jnp.float32)


def _over_queries(body, s: int, *per_query):
    """``body(first, *chunks)`` over chunks of queries, concatenated on the
    sequence axis (1); in one piece where the chunk does not divide s."""
    if s <= QUERY_CHUNK or s % QUERY_CHUNK:
        return body(0, *per_query)

    def cut(a):     # (b, s, ...) -> (chunks, b, chunk, ...)
        return jnp.moveaxis(a.reshape(a.shape[0], s // QUERY_CHUNK,
                                      QUERY_CHUNK, *a.shape[2:]), 1, 0)

    out = jax.lax.map(jax.checkpoint(lambda c: body(c[0], *c[1:])),
                      (jnp.arange(0, s, QUERY_CHUNK),)
                      + tuple(cut(a) for a in per_query))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(out.shape[0], s, *out.shape[3:])


def lightning(y, p: dict, cfg: dict, layer: int, mode: str,
              constant: bool = False):
    """The lightning mixer's output before ``o_proj``'s gate: norm(o)."""
    b, s, _ = y.shape
    heads, _, d = _widths(cfg, LIGHTNING)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = linear(y, p["attn/q_proj/kernel"], mode).reshape(b, s, heads, d)
    k = linear(y, p["attn/k_proj/kernel"], mode).reshape(b, s, heads, d)
    v = linear(y, p["attn/v_proj/kernel"], mode).reshape(b, s, heads, d)
    q = rope(rms_norm(q, p["attn/q_norm/weight"], eps), theta)
    k = rope(rms_norm(k, p["attn/k_norm/weight"], eps), theta)
    rate = decay_rates(cfg, layer, constant)

    def rows(first, qc):
        t = first + jnp.arange(qc.shape[1])
        gap = (t[:, None] - jnp.arange(s)[None, :]).astype(jnp.float32)
        decay = jnp.where(gap >= 0, jnp.exp(-rate[:, None, None]
                                            * jnp.maximum(gap, 0.0)), 0.0)
        scores = product("bqhd,bkhd->bhqk", qc, k, mode, -1, -1) * decay
        return product("bhqk,bkhd->bqhd", scores, v, mode, -1, 1) * d ** -0.5

    o = _over_queries(rows, s, q).reshape(b, s, heads * d)
    return rms_norm(o, p["attn/o_norm/weight"], eps)


def chosen_blocks(q, k, first: int, cfg: dict, mode: str,
                  first_blocks: bool = False):
    """(b, G, chunk, blocks) bool: the blocks the chunk's queries (standing
    at ``first`` ...) choose, q (b, chunk, heads, d) and k (b, s, G, d).
    ``first_blocks`` plants a fault: the first ``topk`` causal blocks in
    place of the ``topk`` best."""
    sp = cfg["sparse_config"]
    b, chunk, heads, d = q.shape
    s, groups = k.shape[1], k.shape[2]
    size, kernel, stride = sp["block_size"], sp["kernel_size"], sp[
        "kernel_stride"]
    blocks = s // size
    t = first + jnp.arange(chunk)[:, None]
    causal = jnp.arange(blocks)[None, :] <= t // size
    if s < sp["dense_len"] or blocks <= sp["topk"]:
        return jnp.broadcast_to(causal, (b, groups, chunk, blocks))
    starts = stride * np.arange((s - kernel) // stride + 1)
    compressed = jnp.mean(k[:, starts[:, None] + np.arange(kernel)], axis=2)
    scores = product("bqgrd,bcgd->bgrqc",
                     q.reshape(b, chunk, groups, heads // groups, d),
                     compressed, mode, -1, -1) / math.sqrt(d)
    counted = (starts + kernel - 1)[None, :] <= t          # (chunk, c)
    probs = jax.nn.softmax(jnp.where(counted, scores, -jnp.inf), axis=-1)
    probs = jnp.where(counted, probs, 0.0)                 # none counted: 0
    r = jnp.sum(probs, axis=2)                             # (b, G, chunk, c)
    block = np.arange(blocks)
    overlap = ((starts[:, None] < (block + 1) * size)
               & (starts[:, None] + kernel > block * size))    # (c, blocks)
    score = jnp.max(r[..., :, None] * overlap, axis=-2)
    if first_blocks:
        score = jnp.broadcast_to(-block.astype(np.float32), score.shape)
    own = t // size
    forced = (block < sp["init_blocks"]) | (
        block > own - sp["window_size"] // size)
    score = jnp.where(causal, jnp.where(forced, jnp.inf, score), -jnp.inf)
    least = jax.lax.top_k(score, sp["topk"])[0][..., -1:]
    above, level = score > least, score == least
    room = sp["topk"] - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (level & (jnp.cumsum(level, axis=-1) <= room))) & causal


def sparse(y, p: dict, cfg: dict, mode: str, first_blocks: bool = False):
    """The sparse mixer's attention output before the gate."""
    b, s, _ = y.shape
    heads, kv_heads, d = _widths(cfg, SPARSE)
    eps, size = cfg["rms_norm_eps"], cfg["sparse_config"]["block_size"]
    q = linear(y, p["attn/q_proj/kernel"], mode).reshape(b, s, heads, d)
    k = linear(y, p["attn/k_proj/kernel"], mode).reshape(b, s, kv_heads, d)
    v = linear(y, p["attn/v_proj/kernel"], mode).reshape(b, s, kv_heads, d)
    q = rms_norm(q, p["attn/q_norm/weight"], eps)
    k = rms_norm(k, p["attn/k_norm/weight"], eps)
    detached_k = jax.lax.stop_gradient(k)

    def rows(first, qc):
        chunk = qc.shape[1]
        chosen = chosen_blocks(jax.lax.stop_gradient(qc), detached_k, first,
                               cfg, mode, first_blocks)
        keys = jnp.repeat(chosen, size, axis=-1) & (
            (first + jnp.arange(chunk))[:, None] >= jnp.arange(s)[None, :])
        grouped = qc.reshape(b, chunk, kv_heads, heads // kv_heads, d)
        scores = product("bqgrd,bkgd->bgrqk", grouped, k, mode, -1, -1)
        probs = jax.nn.softmax(jnp.where(keys[:, :, None], scores / math.sqrt(
            d), -jnp.inf), axis=-1)
        return product("bgrqk,bkgd->bqgrd", probs, v, mode, -1, 1)

    return _over_queries(rows, s, q).reshape(b, s, heads * d)


def block(x, p: dict, cfg: dict, layer: int, mode: str, fault: str = ""):
    """One layer on (batch, seq, hidden). ``fault`` plants one: ``decay``
    (lam = 1), ``first_blocks`` (the first causal blocks chosen), ``alpha``
    (the residual branches' scale left out)."""
    eps = cfg["rms_norm_eps"]
    alpha = 1.0 if fault == "alpha" else cfg["scale_depth"] / math.sqrt(
        cfg["published"]["num_hidden_layers"])
    if layer == 0:
        x = cfg["scale_emb"] * x
    y = rms_norm(x, p["attn_norm/weight"], eps)
    if _kinds(cfg)[layer] == SPARSE:
        mixed = sparse(y, p, cfg, mode, fault == "first_blocks")
    else:
        mixed = lightning(y, p, cfg, layer, mode, fault == "decay")
    gate = jax.nn.sigmoid(linear(y, p["attn/o_gate/kernel"], mode))
    x = x + alpha * linear(gate * mixed, p["attn/o_proj/kernel"], mode)
    y = rms_norm(x, p["mlp_norm/weight"], eps)
    up = jax.nn.silu(linear(y, p["mlp/gate_proj/kernel"], mode)) * linear(
        y, p["mlp/up_proj/kernel"], mode)
    return x + alpha * linear(up, p["mlp/down_proj/kernel"], mode)
