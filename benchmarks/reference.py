"""The plain reference, the part every model class shares: rows and weights
from the seed, the operand precisions, the head's loss, the factored-RMS
update and a layer-by-layer training step, in straightforward ``jax.numpy``
and float32. What is a class's own (its leaves, its block's forward and any
term its block adds to the objective) lies in ``models/<key>_reference.py``
and is handed in as ``model``.

It imports nothing of ``dlrover_tpu`` and takes nothing the program has made:
weights come from ``--seed`` by the same rule flax uses (a key folded from the
parameter's path), the rows from the seed and the sampler's documented order.
No kernels, no cache, no batching tricks. A step runs layer by layer: the
forward keeps each block's input, the backward recomputes one block at a time
and applies that block's update at once, so beside the float32 parameters only
one block's gradients are ever alive and a 2B model fits a 16 GB chip once the
program's state is freed.

``mode`` is the precision of every matrix product's operands:
``f32`` (the reference: float32 at ``highest``), ``bf16`` (what the
configurations state), ``int8`` (the control: the nearest step below bf16,
operands rounded to 8 bits per row with a straight-through gradient).
Departures from the published models are listed in each configuration's
``assumed``.
"""

from __future__ import annotations

import functools
import hashlib
import json
import random
import typing

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPSILON = 1e-30          # optax.scale_by_factored_rms defaults
DECAY_EXPONENT = 0.8
MIN_DIM_TO_FACTOR = 128


# ---------------------------------------------------------------------------
# Inputs and weights from the seed
# ---------------------------------------------------------------------------


def token_rows(seed: int, vocab_size: int, rows: int, seq_len: int
               ) -> np.ndarray:
    """The token stream the traffic file describes: uniform over the
    vocabulary, cut into ``rows`` windows of ``seq_len + 1``."""
    stream = np.random.default_rng(seed).integers(
        0, vocab_size, rows * (seq_len + 1), dtype=np.int32)
    return stream.reshape(rows, seq_len + 1)


def sampler_order(seed: int, rows: int, shuffle: bool = True) -> list:
    """Row indices in the order an ``ElasticDistributedSampler(shuffle,
    seed)`` of one replica deals them in epoch 0 (its documented rule:
    ``random.Random(seed + epoch).shuffle``)."""
    order = list(range(rows))
    if shuffle:
        random.Random(seed).shuffle(order)
    return order


class Rows:
    """The rows of one seed and the batches the sampler deals from them."""

    def __init__(self, seed: int, vocab_size: int, rows: int, seq_len: int,
                 shuffle: bool = True):
        self.data = token_rows(seed, vocab_size, rows, seq_len)
        self.order = sampler_order(seed, rows, shuffle)

    def batch(self, index: int, global_batch: int):
        """(tokens, targets) of the ``index``-th global batch."""
        picked = self.data[self.order[index * global_batch:
                                      (index + 1) * global_batch]]
        return picked[:, :-1], picked[:, 1:]


def _flax_fold(path: tuple, count: int) -> int:
    """What flax's ``Module.param`` folds into the root key for the
    ``count``-th parameter made in the scope at ``path``: 32 bits of the
    SHA-1 of the path and the count
    (``flax.core.scope._fold_in_static``)."""
    try:
        import flax
        separator = bool(flax.config.flax_fix_rng_separator)
    except Exception:  # noqa: BLE001 - flax absent or renamed: old rule
        separator = False
    m = hashlib.sha1()
    for part in path + (count,):
        if separator:
            m.update(b"\00")
        if isinstance(part, str):
            m.update(part.encode("utf-8"))
        else:
            m.update(part.to_bytes((part.bit_length() + 7) // 8, "big"))
    return int.from_bytes(m.digest()[:4], "big")


class Leaf(typing.NamedTuple):
    """One parameter as a model class's ``leaves(cfg)`` describes it."""
    shape: tuple
    path: tuple             # the flax scope it is made in
    count: int              # which parameter of that scope, from 1
    stddev: float | None    # of its normal initialiser; None: ones


@functools.partial(jax.jit, static_argnums=(2, 3))
def _init_normal(root, fold, shape, stddev):
    # one program per shape: the path's hash is an argument
    return jax.random.normal(jax.random.fold_in(root, fold), shape,
                             jnp.float32) * stddev


def init_leaf(seed: int, leaf: Leaf):
    if leaf.stddev is None:
        return jnp.ones(leaf.shape, jnp.float32)
    return _init_normal(jax.random.PRNGKey(seed),
                        np.uint32(_flax_fold(leaf.path, leaf.count)),
                        leaf.shape, leaf.stddev)


def init_params(seed: int, leaves: dict) -> dict:
    return {name: init_leaf(seed, leaf) for name, leaf in leaves.items()}


# ---------------------------------------------------------------------------
# What every class's model is made of
# ---------------------------------------------------------------------------


def _int8(x, axis: int):
    """Round to 8 bits along ``axis`` (absmax scale per row); the gradient
    passes straight through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0.0, 1.0, scale)
    rounded = jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def product(spec: str, a, b, mode: str, a_axis: int, b_axis: int):
    """einsum in the given operand precision; ``*_axis`` is each operand's
    contracted axis (the one an int8 scale runs along)."""
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if mode == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if mode == "int8":
        return jnp.einsum(spec, _int8(a, a_axis), _int8(b, b_axis),
                          precision=HIGHEST)
    raise ValueError(f"unknown precision mode {mode!r}")


def linear(x, w, mode):
    return product("...k,kn->...n", x, w, mode, -1, 0)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def head_loss(x, final_norm, head, targets, eps: float, mode: str):
    """Final norm, output head and the mean next-token cross entropy."""
    logits = linear(rms_norm(x, final_norm, eps), head, mode)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


# ---------------------------------------------------------------------------
# The optimizer: optax.scale_by_factored_rms then scale(-lr), written out
# ---------------------------------------------------------------------------


def _factored_dims(shape):
    if len(shape) < 2:
        return None
    order = np.argsort(shape)
    if shape[order[-2]] < MIN_DIM_TO_FACTOR:
        return None
    return int(order[-2]), int(order[-1])


def init_moment(shape) -> dict:
    dims = _factored_dims(shape)
    if dims is None:
        return {"v": jnp.zeros(shape, jnp.float32)}
    d1, d0 = dims
    return {"v_row": jnp.zeros(np.delete(shape, d0), jnp.float32),
            "v_col": jnp.zeros(np.delete(shape, d1), jnp.float32)}


def factored_rms_update(p, g, moment: dict, count, lr: float):
    """(new parameter, new moment) after one update; ``count`` is the number
    of updates already made."""
    decay = 1.0 - (jnp.asarray(count, jnp.float32) + 1.0) ** (-DECAY_EXPONENT)
    g2 = g * g + EPSILON
    dims = _factored_dims(p.shape)
    if dims is None:
        v = decay * moment["v"] + (1.0 - decay) * g2
        return p - lr * g * v ** -0.5, {"v": v}
    d1, d0 = dims
    v_row = decay * moment["v_row"] + (1.0 - decay) * jnp.mean(g2, axis=d0)
    v_col = decay * moment["v_col"] + (1.0 - decay) * jnp.mean(g2, axis=d1)
    reduced_d1 = d1 - 1 if d1 > d0 else d1
    row_mean = jnp.mean(v_row, axis=reduced_d1, keepdims=True)
    update = (g * jnp.expand_dims((v_row / row_mean) ** -0.5, d0)
              * jnp.expand_dims(v_col ** -0.5, d1))
    return p - lr * update, {"v_row": v_row, "v_col": v_col}


def _apply(ps: dict, gs: dict, moments: dict, count, lr):
    """Update every leaf of one group; also each gradient's norm."""
    new_p, new_m, norms = {}, {}, {}
    for name, g in gs.items():
        new_p[name], new_m[name] = factored_rms_update(
            ps[name], g, moments[name], count, lr)
        norms[name] = jnp.sqrt(jnp.sum(g * g))
    return new_p, new_m, norms


# ---------------------------------------------------------------------------
# One training step, layer by layer
# ---------------------------------------------------------------------------


class _Block:
    """A class's block with its configuration, as one hashable argument of a
    jitted function: a configuration holds lists (a kind for every layer)."""

    def __init__(self, forward, cfg: dict):
        self.forward, self.cfg = forward, cfg
        self._key = (forward, json.dumps(cfg, sort_keys=True))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Block) and self._key == other._key


# ``layer`` is the first layer of its kind, so layers of one kind share a
# compiled program. A block returns ``x``, or ``(x, extra)`` where its layer
# adds a term to the objective: ``extra`` is a scalar already weighted, as the
# program's model sows it into ``losses``. Which of the two is Python's to see
# while tracing, so a block that returns ``x`` alone traces as it always has.
@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block_forward(x, p, block, layer, mode):
    return block.forward(x, p, block.cfg, layer, mode)


@functools.partial(jax.jit, static_argnums=(6, 7, 8), donate_argnums=(1, 2))
def _block_backward(x, p, moments, dy, count, lr, block, layer, mode):
    out, vjp = jax.vjp(
        lambda x_, p_: block.forward(x_, p_, block.cfg, layer, mode), x, p)
    # the objective is the head's loss plus every layer's extra: d/d extra = 1
    dx, gp = vjp((dy, jnp.ones_like(out[1])) if isinstance(out, tuple)
                 else dy)
    new_p, new_m, norms = _apply(p, gp, moments, count, lr)
    return dx, new_p, new_m, norms


@functools.partial(jax.jit, static_argnums=(6, 7), donate_argnums=(1, 2))
def _head_backward(x, p, moments, targets, count, lr, eps, mode):
    def f(x_, p_):
        return head_loss(x_, p_["final_norm/weight"], p_["lm_head"],
                         targets, eps, mode)

    loss, (dx, gp) = jax.value_and_grad(f, argnums=(0, 1))(x, p)
    new_p, new_m, norms = _apply(p, gp, moments, count, lr)
    return loss, dx, new_p, new_m, norms


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _embed_backward(embed, moment, tokens, dx, count, lr):
    g = jnp.zeros_like(embed).at[tokens].add(dx)
    new_p, new_m = factored_rms_update(embed, g, moment, count, lr)
    return new_p, new_m, jnp.sqrt(jnp.sum(g * g))


class Trainer:
    """The reference's training state and its step: an embedding, the
    class's blocks one after the other, a final norm and an untied head
    (every configuration's case). ``model`` is the class's reference
    (``harness.model_reference``), ``cfg`` a configuration file's dict."""

    def __init__(self, model, seed: int, cfg: dict, mode: str = "f32"):
        if cfg.get("tie_word_embeddings"):
            raise NotImplementedError("tied embeddings: no cell has them")
        self.cfg, self.mode, self.seed = cfg, mode, seed
        self.lr = float(cfg["optimizer"]["learning_rate"])
        self.leaves = model.leaves(cfg)
        self.prefixes = [model.layer_prefix(layer)
                         for layer in range(cfg["num_hidden_layers"])]
        kinds = [model.layer_kind(cfg, layer)
                 for layer in range(len(self.prefixes))]
        self.first_of_kind = [kinds.index(kind) for kind in kinds]
        self.block = _Block(model.block, cfg)
        self.params = init_params(seed, self.leaves)
        self.moments = {name: init_moment(p.shape)
                        for name, p in self.params.items()}
        self.count = 0

    def _layer(self, tree: dict, layer: int, pop: bool = False) -> dict:
        """One layer's entries of ``tree`` by their short names."""
        prefix = self.prefixes[layer]
        take = tree.pop if pop else tree.get
        return {name[len(prefix):]: take(name) for name in list(tree)
                if name.startswith(prefix)}

    def step(self, tokens, targets) -> dict:
        """One update on a global batch; returns the loss (the head's plus
        what the layers' blocks add: the whole objective, as the program
        reports it) and every leaf's gradient norm (floats)."""
        tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
        layers = range(len(self.prefixes))
        count = jnp.asarray(self.count, jnp.int32)
        x = self.params["embed"][tokens]
        inputs, extras = [], []
        for layer in layers:
            inputs.append(x)
            x = _block_forward(x, self._layer(self.params, layer), self.block,
                               self.first_of_kind[layer], self.mode)
            if isinstance(x, tuple):
                x, extra = x
                extras.append(extra)
        top = ("final_norm/weight", "lm_head")
        loss, dx, new_p, new_m, norms = _head_backward(
            x, {k: self.params.pop(k) for k in top},
            {k: self.moments.pop(k) for k in top},
            targets, count, self.lr, self.cfg["rms_norm_eps"], self.mode)
        self.params.update(new_p)
        self.moments.update(new_m)
        grad_norms = dict(norms)
        for layer in reversed(layers):
            prefix = self.prefixes[layer]
            dx, new_p, new_m, norms = _block_backward(
                inputs.pop(), self._layer(self.params, layer, pop=True),
                self._layer(self.moments, layer, pop=True), dx, count,
                self.lr, self.block, self.first_of_kind[layer], self.mode)
            for name in new_p:
                self.params[prefix + name] = new_p[name]
                self.moments[prefix + name] = new_m[name]
                grad_norms[prefix + name] = norms[name]
        embed, moment, norm = _embed_backward(
            self.params.pop("embed"), self.moments.pop("embed"), tokens, dx,
            count, self.lr)
        self.params["embed"], self.moments["embed"] = embed, moment
        grad_norms["embed"] = norm
        self.count += 1
        return {"loss": float(loss) + sum(map(float, extras)),
                "grad_norms": {k: float(v) for k, v in grad_norms.items()}}

    def change_norms(self) -> dict:
        """Norm of each leaf's change since the seed's initial value, the
        initial leaf made again one at a time."""
        return {name: float(_change_norm(p, init_leaf(self.seed,
                                                      self.leaves[name])))
                for name, p in self.params.items()}


@jax.jit
def _change_norm(now, initial):
    delta = now - initial
    return jnp.sqrt(jnp.sum(delta * delta))


def follow(model, seed: int, cfg: dict, batches: list, mode: str = "f32",
           keep_rows=None) -> dict:
    """Drive a fresh reference of the class ``model`` through ``batches``
    ((tokens, targets) each) and return what the comparison reads: each
    step's loss (the whole objective), the first gradient's norm by leaf,
    each leaf's change after the last step. ``keep_rows`` plants a fault:
    only that many rows of each batch are trained on, the mean taken over
    them."""
    trainer = Trainer(model, seed, cfg, mode)
    steps = [trainer.step(tokens[:keep_rows], targets[:keep_rows])
             for tokens, targets in batches]
    return {"losses": [s["loss"] for s in steps],
            "grad_norms": steps[0]["grad_norms"],
            "change_norms": trainer.change_norms()}
