"""Streaming per-layer training step: >HBM models on one chip.

Capability parity: the reference trains models whose full gradient set
does not fit device memory via FSDP param/grad sharding
(atorch/atorch/distributed/zero_optimization.py:215) and CPU-offloaded
Adam (atorch/atorch/optim/adam_offload.py). On a single TPU chip the
same wall is the *simultaneous* gradient tree: a standard
``jax.value_and_grad`` step materializes every layer's gradient at once,
so bf16 Llama-7B needs params (13.5 GB) + grads (13.5 GB) > 15.75 GB
HBM. TPU re-design: per-leaf optimizers (adafactor family) don't need
the whole gradient tree — so this trainer hand-orchestrates the backward
pass as a reverse ``fori_loop`` over layers, where each iteration

    1. recomputes the layer forward from its stashed input (remat),
    2. runs the layer-local VJP,
    3. applies the optimizer update to that layer in place
       (``dynamic_update_index_in_dim`` on the loop carry — XLA's
       in-place loop-carry aliasing keeps ONE params buffer live),
    4. frees the layer gradient by construction (it dies with the loop
       iteration).

Peak memory: params + ONE layer's grads + the layer-input stash
(L, micro, seq, hidden) — ~14.5 GB for 7B at micro 1 / seq 2048, which
fits. The math is identical to the dense step: every layer's VJP uses
the pre-update params (updates touch only already-differentiated
layers), so the result matches ``build_trainer``'s step bit-for-bit up
to float reassociation (asserted by tests/test_streaming.py).

Constraints: the model is the scan-shaped Llama stack (identical
decoder blocks); the optimizer must be per-leaf (no cross-leaf state —
factored_rms/adafactor qualify, global-norm clipping does not, which is
why it takes an explicit ``tx`` and documents the contract).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.models.llama import (
    DecoderBlock,
    LlamaConfig,
    RMSNorm,
    embed_lookup,
)


@flax.struct.dataclass
class StreamingState:
    step: jax.Array
    block_params: Any        # every leaf stacked with leading dim L
    embed: jax.Array         # (vocab, hidden)
    head: Optional[jax.Array]  # (hidden, vocab); None = tied to embed
    norm_params: Any         # final RMSNorm params
    block_opt: Any           # per-layer optimizer state, stacked
    embed_opt: Any
    head_opt: Any
    norm_opt: Any

    @property
    def params(self) -> Any:
        """Parameter subtree (TrainState.params parity) so generic
        consumers — e.g. the elastic loop's model-info report — can
        size the model without knowing the streaming layout."""
        return {"blocks": self.block_params, "embed": self.embed,
                "head": self.head, "norm": self.norm_params}


def _tree_index(tree: Any, i) -> Any:
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
        tree)


def _tree_update(tree: Any, leaf_tree: Any, i) -> Any:
    return jax.tree.map(
        lambda x, v: jax.lax.dynamic_update_index_in_dim(
            x, v.astype(x.dtype), i, 0),
        tree, leaf_tree)


@dataclasses.dataclass
class StreamingTrainer:
    """Mirror of ShardedTrainer's surface for the streaming step —
    including `mesh` and `precompile()`, so ElasticTrainLoop can take it
    as an injected trainer (checkpoint/resume, restore-compile overlap,
    speed reports all apply unchanged)."""

    config: LlamaConfig
    init_fn: Callable[[jax.Array], StreamingState]
    step_fn: Callable[..., Tuple[StreamingState, dict]]
    micro_batch: int
    seq_len: int
    accum_steps: int = 1
    mesh: Any = None                 # single-device mesh
    precompile_timings: dict = dataclasses.field(default_factory=dict)
    _compiled: Any = None

    def init(self, rng: jax.Array) -> StreamingState:
        return self.init_fn(rng)

    def abstract_state(self, rng: jax.Array) -> StreamingState:
        return jax.eval_shape(self.init_fn, rng)

    def precompile(self, rng: Optional[jax.Array] = None) -> None:
        """AOT-compile the step for the built shapes (idempotent); the
        elastic loop calls this concurrently with the checkpoint read."""
        if self._compiled is not None:
            return
        import time as _time

        t0 = _time.monotonic()
        abstract = self.abstract_state(
            rng if rng is not None else jax.random.PRNGKey(0))
        tok = jax.ShapeDtypeStruct((self.micro_batch, self.seq_len),
                                   jnp.int32)
        self._compiled = self.step_fn.lower(abstract, tok, tok).compile()
        self.precompile_timings = {
            "streaming_aot_s": round(_time.monotonic() - t0, 2)}

    def step(self, state: StreamingState, tokens, targets):
        # the AOT executable is shape-pinned; any other shape (shorter
        # final batch, a longer sequence) takes the jitted path, which
        # retraces — the head-loss chunking derives from the runtime
        # length, so other sequence lengths stay supported
        if (self._compiled is not None
                and tuple(tokens.shape) == (self.micro_batch,
                                            self.seq_len)):
            return self._compiled(state, tokens, targets)
        return self.step_fn(state, tokens, targets)

    def shard_batch(self, tokens, targets):
        return jnp.asarray(tokens), jnp.asarray(targets)


def build_streaming_trainer(
    cfg: LlamaConfig,
    tx: optax.GradientTransformation,
    micro_batch: int,
    seq_len: int,
    rng_seed: int = 0,
    devices: Any = None,
) -> StreamingTrainer:
    """Lower a scan-shaped Llama + per-leaf optimizer into a streaming
    step. Single-device by definition (the >HBM single-chip escape
    hatch); multi-chip scale-out composes the ordinary trainers with
    FSDP/PP. ``devices``: the ONE device to run on (default: the first
    local device); the state is committed there, so the step follows."""
    devices = (list(devices) if devices is not None
               else jax.devices()[:1])
    if len(devices) != 1:
        raise ValueError(
            f"the streaming trainer runs on exactly one device, got "
            f"{len(devices)}: shard across chips with fsdp / "
            f"pipeline_parallel instead")
    L = cfg.num_layers
    hidden = cfg.hidden_size
    block = DecoderBlock(cfg)
    norm = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl)

    x0 = jax.ShapeDtypeStruct((micro_batch, seq_len, hidden), cfg.dtype)
    pos0 = jax.ShapeDtypeStruct((micro_batch, seq_len), jnp.int32)
    block_abstract = jax.eval_shape(
        lambda k, x, p: block.init(k, x, p),
        jax.random.key(0), x0, pos0)["params"]
    norm_abstract = jax.eval_shape(
        lambda k, x: norm.init(k, x), jax.random.key(0), x0)["params"]

    def _init_leaf(key, a, path):
        name = "/".join(str(p) for p in path).lower()
        # norm scales init to ones (models/llama.py RMSNorm uses
        # nn.initializers.ones); they are the only 1-D params in the
        # stack, so the rank check catches the bare "weight" path of the
        # final norm too
        if "norm" in name or "scale" in name or len(a.shape) == 1:
            return jnp.ones(a.shape, a.dtype)
        return (jax.random.normal(key, a.shape, jnp.float32) * 0.02
                ).astype(a.dtype)

    def _init(rng) -> StreamingState:
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            block_abstract)
        keys = jax.random.split(jax.random.fold_in(rng, 0),
                                len(leaves) * L)
        stacked = []
        for n, (path, a) in enumerate(leaves):
            per_layer = [
                _init_leaf(keys[n * L + layer], a, path)
                for layer in range(L)
            ]
            stacked.append(jnp.stack(per_layer))
        block_params = jax.tree.unflatten(
            jax.tree.structure(block_abstract), stacked)
        embed = (jax.random.normal(
            jax.random.fold_in(rng, 1), (cfg.vocab_size, hidden),
            jnp.float32) * 0.02).astype(cfg.param_dtype)
        head = None
        if not cfg.tie_embeddings:
            head = (jax.random.normal(
                jax.random.fold_in(rng, 2), (hidden, cfg.vocab_size),
                jnp.float32) * 0.02).astype(cfg.param_dtype)
        norm_params = jax.tree_util.tree_map_with_path(
            lambda p, a: _init_leaf(jax.random.fold_in(rng, 3), a, p),
            norm_abstract)
        return StreamingState(
            step=jnp.zeros((), jnp.int32),
            block_params=block_params,
            embed=embed,
            head=head,
            norm_params=norm_params,
            block_opt=jax.vmap(tx.init)(block_params),
            embed_opt=tx.init(embed),
            head_opt=None if head is None else tx.init(head),
            norm_opt=tx.init(norm_params),
        )

    def _apply_update(params, grads, opt_state):
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt

    def _step(state: StreamingState, tokens, targets):
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[-1]), tokens.shape)

        # ---- forward: loop over layers, stash each layer's INPUT -----
        h = embed_lookup(state.embed, tokens, cfg)
        stash = jnp.zeros((L,) + h.shape, h.dtype)

        def fwd_body(i, carry):
            h, stash = carry
            stash = jax.lax.dynamic_update_index_in_dim(stash, h, i, 0)
            p_i = _tree_index(state.block_params, i)
            h = block.apply({"params": p_i}, h, positions)
            return h, stash

        h, stash = jax.lax.fori_loop(0, L, fwd_body, (h, stash))

        # ---- head + final norm: ordinary VJP (small params) ----------
        head_param = state.embed if state.head is None else state.head
        # chunk the (seq, vocab) logits over sequence with per-chunk
        # recompute: peak logits memory = one chunk, not B*S*V fp32
        # (for 7B at seq 2048 that's ~790 MB of softmax temps saved)

        def head_loss(norm_params, head_p, h):
            x = norm.apply({"params": norm_params}, h)
            w = head_p.astype(cfg.dtype)
            wt = w.T if state.head is None else w
            b, s, hid = x.shape
            # chunk from the RUNTIME length (trace-time static), so any
            # sequence length steps — not just the build-time one
            seq_chunk = next((c for c in (512, 256, 128)
                              if s % c == 0), s)
            nc = s // seq_chunk
            xc = x.reshape(b, nc, seq_chunk, hid).swapaxes(0, 1)
            tc = targets.reshape(b, nc, seq_chunk).swapaxes(0, 1)

            @jax.checkpoint
            def chunk_nll(x_chunk, t_chunk):
                logits = jnp.dot(x_chunk, wt).astype(jnp.float32)
                logp = jax.nn.log_softmax(logits, axis=-1)
                return jnp.sum(-jnp.take_along_axis(
                    logp, t_chunk[..., None], axis=-1)[..., 0])

            def body(acc, ct):
                return acc + chunk_nll(*ct), None

            total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                    (xc, tc))
            return total / (b * s)

        loss, head_vjp = jax.vjp(
            head_loss, state.norm_params, head_param, h)
        d_norm, d_head, dh = head_vjp(jnp.ones((), jnp.float32))

        new_norm, new_norm_opt = _apply_update(
            state.norm_params, d_norm, state.norm_opt)
        new_head = state.head
        new_head_opt = state.head_opt
        embed_grad_from_head = None
        if state.head is None:
            embed_grad_from_head = d_head   # tied: fold into embed grad
        else:
            new_head, new_head_opt = _apply_update(
                state.head, d_head, state.head_opt)

        # ---- backward: reverse loop, update-in-place per layer -------
        def bwd_body(j, carry):
            dh, params, opt = carry
            i = L - 1 - j
            h_in = jax.lax.dynamic_index_in_dim(stash, i, 0,
                                                keepdims=False)
            p_i = _tree_index(params, i)

            def f(p, x):
                return block.apply({"params": p}, x, positions)

            _, vjp_fn = jax.vjp(f, p_i, h_in)
            dp_i, dh_in = vjp_fn(dh)
            new_p_i, new_opt_i = _apply_update(
                p_i, dp_i, _tree_index(opt, i))
            return (dh_in, _tree_update(params, new_p_i, i),
                    _tree_update(opt, new_opt_i, i))

        dh0, new_block, new_block_opt = jax.lax.fori_loop(
            0, L, bwd_body, (dh, state.block_params, state.block_opt))

        # ---- embedding backward (scatter-add of dh0) -----------------
        def embed_fwd(e):
            return embed_lookup(e, tokens, cfg)

        _, embed_vjp = jax.vjp(embed_fwd, state.embed)
        (d_embed,) = embed_vjp(dh0)
        if embed_grad_from_head is not None:
            d_embed = d_embed + embed_grad_from_head.astype(d_embed.dtype)
        new_embed, new_embed_opt = _apply_update(
            state.embed, d_embed, state.embed_opt)

        new_state = StreamingState(
            step=state.step + 1,
            block_params=new_block,
            embed=new_embed,
            head=new_head,
            norm_params=new_norm,
            block_opt=new_block_opt,
            embed_opt=new_embed_opt,
            head_opt=new_head_opt,
            norm_opt=new_norm_opt,
        )
        return new_state, {"loss": loss}

    from jax.sharding import SingleDeviceSharding

    from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh

    return StreamingTrainer(
        config=cfg,
        init_fn=jax.jit(
            _init, out_shardings=SingleDeviceSharding(devices[0])),
        step_fn=jax.jit(_step, donate_argnums=(0,)),
        micro_batch=micro_batch,
        seq_len=seq_len,
        mesh=create_mesh(MeshSpec(), devices),
    )
