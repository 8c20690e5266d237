"""Pipelined transformer trainer: circular-schedule PP over layer chunks.

Capability parity: atorch's pipeline-parallel training path (PiPPy
compile → stages → driver with GPipe/interleaved/1F1B schedules,
distributed_pippy_compiler.py:378) and the DeepSpeed 3D composition
(ds_3d_parallel_optimization.py:53 — pipe × tensor × data in one
topology); arbitrary fx-traceable models map here to any stacked-block
model via PipelineModelSpec (Llama and GPT ship built in).

TPU re-design: decoder-layer params are stacked (rounds, stages,
layers_per_chunk, ...) with the stage dim sharded over the `pipe` mesh
axis AND their trailing dims sharded over fsdp/tensor through the model's
logical axis names — the pipe shard_map is manual only over `pipe`
(jax.shard_map axis_names), so XLA keeps the stage-internal shardings and
inserts the intra-stage collectives. The embedding runs at stage 0 and
the norm + LM head + loss at the last stage INSIDE the pipeline
(parallel/pipeline.py pipeline_train), so that work is not replicated
across pipe ranks and only a scalar loss crosses stages. num_rounds > 1
gives the circular (interleaved) schedule that divides the pipeline
bubble by the round count. Same init/step/shard_batch surface as
build_trainer.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.common.constants import MeshAxis
from dlrover_tpu.models.gpt import Block as GPTBlock, GPTConfig
from dlrover_tpu.models.llama import (
    DecoderBlock,
    LlamaConfig,
    embed_lookup,
)
from dlrover_tpu.parallel.pipeline import pipeline_train
from dlrover_tpu.parallel.sharding import DEFAULT_RULES
from dlrover_tpu.trainer.train_step import TrainState

_BATCH_AXES = (MeshAxis.DATA, MeshAxis.FSDP)


def _per_row(loss_fn):
    """Lift a batch-mean loss (logits, targets) -> scalar into a per-row
    vector loss (micro, seq, V), (micro, seq) -> (micro,): the pipeline
    exit must not reduce across (sharded) rows."""

    def row_losses(logits, targets):
        return jax.vmap(
            lambda lg, tg: loss_fn(lg[None], tg[None]))(logits, targets)

    return row_losses


@dataclasses.dataclass
class PipelineModelSpec:
    """Everything the pipeline needs to know about a stacked-block model.

    The reference pipelines arbitrary fx-traceable models; the analog
    here is any model expressible as enter → N identical blocks → exit.
    """

    num_layers: int
    # init ONE block's params: (rng) -> params tree (unboxed)
    init_layer: Callable[[jax.Array], Any]
    # init the shared (non-stage) params: (rng) -> dict (embedding, head…)
    init_shared: Callable[[jax.Array], Any]
    # chunk_fn(stacked_layer_params, act) -> act: run this chunk's layers
    chunk_fn: Callable[[Any, jax.Array], jax.Array]
    # enter_fn(shared, tokens_micro) -> (micro, seq, H) activation
    enter_fn: Callable[[Any, jax.Array], jax.Array]
    # exit_fn(shared, act, targets_micro) -> (micro,) per-row losses
    # (NO cross-row reduction — it runs inside a stage-divergent cond,
    # see pipeline_train)
    exit_fn: Callable[[Any, jax.Array, jax.Array], jax.Array]
    # abstract ONE-layer boxed params (for shardings): () -> boxed tree
    abstract_layer: Callable[[], Any]
    # logical specs for the shared params: dict name -> P(logical axes)
    shared_logical: Any
    # chunk_fn returns (act, aux_scalar) — MoE router losses carried to
    # the exit through the pipeline's aux accumulator
    has_aux: bool = False


# ---------------------------------------------------------------------------
# Built-in specs: Llama family and GPT (nanogpt)
# ---------------------------------------------------------------------------


def llama_pipeline_spec(cfg: LlamaConfig, seq_len: int,
                        loss_fn) -> PipelineModelSpec:
    block = DecoderBlock(cfg)
    x = jnp.zeros((1, seq_len, cfg.hidden_size), cfg.dtype)
    positions0 = jnp.zeros((1, seq_len), jnp.int32)
    # enter_fn runs once per pipeline STEP on every device (uniform
    # where-select, pipeline_train docstring): the gather lookup is
    # near-free there, the one-hot matmul is micro·seq·V·H per step.
    cfg_embed = dataclasses.replace(cfg, embed_impl="gather")

    def init_layer(rng):
        return nn.unbox(block.init(rng, x, positions0))["params"]

    def init_shared(rng):
        r_embed, r_head = jax.random.split(rng)
        return {
            "embed": jax.random.normal(
                r_embed, (cfg.vocab_size, cfg.hidden_size),
                cfg.param_dtype) * 0.02,
            "final_norm": jnp.ones((cfg.hidden_size,), cfg.param_dtype),
            "lm_head": jax.random.normal(
                r_head, (cfg.hidden_size, cfg.vocab_size),
                cfg.param_dtype) * 0.02,
        }

    def chunk_fn(stacked, h):
        positions = jnp.broadcast_to(jnp.arange(h.shape[1]), h.shape[:2])

        def one_layer(carry, layer_params):
            return block.apply({"params": layer_params}, carry,
                               positions), None

        h, _ = lax.scan(one_layer, h, stacked)
        return h

    def enter_fn(shared, tokens):
        return embed_lookup(shared["embed"], tokens, cfg_embed)

    row_losses = _per_row(loss_fn)

    def exit_fn(shared, h, targets):
        from dlrover_tpu.ops.norms import reference_rms_norm

        h = reference_rms_norm(
            h, shared["final_norm"].astype(jnp.float32), cfg.rms_norm_eps)
        logits = jnp.dot(h.astype(cfg.dtype),
                         shared["lm_head"].astype(cfg.dtype))
        logits = logits.astype(jnp.float32)
        return row_losses(logits, targets)

    def abstract_layer():
        return jax.eval_shape(
            lambda r: block.init(r, x, positions0)["params"],
            jax.random.PRNGKey(0))

    return PipelineModelSpec(
        num_layers=cfg.num_layers,
        init_layer=init_layer,
        init_shared=init_shared,
        chunk_fn=chunk_fn,
        enter_fn=enter_fn,
        exit_fn=exit_fn,
        abstract_layer=abstract_layer,
        shared_logical={
            "embed": ("vocab", "embed"),
            "final_norm": ("norm",),
            "lm_head": ("embed", "vocab"),
        },
    )


def llama_moe_pipeline_spec(cfg, seq_len: int,
                            loss_fn) -> PipelineModelSpec:
    """MoE decoder blocks through the pipeline (the
    reference's 3D path composes pipe with MoE,
    ds_3d_parallel_optimization.py:53 + modules/moe/moe_layer.py:161).

    The expert axis lives INSIDE each stage: expert weights carry the
    'expert' logical axis, which stays auto under the pipe-manual
    shard_map, so XLA shards experts and places the dispatch all-to-all
    per stage — pipe × expert × fsdp/tensor in one program. Router aux
    losses flow through the pipeline's aux accumulator (has_aux) and are
    folded into the objective exactly as the dense trainer's
    moe_cross_entropy_loss does. Routing is deterministic (no jitter
    rng): the per-chunk scan has no rng plumbing; use jitter_noise=0
    configs under PP (the dense trainer supports jittered gating)."""
    from dlrover_tpu.models.llama_moe import MoEDecoderBlock
    from dlrover_tpu.parallel.moe import moe_aux_loss

    block = MoEDecoderBlock(cfg, deterministic=True)
    x = jnp.zeros((1, seq_len, cfg.hidden_size), cfg.dtype)
    positions0 = jnp.zeros((1, seq_len), jnp.int32)
    dense = llama_pipeline_spec(
        dataclasses.replace(cfg, num_experts=0), seq_len, loss_fn)

    def init_layer(rng):
        return nn.unbox(block.init(rng, x, positions0))["params"]

    def chunk_fn(stacked, h):
        from dlrover_tpu.parallel.pipeline import _varying

        positions = jnp.broadcast_to(jnp.arange(h.shape[1]), h.shape[:2])

        def one_layer(carry, layer_params):
            h, aux = carry
            y, mutables = block.apply(
                {"params": layer_params}, h, positions,
                mutable=["losses"])
            return (y, aux + moe_aux_loss(mutables)), None

        # runs inside the pipe-manual shard_map: the aux carry must be
        # marked pipe-varying like the activations it will join
        aux0 = _varying(jnp.zeros((), jnp.float32), MeshAxis.PIPE)
        (h, aux), _ = lax.scan(one_layer, (h, aux0), stacked)
        return h, aux

    def abstract_layer():
        return jax.eval_shape(
            lambda r: block.init(r, x, positions0)["params"],
            jax.random.PRNGKey(0))

    return dataclasses.replace(
        dense,
        init_layer=init_layer,
        chunk_fn=chunk_fn,
        abstract_layer=abstract_layer,
        has_aux=True,
    )


def gpt_pipeline_spec(cfg: GPTConfig, seq_len: int,
                      loss_fn) -> PipelineModelSpec:
    block = GPTBlock(cfg)
    x = jnp.zeros((1, seq_len, cfg.n_embd), cfg.dtype)
    ln = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")

    def init_layer(rng):
        return nn.unbox(block.init(rng, x))["params"]

    # enter_fn runs once per pipeline STEP on every device: force the
    # cheap gather lookup (see llama_pipeline_spec).
    cfg_embed = dataclasses.replace(cfg, embed_impl="gather")

    def init_shared(rng):
        r_wte, r_wpe, r_ln = jax.random.split(rng, 3)
        return {
            "wte": jax.random.normal(
                r_wte, (cfg.vocab_size, cfg.n_embd),
                cfg.param_dtype) * 0.02,
            "wpe": jax.random.normal(
                r_wpe, (cfg.block_size, cfg.n_embd),
                cfg.param_dtype) * 0.02,
            "ln_f": nn.unbox(ln.init(r_ln, x))["params"],
        }

    def chunk_fn(stacked, h):
        def one_layer(carry, layer_params):
            return block.apply({"params": layer_params}, carry), None

        h, _ = lax.scan(one_layer, h, stacked)
        return h

    def enter_fn(shared, tokens):
        seq = tokens.shape[-1]
        return (embed_lookup(shared["wte"], tokens, cfg_embed)
                + shared["wpe"].astype(cfg.dtype)[:seq])

    row_losses = _per_row(loss_fn)

    def exit_fn(shared, h, targets):
        h = ln.apply({"params": shared["ln_f"]}, h)
        # weight-tied LM head (as nanoGPT)
        logits = jnp.dot(h, shared["wte"].astype(cfg.dtype).T)
        return row_losses(logits.astype(jnp.float32), targets)

    def abstract_layer():
        return jax.eval_shape(
            lambda r: block.init(r, x)["params"], jax.random.PRNGKey(0))

    return PipelineModelSpec(
        num_layers=cfg.n_layer,
        init_layer=init_layer,
        init_shared=init_shared,
        chunk_fn=chunk_fn,
        enter_fn=enter_fn,
        exit_fn=exit_fn,
        abstract_layer=abstract_layer,
        shared_logical={
            "wte": ("vocab", "embed"),
            "wpe": (None, "embed"),
            "ln_f": {"scale": ("norm",), "bias": ("norm",)},
        },
    )


def bert_pipeline_spec(cfg, seq_len: int, loss_fn) -> PipelineModelSpec:
    """Encoder (BERT) pipeline (reference pipelines
    arbitrary fx-traceable models, distributed_pippy_compiler.py:378).

    enter: word + position embeddings + embed LayerNorm; chunks: scanned
    EncoderBlocks (bidirectional attention); exit: MLM transform + LN +
    the weight-tied decoder over the word table + per-row loss.
    token_types ride as zeros (the segment embedding is a fine-tuning
    feature; pipeline pretraining uses single-segment packed batches)."""
    from dlrover_tpu.models.bert import BertConfig, EncoderBlock

    assert isinstance(cfg, BertConfig)
    block = EncoderBlock(cfg)
    x = jnp.zeros((1, seq_len, cfg.hidden_size), cfg.dtype)
    cfg_embed = dataclasses.replace(cfg, embed_impl="gather")
    embed_norm = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                              name="embed_norm")
    mlm_transform = nn.Dense(
        cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        name="mlm_transform")
    mlm_norm = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                            name="mlm_norm")

    def init_layer(rng):
        return nn.unbox(block.init(rng, x))["params"]

    def init_shared(rng):
        r_word, r_pos, r_en, r_tr, r_mn = jax.random.split(rng, 5)
        return {
            "word_embed": jax.random.normal(
                r_word, (cfg.vocab_size, cfg.hidden_size),
                cfg.param_dtype) * 0.02,
            "pos_embed": jax.random.normal(
                r_pos, (cfg.max_seq_len, cfg.hidden_size),
                cfg.param_dtype) * 0.02,
            "embed_norm": nn.unbox(embed_norm.init(r_en, x))["params"],
            "mlm_transform": nn.unbox(
                mlm_transform.init(r_tr, x))["params"],
            "mlm_norm": nn.unbox(mlm_norm.init(r_mn, x))["params"],
        }

    def chunk_fn(stacked, h):
        def one_layer(carry, layer_params):
            return block.apply({"params": layer_params}, carry), None

        h, _ = lax.scan(one_layer, h, stacked)
        return h

    def enter_fn(shared, tokens):
        seq = tokens.shape[-1]
        h = (embed_lookup(shared["word_embed"], tokens, cfg_embed)
             + shared["pos_embed"].astype(cfg.dtype)[:seq])
        return embed_norm.apply({"params": shared["embed_norm"]}, h)

    row_losses = _per_row(loss_fn)

    def exit_fn(shared, h, targets):
        h = mlm_transform.apply({"params": shared["mlm_transform"]}, h)
        h = nn.gelu(h)
        h = mlm_norm.apply({"params": shared["mlm_norm"]}, h)
        logits = jnp.dot(h, shared["word_embed"].astype(cfg.dtype).T)
        return row_losses(logits.astype(jnp.float32), targets)

    def abstract_layer():
        return jax.eval_shape(
            lambda r: block.init(r, x)["params"], jax.random.PRNGKey(0))

    return PipelineModelSpec(
        num_layers=cfg.num_layers,
        init_layer=init_layer,
        init_shared=init_shared,
        chunk_fn=chunk_fn,
        enter_fn=enter_fn,
        exit_fn=exit_fn,
        abstract_layer=abstract_layer,
        shared_logical={
            "word_embed": ("vocab", "embed"),
            "pos_embed": (None, "embed"),
            "embed_norm": {"scale": ("norm",), "bias": ("norm",)},
            "mlm_transform": {"kernel": ("embed", "mlp"),
                              "bias": ("mlp",)},
            "mlm_norm": {"scale": ("norm",), "bias": ("norm",)},
        },
    )


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------


class PipelinedTrainer:
    """Same surface as ShardedTrainer (init/step/shard_batch)."""

    def __init__(self, spec: PipelineModelSpec,
                 tx: optax.GradientTransformation,
                 mesh: Mesh, num_microbatches: int, micro_batch: int,
                 seq_len: int, num_rounds: int = 1, remat: bool = False,
                 rules: Optional[Sequence] = None,
                 offload_opt_state: bool = False,
                 bound_activations: bool = False):
        self.spec = spec
        self._offload = offload_opt_state
        self.mesh = mesh
        self.num_stages = mesh.shape[MeshAxis.PIPE]
        self.num_rounds = num_rounds
        self.num_microbatches = num_microbatches
        self.micro_batch = micro_batch
        self.accum_steps = num_microbatches  # microbatches play this role
        self.seq_len = seq_len
        self._tx = tx
        self._remat = remat
        self._bound_activations = bound_activations
        self._rules = list(rules if rules is not None else DEFAULT_RULES)
        # batch arrays: (M, micro, seq) with micro rows over the dp axes
        self.batch_sharding = NamedSharding(mesh, P(None, _BATCH_AXES))
        self.state_shardings = None
        self._step = None

    @property
    def num_chunks(self) -> int:
        return self.num_stages * self.num_rounds

    @property
    def layers_per_chunk(self) -> int:
        if self.spec.num_layers % self.num_chunks:
            raise ValueError(
                f"{self.spec.num_layers} layers not divisible by "
                f"{self.num_chunks} chunks "
                f"({self.num_stages} stages × {self.num_rounds} rounds)")
        return self.spec.num_layers // self.num_chunks

    # -- params ---------------------------------------------------------
    def _param_shardings(self):
        """NamedSharding tree matching the params dict: chunk leaves get
        P(None, pipe, None, *mesh-mapped logical axes) — stage-internal
        fsdp/tensor sharding composed with pipe (the reference's 3D
        topology, ds_3d_parallel_optimization.py:53)."""
        from dlrover_tpu.parallel.sharding import mesh_shardings

        boxed = self.spec.abstract_layer()
        layer_shardings = mesh_shardings(boxed, self.mesh, self._rules)
        chunk_shardings = jax.tree.map(
            lambda s: NamedSharding(
                self.mesh, P(None, MeshAxis.PIPE, None, *s.spec)),
            layer_shardings,
            is_leaf=lambda s: isinstance(s, NamedSharding),
        )

        # Shared params (embedding / final norm / head) replicate over
        # pipe but keep their fsdp/tensor shardings: the enter/exit
        # bodies execute uniformly on every device (where-selected, see
        # pipeline_train), so their auto-axis collectives are uniform.
        def from_logical(names):
            if isinstance(names, dict):
                return {k: from_logical(v) for k, v in names.items()}
            sh = nn.logical_to_mesh_sharding(
                P(*names), self.mesh, self._rules)
            return NamedSharding(self.mesh, sh.spec)

        shared = {name: from_logical(names)
                  for name, names in self.spec.shared_logical.items()}
        return {"shared": shared, "chunks": chunk_shardings}

    def _make_params(self, rng):
        spec = self.spec
        per_chunk = self.layers_per_chunk
        r_layers, r_shared = jax.random.split(rng)
        rngs = jax.random.split(r_layers, spec.num_layers)
        stacked = jax.vmap(spec.init_layer)(rngs)
        # layer ℓ = (r·S + s)·per_chunk + j  ↔  [r, s, j] (row-major)
        stacked = jax.tree.map(
            lambda leaf: leaf.reshape(
                (self.num_rounds, self.num_stages, per_chunk)
                + leaf.shape[1:]),
            stacked)
        return {"shared": spec.init_shared(r_shared), "chunks": stacked}

    def _make_state(self, rng):
        params = self._make_params(rng)
        return TrainState(step=jnp.zeros((), jnp.int32),
                          params=params,
                          opt_state=self._tx.init(params))

    def _ensure_shardings(self, rng) -> None:
        if self.state_shardings is not None:
            return
        _ = self.layers_per_chunk   # validate divisibility eagerly
        abstract = jax.eval_shape(self._make_state, rng)
        param_shardings = self._param_shardings()
        flat_params = {
            tuple(str(getattr(k, "key", k)) for k in path): sharding
            for path, sharding in
            jax.tree_util.tree_flatten_with_path(param_shardings)[0]
        }
        replicated = NamedSharding(self.mesh, P())

        def for_path(path, leaf):
            """Optimizer moments mirror the params tree: match the longest
            path suffix against the params sharding table."""
            keys = tuple(str(getattr(k, "key", getattr(k, "name", k)))
                         for k in path)
            for start in range(len(keys)):
                if keys[start:] in flat_params:
                    sharding = flat_params[keys[start:]]
                    if len(sharding.spec) <= leaf.ndim:
                        return sharding
            return replicated

        self.state_shardings = jax.tree_util.tree_map_with_path(
            for_path, abstract)
        if self._offload:
            # optimizer moments live in HOST memory (same mechanism as
            # build_trainer's offload_opt_state: pinned_host memory kind
            # on the shardings; XLA inserts the host↔HBM transfers
            # around the update). Scalars stay on device — the SPMD
            # partitioner rejects memory kinds on them.
            self.state_shardings = self.state_shardings.replace(
                opt_state=jax.tree.map(
                    lambda s, a: s if a.ndim == 0 else NamedSharding(
                        self.mesh, s.spec, memory_kind="pinned_host"),
                    self.state_shardings.opt_state, abstract.opt_state,
                ))

    def abstract_state(self, rng: jax.Array) -> TrainState:
        """Abstract TrainState (shapes + shardings) — the checkpoint
        restore target, same surface as ShardedTrainer."""
        from dlrover_tpu.trainer.train_step import (
            abstract_state_with_shardings,
        )

        self._ensure_shardings(rng)
        return abstract_state_with_shardings(
            jax.eval_shape(self._make_state, rng), self.state_shardings)

    def init(self, rng: jax.Array) -> TrainState:
        self._ensure_shardings(rng)
        # jit with out_shardings: nothing ever materializes replicated
        return jax.jit(self._make_state,
                       out_shardings=self.state_shardings)(rng)

    # -- data -----------------------------------------------------------
    def shard_batch(self, tokens, targets):
        m, micro = self.num_microbatches, self.micro_batch
        tokens = tokens.reshape(m, micro, *tokens.shape[1:])
        targets = targets.reshape(m, micro, *targets.shape[1:])
        put = lambda x: jax.device_put(x, self.batch_sharding)
        return put(tokens), put(targets)

    # -- step -----------------------------------------------------------
    def _loss(self, params, tokens, targets):
        spec = self.spec
        return pipeline_train(
            self.mesh, spec.chunk_fn, params["chunks"], params["shared"],
            spec.enter_fn, spec.exit_fn, tokens, targets,
            num_rounds=self.num_rounds, remat=self._remat,
            chunk_has_aux=spec.has_aux,
            # 1F1B-style bound: one checkpointed window of num_stages
            # schedule steps live at a time (see pipeline_train)
            activation_groups=(self.num_stages
                               if self._bound_activations else 0))

    def step(self, state: TrainState, tokens, targets):
        if self._step is None:
            tx = self._tx

            def train_step(state, tokens, targets):
                loss, grads = jax.value_and_grad(self._loss)(
                    state.params, tokens, targets)
                updates, opt_state = tx.update(grads, state.opt_state,
                                               state.params)
                params = optax.apply_updates(state.params, updates)
                return TrainState(step=state.step + 1, params=params,
                                  opt_state=opt_state), {"loss": loss}

            self._step = jax.jit(train_step, donate_argnums=(0,))
        return self._step(state, tokens, targets)


def build_pipeline_trainer(cfg: Union[LlamaConfig, GPTConfig],
                           tx: optax.GradientTransformation,
                           mesh: Mesh, num_microbatches: int,
                           micro_batch: int, seq_len: int, loss_fn,
                           num_rounds: int = 1,
                           remat: bool = False,
                           rules: Optional[Sequence] = None,
                           offload_opt_state: bool = False,
                           bound_activations: bool = False
                           ) -> PipelinedTrainer:
    """Lower a stacked-block model config to a pipelined trainer.

    Any model family with a PipelineModelSpec pipelines; LlamaConfig and
    GPTConfig ship built in (the reference pipelines arbitrary
    fx-traceable models via PiPPy — spec construction is the analog).

    loss_fn contract: a BATCH-MEAN loss (logits, targets) -> scalar, the
    mean over its batch rows (cross_entropy_loss qualifies). The pipeline
    applies it per microbatch row and averages — a sum-reducing loss
    would silently change scale vs the dense trainer."""
    # bf16 pipelines compile everywhere: the XLA-CPU half-precision
    # collective bug is dodged surgically inside pipeline_train (shared
    # params cross the shard_map boundary in fp32 on CPU — pvary'd
    # BEFORE the compute-dtype cast — so their grad psum, the
    # instruction the CPU compiler CHECK-failed on, runs fp32 while
    # every stage computes in the real dtype). One residue: MoE chunks
    # under PP put the expert axis auto INSIDE the pipe-manual region,
    # and GSPMD inserts bf16 expert collectives there that the same CPU
    # promotion pass chokes on — those configs force fp32 on CPU only.
    from dlrover_tpu.models.llama_moe import LlamaMoEConfig

    if (jax.default_backend() == "cpu"
            and isinstance(cfg, LlamaMoEConfig)
            and getattr(cfg, "num_experts", 0) > 0
            and jnp.dtype(cfg.dtype) in (jnp.bfloat16, jnp.float16)):
        from dlrover_tpu.common.log import default_logger as logger

        logger.info("MoE pipeline: forcing fp32 on the cpu backend "
                    "(GSPMD-inserted half-precision expert collectives "
                    "inside the pipe-manual region hit the XLA-CPU "
                    "promotion bug); dense pipelines stay bf16")
        replace = {"dtype": jnp.float32}
        if jnp.dtype(cfg.param_dtype) in (jnp.bfloat16, jnp.float16):
            replace["param_dtype"] = jnp.float32
        cfg = dataclasses.replace(cfg, **replace)

    if isinstance(cfg, LlamaMoEConfig):
        # (checked before LlamaConfig — LlamaMoEConfig subclasses it;
        # without this order an MoE config would pipeline as dense)
        spec = llama_moe_pipeline_spec(cfg, seq_len, loss_fn)
    elif isinstance(cfg, LlamaConfig):
        spec = llama_pipeline_spec(cfg, seq_len, loss_fn)
    elif isinstance(cfg, GPTConfig):
        spec = gpt_pipeline_spec(cfg, seq_len, loss_fn)
    else:
        from dlrover_tpu.models.bert import BertConfig

        if isinstance(cfg, BertConfig):
            spec = bert_pipeline_spec(cfg, seq_len, loss_fn)
        else:
            raise NotImplementedError(
                f"no pipeline spec for {type(cfg).__name__}; provide a "
                "PipelineModelSpec and construct PipelinedTrainer "
                "directly")
    return PipelinedTrainer(spec, tx, mesh, num_microbatches,
                            micro_batch, seq_len, num_rounds=num_rounds,
                            remat=remat, rules=rules,
                            offload_opt_state=offload_opt_state,
                            bound_activations=bound_activations)
