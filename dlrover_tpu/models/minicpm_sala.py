"""MiniCPM-SALA: a Llama skeleton whose layers are of two kinds, with
MiniCPM's muP scalings.

Built from the pieces `Llama` is built from (`models/llama.py`: RMSNorm,
`project_qkv` with its q/k norms, `apply_rope`, `tied_dot` projections, the
MLP, gather embedding, `hidden_and_head`), so it trains through the same
`build_trainer` and `ElasticTrainLoop`. What is its own:

- `mixer_types`, one published kind a layer, picks each block's mixer
  (this stage's layer i is the published layer i):
  - ``minicpm4``: InfLLM-v2 block-sparse attention
    (`ops/block_sparse_attention.py`), GQA with q/k norms and no RoPE;
  - ``lightning-attn``: linear attention with a decay per head
    (`ops/linear_attention.py`), q/k norms and RoPE, an RMSNorm over the
    whole output;
  both end in a sigmoid gate from the block's normed input, then `o_proj`.
- muP: the embedding times `embed_scale` (`scale_emb`), each residual
  branch times scale_depth / sqrt(published depth). The published model
  also divides the final hidden states by hidden / dim_model_base before
  the head; this class does not (the benchmark's plain reference has no
  such head, and the configuration lists it under `assumed`).
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models.llama import (
    ACT_AXES,
    MLP,
    Llama,
    LlamaConfig,
    RMSNorm,
    functools_partial_dense,
    project_qkv,
    tie_weight_grads,
)
from dlrover_tpu.ops.block_sparse_attention import (
    Sparsity,
    block_sparse_attention,
)
from dlrover_tpu.ops.linear_attention import linear_attention

SPARSE = "minicpm4"
LIGHTNING = "lightning-attn"


@dataclasses.dataclass(frozen=True)
class SalaConfig(LlamaConfig):
    """`num_heads`, `num_kv_heads` and `attn_head_dim` are the sparse
    layers' (published `num_attention_heads`, `num_key_value_heads`,
    `head_dim`); the lightning layers have their own."""

    qk_norm: bool = True
    # with `remat`: a block's recomputation keeps what its mixer's kernels
    # made (`ops/remat.py:Kept`) and its projections' outputs, and
    # recomputes the rest (the norms, the gates' elementwise work)
    remat_policy: str = "matmul_and_kernel_outputs"
    mixer_types: tuple = ()          # published, whole
    published_layers: int = 32       # the depth muP's and the decay's are of
    scale_depth: float = 1.4
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    sparsity: Sparsity = Sparsity()

    def mixer(self, layer: int) -> str:
        return self.mixer_types[layer]

    def lightning(self) -> LlamaConfig:
        """The lightning layers' heads, as `project_qkv` reads them."""
        return dataclasses.replace(
            self, num_heads=self.lightning_heads,
            num_kv_heads=self.lightning_heads,
            attn_head_dim=self.lightning_head_dim)

    def _layer_params(self, layer: int) -> int:
        h, i = self.hidden_size, self.intermediate_size
        if self.mixer(layer) == SPARSE:
            q, kv, d = (self.num_heads * self.head_dim,
                        self.num_kv_heads * self.head_dim, self.head_dim)
            out_norm = 0
        else:
            q = kv = self.lightning_heads * self.lightning_head_dim
            d, out_norm = self.lightning_head_dim, h
        # q, gate, o; k, v; the q/k norms and the output's
        mixer = 3 * h * q + 2 * h * kv + 2 * d + out_norm
        return mixer + 3 * h * i + 2 * h

    def param_count(self) -> int:
        emb = self.vocab_size * self.hidden_size * (
            1 if self.tie_embeddings else 2)
        layers = sum(self._layer_params(layer)
                     for layer in range(self.num_layers))
        return layers + emb + self.hidden_size

    def flops_per_token(self, seq_len: int) -> float:
        """Model FLOPs a trained token needs, forward and backward, nothing
        recomputed: 6 for each matmul parameter, the sparse layers'
        attention over the keys selected (`Sparsity.topk` blocks past
        `dense_len`, ``k s - k^2 / 2`` pairs a head, QK^T and PV x3 with
        the backward) and the lightning layers' chunked form at a chunk of
        64 (4 C d + 4 d^2 a head and token, x3). `obs/mfu.py` and the
        loop's report ask the model (`_report_model_info`)."""
        total = 6.0 * self.vocab_size * self.hidden_size
        for layer in range(self.num_layers):
            norms = 2 * self.hidden_size + (
                self.hidden_size if self.mixer(layer) == LIGHTNING else 0)
            total += 6.0 * (self._layer_params(layer) - norms)
            if self.mixer(layer) == SPARSE:
                total += 12.0 * self.num_heads * self.head_dim * (
                    selected_pairs(self.sparsity, seq_len) / seq_len)
            else:
                d = self.lightning_head_dim
                total += 3.0 * self.lightning_heads * (4 * 64 * d + 4 * d * d)
        return total


def selected_pairs(sp: Sparsity, seq_len: int) -> float:
    """(query, key) pairs one head attends, in the accepted convention that
    counts half the diagonal: ``k s - k^2 / 2`` for the ``k = topk x
    block`` keys a query takes past `dense_len`, ``s^2 / 2`` below it."""
    keys = sp.topk * sp.block
    if seq_len < sp.dense_len or keys >= seq_len:
        return seq_len * seq_len / 2.0
    return keys * seq_len - keys * keys / 2.0


def lightning_rates(heads: int, layer: int, layers: int) -> np.ndarray:
    """Each head's decay exp(-rate) in the published layer `layer` of
    `layers`: MiniMax's ALiBi slopes 2^(-8 (h + 1) / heads) times
    1 - layer / (layers - 1) + 1e-5."""
    slopes = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return (slopes * (1.0 - layer / (layers - 1) + 1e-5)).astype(np.float32)


def _gated_out(cfg: SalaConfig, x, tie, out):
    """`o_proj` of the mixer's output (b, s, heads x d) under the sigmoid
    gate read from the block's normed input."""
    width = out.shape[-1]
    dense = functools_partial_dense(cfg)
    gate = dense("o_gate", (cfg.hidden_size, width), ("embed", "heads"))(
        x, tie)
    return dense("o_proj", (width, cfg.hidden_size), ("heads", "embed"))(
        jax.nn.sigmoid(gate) * out, tie)


class SparseMixer(nn.Module):
    """Published ``minicpm4``: InfLLM-v2 over GQA heads, no RoPE."""

    config: SalaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        batch, seq, _ = x.shape
        x, tie = tie_weight_grads(x)
        q, k, v = project_qkv(cfg, x, tie, positions, rope=False)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        out, share = block_sparse_attention(q, k, v, cfg.sparsity)
        self.sow("counters", "block_sparse_tiles_visited_share", share)
        out = out.transpose(0, 2, 1, 3).reshape(batch, seq, -1)
        return _gated_out(cfg, x, tie, out)


class LightningMixer(nn.Module):
    """Published ``lightning-attn``: the decayed linear recurrence over
    normed, rotated q and k, its output normed over the whole width."""

    config: SalaConfig
    layer: int          # published index: the decay depends on it

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        batch, seq, _ = x.shape
        heads, d = cfg.lightning_heads, cfg.lightning_head_dim
        x, tie = tie_weight_grads(x)
        q, k, v = project_qkv(cfg.lightning(), x, tie, positions)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        rate = jnp.asarray(lightning_rates(heads, self.layer,
                                           cfg.published_layers))
        out = linear_attention(q, k, v, rate, d ** -0.5)
        out = out.transpose(0, 2, 1, 3).reshape(batch, seq, heads * d)
        out = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl,
                      name="o_norm")(out)
        return _gated_out(cfg, x, tie, out)


def residual_scale(cfg: SalaConfig) -> float:
    """muP's scale of every residual branch: scale_depth / sqrt(depth), the
    published depth (this stage stands for one of several)."""
    return cfg.scale_depth / math.sqrt(cfg.published_layers)


class SalaBlock(nn.Module):
    """x + a Mixer(norm(x)), then x + a MLP(norm(x)), a = scale_depth /
    sqrt(published depth); the mixer is the layer's published kind."""

    config: SalaConfig
    layer: int          # in this stage

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        alpha = residual_scale(cfg)
        kind = cfg.mixer(self.layer)
        if kind == SPARSE:
            mixer = SparseMixer(cfg, name="attn")
        elif kind == LIGHTNING:
            mixer = LightningMixer(cfg, self.layer, name="attn")
        else:
            raise ValueError(f"no mixer {kind!r} in this model")
        x = nn.with_logical_constraint(x, ACT_AXES)
        x = x + alpha * mixer(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl,
                    name="attn_norm")(x), positions)
        x = nn.with_logical_constraint(x, ACT_AXES)
        x = x + alpha * MLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl,
                    name="mlp_norm")(x))
        return nn.with_logical_constraint(x, ACT_AXES)


class MiniCPMSala(Llama):
    """`Llama` whose layer i is a `SalaBlock` of `mixer_types`' kind:
    embedding, final norm, head, `hidden_and_head` and recomputation by
    block are Llama's."""

    config: SalaConfig

    def block(self, layer: int) -> nn.Module:
        return self.recomputed(SalaBlock)(self.config, layer,
                                          name=f"layer_{layer}")
