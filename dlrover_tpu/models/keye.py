"""Keye-VL-2.0's language model: a Llama skeleton whose attention runs over
keys that a learned indexer selects and whose MLP is a mixture of experts
held by share.

Built from the pieces `Llama` is built from (`models/llama.py`: RMSNorm,
`project_qkv` with its q/k norms, `apply_rope`, `tied_dot` projections,
gather embedding, `hidden_and_head`), so it trains through the same
`build_trainer` and `ElasticTrainLoop`. What is its own:

- `Indexer` (after DeepSeek-V3.2's): on the attention's normed input,
  detached, `qi = rope(h Wq)` (J heads of D), `ki = rope(norm(h Wk))` (one
  head), `w = J^-0.5 h Ww`; `I[t, s] = sum_j w[t, j] relu(D^-0.5 qi[t, j] .
  ki[s])`. `ops/sparse_attention.py` selects each query's `index_topk` keys
  by I, runs the main softmax over them alone and returns the indexer's
  objective, KL(main attention's head-summed probabilities || softmax of I
  over the selected keys), which the block sows into `losses` once a layer
  (the trainer adds the collection to the loss it reports and differentiates).
  The language-model loss trains everything but the indexer; the KL term
  trains the indexer alone.
- `parallel/moe.py:HeldExpertsLayer`: `num_experts` routed experts of which
  this chip holds `experts_held` from `first_expert` on; nothing dropped.

The vision tower is not built: on text M-RoPE's three position ids are equal
and it is RoPE over the whole head.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.common.constants import TraceScope
from dlrover_tpu.models.llama import (
    ACT_AXES,
    Llama,
    LlamaConfig,
    RMSNorm,
    apply_rope,
    functools_partial_dense,
    project_qkv,
    tie_weight_grads,
)
from dlrover_tpu.ops.sparse_attention import sparse_attention
from dlrover_tpu.parallel.moe import HeldExpertsConfig, HeldExpertsLayer


@dataclasses.dataclass(frozen=True)
class KeyeConfig(LlamaConfig):
    qk_norm: bool = True
    # with `remat`: the block's recomputation keeps what the attention's
    # kernels made (`ops/remat.py:Kept`) and recomputes the rest
    remat_policy: str = "kernel_outputs"
    # the mixture: `intermediate_size` is not used by this model's blocks
    num_experts: int = 128
    experts_held: int = 128          # this chip's, from first_expert on
    first_expert: int = 0
    experts_per_token: int = 8
    expert_intermediate: int = 768
    norm_topk_prob: bool = True
    # the indexer (the published `sa_config`)
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    index_loss_weight: float = 1.0

    @classmethod
    def tiny(cls, **kw) -> "KeyeConfig":
        """Every mechanism alive at a size the CPU runs: fewer keys
        selected than the sequence has, more experts than are held, a head
        width that is not hidden // heads."""
        sizes = dict(vocab_size=256, max_seq_len=64, hidden_size=64,
                     num_layers=2, num_heads=4, num_kv_heads=2,
                     attn_head_dim=32, rms_norm_eps=1e-6, rope_theta=1e7,
                     num_experts=8, experts_held=4, first_expert=2,
                     experts_per_token=2, expert_intermediate=32,
                     index_heads=2, index_head_dim=16, index_topk=16)
        return cls(**{**sizes, **kw})

    def moe_config(self) -> HeldExpertsConfig:
        return HeldExpertsConfig(
            num_experts=self.num_experts, experts_held=self.experts_held,
            first_expert=self.first_expert, top_k=self.experts_per_token,
            hidden_size=self.hidden_size,
            expert_intermediate=self.expert_intermediate,
            norm_topk_prob=self.norm_topk_prob, dtype=self.dtype,
            param_dtype=self.param_dtype)

    def param_count(self) -> int:
        h, d = self.hidden_size, self.head_dim
        q, kv = self.num_heads * d, self.num_kv_heads * d
        index = (h * self.index_heads * self.index_head_dim
                 + h * self.index_head_dim + self.index_head_dim
                 + h * self.index_heads)
        experts = self.experts_held * 3 * h * self.expert_intermediate
        per_layer = (2 * h * q + 2 * h * kv + 2 * d + 2 * h + index
                     + h * self.num_experts + experts)
        emb = self.vocab_size * h * (1 if self.tie_embeddings else 2)
        return self.num_layers * per_layer + emb + h

    def flops_per_token(self, seq_len: int) -> float:
        """Model FLOPs a trained token needs on this chip, forward and
        backward, nothing recomputed: 6 for each matmul parameter on the
        token's path (of its `experts_per_token` experts the share held
        here, in expectation), the main attention over the selected pairs,
        the indexer's scores over every causal pair forward and over the
        selected ones backward. `obs/mfu.py` and the loop's report ask the
        model (`_report_model_info`)."""
        h, d = self.hidden_size, self.head_dim
        q, kv = self.num_heads * d, self.num_kv_heads * d
        index = self.index_heads * self.index_head_dim
        held = self.experts_per_token * self.experts_held / self.num_experts
        matmul = (2 * h * q + 2 * h * kv + h * index
                  + h * self.index_head_dim + h * self.index_heads
                  + h * self.num_experts
                  + held * 3 * h * self.expert_intermediate)
        causal = seq_len / 2.0
        selected = selected_pairs(seq_len, self.index_topk) / seq_len
        attention = 3 * 4.0 * q * selected      # QK^T, PV; x3 with backward
        indexer = 2.0 * index * causal + 4.0 * index * selected
        head = self.vocab_size * h
        return (self.num_layers * (6.0 * matmul + attention + indexer)
                + 6.0 * head)


def selected_pairs(seq_len: int, topk: int) -> float:
    """(query, key) pairs a head scores when each query sees at most `topk`
    of its causal keys, in the accepted convention that counts half the
    diagonal: s^2 / 2 while everything is selected, k s - k^2 / 2 beyond."""
    if topk >= seq_len:
        return seq_len * seq_len / 2.0
    return topk * seq_len - topk * topk / 2.0


class Indexer(nn.Module):
    """(qi (b, S, J, D), ki (b, S, D), w (b, S, J) float32) from the
    attention's normed input; the two scalings ride on `w` (relu is
    positively homogeneous)."""

    config: KeyeConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        batch, seq, _ = x.shape
        heads, d = cfg.index_heads, cfg.index_head_dim
        dense = functools_partial_dense(cfg)
        qi = dense("q_proj", (cfg.hidden_size, heads * d),
                   ("embed", "heads"))(x).reshape(batch, seq, heads, d)
        ki = dense("k_proj", (cfg.hidden_size, d), ("embed", None))(x)
        ki = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl,
                     name="k_norm")(ki)
        w = dense("w_proj", (cfg.hidden_size, heads), ("embed", None))(x)
        qi = apply_rope(qi, positions, cfg.rope_theta)
        ki = apply_rope(ki[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
        scale = (heads ** -0.5) * (d ** -0.5)
        return qi, ki, w.astype(jnp.float32) * scale


class SparseAttention(nn.Module):
    config: KeyeConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        batch, seq, _ = x.shape
        qi, ki, w = Indexer(cfg, name=TraceScope.INDEXER)(
            jax.lax.stop_gradient(x), positions)
        x, tie = tie_weight_grads(x)
        q, k, v = project_qkv(cfg, x, tie, positions)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        out, kl = sparse_attention(q, k, v, qi, ki, w, cfg.index_topk)
        self.sow("losses", "index_kl", cfg.index_loss_weight * kl)
        out = out.transpose(0, 2, 1, 3).reshape(batch, seq, -1)
        return functools_partial_dense(cfg)(
            "o_proj", (cfg.num_heads * cfg.head_dim, cfg.hidden_size),
            ("heads", "embed"))(out, tie)


class KeyeBlock(nn.Module):
    config: KeyeConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        x = nn.with_logical_constraint(x, ACT_AXES)
        x = x + SparseAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl,
                    name="attn_norm")(x), positions)
        x = nn.with_logical_constraint(x, ACT_AXES)
        x = x + HeldExpertsLayer(cfg.moe_config(), name=TraceScope.MOE)(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_impl,
                    name="mlp_norm")(x))
        return nn.with_logical_constraint(x, ACT_AXES)


class Keye(Llama):
    """`Llama` with `KeyeBlock`s: embedding, final norm, head,
    `hidden_and_head` and recomputation by block are Llama's."""

    config: KeyeConfig
    _BLOCK = KeyeBlock
