"""The main path's kernels and step program, compiled for the chip.

No chip is attached here. The TPU's compiler is installed, and it compiles
for a DESCRIBED v5e (`on-chip-measurement` guide §2.3): what it refuses —
a misaligned slice, too much VMEM, a program that does not fit HBM, a
Mosaic kernel the SPMD partitioner is asked to split — it refuses here, at
no chip time. A compile that passes is not a chip run; these tests only
assert that the program lowers and that the kernels are IN it
(`tpu_custom_call` in the compiled text), not interpreted or swapped for
the reference.

This is the ONLY test file that describes a topology, and it does so
inside a module-scoped fixture: one process may load the TPU library, and
under xdist every worker imports every test file. Nothing here touches
the topology at import, in a `skipif`, in `parametrize` or in a child
process. The backend selections (`ops/backend.py`) are steered with
monkeypatch, never through an option of the program.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from dlrover_tpu.common.constants import TraceScope
from dlrover_tpu.models.llama import Llama, LlamaConfig, cross_entropy_loss
from dlrover_tpu.ops import norms
from dlrover_tpu.ops.flash_attention import (
    KERNEL_DKV,
    KERNEL_DQ,
    KERNEL_FWD,
    flash_attention,
)
from dlrover_tpu.ops.norms import fused_rms_norm, mesh_rms_norm
from dlrover_tpu.ops.quantization import dequantize, quantize
from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh, use_mesh
from dlrover_tpu.trainer.train_step import build_trainer, schedule_counts


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the
    # persistent cache but cannot be read back without the chip: keep the
    # cache out of these compiles so later tests stay silent
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def chip_path(monkeypatch):
    """Every selection by backend takes its TPU branch: kernels compile
    (no interpret mode), the model picks the fused norm."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kv_heads", [16, 4])
def test_flash_attention_fwd_bwd(topo, one_chip, chip_path, kv_heads):
    """Forward + both backward kernels at the 1.47B model's attention
    shape (micro 4, 16 heads, seq 2048, head 128, bf16); 16/4 is GQA."""
    q = jax.ShapeDtypeStruct((4, 16, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, kv_heads, 2048, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True).astype(jnp.float32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # forward, dq, dk/dv
    assert text.count("tpu_custom_call") >= 3


SPARSE = dict(heads=32, kv_heads=4, seq=16384, d=128, index_heads=16,
              index_dim=64, topk=2048)      # Keye-VL-2.0's, batch 1


@pytest.mark.parametrize("kernel", ["indexer_select", "sparse_attn",
                                    "indexer_kl", "indexer_kl_grads"])
def test_sparse_attention_kernels_at_the_published_widths(
        topo, one_chip, chip_path, kernel):
    """Selection, masked attention (forward and both backward kernels) and
    the KL kernel, alone and with the gradient it takes back to qi, w and
    ki, at 16,384 x 16,384 with 32 heads of 128 and 16 index heads of 64:
    the VMEM each asks for (a row block's scores for the bisection, a
    1024 x 1024 float32 sum over the heads, d KL / d ki over the whole
    sequence) and every slice of them is the chip compiler's to refuse."""
    import importlib

    kernels = importlib.import_module(
        "dlrover_tpu.ops.sparse_attention_kernels")

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s = SPARSE
    seq = s["seq"]
    q = of((1, s["heads"], seq, s["d"]))
    kv = of((1, s["kv_heads"], seq, s["d"]))
    qi = of((1, s["index_heads"], seq, s["index_dim"]))
    ki = of((1, seq, s["index_dim"]))
    w = of((1, seq, s["index_heads"]), jnp.float32)
    mask = of((1, seq, seq), jnp.int8)
    rows = of((1, seq, 1), jnp.float32)
    if kernel == "indexer_select":
        text = _compiled_text(
            lambda qi, ki, w: kernels._select_call(
                qi, ki, w, topk=s["topk"], interpret=False), qi, ki, w)
        names = (kernels.KERNEL_SELECT,)
    elif kernel == "sparse_attn":
        def loss(q, k, v, mask):
            return jnp.sum(kernels.masked_attention(
                q, k, v, mask, 0.088)[0].astype(jnp.float32))
        text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv,
                              mask)
        names = ("sparse_attn_fwd", "sparse_attn_dq", "sparse_attn_dkv")
        assert "flash_attn_" not in text
    else:
        grads = kernel == "indexer_kl_grads"
        text = _compiled_text(
            lambda *a: kernels._kl_call(*a, sm_scale=0.088, grads=grads,
                                        interpret=False),
            q, kv, of((1, s["heads"], seq, 1), jnp.float32), mask, qi, w, ki,
            rows)
        names = (kernels.KERNEL_KL,)
        # the rows, and with `grads` d qi, d w, d ki^T: nothing (seq, seq)
        launch = re.search(rf"%{kernels.KERNEL_KL}[.\d]* = (.*?) custom-call\(",
                           text).group(1)
        assert re.findall(r"\w+\[[\d,]*\]", launch) == (
            ["f32[1,16384,1]", "f32[1,16,16384,64]", "f32[1,16384,16]",
             "f32[1,64,16384]"] if grads else ["f32[1,16384,1]"])
    for name in names:
        assert re.search(rf"%{name}[.\d]* = ", text), name
    assert text.count("tpu_custom_call") >= len(names)


def test_the_expert_layer_multiplies_a_chunk_of_the_sorted_rows(topo,
                                                                one_chip):
    """`parallel/moe.py:HeldExpertsLayer` at the published widths (16 of
    128 experts of width 768 held, 8 a token, 16,384 tokens), forward and
    backward, as the chip's compiler takes it: every grouped product is
    over one chunk's 8,192 rows, none over the 131,072 assignments, under
    real branches (a chunk no held expert reaches is skipped, not masked),
    and nothing of the layer is tokens x top_k rows of `hidden` columns."""
    from dlrover_tpu.parallel.moe import HeldExpertsConfig, HeldExpertsLayer

    layer = HeldExpertsLayer(HeldExpertsConfig(
        num_experts=128, experts_held=16, first_expert=0, top_k=8,
        hidden_size=2048, expert_intermediate=768, dtype=jnp.bfloat16))
    x = jax.ShapeDtypeStruct((1, SPARSE["seq"], 2048), jnp.bfloat16,
                             sharding=one_chip)
    params = {
        name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
        for name, shape in (("router", (2048, 128)), ("w1", (16, 2048, 768)),
                            ("w3", (16, 2048, 768)), ("w2", (16, 768, 2048)))}

    def loss(p, x):
        out = layer.apply({"params": p}, x, mutable=["counters"])[0]
        return jnp.sum(out.astype(jnp.float32))

    # the value too: the rule keeps only its inputs, so a gradient alone
    # would leave the forward's chunks dead code
    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)), params,
                          x)
    products = re.findall(r"%ragged-dot[-\w]*(?:\.\d+)? = (\w+\[[\d,]*\])",
                          text)
    products = [shape for shape in products if not shape.startswith("(")]
    assert len(products) >= 9, products     # 3 forward, 3 + 2 x 3 backward
    assert not [shape for shape in products if "[131072," in shape]
    assert [shape for shape in products if "[8192," in shape]
    assert len(re.findall(r" conditional\(", text)) == 2
    assert "[131072,2048]" not in text


def test_a_recomputed_block_launches_none_of_its_attentions_kernels_again(
        topo, chip_path):
    """One layer of `models/keye.py` at the published widths and 16,384
    tokens, recomputed by block under `KeyeConfig`'s default policy
    (`ops/remat.py`: `kernel_outputs`), as the chip's compiler schedules
    it: every kernel of the attention stands once in the step, only the
    norms' forward kernels are launched again in the backward pass, and
    XLA rematerialises nothing of its own."""
    from dlrover_tpu.models.keye import Keye, KeyeConfig

    s = SPARSE
    cfg = KeyeConfig(
        vocab_size=18992, hidden_size=2048, num_layers=1,
        num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        attn_head_dim=s["d"], max_seq_len=s["seq"], rope_theta=1e7,
        rms_norm_eps=1e-6, dtype=jnp.bfloat16, norm_impl="fused",
        embed_impl="gather", remat=True, experts_held=16,
        index_heads=s["index_heads"], index_head_dim=s["index_dim"],
        index_topk=s["topk"])
    assert cfg.remat_policy == "kernel_outputs"
    tx = optax.chain(optax.scale_by_factored_rms(), optax.scale(-3e-4))
    trainer = build_trainer(
        Keye(cfg), tx, create_mesh(MeshSpec(), topo.devices[:1]),
        jnp.zeros((1, s["seq"]), jnp.int32), cross_entropy_loss,
        accum_steps=1, micro_batch=1)
    trainer.precompile()
    text = trainer._compiled_step.as_text()
    counts = schedule_counts(text, (1, s["seq"]))
    assert set(counts["recomputed_kernels"]) == {norms.KERNEL_FWD}
    assert counts["remat_instructions"] == 0
    for kernel in ("indexer_select", "sparse_attn_fwd", "indexer_kl",
                   "sparse_attn_dq", "sparse_attn_dkv"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    # the KL term's gradient is the KL kernel's: no kernel of its own
    assert "%indexer_dq" not in text and "%indexer_dk" not in text


@pytest.mark.parametrize("kernel", ["lightning", "block_sparse"])
def test_sala_kernels_at_the_published_widths(topo, one_chip, chip_path,
                                              kernel):
    """MiniCPM-SALA's mixers' kernels, forward and backward, at 16,384
    tokens: lightning attention over 32 heads of 128 in chunks of 256, and
    InfLLM-v2's selection with the flash kernels given its blocks over 32
    query heads and 2 kv heads of 128."""
    from dlrover_tpu.ops import block_sparse_attention, linear_attention

    seq = 16384

    def of(heads):
        return jax.ShapeDtypeStruct((1, heads, seq, 128), jnp.bfloat16,
                                    sharding=one_chip)

    if kernel == "lightning":
        rate = jax.ShapeDtypeStruct((32,), jnp.float32, sharding=one_chip)

        def loss(q, k, v, rate):
            return jnp.sum(linear_attention.linear_attention(
                q, k, v, rate, 128 ** -0.5).astype(jnp.float32))

        names = (linear_attention.KERNEL_FWD, linear_attention.KERNEL_BWD)
        args = (of(32), of(32), of(32), rate)
    else:
        def loss(q, k, v):
            out, share = block_sparse_attention.block_sparse_attention(
                q, k, v, block_sparse_attention.Sparsity())
            return jnp.sum(out.astype(jnp.float32)) + share

        names = ("block_sparse_attn_fwd", "block_sparse_attn_dq",
                 "block_sparse_attn_dkv")
        args = (of(32), of(2), of(2))
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *args)
    for name in names:
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == 1, name


def test_a_recomputed_sala_stage_launches_no_mixer_kernel_again(topo,
                                                               chip_path):
    """`minicpm_sala_l4`'s stage (published layers 0-3: one sparse, three
    lightning) at the published widths and 16,384 tokens, recomputed by
    block under its class's default policy (`matmul_and_kernel_outputs`):
    each mixer kernel stands once a layer in the step, no projection's
    matmul and only the norms' forward kernels are launched again, XLA
    rematerialises nothing of its own, and the step fits the chip with
    room (14.26 GiB of 15.75; 9.84 under `kernel_outputs`)."""
    from dlrover_tpu.models.minicpm_sala import MiniCPMSala, SalaConfig

    cfg = SalaConfig(
        vocab_size=18362, hidden_size=4096, intermediate_size=16384,
        num_layers=4, num_heads=32, num_kv_heads=2, attn_head_dim=128,
        max_seq_len=16384, rms_norm_eps=1e-6, dtype=jnp.bfloat16,
        norm_impl="fused", embed_impl="gather", remat=True,
        embed_scale=12.0, mixer_types=("minicpm4",) + ("lightning-attn",) * 3)
    assert cfg.remat_policy == "matmul_and_kernel_outputs"
    tx = optax.chain(optax.scale_by_factored_rms(), optax.scale(-3e-4))
    trainer = build_trainer(
        MiniCPMSala(cfg), tx, create_mesh(MeshSpec(), topo.devices[:1]),
        jnp.zeros((1, 16384), jnp.int32), cross_entropy_loss,
        accum_steps=1, micro_batch=1)
    trainer.precompile()
    text = trainer._compiled_step.as_text()
    counts = schedule_counts(text, (1, 16384))
    assert set(counts["recomputed_kernels"]) == {norms.KERNEL_FWD}
    assert counts["recomputed_matmuls"] == 0
    assert counts["remat_instructions"] == 0
    for kernel, layers in (("lightning_fwd", 3), ("lightning_bwd", 3),
                           ("block_sparse_attn_fwd", 1),
                           ("block_sparse_attn_dq", 1),
                           ("block_sparse_attn_dkv", 1)):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == layers, kernel
    memory = trainer._compiled_step.memory_analysis()
    footprint = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                 + memory.generated_code_size_in_bytes)
    assert footprint < 14.5 * 2 ** 30, footprint


@pytest.mark.parametrize("hidden", [2048, 4096])
def test_fused_rms_norm_fwd_bwd(topo, one_chip, chip_path, hidden):
    x = jax.ShapeDtypeStruct((2, 2048, hidden), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((hidden,), jnp.float32, sharding=one_chip)

    def loss(x, w):
        return jnp.sum(fused_rms_norm(x, w).astype(jnp.float32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1)), x, w)
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("bits", [8, 4])
def test_group_quantize_dequantize(topo, one_chip, chip_path, bits):
    x = jax.ShapeDtypeStruct((2048, 8192), jnp.float32, sharding=one_chip)

    def roundtrip(x):
        q, scales = quantize(x, bits=bits)
        return dequantize(q, scales, bits=bits)

    assert _compiled_text(roundtrip, x).count("tpu_custom_call") >= 2


def test_shard_mapped_norm_on_four_devices(topo, chip_path):
    """Called bare under a four-device mesh the fused norm does not lower
    at all ("Mosaic kernels cannot be automatically partitioned");
    `mesh_rms_norm` must, with the weight gradient's psum in the
    program."""
    mesh = create_mesh(MeshSpec(fsdp=4), topo.devices)
    x = jax.ShapeDtypeStruct(
        (8, 2048, 2048), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(("data", "fsdp"), None, None)))
    w = jax.ShapeDtypeStruct((2048,), jnp.float32,
                             sharding=NamedSharding(mesh, P()))

    def loss(x, w):
        with use_mesh(mesh):
            return jnp.sum(mesh_rms_norm(x, w).astype(jnp.float32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1)), x, w)
    assert text.count("tpu_custom_call") >= 2
    # dw: one f32[2048] all-reduce over all four devices
    psums = [line for line in text.splitlines()
             if "all-reduce(" in line and "f32[2048]" in line]
    assert psums and all("{{0,1,2,3}}" in line for line in psums), psums


_STEP_TEXTS: dict = {}


def _full_width_step_text(topo, n_devices: int) -> str:
    """The compiled text of one whole step program of the 1.47B Llama at
    full width (hidden 2048, MLP 8192, 16 heads, seq 2048, bf16, flash +
    fused norm, factored-RMS), depth cut to 2 layers, for one described
    chip or for a four-chip mesh with the state sharded fsdp=4. Compiled
    once a mesh, under the caller's `chip_path`."""
    if n_devices not in _STEP_TEXTS:
        cfg = dataclasses.replace(
            LlamaConfig.llama_wide_1b(
                max_seq_len=2048, attn_impl="flash", norm_impl="fused",
                embed_impl="gather", dtype=jnp.bfloat16),
            num_layers=2)
        tx = optax.chain(optax.scale_by_factored_rms(), optax.scale(-3e-4))
        spec = MeshSpec(fsdp=4) if n_devices == 4 else MeshSpec()
        mesh = create_mesh(spec, topo.devices[:n_devices])
        micro = 2 * n_devices
        trainer = build_trainer(
            Llama(cfg), tx, mesh, jnp.zeros((micro, 2048), jnp.int32),
            cross_entropy_loss, accum_steps=1, micro_batch=micro)
        trainer.precompile()
        _STEP_TEXTS[n_devices] = trainer._compiled_step.as_text()
    return _STEP_TEXTS[n_devices]


@pytest.mark.parametrize("n_devices", [1, 4])
def test_full_width_train_step(topo, chip_path, n_devices):
    text = _full_width_step_text(topo, n_devices)
    # per layer: 2 norms + attention, forward and backward; final norm
    assert text.count("tpu_custom_call") >= 2 * (2 * 2 + 1)
    if n_devices == 4:
        assert "all-gather" in text
    # what the trace readers hold the program to (docs/observability.md):
    # each kernel's name is its custom-call's HLO instruction name, which
    # is the start of the profiler's event name ...
    for kernel in (KERNEL_FWD, KERNEL_DQ, KERNEL_DKV,
                   norms.KERNEL_FWD, norms.KERNEL_BWD):
        assert re.search(rf"%{kernel}(\.\d+)* = .* custom-call\(", text), kernel
    # ... and the scopes no Flax module gives are in the op_names, with
    # JAX's own mark on the backward pass
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in (TraceScope.HEAD_LOSS, TraceScope.OPTIMIZER,
                  TraceScope.GRAD_ACCUM, TraceScope.EMBED):
        assert any(re.search(rf"(^|[/(]){scope}([/)]|$)", name)
                   for name in op_names), scope
    assert any("transpose(jvp(" in name for name in op_names)


@pytest.mark.parametrize("n_devices", [1, 4])
def test_weight_gradients_stand_inside_their_layers_backward(
        topo, chip_path, n_devices):
    """The mechanism of `models/llama.py:tied_dot`, read from the schedule
    (ENTRY is printed in its order): no projection's weight-gradient
    matmul stands after the backward pass's last activation-gradient
    matmul, where it would hold what it reads (gate, up, d h, the normed
    inputs) alive through every earlier layer's backward, and no forward
    matmul is launched a second time. Left to itself XLA puts every
    weight gradient behind the whole backward pass, in the order of the
    optimizer's parameter tree; at 24 layers it then recomputes 18 MLP
    matmuls a step to fit the chip (PERF.md section 6, PR 36)."""
    text = _full_width_step_text(topo, n_devices)
    # 14 projections, each with an activation gradient and a weight
    # gradient the count can tell apart: a device's rows x sequence
    assert schedule_counts(text, (2, 2048)) == {
        "remat_instructions": 0, "late_weight_grads": 0,
        "recomputed_kernels": {}, "recomputed_matmuls": 0}
    assert len(re.findall(
        r'kind=kOutput[^\n]*transpose\(jvp\([^"\n]*_proj/dot_general"',
        text[text.rfind("\nENTRY "):])) >= 2 * 14
