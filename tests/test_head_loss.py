"""Head and loss as one function (models/llama.py:head_cross_entropy) and
how build_trainer comes to take it: against the plain logits path in
float32, by the program's structure, and end to end through two steps."""

import functools
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import trace_reduce
from dlrover_tpu import obs
from dlrover_tpu.common.constants import TraceScope
from dlrover_tpu.models import llama
from dlrover_tpu.models.llama import (
    Llama,
    LlamaConfig,
    cross_entropy_loss,
    head_cross_entropy,
    head_loss_slices,
)
from dlrover_tpu.models.llama_moe import LlamaMoE, LlamaMoEConfig
from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
from dlrover_tpu.trainer.elastic_loop import ElasticTrainLoop, TrainLoopConfig
from dlrover_tpu.trainer.train_step import build_trainer, schedule_counts

BATCH, SEQ, HIDDEN, VOCAB = 2, 16, 8, 40


def _inputs(seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (BATCH, SEQ, HIDDEN), jnp.float32)
    matrix = jax.random.normal(keys[1], (VOCAB, HIDDEN), jnp.float32) * 0.3
    targets = jax.random.randint(keys[2], (BATCH, SEQ), 0, VOCAB)
    return x, matrix, targets


def _head(matrix, tied):
    """(hidden, vocab) from the leaf the model keeps: an embedding
    (vocab, hidden), transposed, when tied; the head itself when not."""
    return matrix.T if tied else matrix


@pytest.mark.parametrize("cotangent", [1.0, 0.37])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("slices", [1, 2, 4])
def test_loss_and_both_gradients_match_the_logits_path(slices, tied,
                                                       cotangent):
    x, matrix, targets = _inputs()
    if not tied:
        matrix = matrix.T

    def plain(x, matrix):
        logits = jnp.dot(x, _head(matrix, tied)).astype(jnp.float32)
        return cotangent * cross_entropy_loss(logits, targets)

    def fused(x, matrix):
        return cotangent * head_cross_entropy(
            x, _head(matrix, tied), targets, slices)

    want, (want_dx, want_dm) = jax.value_and_grad(plain, (0, 1))(x, matrix)
    got, (got_dx, got_dm) = jax.jit(
        jax.value_and_grad(fused, (0, 1)))(x, matrix)
    assert got_dm.shape == matrix.shape
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got_dx, want_dx, rtol=2e-5, atol=1e-8)
    np.testing.assert_allclose(got_dm, want_dm, rtol=2e-5, atol=1e-8)


def test_head_gradient_is_summed_in_float32_and_rounded_once():
    """bf16 operands: each slice's head gradient is accumulated in
    float32 and the sum rounded once, so four slices give what one
    does to a bf16 ulp, not four roundings' worth."""
    x, matrix, targets = _inputs(1)
    x, head = x.astype(jnp.bfloat16), matrix.T.astype(jnp.bfloat16)
    grad = jax.grad(head_cross_entropy, (0, 1))
    dx1, dhead1 = grad(x, head, targets, 1)
    dx4, dhead4 = grad(x, head, targets, 4)
    assert dhead4.dtype == jnp.bfloat16 and dx4.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(dx4, np.float32),
                                  np.asarray(dx1, np.float32))
    np.testing.assert_allclose(np.asarray(dhead4, np.float32),
                               np.asarray(dhead1, np.float32),
                               rtol=2 ** -7, atol=1e-6)


@pytest.mark.parametrize("rows, seq_len, vocab, itemsize, want", [
    (2, 2048, 92544, 2, 1),      # internlm2_1p8b.steady: 758 MB
    (2, 2048, 32000, 2, 1),      # mistral_7b_l8.steady: 262 MB
    (2, 4096, 92544, 2, 2),      # twice the sequence: cut in two
    (2, 8192, 92544, 4, 8),      # float32 logits at 8k
    (1, 6, 1 << 30, 4, 6),       # nothing fits: a token a slice
    (3, 12, 1 << 25, 2, 3),      # 3 divides 12; 2 would not fit
])
def test_slices_follow_from_bytes(rows, seq_len, vocab, itemsize, want):
    got = head_loss_slices(rows, seq_len, vocab, itemsize)
    assert got == want and seq_len % got == 0
    assert got == seq_len or (rows * (seq_len // got) * vocab * itemsize
                              <= llama.HEAD_LOSS_SLICE_BYTES)


# -- the program's structure -------------------------------------------------


def _equations(jaxpr):
    """Every equation once, through scans, calls and custom rules."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _vocab_wide(eqn, vocab):
    return any(vocab in v.aval.shape
               for v in (*eqn.invars, *eqn.outvars) if hasattr(v, "aval"))


@pytest.mark.parametrize("slices", [1, 2, 4])
def test_three_vocabulary_matmuls_and_no_whole_logits(slices):
    x, matrix, targets = _inputs()
    step = jax.value_and_grad(
        lambda x, head: head_cross_entropy(x, head, targets, slices), (0, 1))
    eqns = list(_equations(jax.make_jaxpr(step)(x, matrix.T).jaxpr))
    dots = [e for e in eqns if e.primitive.name == "dot_general"
            and _vocab_wide(e, VOCAB)]
    # the scan's body is traced once, whatever the number of slices
    assert len(dots) == 3
    if slices > 1:
        assert not [v.aval for e in eqns for v in e.outvars
                    if v.aval.shape == (BATCH, SEQ, VOCAB)]
        scans = [e for e in eqns if e.primitive.name == "scan"]
        assert len(scans) == 1 and scans[0].params["length"] == slices


def test_plain_path_keeps_whole_logits_for_the_backward():
    """What the fused function is held against: autodiff of the logits
    path has the same three matmuls but a (batch, seq, vocab) residual."""
    x, matrix, targets = _inputs()
    step = jax.value_and_grad(lambda x, head: cross_entropy_loss(
        jnp.dot(x, head), targets), (0, 1))
    eqns = list(_equations(jax.make_jaxpr(step)(x, matrix.T).jaxpr))
    assert any(v.aval.shape == (BATCH, SEQ, VOCAB)
               for e in eqns for v in e.outvars)


# -- build_trainer: which path, and that the two agree -----------------------


def _wrapped_loss(logits, targets):
    return cross_entropy_loss(logits, targets)


class _NoMethod(nn.Module):
    """A model that ends in a head and does not say so."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, tokens):
        return Llama(self.config, name="inner")(tokens)


def _config(**kw):
    return LlamaConfig.tiny(attn_impl="reference", norm_impl="reference",
                            vocab_size=VOCAB, **kw)


def _trainer(mesh, model, loss_fn, accum=1, micro=8, **kw):
    return build_trainer(model, optax.sgd(0.1), mesh,
                         jnp.zeros((micro, SEQ), jnp.int32), loss_fn,
                         accum_steps=accum, micro_batch=micro, **kw)


def _two_steps(trainer, rows):
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, VOCAB, (rows, SEQ), dtype=np.int32)
    targets = np.roll(tokens, -1, axis=-1)
    state = trainer.init(jax.random.PRNGKey(0))
    losses = []
    for _ in range(2):
        state, metrics = trainer.step(
            state, *trainer.shard_batch(tokens, targets))
        losses.append(float(metrics["loss"]))
    return losses, state.params


@pytest.fixture
def one_device(cpu_devices):
    return create_mesh(MeshSpec(), cpu_devices[:1])


@pytest.mark.parametrize("model, loss_fn, want", [
    (lambda: Llama(_config()), cross_entropy_loss, "fused"),
    (lambda: Llama(_config(tie_embeddings=True)), cross_entropy_loss,
     "fused"),
    (lambda: Llama(_config()), _wrapped_loss, "logits"),
    (lambda: Llama(_config()),
     functools.partial(cross_entropy_loss), "logits"),
    (lambda: _NoMethod(_config()), cross_entropy_loss, "logits"),
    (lambda: LlamaMoE(LlamaMoEConfig.mixtral_tiny(
        attn_impl="reference", norm_impl="reference")),
     cross_entropy_loss, "logits"),
], ids=["llama", "llama-tied", "wrapped-loss", "partial-loss",
        "model-without-method", "llama-moe"])
def test_path_follows_from_what_model_and_loss_say(one_device, model,
                                                   loss_fn, want):
    trainer = _trainer(one_device, model(), loss_fn, micro=2)
    assert trainer.head_loss_path == want
    assert trainer.head_loss_slices == 1


def test_a_sharded_sequence_keeps_whole_logits(cpu_devices):
    mesh = create_mesh(MeshSpec(data=2, sequence=2), cpu_devices[:4])
    trainer = _trainer(mesh, Llama(_config()), cross_entropy_loss, micro=2)
    assert trainer.head_loss_path == "logits"


@pytest.mark.parametrize("case", ["one-device", "tied", "accum2", "fsdp8",
                                  "fsdp8-sliced", "split-grad"])
def test_fused_and_logits_paths_agree_after_two_steps(cpu_devices, case,
                                                      monkeypatch):
    """Loss and every parameter after two optimizer steps, float32
    compute: the fused path against the same model under a wrapped loss."""
    mesh = (create_mesh(MeshSpec(fsdp=8), cpu_devices[:8])
            if case.startswith("fsdp8")
            else create_mesh(MeshSpec(), cpu_devices[:1]))
    accum = 2 if case == "accum2" else 1
    if case == "fsdp8-sliced":
        # one row a device: 16 tokens x 40 columns x 4 B = 2,560 B
        monkeypatch.setattr(llama, "HEAD_LOSS_SLICE_BYTES", 700)
    config = _config(dtype=jnp.float32, tie_embeddings=case == "tied")
    kw = {"split_grad_apply": True} if case == "split-grad" else {}
    runs = {}
    for name, loss_fn in (("fused", cross_entropy_loss),
                          ("logits", _wrapped_loss)):
        trainer = _trainer(mesh, Llama(config), loss_fn, accum=accum, **kw)
        assert trainer.head_loss_path == name
        if case == "split-grad":
            state = trainer.init(jax.random.PRNGKey(0))
            tokens = np.arange(8 * SEQ, dtype=np.int32).reshape(8, SEQ) % VOCAB
            grads, metrics = trainer.grad_step(
                state, *trainer.shard_batch(tokens, np.roll(tokens, -1, -1)))
            runs[name] = [float(metrics["loss"])], grads
        else:
            runs[name] = _two_steps(trainer, 8 * accum)
        if name == "fused":
            assert trainer.head_loss_slices == (
                4 if case == "fsdp8-sliced" else 1)
    np.testing.assert_allclose(runs["fused"][0], runs["logits"][0],
                               rtol=1e-5)
    for got, want in zip(jax.tree.leaves(runs["fused"][1]),
                         jax.tree.leaves(runs["logits"][1])):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_init_is_the_same_whichever_path(one_device):
    """The fused path's abstract pass goes through `hidden_and_head`, the
    initialiser through `__call__`: one tree, one set of values."""
    fused = _trainer(one_device, Llama(_config()), cross_entropy_loss)
    logits = _trainer(one_device, Llama(_config()), _wrapped_loss)
    key = jax.random.PRNGKey(3)
    a, b = fused.init(key).params, logits.init(key).params
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for got, want in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(got, want)
    assert jax.tree.structure(fused.abstract_state(key)) == (
        jax.tree.structure(logits.abstract_state(key)))


# -- the compiled step: everything vocabulary-wide counts as head_loss -------


def _instructions(text):
    """(name, opcode, result shapes without layouts) of every instruction
    of a compiled program's text."""
    for line in text.splitlines():
        found = trace_reduce._INSTRUCTION.match(line)
        if not found:
            continue
        flat = re.sub(r"\{[^{}]*\}", "", line.split(" = ", 1)[1])
        shape, opcode = re.match(r"(\(.*?\)|\S+) ([\w\-]+)\(", flat).groups()
        yield found.group(1), opcode, shape


@pytest.mark.parametrize("slices", [1, 4])
def test_every_vocabulary_wide_instruction_is_under_head_loss(
        one_device, slices, monkeypatch):
    """The compiled step, read as the benchmark reads it (`op_names`,
    `scopes_of`): whatever is as wide as the vocabulary and as long as
    the tokens, and every matmul that wide, counts under `head_loss`.
    What is left over is shaped like the head's own leaf: its cast, its
    sum over micro-batches and its update, which belong to their scopes."""
    if slices > 1:
        monkeypatch.setattr(llama, "HEAD_LOSS_SLICE_BYTES",
                            2 * (SEQ // slices) * VOCAB * 4)
    # the embedding a gather, so only head and loss are vocabulary-wide
    config = _config(dtype=jnp.float32, embed_impl="gather")
    trainer = _trainer(one_device, Llama(config), cross_entropy_loss,
                       micro=2)
    assert trainer.head_loss_slices == slices
    trainer.precompile()
    text = trainer._compiled_step.as_text()
    named = trace_reduce.op_names(text)
    leaf = {f"f32[{config.hidden_size},{VOCAB}]",
            f"f32[{VOCAB},{config.hidden_size}]"}
    wide, matmuls = 0, 0
    for name, opcode, shape in _instructions(text):
        if name not in named or opcode in (
                "parameter", "tuple", "get-tuple-element", "while"):
            continue
        if not re.search(rf"[\[,]{VOCAB}[\],]", shape):
            continue
        scopes = trace_reduce.scopes_of(named[name])
        matmul = scopes[-1] == "dot_general"
        if shape in leaf and not matmul:
            continue
        assert TraceScope.HEAD_LOSS in scopes, (name, shape, named[name])
        wide += 1
        matmuls += opcode == "dot"
    # logits, the hidden states' gradient (contracted over the vocabulary:
    # not that wide itself) and the head's
    assert matmuls == 2 and wide > matmuls


def test_backward_rule_opens_the_scope():
    """A custom VJP's backward is traced apart from the scope around the
    call: what it computes (the two gradients times the cotangent, here
    not 1) must still read `head_loss`."""
    x, matrix, targets = _inputs()

    def step(x, head, weight):
        return jax.grad(lambda x, head: weight * head_cross_entropy(
            x, head, targets, 2), (0, 1))(x, head)

    text = jax.jit(step).lower(x, matrix.T, 0.5).compile().as_text()
    backward = [trace_reduce.scopes_of(op_name)
                for op_name in trace_reduce.op_names(text).values()
                if "transpose(" in op_name]
    assert backward and all(
        TraceScope.HEAD_LOSS in scopes for scopes in backward), backward


# -- the counter -------------------------------------------------------------


@pytest.fixture
def spans():
    caught = []
    obs.add_span_sink(caught.append)
    yield caught
    obs.remove_span_sink(caught.append)


def _recompiles(spans, phase):
    return [s.attrs for s in spans
            if s.name == "recompile" and s.attrs.get("phase") == phase]


def test_precompiles_span_carries_path_and_slices(one_device, spans):
    trainer = _trainer(one_device, Llama(_config()), cross_entropy_loss,
                       micro=2)
    trainer.precompile()
    (aot,) = _recompiles(spans, "aot")
    assert (aot["head_loss_path"], aot["head_loss_slices"]) == ("fused", 1)


def test_precompiles_span_carries_the_schedules_counts(one_device, spans):
    """Read from the compiled step's text inside the span (PR 36); on the
    CPU nothing is recomputed and the backend fuses no matmul, so both
    read 0: that they are there, and what reading them cost."""
    trainer = _trainer(one_device, Llama(_config()), cross_entropy_loss,
                       micro=2)
    trainer.precompile()
    (aot,) = _recompiles(spans, "aot")
    assert (aot["remat_instructions"], aot["late_weight_grads"],
            aot["recomputed_kernels"], aot["recomputed_matmuls"]) == (
                0, 0, 0, 0)
    assert 0 <= aot["schedule_read_s"] < 5


def _entry(*instructions) -> str:
    """A compiled step's text cut to what `schedule_counts` reads: some
    computation before ENTRY, then ENTRY's instructions in order."""
    lines = ["%fused_computation.1 (p: bf16[8]) -> bf16[8] {",
             '  ROOT %x.remat = bf16[8]{0} fusion(%p), kind=kOutput, '
             'metadata={op_name="jit(s)/jvp(M)/layer_0/mlp/up_proj/'
             'dot_general"}', "}", "", "ENTRY %main (a: s32[]) -> s32[] {"]
    for name, result, op_name in instructions:
        lines.append(f"  %{name} = {result} fusion(%a), kind=kOutput, "
                     f'calls=%c, metadata={{op_name="jit(s)/{op_name}"}}')
    return "\n".join(lines + ["}"])


_ACT, _KERNEL = "bf16[2,64,128]{2,1,0}", "bf16[128,256,1]{1,0,2}"
_FWD = "jvp(M)/layer_{}/mlp/{}_proj/dot_general"
_BWD = "transpose(jvp(M))/layer_{}/mlp/{}_proj/dot_general"


@pytest.mark.parametrize("order, want", [
    # left alone: both layers' backward, then the weight gradients, and a
    # forward matmul launched again in front of the one that reads it
    ([("f.1", _ACT, _FWD.format(0, "up")), ("f.2", _ACT, _FWD.format(1, "up")),
      ("dx.2", _ACT, _BWD.format(1, "up")), ("dx.1", _ACT, _BWD.format(0, "up")),
      ("f.1.remat", _ACT, _FWD.format(0, "up")),
      ("dw.1", f"(f32[], {_KERNEL})", _BWD.format(0, "up")),
      ("dw.2", _KERNEL, _BWD.format(1, "up"))], (1, 1)),
    # tied: each weight gradient beside its layer's activation gradient;
    # the last projection's own may stand behind its sibling
    ([("f.1", _ACT, _FWD.format(0, "up")), ("f.2", _ACT, _FWD.format(1, "up")),
      ("dw.2", _KERNEL, _BWD.format(1, "up")), ("dx.2", _ACT, _BWD.format(1, "up")),
      ("dx.1", _ACT, _BWD.format(0, "up")),
      ("dw.1", _KERNEL, _BWD.format(0, "up"))], (0, 0)),
    # neither sign alone is a recomputation: the only copy under XLA's
    # mark, and one product a mesh split in two
    ([("f.1.remat2", _ACT, _FWD.format(0, "up")),
      ("f.2", _ACT, _FWD.format(1, "up")), ("f.3", _ACT, _FWD.format(1, "up")),
      ("dx.1", _ACT, _BWD.format(0, "up"))], (0, 0))],
    ids=["left_alone", "tied", "one_sign"])
def test_schedule_counts_on_a_hand_made_entry(order, want):
    counts = schedule_counts(_entry(*order), (2, 64))
    assert (counts["remat_instructions"],
            counts["late_weight_grads"]) == want
    assert counts["recomputed_kernels"] == {}
    assert counts["recomputed_matmuls"] == 0


_BLOCK = "transpose(jvp(M))/layer_{}/checkpoint/rematted_computation/{}"


def _kernel(name, result, op_name) -> str:
    return (f"  %{name} = {result} custom-call(%a), "
            'custom_call_target="tpu_custom_call", '
            f'metadata={{op_name="jit(s)/{op_name}/pallas_call"}}')


@pytest.mark.parametrize("again, want", [
    # recomputed whole: the block's forward kernels stand a second time
    # under `rematted_computation`, by name whatever XLA numbers them
    ([("sparse_attn_fwd.9", "sparse_attn"), ("sparse_attn_fwd.10",
      "sparse_attn"), ("indexer_select.4", "indexer"),
      ("rms_norm_fwd.31", "attn_norm")],
     {"sparse_attn_fwd": 2, "indexer_select": 1, "rms_norm_fwd": 1}),
    # their outputs kept (`ops/remat.py`): only the norm's is left
    ([("rms_norm_fwd.31", "attn_norm")], {"rms_norm_fwd": 1}),
    ([], {})], ids=["whole", "kept", "no_remat"])
def test_schedule_counts_names_the_kernels_a_block_recomputes(again, want):
    """Kernels of the forward pass, of the backward pass and a fusion
    under `rematted_computation` are not counted; a `custom-call` there is,
    under its `pl.pallas_call`'s name."""
    tuple_result = "(bf16[1,4,64,32]{3,2,1,0}, f32[1,4,64,1]{3,2,1,0})"
    lines = _entry(("f.1", _ACT, _BLOCK.format(0, "mlp/up_proj/dot_general"))
                   ).splitlines()
    lines[-1:-1] = [
        _kernel("sparse_attn_fwd.1", tuple_result, "jvp(M)/layer_0/sparse_attn"),
        _kernel("sparse_attn_dq.2", _ACT,
                "transpose(jvp(M))/layer_0/sparse_attn")] + [
        _kernel(name, tuple_result, _BLOCK.format(0, scope))
        for name, scope in again]
    counts = schedule_counts("\n".join(lines), (2, 64))
    assert counts["recomputed_kernels"] == want
    assert (counts["remat_instructions"], counts["late_weight_grads"]) == (0, 0)


def test_schedule_counts_the_matmuls_a_block_recomputes():
    """A projection's matmul under `rematted_computation` is counted, its
    first launch in the forward pass is not, and neither is XLA's own
    recomputation (`.remat`, which `remat_instructions` counts); a
    computation before ENTRY is never read."""
    counts = schedule_counts(_entry(
        ("f.1", _ACT, _FWD.format(0, "up")),
        ("f.1.remat", _ACT, _FWD.format(0, "up")),
        ("r.1", _ACT, _BLOCK.format(0, "mlp/up_proj/dot_general")),
        ("dx.1", _ACT, _BWD.format(0, "up"))), (2, 64))
    assert counts["recomputed_matmuls"] == 1
    assert counts["remat_instructions"] == 1


@pytest.mark.parametrize("loss_fn, want", [
    (cross_entropy_loss, "fused"), (_wrapped_loss, "logits")],
    ids=["fused", "logits"])
def test_the_loops_relower_span_carries_path_and_slices(
        cpu_devices, tmp_path, spans, loss_fn, want):
    loop = ElasticTrainLoop(
        Llama(_config()), optax.sgd(0.1), loss_fn,
        TrainLoopConfig(global_batch=2, seq_len=SEQ, max_steps=1,
                        checkpoint_dir=str(tmp_path / "ckpt")),
        devices=cpu_devices[:1])
    try:
        (relower,) = _recompiles(spans, "relower")
        assert relower["head_loss_path"] == want == (
            loop.trainer.head_loss_path)
        assert relower["head_loss_slices"] == 1
    finally:
        loop.close()
