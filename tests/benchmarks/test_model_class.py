"""A model class is files (PR 31): the harness asks the class for its plain
reference, its FLOPs a token, its attention layers and its rehearsal sizes;
and (PR 33) for whatever else a harness written for Llama would assume: a
block may add a term to the objective, and what a program does outside a
kernel is read by scope.

The second class here, ``twokind`` (``tests/benchmarks/models/``: one kind of
layer is the product's own block; the other has a head width of its own, a
stack of experts in one leaf, a leaf with a gradient of exactly zero and a
second objective sown into ``losses``), exists only as files this directory
owns; ``bench_cells.py`` enters it beside the accepted cells, ``conftest.py``
points the harness's lookup at it and nothing under ``benchmarks/`` names it.
All on the CPU.
"""

from __future__ import annotations

import os
import re
import shutil
import types

import jax.numpy as jnp
import pytest
from bench_cells import BENCH, CELL, MODELS
from bench_cells import TINY_LIMITS as LIMITS

from benchmarks import (
    flops,
    harness,
    kernel_needs,
    reference,
    trace_reduce,
    worker,
)
from benchmarks import run as bench_run

KIND = "TPU v5 lite"


def _context(tmp_path):
    return worker.context(CELL, seed=2_147_483_783, seconds=0.5, trace=False,
                          report_path=str(tmp_path / "report.jsonl"),
                          workdir=str(tmp_path), rehearse=True,
                          in_process=True)


def _with_block(plain, block):
    """The class's reference with another block in its place."""
    return types.SimpleNamespace(
        leaves=plain.leaves, layer_prefix=plain.layer_prefix,
        layer_kind=plain.layer_kind, block=block)


def _without_extra(plain):
    """The planted fault of a second objective: the reference's block drops
    the term it should add."""
    def block(x, p, cfg, layer, mode):
        out = plain.block(x, p, cfg, layer, mode)
        return out[0] if isinstance(out, tuple) else out

    return _with_block(plain, block)


@pytest.mark.parametrize("block", ["its_own", "extra_dropped"])
def test_a_second_class_is_correct_on_its_own_block_only(block, lookup,
                                                         tmp_path):
    """Through ``windows/steady.py`` as a run goes: the program's two kinds
    of layers, the second objective in its loss, against the class's own
    reference read ``correct``; against its own block with the second
    objective dropped they do not."""
    ctx = _context(tmp_path)
    assert ctx.cfg["layer_types"] == ["local", "global"]   # the class's tiny
    assert ctx.model.__file__.startswith(MODELS)
    plain = ctx.model_reference
    if block == "extra_dropped":
        ctx.model_reference = _without_extra(plain)
    assert harness.load_module("windows", ctx.traffic["window"]).run(ctx) == 0
    run = bench_run.gather(ctx.report.read(), BENCH, CELL, started_wall=0.0,
                           seconds=0.5, trace=False)
    line = bench_run.conclude(run, LIMITS, lenient=True)
    compared = run["compared"]["compared"]
    assert run["window"]["steps"] > 0
    assert line["correct"] is (block == "its_own"), line["compared"]
    if block == "its_own":
        # the program's loss is the whole objective, and so is the
        # reference's: the head's alone lies the extra below both
        alone = reference.follow(
            _without_extra(plain), ctx.seed, ctx.cfg,
            [reference.Rows(ctx.seed, ctx.cfg["vocab_size"],
                            ctx.traffic["rows"], ctx.traffic["seq_len"],
                            ctx.traffic["shuffle"]).batch(
                                0, ctx.traffic["global_batch"])])
        extra = run["compared"]["reference_losses"][0] - alone["losses"][0]
        assert extra > 10 * LIMITS["loss_gap"] * alone["losses"][0]
        assert run["compared"]["program_losses"][0] == pytest.approx(
            alone["losses"][0] + extra, rel=LIMITS["loss_gap"])
    if block == "extra_dropped":
        # the loss lacks the term, and the leaf that only the term trains
        # has no gradient in the reference
        assert compared["loss_gap"]["value"] > LIMITS["loss_gap"]
        assert compared["grad_gap"]["value"] > LIMITS["grad_gap"]
        assert compared["grad_gap"]["where"] == "layer_0/index/kernel"


def test_llamas_block_cannot_stand_in_for_the_second_classs(lookup):
    """The global kind's leaves are Llama's and its block could follow them;
    the local kind's are not even named alike, so the harness had to ask."""
    _, cfg, _ = harness.cell(BENCH, CELL)
    plain = harness.model_reference(cfg)
    llama = harness.load_module("models", "llama_reference")
    rows = reference.Rows(7, cfg["vocab_size"], 16, 32)
    with pytest.raises(KeyError, match="gate_proj"):
        reference.follow(_with_block(
            plain, lambda x, p, c, layer, mode: llama.block(x, p, dict(
                c, num_attention_heads=4, rope_theta=5e5), layer, mode)),
            7, cfg, [rows.batch(0, 2)])


def test_the_readers_take_the_second_classs_own_counts(lookup):
    """``step.mfu`` divides by the class's FLOPs a token and the named-kernel
    readers hold the time against the class's layers, not Llama's formula
    (which this configuration has not even the keys for)."""
    _, cfg, traffic = harness.cell(BENCH, CELL)
    model = harness.model_class(cfg)
    run = bench_run.gather(
        [{"record": "device", "kind": KIND},
         {"record": "window", "steps": 10, "tokens_per_step": 4096,
          "seconds": 2.0}], BENCH, CELL, 0.0, 10.0, True)
    assert run["model"].__file__ == model.__file__
    # by hand, four layers, two of each kind. global: q and o hidden x
    # hidden, k and v 2 heads of 32, three MLP matrices. local: 8 heads of 32
    # on a hidden of 128, so q and o are 128 x 256; two experts' up and one
    # down, a router on 2 and the index's two matrices on 8
    matmul = 256 * 128 + 2 * (
        2 * 128 * 128 + 2 * 128 * 64 + 3 * 128 * 256) + 2 * (
        2 * 128 * 256 + 2 * 128 * 64 + 3 * 128 * 256 + 128 * 2
        + 2 * 128 * 8)
    per_token = 6.0 * matmul + 6.0 * 2 * (128 + 256) * 2048
    assert model.flops_per_token(cfg, 2048) == per_token
    assert harness.load_module("metrics", "step.mfu").read(run) == (
        pytest.approx(100.0 * 10 * 4096 * per_token / (2.0 * 197e12)))
    assert [(layer["heads"], layer["head_dim"])
            for layer in model.attention_layers(cfg)] == [
                (8, 32), (4, 32), (4, 32), (8, 32)]
    assert cfg["hidden_size"] // 8 != 32        # a head width of its own
    # three steps' launches, all at a quarter of their roofline
    least = sum(flops.roofline_seconds(
        kernel_needs.flash_attention_fwd(layer, 2, traffic["seq_len"]),
        KIND)[0] for layer in model.attention_layers(cfg))
    run["traced"] = {"by_name": {"%flash_attn_fwd.1 = bf16[]": 3 * 4 * least},
                     "count_by_name": {"%flash_attn_fwd.1 = bf16[]": 3 * 4}}
    assert harness.load_module(
        "metrics", "kernels.flash_attn_fwd_roofline").read(run) == (
            pytest.approx(25.0))


@pytest.mark.parametrize("function", harness.MODEL_CLASS
                         + harness.MODEL_REFERENCE)
def test_a_class_that_lacks_a_function_fails_at_the_context(
        function, lookup, monkeypatch, tmp_path):
    """... by the function's name, not later inside a window or a reader."""
    lacking = tmp_path / "models"
    shutil.copytree(MODELS, lacking)
    for name in os.listdir(lacking):
        if name.endswith(".py"):
            text = (lacking / name).read_text()
            text = re.sub(rf"^def {function}\(", f"def _no_{function}(", text,
                          flags=re.M)
            text = re.sub(rf"^    {function},\n", "", text, flags=re.M)
            (lacking / name).write_text(text)
    monkeypatch.setattr(harness, "MODELS", str(lacking))
    with pytest.raises(AttributeError, match=rf"lacks {function}\(\)"):
        _context(tmp_path)


# -- the reference's step: one compiled block a kind of layer ---------------


def test_the_reference_traces_a_block_once_a_kind_not_once_a_layer(lookup):
    _, cfg, _ = harness.cell(BENCH, CELL)
    plain = harness.model_reference(cfg)
    traced = []

    def block(x, p, cfg_, layer, mode):
        traced.append(layer)
        return plain.block(x, p, cfg_, layer, mode)

    counting = _with_block(plain, block)
    rows = reference.Rows(7, cfg["vocab_size"], 16, 32)
    batches = [rows.batch(k, 2) for k in range(2)]
    got = reference.follow(counting, 7, cfg, batches)
    # four layers [local, global, global, local], two steps: each kind once
    # forward and once under the backward's vjp, by its first layer's index
    assert sorted(traced) == [0, 0, 1, 1]
    truth = reference.follow(plain, 7, cfg, batches)
    assert got["losses"] == truth["losses"]
    assert set(got["grad_norms"]) == set(plain.leaves(cfg))
    # the kinds are not each other: the second layer under the first's kind
    other = reference.follow(_with_block(
        plain, lambda x, p, c, layer, mode: plain.block(x, p, dict(
            c, rope_theta_by_type={"global": 5e5, "local": 5e5}), layer,
            mode)), 7, cfg, batches)
    assert other["losses"][0] != truth["losses"][0]


# -- what a banded layer's kernels need --------------------------------------


@pytest.mark.parametrize("window", ["1", "512", "s-1", "s", "2s"])
@pytest.mark.parametrize("seq", [2048, 640])
def test_the_band_count_against_a_count_of_pairs(window, seq):
    w = {"1": 1, "512": 512, "s-1": seq - 1, "s": seq, "2s": 2 * seq}[window]
    exact = sum(min(i + 1, w) for i in range(seq))
    counted = kernel_needs.scored_pairs(seq, w)
    # the convention leaves out half of the diagonal's cells, as the
    # accepted causal count s^2 / 2 does
    assert 0 <= exact - counted <= (seq + w) / 2
    assert exact - counted == min(w, seq) / 2
    if w >= seq:
        assert counted == kernel_needs.scored_pairs(seq, None) == seq * seq / 2
    layer = {"heads": 64, "kv_heads": 8, "head_dim": 128, "window": w}
    causal = dict(layer, window=None)
    for needs in (kernel_needs.flash_attention_fwd,
                  kernel_needs.flash_attention_bwd):
        banded, full = needs(layer, 2, seq), needs(causal, 2, seq)
        assert banded["bytes"] == full["bytes"]
        assert banded["flops"] / full["flops"] == pytest.approx(
            counted / (seq * seq / 2))


def test_lagunas_band_holds_an_eighth_of_the_causal_pairs():
    # ISSUE 31: a 512-wide band at seq 8192 holds 4.06M of 33.6M pairs
    assert kernel_needs.scored_pairs(8192, 512) == 512 * 8192 - 512 * 512 / 2
    assert kernel_needs.scored_pairs(8192, None) / kernel_needs.scored_pairs(
        8192, 512) == pytest.approx(8.26, abs=0.01)


# -- the named-kernel readers on layers of two head counts -------------------

LAGUNA_LIKE = [{"heads": 48, "kv_heads": 8, "head_dim": 128, "window": None},
               {"heads": 64, "kv_heads": 8, "head_dim": 128, "window": 512},
               {"heads": 64, "kv_heads": 8, "head_dim": 128, "window": 512},
               {"heads": 64, "kv_heads": 8, "head_dim": 128, "window": 512}]


def _event(name, heads):
    return (f"%{name} = bf16[1,{heads},8192,128]{{3,2,1,0}} custom-call("
            f"bf16[1,{heads},8192,128]{{3,2,1,0}} %a), "
            'custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("name,kernels,needs", [
    ("kernels.flash_attn_fwd_roofline", ("flash_attn_fwd",),
     kernel_needs.flash_attention_fwd),
    ("kernels.flash_attn_bwd_roofline", ("flash_attn_dq", "flash_attn_dkv"),
     kernel_needs.flash_attention_bwd)])
def test_kernel_readers_on_a_trace_of_layers_of_two_head_counts(
        name, kernels, needs):
    """One full layer of 48 heads and three window layers of 64, five steps
    traced: the full layer's kernels run at 60 % of their roofline, the
    window layers' at 30 %. The share is the layers' least times over the
    time taken; held against one layer's count times the launches (what the
    readers did before PR 31) the 45.2 % would read 122 % or 19.7 %."""
    run = {"cfg": {}, "traffic": {"seq_len": 8192, "global_batch": 1},
           "workload": {"chips": 1}, "device": {"kind": KIND},
           "model": types.SimpleNamespace(
               attention_layers=lambda cfg: LAGUNA_LIKE)}
    least = [flops.roofline_seconds(needs(layer, 1, 8192), KIND)[0]
             for layer in LAGUNA_LIKE]
    steps, by_name, counts = 5, {}, {}
    for k, kernel in enumerate(kernels):
        part = 1.0 / len(kernels)       # dQ and dK/dV halve the pair's time
        by_name[_event(f"{kernel}.{k}", 48)] = (
            steps * part * least[0] / 0.6)
        counts[_event(f"{kernel}.{k}", 48)] = steps
        by_name[_event(f"{kernel}.{k + 2}", 64)] = (
            steps * part * sum(least[1:]) / 0.3)
        counts[_event(f"{kernel}.{k + 2}", 64)] = 3 * steps
    run["traced"] = {"by_name": by_name, "count_by_name": counts}
    spent = sum(by_name.values())
    expected = 100.0 * steps * sum(least) / spent
    assert least[0] / least[1] > 5          # the band is worth having
    read = harness.load_module("metrics", name).read
    assert read(run) == pytest.approx(expected)
    assert 30.0 < expected < 60.0
    for one in (least[0], least[1]):        # one layer's count for all
        assert not 30.0 < 100.0 * one * 4 * steps / spent < 60.0


# -- a block may return a second objective -----------------------------------

LLAMA_TINY = dict(
    hidden_size=128, intermediate_size=256, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
    rope_theta=1e4, rms_norm_eps=1e-5, tie_word_embeddings=False,
    optimizer={"name": "factored_rms", "learning_rate": 3e-4})


def test_a_block_that_returns_x_alone_reads_as_it_always_has():
    """Llama's block returns ``x`` alone and never meets the new branch;
    made to return ``(x, 0)`` it goes through it (the pair's cotangent, the
    sum of the losses) and every loss and gradient norm still reads the
    same to the last digit."""
    llama = harness.model_reference({"model": "llama"})
    seed = 2_147_483_659
    rows = reference.Rows(seed, 256, 64, 64)
    batches = [rows.batch(k, 2) for k in range(3)]
    took_the_branch = []

    def block(x, p, cfg, layer, mode):
        took_the_branch.append(layer)
        return llama.block(x, p, cfg, layer, mode), jnp.zeros((), x.dtype)

    alone = reference.follow(llama, seed, LLAMA_TINY, batches)
    paired = reference.follow(_with_block(llama, block), seed, LLAMA_TINY,
                              batches)
    assert took_the_branch
    assert paired["losses"] == alone["losses"]
    assert paired["grad_norms"] == alone["grad_norms"]
    assert paired["change_norms"] == alone["change_norms"]


def test_the_second_objective_reaches_the_layers_below_it(lookup):
    """The extra's cotangent is 1: its gradient flows through the block's
    input to the embedding, and a leaf whose output is detached gets a
    gradient of exactly zero without leaving the comparison."""
    _, cfg, traffic = harness.cell(BENCH, CELL)
    cfg, _ = harness.model_class(cfg).tiny(cfg, traffic)
    plain = harness.model_reference(cfg)
    rows = reference.Rows(11, cfg["vocab_size"], 16, 32)
    batches = [rows.batch(0, 2)]
    whole = reference.follow(plain, 11, cfg, batches)
    alone = reference.follow(_without_extra(plain), 11, cfg, batches)
    assert whole["losses"][0] > alone["losses"][0]
    assert whole["grad_norms"]["layer_0/index/target"] == 0.0
    assert whole["change_norms"]["layer_0/index/target"] == 0.0
    assert alone["grad_norms"]["layer_0/index/kernel"] == 0.0
    assert whole["grad_norms"]["layer_0/index/kernel"] > 0.1
    # layer 0 is the local one: below it lies the embedding alone
    assert whole["grad_norms"]["embed"] != alone["grad_norms"]["embed"]
    assert whole["grad_norms"]["layer_1/mlp/up_proj/kernel"] == (
        alone["grad_norms"]["layer_1/mlp/up_proj/kernel"])
    assert len(plain.leaves(cfg)["layer_0/mlp/experts_up"].shape) == 3


# -- device time by scope ----------------------------------------------------

STEP_TEXT = """\
HloModule jit__train_step, entry_computation_layout={()->()}

%fused_computation.1 (param_0.1: bf16[8,8]) -> bf16[8,8] {
  %param_0.1 = bf16[8,8]{1,0} parameter(0)
  %convert.1 = f32[8,8]{1,0} convert(%param_0.1), metadata={op_name="jit(_train_step)/grad_accum/while/body/closed_call/jvp(TwoKind)/layer_0/mlp/convert_element_type"}
  ROOT %dot.1 = bf16[8,8]{1,0} dot(%convert.1, %convert.1), metadata={op_name="jit(_train_step)/grad_accum/while/body/closed_call/jvp(TwoKind)/layer_0/mlp/down_proj/dot_general" source_file="llama.py" source_line=288}
}

%fused_computation.2 (param_0.2: f32[8]) -> (f32[8], f32[8]) {
  %param_0.2 = f32[8]{0} parameter(0)
  %multiply.2 = f32[8]{0} multiply(%param_0.2, %param_0.2), metadata={op_name="jit(_train_step)/optimizer/mul"}
  ROOT %tuple.2 = (f32[8]{0}, f32[8]{0}) tuple(%multiply.2, %param_0.2)
}

%fused_computation.3 (param_0.3: f32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0} parameter(0)
  ROOT %bitcast.3 = f32[8]{0} bitcast(%param_0.3)
}

ENTRY %main.9 (Arg_0.1: bf16[8,8], Arg_1.2: f32[8]) -> f32[8] {
  %Arg_0.1 = bf16[8,8]{1,0} parameter(0), metadata={op_name="state.params['layer_0']['mlp']"}
  %Arg_1.2 = f32[8]{0} parameter(1)
  %fusion.1 = bf16[8,8]{1,0} fusion(%Arg_0.1), kind=kOutput, calls=%fused_computation.1
  %fusion.2 = (f32[8]{0}, f32[8]{0}) fusion(%Arg_1.2), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8]{0} fusion(%Arg_1.2), kind=kLoop, calls=%fused_computation.3
  %index_gap.4 = f32[8]{0} custom-call(%Arg_1.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(_train_step)/grad_accum/while/body/closed_call/transpose(jvp(TwoKind))/layer_0/index/jit(_gap)/index_gap"}
  %fusion.5 = f32[8]{0} fusion(%Arg_1.2), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(_train_step)/grad_accum/while/body/closed_call/transpose(jvp(head_loss))/mul"}
  ROOT %copy.6 = f32[8]{0} copy(%fusion.5), metadata={op_name="jit(_train_step)/sqrt"}
}
"""


def _step_event(instruction: str) -> str:
    return f"%{instruction} = f32[8]{{0:T(128)}} fusion(f32[8]{{0}} %a)"


def test_op_names_a_fusion_takes_its_roots():
    named = trace_reduce.op_names(STEP_TEXT)
    assert named["fusion.1"].endswith("layer_0/mlp/down_proj/dot_general")
    # a root that is a tuple has none: the last named instruction's before it
    assert named["fusion.2"] == "jit(_train_step)/optimizer/mul"
    assert "fusion.3" not in named and "Arg_1.2" not in named
    assert named["fusion.5"].endswith("transpose(jvp(head_loss))/mul")
    assert trace_reduce.op_names("") == {}


def test_time_by_scope_on_a_hand_made_record():
    """An event counts under every scope of its path; a fusion takes its
    root's; an instruction the text does not know, or knows without an
    ``op_name``, is ``unscoped``; the first scope of each event and
    ``unscoped`` are all the time there is."""
    seconds = {"fusion.1": 4.0, "fusion.2": 1.0, "fusion.3": 0.25,
               "index_gap.4": 2.0, "fusion.5": 1.5, "copy.6": 0.25,
               "fusion.77": 1.0}       # of another program: not in the text
    events = [(_step_event(name), 10.0 * k, 10.0 * k + spent)
              for k, (name, spent) in enumerate(seconds.items())]
    events.append((_step_event("fusion.1"), 100.0, 102.0))   # a 2nd launch
    named = trace_reduce.op_names(STEP_TEXT)
    traced = trace_reduce.reduce({"/device:TPU:0": events}, [],
                                 op_name_of=named)
    whole = sum(seconds.values()) + 2.0
    by_scope = traced["by_scope"]
    top: dict = {}
    for event, start, end in events:
        first = (trace_reduce.scopes_of(named.get(
            event[1:].split(" ")[0], "")) or ["unscoped"])[0]
        top[first] = top.get(first, 0.0) + end - start
    assert top == {"unscoped": 1.25, "grad_accum": 9.5, "optimizer": 1.0,
                   "sqrt": 0.25}
    assert {scope: by_scope[scope] for scope in top} == top
    assert sum(top.values()) == whole == sum(traced["by_name"].values())
    # nested: the module, its layer, the model and the transformation
    for scope in ("mlp", "down_proj", "dot_general", "jvp("):
        assert by_scope[scope] == 6.0
    assert by_scope["layer_0"] == by_scope["TwoKind"] == 8.0
    assert by_scope["transpose(jvp("] == 3.5         # the backward
    assert by_scope["index"] == by_scope["index_gap"] == 2.0
    assert by_scope["head_loss"] == 1.5 and by_scope["mul"] == 2.5
    assert "jit(_train_step)" not in by_scope and "_train_step" not in by_scope
    assert by_scope["jit("] == by_scope["_gap"] == 2.0   # a jit inside is kept
    share = kernel_needs.scope_share
    assert share(traced, "head_loss") == pytest.approx(100.0 * 1.5 / whole)
    assert share(traced, "unscoped") == pytest.approx(100.0 * 1.25 / whole)
    assert share(traced, "no_such_scope") is None
    assert sum(share(traced, scope) for scope in top) == pytest.approx(100.0)
    # a later class's scope is read by a new file that asks for its name
    assert share(traced, "index") == pytest.approx(100.0 * 2.0 / whole)
    # every event joined: the share of the unscoped is a 0 that was read
    joined = trace_reduce.reduce(
        {"/device:TPU:0": events[:2]}, [], op_name_of=named)
    assert share(joined, "unscoped") == 0.0
    assert "outside_program" not in by_scope        # held to no runs


def test_an_event_outside_the_programs_runs_is_another_programs():
    """An instruction's name is unique in its program only: ``fusion.1`` of
    a transfer's or a fetch's program, between two runs of the step, is not
    the step's ``mlp``. The step's runs are its module's events on the
    ``XLA Modules`` line, named ``<module>(<fingerprint>)``."""
    chip = "/device:TPU:0"
    assert trace_reduce.module_name(STEP_TEXT) == "jit__train_step"
    assert trace_reduce.module_name("") is None
    modules = {chip: [("jit__train_step(8471603499039890235)", 20.0, 30.0),
                      ("jit__train_step_again(1)", 0.0, 5.0),
                      ("jit_is_ready(77)", 15.0, 16.0),
                      ("jit__train_step(8471603499039890235)", 10.0, 15.0)]}
    runs = trace_reduce.program_runs(modules, "jit__train_step")
    assert runs == {chip: [(10.0, 15.0), (20.0, 30.0)]}
    assert trace_reduce.program_runs(modules, None) == {chip: []}
    events = [(_step_event("fusion.1"), 1.0, 2.0),     # before the first run
              (_step_event("fusion.1"), 10.0, 14.0),   # at a run's start
              (_step_event("fusion.1"), 15.0, 15.5),   # at its end: outside
              (_step_event("fusion.5"), 29.0, 30.5),   # starts inside
              (_step_event("fusion.77"), 21.0, 22.0),  # inside, not in text
              (_step_event("fusion.2"), 31.0, 33.0)]   # after the last
    named = trace_reduce.op_names(STEP_TEXT)
    held = trace_reduce.reduce({chip: events}, [], op_name_of=named,
                               runs=runs)["by_scope"]
    assert held["mlp"] == 4.0 and held["head_loss"] == 1.5
    assert held["outside_program"] == 1.0 + 0.5 + 2.0
    assert held["unscoped"] == held["outside_program"] + 1.0
    assert "optimizer" not in held
    free = trace_reduce.reduce({chip: events}, [], op_name_of=named)
    assert free["by_scope"]["mlp"] == 5.5 and free["by_scope"]["optimizer"]
    # a trace whose modules line names no run of the program: all unscoped
    lost = trace_reduce.reduce({chip: events}, [], op_name_of=named,
                               runs={})["by_scope"]
    assert lost["unscoped"] == lost["outside_program"] == 10.0


SCOPE_METRICS = {"step.head_loss_share": "head_loss",
                 "step.optimizer_share": "optimizer",
                 "step.unscoped_share": "unscoped"}


@pytest.mark.parametrize("name", list(SCOPE_METRICS))
def test_scope_readers_read_by_scope_and_nothing_without_a_map(name):
    read = harness.load_module("metrics", name).read
    events = [(_step_event("fusion.2"), 0.0, 1.0),
              (_step_event("fusion.5"), 1.0, 2.5),
              (_step_event("fusion.77"), 3.0, 3.5),
              (_step_event("fusion.1"), 4.0, 9.0)]
    with_map = trace_reduce.reduce({"/device:TPU:0": events}, [],
                                   op_name_of=trace_reduce.op_names(STEP_TEXT))
    expected = {"head_loss": 1.5, "optimizer": 1.0, "unscoped": 0.5}
    assert read({"traced": with_map}) == pytest.approx(
        100.0 * expected[SCOPE_METRICS[name]] / 8.0)
    # the parent's record has no map: nothing to read, not a zero
    without = trace_reduce.reduce({"/device:TPU:0": events}, [])
    assert "by_scope" not in without
    assert read({"traced": without}) is None
    assert read({"traced": {}}) is None and read({}) is None
    listed = {m["name"]: m for m in BENCH["per_layer"]}[name]
    assert listed == {"name": name, "unit": "%", "better": "lower",
                      "source": "device_trace", "layer": "step program",
                      "moves": "tokens_per_s"}
