"""A model class is files (PR 31): the harness asks the class for its plain
reference, its FLOPs a token, its attention layers and its rehearsal sizes.

The second class here, ``twokind`` (``tests/benchmarks/models/``: layers of
two kinds and two head counts, built from the product's own modules, with a
block of its own in a reference of its own), exists only as files this
directory owns; the harness's lookup is pointed at them and nothing under
``benchmarks/`` names them. All on the CPU.
"""

from __future__ import annotations

import os
import re
import shutil
import types

import pytest

from benchmarks import flops, harness, kernel_needs, reference, worker
from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
MODELS = os.path.join(HERE, "models")
KIND = "TPU v5 lite"
CELL = "twokind.steady"
BENCH = dict(
    harness.benchmark(),
    configs=[{"name": "twokind", "source": "tests/benchmarks",
              "file": "tests/benchmarks/configs/twokind.json", "reduced": [],
              "why": "a second model class, as files"}],
    workloads=[{"name": CELL, "config": "twokind",
                "traffic": "steady_b2_s2048", "chips": 1,
                "why": "the window, the comparison and the readers on a "
                       "class the harness has never heard of"}])
# the rehearsal's own limits, as test_benchmark.py has them for Llama's
LIMITS = {"rows_wrong": 0, "loss_gap": 1e-3, "grad_gap": 1e-2,
          "change_gap": 1e-2, "grad_gap_whole": 1e-2,
          "compiles_in_window": 0, "saves_uncommitted": 0}


@pytest.fixture
def lookup(monkeypatch):
    """The harness's own lookup, pointed at the tests' class and cell."""
    monkeypatch.setattr(harness, "MODELS", MODELS)
    monkeypatch.setattr(harness, "benchmark", lambda: BENCH)


def _context(tmp_path):
    return worker.context(CELL, seed=2_147_483_783, seconds=0.5, trace=False,
                          report_path=str(tmp_path / "report.jsonl"),
                          workdir=str(tmp_path), rehearse=True,
                          in_process=True)


@pytest.mark.parametrize("block", ["its_own", "llamas"])
def test_a_second_class_is_correct_on_its_own_block_only(block, lookup,
                                                         tmp_path):
    """Through ``windows/steady.py`` as a run goes: the program's two kinds
    of layers against the class's own reference read ``correct``; against
    Llama's block on the same leaves they do not."""
    ctx = _context(tmp_path)
    assert ctx.cfg["layer_types"] == ["global", "local"]   # the class's tiny
    assert ctx.model.__file__.startswith(MODELS)
    if block == "llamas":
        llama = harness.load_module("models", "llama_reference")
        # Llama's block wants Llama's keys: the first layer's, for all
        ctx.cfg = dict(ctx.cfg, num_attention_heads=4, rope_theta=500000.0)
        ctx.model_reference = types.SimpleNamespace(
            leaves=ctx.model_reference.leaves,
            layer_prefix=ctx.model_reference.layer_prefix,
            layer_kind=ctx.model_reference.layer_kind, block=llama.block)
    assert harness.load_module("windows", ctx.traffic["window"]).run(ctx) == 0
    run = bench_run.gather(ctx.report.read(), BENCH, CELL, started_wall=0.0,
                           seconds=0.5, trace=False)
    line = bench_run.conclude(run, LIMITS, lenient=True)
    assert run["window"]["steps"] > 0
    assert line["correct"] is (block == "its_own"), line["compared"]
    if block == "llamas":       # the first layer is Llama's, the second not
        assert run["compared"]["compared"]["grad_gap"]["value"] > 0.1


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
    # by hand: q and o are hidden x hidden in both kinds, k and v 2 heads of
    # 32 (global) or of 16 (local); 4 layers, two of each kind
    matmul = 256 * 128 + 4 * (2 * 128 * 128 + 3 * 128 * 256) + 2 * (
        2 * 128 * 64 + 2 * 128 * 32)
    per_token = 6.0 * matmul + 6.0 * 4 * 128 * 2048
    assert model.flops_per_token(cfg, 2048) == per_token
    assert harness.load_module("metrics", "step.mfu").read(run) == (
        pytest.approx(100.0 * 10 * 4096 * per_token / (2.0 * 197e12)))
    assert [(layer["heads"], layer["head_dim"])
            for layer in model.attention_layers(cfg)] == [
                (4, 32), (8, 16), (8, 16), (4, 32)]
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

    counting = types.SimpleNamespace(
        leaves=plain.leaves, layer_prefix=plain.layer_prefix,
        layer_kind=plain.layer_kind, block=block)
    rows = reference.Rows(7, cfg["vocab_size"], 16, 32)
    batches = [rows.batch(k, 2) for k in range(2)]
    got = reference.follow(counting, 7, cfg, batches)
    # four layers [global, local, local, global], two steps: each kind once
    # forward and once under the backward's vjp, by its first layer's index
    assert sorted(traced) == [0, 0, 1, 1]
    truth = reference.follow(plain, 7, cfg, batches)
    assert got["losses"] == truth["losses"]
    assert set(got["grad_norms"]) == set(plain.leaves(cfg))
    # the kinds are not each other: the second layer under the first's kind
    other = reference.follow(types.SimpleNamespace(
        leaves=plain.leaves, layer_prefix=plain.layer_prefix,
        layer_kind=plain.layer_kind,
        block=lambda x, p, c, layer, mode: plain.block(x, p, dict(
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
