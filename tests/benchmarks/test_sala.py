"""Model class ``minicpm_sala``: its entries in ``BENCHMARK.json``, the
readers of its seven metrics on hand-made records, its counts against the
program and against brute force, and the program's ``MiniCPMSala`` against
the class's plain reference through the window, each planted fault failing ``correct`` (in the
reference and in the program). All on the CPU at ``tiny()``'s sizes, where
both kinds of layer are present and the sparse one is sparse: 256 tokens in
blocks of 16 of which a query takes 4.

The class's cell is entered in ``BENCHMARK.json``, so the for-every-cell
tests of ``test_benchmark.py`` and ``test_readers.py`` run over it too; what
its limits file can and cannot tell on the chip is PERF.md section 2's.
"""

from __future__ import annotations

import math
import types

import jax.numpy as jnp
import numpy as np
import pytest
from bench_cells import BENCH
from bench_cells import TINY_LIMITS as LIMITS

from benchmarks import check, flops, harness, reference, worker
from benchmarks import run as bench_run

CELL = "minicpm_sala_l4.steady_16k"
SEED = 2_147_484_001
KIND = "TPU v5 lite"
NEW = ("kernels.lightning_fwd_roofline", "kernels.lightning_bwd_roofline",
       "kernels.block_sparse_attn_fwd_roofline",
       "kernels.block_sparse_attn_bwd_roofline", "step.lightning_share",
       "step.block_select_share", "attn.kv_tiles_visited_share")


def _cell() -> tuple:
    entry, cfg, traffic = harness.cell(BENCH, CELL)
    return entry, cfg, traffic, harness.model_class(cfg)


# -- the entries ------------------------------------------------------------


def test_the_cell_is_entered_with_the_accepted_metrics():
    """The configuration and the cell; the configuration's file against its
    entry and the published sizes. The cell reports every metric that lists
    no cells, and no flash roofline. The seven readers of the class's
    kernels, scopes and counter are files with no entry yet: entering them
    takes a change to tests the benchmark already has (PERF.md section 7)."""
    entry, cfg, traffic, _ = _cell()
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "minicpm_sala_l4")
    assert entry["traffic"] == "steady_b1_s16384" and entry["chips"] == 1
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"] == cfg[
        "reduced"]
    assert cfg["published"] == {"num_hidden_layers": 32, "vocab_size": 73448}
    assert cfg["vocab_size"] * 4 == 73448
    assert cfg["num_hidden_layers"] == 4 and len(cfg["mixer_types"]) == 32
    assert cfg["mixer_types"][:4] == ["minicpm4"] + ["lightning-attn"] * 3
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["lightning_nh"],
            cfg["lightning_head_dim"]) == (4096, 16384, 18362, 32, 2, 128,
                                           32, 128)
    names = {m["name"] for m in BENCH["per_layer"]}
    for name in NEW:
        assert name not in names
        assert callable(harness.load_module("metrics", name).read)
    reported = {m["name"] for m in harness.metrics_of(BENCH, CELL,
                                                       "per_layer")}
    assert reported == {m["name"] for m in BENCH["per_layer"]
                        if "workloads" not in m}
    for metric in BENCH["per_layer"]:
        if metric["name"].startswith("kernels.flash_attn"):
            assert CELL not in metric["workloads"]
    # each limit above the largest sound reading the file records, and the
    # two a state left unchanged reads 1 on, under 1
    limits = harness.load_json(harness.HERE, "limits", CELL + ".json")
    lower = limits["set_from"]["lower"]
    for name in ("loss_gap", "grad_gap", "grad_gap_whole", "change_gap"):
        assert lower[name] < limits["limits"][name] < 1.0
        assert name in limits["set_from"]["upper"]


# -- the counts -------------------------------------------------------------


@pytest.mark.parametrize("vocab, params, matmul_flops, per_token", [
    (73448, 1.711e9, 8.461e9, 8.67e9),      # the whole vocabulary
    (18362, 1.2599e9, 7.108e9, 7.316e9)])  # the cell's quarter
def test_the_classs_counts_are_the_programs_and_the_published(
        vocab, params, matmul_flops, per_token):
    """A sparse layer 253.8 M parameters, a lightning layer 285.2 M, and
    embedding and head twice vocabulary x 4,096, as the program's config and
    the reference's leaves count them; 6 per matmul parameter a token and
    the mixers' own FLOPs on top."""
    _, cfg, traffic, model = _cell()
    cfg = dict(cfg, vocab_size=vocab)
    built, _, _ = model.build(cfg, traffic)
    count = model.param_count(cfg)
    assert count == built.config.param_count()
    assert count == sum(int(np.prod(leaf.shape)) for leaf in harness.
                        model_reference(cfg).leaves(cfg).values())
    assert count == pytest.approx(params, rel=1e-3)
    assert 6 * model.param_counts(cfg)["matmul"] == pytest.approx(
        matmul_flops, rel=1e-3)
    assert model.flops_per_token(cfg, 16384) == pytest.approx(per_token,
                                                              rel=5e-3)
    assert built.config.flops_per_token(16384) == pytest.approx(
        model.flops_per_token(cfg, 16384), rel=2e-3)  # less the selection


@pytest.mark.parametrize("seq", [256, 16384])
def test_the_selected_pairs_against_a_count_by_query(seq):
    """The accepted convention (``k s - k^2 / 2``, half the diagonal) against
    the exact count a query at a time: ``min(t + 1, keys)`` past
    ``dense_len``, ``t + 1`` below it; they differ by half a diagonal."""
    _, cfg, _, model = _cell()
    if seq == 256:
        cfg, _ = model.tiny(cfg, {})
    sp = cfg["sparse_config"]
    keys = sp["topk"] * sp["block_size"] if seq >= sp["dense_len"] else seq
    exact = sum(min(t + 1, keys) for t in range(seq))
    assert model.selected_pairs(cfg, seq) == pytest.approx(
        exact - min(keys, seq) / 2.0)
    if seq == 16384:
        assert model.selected_pairs(cfg, seq) / (seq * seq / 2) == \
            pytest.approx(0.4375)


def test_the_kernels_needs():
    """Backward over forward as the forms say (3x for the lightning one's
    three outputs, 2x for attention's four matmuls against two); the
    lightning kernels bound by memory and the attention's by compute at the
    cell's size."""
    _, cfg, traffic, model = _cell()
    seq = traffic["seq_len"]
    lf, lb = model.lightning_fwd(cfg, 1, seq), model.lightning_bwd(cfg, 1, seq)
    af = model.block_sparse_attn_fwd(cfg, 1, seq)
    ab = model.block_sparse_attn_bwd(cfg, 1, seq)
    assert lb["flops"] == 3 * lf["flops"] and ab["flops"] == 2 * af["flops"]
    assert lf["flops"] == seq * 32 * (4 * 64 * 128 + 4 * 128 * 128)
    assert lf["bytes"] == 4 * seq * 4096 * 2
    assert af["flops"] == 4.0 * 32 * 128 * model.selected_pairs(cfg, seq)
    assert flops.roofline_seconds(lf, KIND)[1] == "memory"
    assert flops.roofline_seconds(lb, KIND)[1] == "memory"
    assert flops.roofline_seconds(af, KIND)[1] == "compute"
    assert flops.roofline_seconds(ab, KIND)[1] == "compute"
    layers = model.attention_layers(cfg)
    assert [layer["kv_heads"] for layer in layers] == [2, 32, 32, 32]


# -- the readers ------------------------------------------------------------


def _kernel_event(name):
    return (f"%{name} = bf16[1,32,16384,128]{{3,2,1,0:T(8,128)(2,1)}} "
            "custom-call(bf16[1,32,16384,128]{3,2,1,0} %a), "
            'custom_call_target="tpu_custom_call"')


def _run() -> dict:
    entry, cfg, traffic, model = _cell()
    return {"workload": entry, "cfg": cfg, "traffic": traffic,
            "model": model, "device": {"kind": KIND}}


@pytest.mark.parametrize("name,kernels,needs,layers", [
    ("kernels.lightning_fwd_roofline", ("lightning_fwd",), "lightning_fwd",
     3),
    ("kernels.lightning_bwd_roofline", ("lightning_bwd",), "lightning_bwd",
     3),
    ("kernels.block_sparse_attn_fwd_roofline", ("block_sparse_attn_fwd",),
     "block_sparse_attn_fwd", 1),
    ("kernels.block_sparse_attn_bwd_roofline",
     ("block_sparse_attn_dq", "block_sparse_attn_dkv"),
     "block_sparse_attn_bwd", 1)])
def test_kernel_readers_hold_the_time_against_the_classs_needs(
        name, kernels, needs, layers):
    """Two steps traced, each kernel launched once a layer a step at half
    its roofline (a backward's time split 40 / 60 between its two
    kernels); a kernel whose name only starts like ours is not ours, and
    the flash kernels' are not."""
    run = _run()
    least = flops.roofline_seconds(getattr(run["model"], needs)(
        run["cfg"], 1, 16384), KIND)[0]
    by_name, counts = {}, {}
    for part, kernel in zip((0.4, 0.6) if len(kernels) == 2 else (1.0,),
                            kernels):
        event = _kernel_event(f"{kernel}.3")
        by_name[event], counts[event] = 2 * least * part * 2 * layers, \
            2 * layers
    by_name[_kernel_event(f"{kernels[0]}x.1")] = 1.0
    by_name[_kernel_event("flash_attn_fwd.1")] = 1.0
    run["traced"] = {"by_name": by_name, "count_by_name": counts}
    assert harness.load_module("metrics", name).read(run) == pytest.approx(
        50.0)
    run["traced"] = {"by_name": {_kernel_event("flash_attn_fwd.1"): 1.0},
                     "count_by_name": {}}
    assert harness.load_module("metrics", name).read(run) is None


@pytest.mark.parametrize("name,scope", [
    ("step.lightning_share", "lightning_attn"),
    ("step.block_select_share", "block_select")])
def test_scope_readers_of_the_new_scopes(name, scope):
    read = harness.load_module("metrics", name).read
    traced = {"by_name": {"a": 3.0, "b": 1.0},
              "by_scope": {scope: 1.0, "unscoped": 3.0}}
    assert read({"traced": traced}) == pytest.approx(25.0)
    assert read({"traced": dict(traced, by_scope={"unscoped": 4.0})}) is None
    assert read({}) is None


def test_the_visited_tiles_counter_is_read_off_the_train_window_spans():
    read = harness.load_module("metrics", "attn.kv_tiles_visited_share").read
    attr = "block_sparse_tiles_visited_share"

    def span(start, end, mean=None, steps=None):
        counted = {} if mean is None else {attr + "_mean": mean,
                                           attr + "_steps": steps}
        return {"name": "train_window", "start": start, "end": end,
                "duration_s": end - start, "attrs": dict(steps=10, **counted)}

    run = {"window": {"opened_wall": 100.0, "seconds": 50.0, "spans": [
        span(90.0, 99.0, 0.1, 5), span(101.0, 110.0),
        span(110.0, 120.0, 0.9, 6), span(120.0, 130.0, 0.7, 2)]}}
    assert read(run) == pytest.approx(100.0 * (0.9 * 6 + 0.7 * 2) / 8)
    run["window"]["spans"] = run["window"]["spans"][:2]
    assert read(run) is None


# -- the program against the reference --------------------------------------


def _window(tmp, patches=()) -> tuple:
    """One run of ``windows/steady.py`` on the tiny cell; ``patches``
    ((module, name, replacement), ...) plant faults in the program."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "benchmark", lambda: BENCH)
        for module, name, replacement in patches:
            patch.setattr(module, name, replacement)
        ctx = worker.context(CELL, seed=SEED, seconds=0.5, trace=False,
                             report_path=str(tmp / "report.jsonl"),
                             workdir=str(tmp), rehearse=True,
                             in_process=True)
        window = harness.load_module("windows", ctx.traffic["window"])
        assert window.run(ctx) == 0
    return ctx, bench_run.gather(ctx.report.read(), BENCH, CELL,
                                 started_wall=0.0, seconds=0.5, trace=False)


@pytest.fixture(scope="module")
def through_the_window(tmp_path_factory):
    ctx, run = _window(tmp_path_factory.mktemp("sala"))
    sp = ctx.cfg["sparse_config"]
    assert ctx.traffic["seq_len"] >= sp["dense_len"]
    assert sp["topk"] * sp["block_size"] < ctx.traffic["seq_len"]
    assert set(ctx.cfg["mixer_types"][:ctx.cfg["num_hidden_layers"]]) == {
        "minicpm4", "lightning-attn"}
    return ctx, run


def test_the_program_is_correct_against_its_reference(through_the_window):
    """Float32 at the tiny sizes: the same mathematics, so the loss to 1e-5
    and every leaf's first gradient to 1e-4 (reassociation of the chunked
    and blocked sums is ~1e-7); and the sparse layer's counter reached the
    loop's spans."""
    _, run = through_the_window
    line = bench_run.conclude(run, LIMITS, lenient=True)
    assert run["window"]["steps"] > 0
    assert line["correct"] is True, line["compared"]
    compared = run["compared"]["compared"]
    assert compared["loss_gap"]["value"] < 1e-5
    assert compared["grad_gap"]["value"] < 1e-4
    counted = [s["attrs"] for s in run["window"]["spans"]
               if s["name"] == "train_window"
               and "block_sparse_tiles_visited_share_mean" in s["attrs"]]
    assert counted
    assert all(0.0 < a["block_sparse_tiles_visited_share_mean"] <= 1.0
               for a in counted)


def _batches(cfg, traffic, steps=3):
    rows = reference.Rows(SEED, cfg["vocab_size"], traffic["rows"],
                          traffic["seq_len"], traffic["shuffle"])
    return [rows.batch(k, traffic["global_batch"]) for k in range(steps)]


def _with_fault(plain, fault: str):
    return types.SimpleNamespace(
        leaves=plain.leaves, layer_prefix=plain.layer_prefix,
        layer_kind=plain.layer_kind,
        block=lambda x, p, cfg, layer, mode: plain.block(x, p, cfg, layer,
                                                         mode, fault))


@pytest.mark.parametrize("fault", ["decay", "first_blocks", "alpha"])
def test_a_fault_planted_in_the_reference_is_not_correct(fault,
                                                         through_the_window):
    """The program reads the reference to 1e-5 (above), so a reference with
    the fault planted stands in for a program with it: against the sound
    reference it must fail at least one limit."""
    ctx, _ = through_the_window
    plain, cfg = ctx.model_reference, ctx.cfg
    batches = _batches(cfg, ctx.traffic)
    truth = reference.follow(plain, SEED, cfg, batches)
    compared = check.compare(reference.follow(_with_fault(plain, fault), SEED,
                                              cfg, batches), truth, 0)
    limits = {name: LIMITS[name] for name in compared}  # no window here
    assert not check.verdict(compared, limits)[0], compared


def _program_faults(fault: str) -> list:
    from dlrover_tpu.models import minicpm_sala
    from dlrover_tpu.ops import block_sparse_attention

    def first_blocks(r, sp, blocks):
        """`_block_scores` with the fault: the lower block scores higher."""
        return jnp.broadcast_to(-jnp.arange(blocks, dtype=jnp.float32),
                                r.shape[:-1] + (blocks,))

    return {
        "decay": [(minicpm_sala, "lightning_rates",
                   lambda heads, layer, layers: np.zeros(heads, np.float32))],
        "first_blocks": [(block_sparse_attention, "_block_scores",
                          first_blocks)],
        "alpha": [(minicpm_sala, "residual_scale", lambda cfg: 1.0)],
    }[fault]


@pytest.mark.parametrize("fault", ["decay", "first_blocks", "alpha"])
def test_a_fault_planted_in_the_program_is_not_correct(fault, tmp_path,
                                                       through_the_window):
    """The program's own decay, block choice and residual scale broken (a
    monkeypatch of the function it calls), run through the window as any
    run goes: the line must say not ``correct``, by a compared number and
    not by a crash."""
    _, sound = through_the_window
    _, run = _window(tmp_path, _program_faults(fault))
    line = bench_run.conclude(run, LIMITS, lenient=True)
    assert run["window"]["steps"] > 0
    assert line["correct"] is False, line["compared"]
    over = {name for name, (value, limit) in line["compared"].items()
            if not value <= limit}
    assert over and over <= {"loss_gap", "grad_gap", "grad_gap_whole",
                             "change_gap"}
    assert bench_run.conclude(sound, LIMITS, lenient=True)["correct"] is True


def test_the_residual_scale_is_the_published_depths():
    from dlrover_tpu.models.minicpm_sala import SalaConfig, residual_scale

    assert residual_scale(SalaConfig()) == pytest.approx(1.4 / math.sqrt(32))
