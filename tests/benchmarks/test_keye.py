"""Model class ``keye`` (PR 37): the program's ``Keye`` against the class's
plain reference through the window, each planted fault failing ``correct``
(in the reference and in the program), the share of the experts tied to the
uncut layer, and the class's counts against brute force. All on the CPU at
``tiny()``'s sizes, where every mechanism is alive: fewer keys selected than
the sequence has, more experts than are held, a head width that is not
``hidden // heads``.

The class's cell is entered in ``BENCHMARK.json``, so the for-every-cell
tests of ``test_benchmark.py`` and ``test_readers.py`` run over it too; what
its limits file can and cannot tell on the chip is PERF.md section 2's.
"""

from __future__ import annotations

import os
import types

import jax.numpy as jnp
import numpy as np
import pytest
from bench_cells import BENCH
from bench_cells import TINY_LIMITS as LIMITS

from benchmarks import check, flops, harness, reference, worker
from benchmarks import run as bench_run
from benchmarks.reference import linear, rms_norm

CELL = "keye_vl2_30b_a3b_share8.steady_16k"
SEED = 2_147_483_783
KIND = "TPU v5 lite"


def _tiny() -> tuple:
    _, cfg, traffic = harness.cell(BENCH, CELL)
    return harness.model_class(cfg).tiny(cfg, traffic)


def _window(tmp, patches=()) -> tuple:
    """One run of ``windows/steady.py`` on the tiny cell, as a run goes;
    ``patches`` ((module, name, replacement), ...) plant faults in the
    program for the length of the run."""
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


def _batches(cfg, traffic, steps=3):
    rows = reference.Rows(SEED, cfg["vocab_size"], traffic["rows"],
                          traffic["seq_len"], traffic["shuffle"])
    return [rows.batch(k, traffic["global_batch"]) for k in range(steps)]


def _with_block(plain, **fault):
    """The class's reference with a fault planted in its block: ``dense``
    (selection off), ``keep_extra`` False (the KL term dropped), and what
    ``experts`` takes (``renormalise`` False, ``capacity``)."""
    dense = fault.pop("dense", False)
    keep_extra = fault.pop("keep_extra", True)

    def block(x, p, cfg, layer, mode):
        eps = cfg["rms_norm_eps"]
        mixed, gap = plain.attention(
            rms_norm(x, p["attn_norm/weight"], eps), p, cfg, mode, dense)
        x = x + linear(mixed, p["attn/o_proj/kernel"], mode)
        x = x + plain.experts(rms_norm(x, p["mlp_norm/weight"], eps), p, cfg,
                              mode, **fault)
        return (x, cfg["index_loss_weight"] * gap) if keep_extra else x

    return types.SimpleNamespace(
        leaves=plain.leaves, layer_prefix=plain.layer_prefix,
        layer_kind=plain.layer_kind, block=block)


@pytest.fixture(scope="module")
def through_the_window(tmp_path_factory):
    ctx, run = _window(tmp_path_factory.mktemp("keye"))
    assert ctx.cfg["sa_config"]["topk"] < ctx.traffic["seq_len"]
    assert ctx.cfg["num_local_experts"] < ctx.cfg["num_experts"]
    assert ctx.cfg["head_dim"] != (ctx.cfg["hidden_size"]
                                   // ctx.cfg["num_attention_heads"])
    return ctx, run


def test_the_program_is_correct_against_its_reference(through_the_window):
    ctx, run = through_the_window
    line = bench_run.conclude(run, LIMITS, lenient=True)
    assert run["window"]["steps"] > 0
    assert line["correct"] is True, line["compared"]
    # float32 compute at these sizes: the two are the same mathematics
    compared = run["compared"]["compared"]
    assert compared["loss_gap"]["value"] < 1e-5
    assert compared["grad_gap"]["value"] < 1e-4
    # the loss the program reports is the whole objective, KL term and all
    plain = ctx.model_reference
    alone = reference.follow(_with_block(plain, keep_extra=False), SEED,
                             ctx.cfg, _batches(ctx.cfg, ctx.traffic, 1))
    extra = run["compared"]["reference_losses"][0] - alone["losses"][0]
    assert extra > 10 * LIMITS["loss_gap"] * alone["losses"][0]
    assert run["compared"]["program_losses"][0] == pytest.approx(
        alone["losses"][0] + extra, rel=1e-5)
    # the expert layer's counters reached the loop's span from the step's
    # own metrics
    counted = [s["attrs"] for s in run["window"]["spans"]
               if s["name"] == "train_window"
               and "moe_load_max_over_mean_mean" in s["attrs"]]
    assert counted
    held = ctx.cfg["num_local_experts"]
    assert all(1.0 <= a["moe_load_max_over_mean_mean"] <= held
               for a in counted)
    # held rows: of 8 experts 4 are held, so about half of the assignments
    assert all(0.2 < a["moe_held_rows_share_mean"] < 0.8
               and a["moe_held_rows_share_steps"]
               == a["moe_load_max_over_mean_steps"] for a in counted)


FAULTS = {
    "selection_off": dict(dense=True),
    "kl_dropped": dict(keep_extra=False),
    "renormalisation_dropped": dict(renormalise=False),
    "over_capacity_dropped": dict(capacity=12),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_is_not_correct(fault, through_the_window):
    """The program reads the reference to 1e-5 (above), so a reference with
    the fault planted stands in for a program with it: against the sound
    reference it must fail at least one limit."""
    ctx, _ = through_the_window
    plain, cfg = ctx.model_reference, ctx.cfg
    batches = _batches(cfg, ctx.traffic)
    truth = reference.follow(plain, SEED, cfg, batches)
    broken = reference.follow(_with_block(plain, **FAULTS[fault]), SEED, cfg,
                              batches)
    compared = check.compare(broken, truth, 0)
    limits = {name: LIMITS[name] for name in compared}  # no window here
    assert not check.verdict(compared, limits)[0], compared
    sound = check.compare(reference.follow(_with_block(plain), SEED, cfg,
                                           batches), truth, 0)
    assert check.verdict(sound, limits)[0], sound
    if fault == "kl_dropped":
        assert compared["grad_gap"]["where"].startswith(
            "layer_") and "indexer" in compared["grad_gap"]["where"]
    if fault == "over_capacity_dropped":
        # the capacity bites: some held expert has more assignments
        z = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 64, cfg["hidden_size"])), jnp.float32)
        p = {"moe/router": reference.init_params(SEED, plain.leaves(cfg))[
            "layer_0/moe/router"]}
        _, chosen = plain.route(z, p["moe/router"], cfg, "f32")
        assert max(int(jnp.sum(chosen == e)) for e in range(
            cfg["first_expert"], cfg["first_expert"]
            + cfg["num_local_experts"])) > 12


def _selection_off(scores, topk):
    """`ops/sparse_attention.py:select_keys` with the fault: every causal
    key selected."""
    seq = scores.shape[-1]
    return jnp.broadcast_to(jnp.tril(jnp.ones((seq, seq), bool)),
                            scores.shape)


def _dropping(capacity: int):
    """`parallel/moe.py:held_assignments` with the fault: a held expert
    keeps its first ``capacity`` assignments in the tokens' order and the
    rest are dropped (handed to no expert)."""
    from dlrover_tpu.parallel import moe

    sound = moe.held_assignments

    def held_assignments(experts, first_expert, experts_held):
        flat = experts.reshape(-1)
        mine = flat[:, None] == (first_expert
                                 + jnp.arange(experts_held))[None, :]
        place = jnp.sum(jnp.cumsum(mine, axis=0) * mine, axis=1)
        kept = jnp.where(place > capacity, -1, flat)
        return sound(kept.reshape(experts.shape), first_expert, experts_held)

    return held_assignments


def _program_faults() -> dict:
    from dlrover_tpu.ops import sparse_attention
    from dlrover_tpu.parallel import moe

    return {
        "selection_off": [(sparse_attention, "select_keys", _selection_off)],
        "over_capacity_dropped": [(moe, "held_assignments", _dropping(12))],
    }


@pytest.mark.parametrize("fault", ["selection_off", "over_capacity_dropped"])
def test_a_fault_planted_in_the_program_is_not_correct(fault, tmp_path,
                                                       through_the_window):
    """The program's OWN selection and no-drop code broken (a monkeypatch of
    the function it calls), run through the window as any run goes: the
    line must say not ``correct``, by a compared number and not by a
    crash."""
    _, sound = through_the_window
    _, run = _window(tmp_path, _program_faults()[fault])
    line = bench_run.conclude(run, LIMITS, lenient=True)
    assert run["window"]["steps"] > 0
    assert line["correct"] is False, line["compared"]
    over = {name for name, (value, limit) in line["compared"].items()
            if not value <= limit}
    assert over and over <= {"loss_gap", "grad_gap", "grad_gap_whole",
                             "change_gap"}
    # the same seed, sound: every one of those numbers inside its limit
    assert bench_run.conclude(sound, LIMITS, lenient=True)["correct"] is True


def test_the_shares_add_up_to_the_uncut_layer():
    """One test ties the share to the model: the experts' outputs of the
    eight shares (one expert each here, ``first_expert`` 0 ... 7) add up to
    the reference's uncut eight-expert layer, to float32 rounding; and the
    program's layer, told the same share, gives the reference's share."""
    from dlrover_tpu.parallel.moe import HeldExpertsConfig, HeldExpertsLayer

    cfg, _ = _tiny()
    plain = harness.model_reference(cfg)
    whole = dict(cfg, num_local_experts=cfg["num_experts"], first_expert=0)
    full = {name[len("layer_0/"):]: leaf for name, leaf in
            reference.init_params(SEED, plain.leaves(whole)).items()
            if name.startswith("layer_0/moe/")}
    z = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, 48, cfg["hidden_size"])), jnp.float32)
    uncut = plain.experts(z, full, whole, "f32")
    total = jnp.zeros_like(uncut)
    for first in range(cfg["num_experts"]):
        share = dict(cfg, num_local_experts=1, first_expert=first)
        held = {name: (leaf if name.endswith("router")
                       else leaf[first:first + 1])
                for name, leaf in full.items()}
        part = plain.experts(z, held, share, "f32")
        total = total + part
        layer = HeldExpertsLayer(HeldExpertsConfig(
            num_experts=cfg["num_experts"], experts_held=1,
            first_expert=first, top_k=cfg["num_experts_per_tok"],
            hidden_size=cfg["hidden_size"],
            expert_intermediate=cfg["moe_intermediate_size"]))
        params = {name.split("/")[1]: leaf for name, leaf in held.items()}
        mine = layer.apply({"params": params}, z, mutable=["counters"])[0]
        np.testing.assert_allclose(mine, part, atol=2e-6)
    assert float(jnp.max(jnp.abs(uncut))) > 1e-3
    np.testing.assert_allclose(total, uncut, atol=1e-6)


@pytest.mark.parametrize("seq", [64, 640])
def test_the_classs_counts_against_a_count_of_pairs(seq):
    """``flops_per_token`` and the kernels' ``needs`` against brute force:
    the selected pairs counted one by one, the parameters multiplied out."""
    _, cfg, _ = harness.cell(BENCH, CELL)
    model = harness.model_class(cfg)
    sa = cfg["sa_config"]
    cfg = dict(cfg, sa_config=dict(sa, topk=48 if seq == 64 else 512))
    topk = cfg["sa_config"]["topk"]
    exact = sum(min(t + 1, topk) for t in range(seq))
    counted = model.selected_pairs(seq, topk)
    # the accepted convention leaves out half of the diagonal's cells
    assert exact - counted == min(topk, seq) / 2
    assert model.selected_pairs(seq, 10 * seq) == seq * seq / 2
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    fwd, bwd = (model.sparse_attn_fwd(cfg, 1, seq),
                model.sparse_attn_bwd(cfg, 1, seq))
    assert fwd["flops"] == 2 * 2.0 * heads * d * counted
    assert bwd["flops"] == 2 * fwd["flops"]
    q_bytes = heads * seq * d * 2
    kv_bytes = cfg["num_key_value_heads"] * seq * d * 2
    assert fwd["bytes"] == 2 * q_bytes + 2 * kv_bytes + heads * seq * 4
    # a token's matmuls by hand: attention, indexer, router, 8 of its 128
    # experts' share held here (1 of 8 in expectation), the head
    h, index = cfg["hidden_size"], 16 * 64
    matmul = (2 * h * heads * d + 2 * h * 4 * d + h * index + h * 64
              + h * 16 + h * 128 + (8 * 16 / 128) * 3 * h * 768)
    layers = cfg["num_hidden_layers"]
    pairs = counted / seq
    per_token = (6.0 * (layers * matmul + cfg["vocab_size"] * h)
                 + layers * (12.0 * heads * d * pairs
                             + 2.0 * index * seq / 2 + 4.0 * index * pairs))
    assert model.flops_per_token(cfg, seq) == pytest.approx(per_token)
    assert model.param_count(cfg) == sum(
        int(np.prod(leaf.shape))
        for leaf in harness.model_reference(cfg).leaves(cfg).values())


def test_the_cells_sizes_are_the_issues():
    """853 M parameters on the chip, 2.44 GFLOP a token at 16,384, selected
    pairs 23.4 % of the causal ones; the program's own count agrees with the
    benchmark's (two countings of one model)."""
    from dlrover_tpu.models.keye import KeyeConfig

    _, cfg, traffic = harness.cell(BENCH, CELL)
    model = harness.model_class(cfg)
    assert model.param_count(cfg) == pytest.approx(853e6, rel=2e-3)
    per_token = model.flops_per_token(cfg, traffic["seq_len"])
    assert per_token == pytest.approx(2.44e9, rel=1e-2)
    assert model.selected_pairs(16384, 2048) / (16384 ** 2 / 2) == (
        pytest.approx(0.234, abs=1e-3))
    for needs in (model.sparse_attn_fwd, model.sparse_attn_bwd):
        assert flops.roofline_seconds(needs(cfg, 1, 16384),
                                      KIND)[1] == "compute"
    sa = cfg["sa_config"]
    mine = KeyeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        attn_head_dim=cfg["head_dim"], num_experts=cfg["num_experts"],
        experts_held=cfg["num_local_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_intermediate=cfg["moe_intermediate_size"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"])
    assert mine.param_count() == model.param_count(cfg)
    assert mine.flops_per_token(16384) == pytest.approx(per_token)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import os

    path = os.path.join(harness.MODELS, "keye_reference.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [a.name for node in ast.walk(tree)
             if isinstance(node, ast.Import) for a in node.names]
    names += [node.module for node in ast.walk(tree)
              if isinstance(node, ast.ImportFrom)]
    assert names and all(not n.startswith("dlrover_tpu") for n in names)
    assert set(n.split(".")[0] for n in names) <= {"__future__", "jax",
                                                   "benchmarks"}


# -- the new readers on hand-made records ------------------------------------


def _event(name):
    return (f"%{name} = bf16[1,32,16384,128]{{3,2,1,0}} custom-call("
            "bf16[1,32,16384,128]{3,2,1,0} %a), "
            'custom_call_target="tpu_custom_call"')


def _cell_run() -> dict:
    entry, cfg, traffic = harness.cell(BENCH, CELL)
    return {"workload": entry, "cfg": cfg, "traffic": traffic,
            "model": harness.model_class(cfg), "device": {"kind": KIND}}


@pytest.mark.parametrize("name,kernels,needs", [
    ("kernels.sparse_attn_fwd_roofline", ("sparse_attn_fwd",),
     "sparse_attn_fwd"),
    ("kernels.sparse_attn_bwd_roofline",
     ("sparse_attn_dq", "sparse_attn_dkv"), "sparse_attn_bwd")])
def test_sparse_kernel_readers_hold_the_time_against_the_selected_pairs(
        name, kernels, needs):
    run = _cell_run()
    least = flops.roofline_seconds(
        getattr(run["model"], needs)(run["cfg"], 1, 16384), KIND)[0]
    by_name, counts = {}, {}
    launches = 16 * 3          # forward and recomputed forward, 8 layers
    for kernel in kernels:
        # every launch at a fifth of the roofline of the selected pairs
        by_name[_event(kernel + ".1")] = launches * least / 0.2 / len(kernels)
        counts[_event(kernel + ".1")] = launches
    by_name[_event("flash_attn_fwd.1")] = 1.0       # not ours
    counts[_event("flash_attn_fwd.1")] = 3
    run["traced"] = {"by_name": by_name, "count_by_name": counts}
    read = harness.load_module("metrics", name).read
    assert read(run) == pytest.approx(20.0)
    run["traced"] = {"by_name": {_event("flash_attn_fwd.1"): 1.0},
                     "count_by_name": {_event("flash_attn_fwd.1"): 3}}
    assert read(run) is None            # the parent's trace: none of ours
    assert read(dict(run, traced={})) is None
    # a class without the count: nothing, not an error
    llama = harness.model_class({"model": "llama"})
    run["traced"] = {"by_name": by_name, "count_by_name": counts}
    assert read(dict(run, model=llama)) is None


@pytest.mark.parametrize("scope", ["indexer", "sparse_attn", "moe"])
def test_scope_readers_of_the_new_scopes(scope):
    read = harness.load_module("metrics", f"step.{scope}_share").read
    traced = {"by_name": {"a": 6.0, "b": 2.0},
              "by_scope": {scope: 2.0, "unscoped": 0.5}}
    assert read({"traced": traced}) == pytest.approx(25.0)
    assert read({"traced": {"by_name": {"a": 1.0}, "by_scope": {}}}) is None
    assert read({}) is None


@pytest.mark.parametrize("name,attr,scale", [
    ("moe.load_max_over_mean", "moe_load_max_over_mean", 1.0),
    ("moe.held_rows_share", "moe_held_rows_share", 100.0)])
def test_a_models_counter_is_read_off_the_train_window_spans(name, attr,
                                                             scale):
    read = harness.load_module("metrics", name).read

    def span(start, end, mean=None, steps=None):
        counted = {} if mean is None else {attr + "_mean": mean,
                                           attr + "_steps": steps}
        return {"name": "train_window", "start": start, "end": end,
                "duration_s": end - start, "attrs": dict(steps=10, **counted)}

    run = {"window": {"opened_wall": 100.0, "seconds": 50.0, "spans": [
        span(90.0, 99.0, 9.0, 5),       # before the window
        span(101.0, 110.0),             # no step seen done yet
        span(110.0, 120.0, 1.5, 6),
        span(120.0, 130.0, 1.1, 2)]}}
    assert read(run) == pytest.approx(scale * (1.5 * 6 + 1.1 * 2) / 8)
    run["window"]["spans"] = run["window"]["spans"][:2]
    assert read(run) is None            # the parent emits no such attr
    assert read({}) is None


def test_the_cell_is_entered_as_the_issue_names_it():
    """The entries ISSUE 37 names, at the end of their lists, each new metric
    listing the new cell only and the flash metrics leaving it out; the
    configuration's file against its entry and the published sizes; the limits
    file saying which reading each limit lies under."""
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) == 2          # after both accepted cells
    cell = BENCH["workloads"][2]
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    listed = {m["name"]: m for m in BENCH["per_layer"]
              if m.get("workloads") == [CELL]}
    assert set(listed) == {
        "step.indexer_share", "step.sparse_attn_share", "step.moe_share",
        "kernels.sparse_attn_fwd_roofline",
        "kernels.sparse_attn_bwd_roofline", "moe.load_max_over_mean",
        "moe.held_rows_share"}
    assert [m["name"] for m in BENCH["per_layer"][-7:]] == list(listed)
    accepted_layers = {m["layer"] for m in BENCH["per_layer"][:-7]}
    for name, metric in listed.items():
        assert metric["moves"] == "tokens_per_s"
        assert metric["layer"] in accepted_layers
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert callable(harness.load_module("metrics", name).read)
    for name in ("kernels.flash_attn_fwd_roofline",
                 "kernels.flash_attn_bwd_roofline"):
        flash = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL not in flash["workloads"]
    assert cell["traffic"] == "steady_b1_s16384"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers", "num_local_experts",
                                "vocab_size"]
    body = harness.load_json(harness.ROOT, entry["file"])
    assert body["published"] == {"num_hidden_layers": 48,
                                 "num_local_experts": 128,
                                 "vocab_size": 151936}
    assert body["vocab_size"] * 8 == 151936 and body["num_experts"] == 128
    # each limit above the largest sound reading the file records, and the
    # two a state left unchanged reads 1 on, under 1
    limits = harness.load_json(harness.HERE, "limits", CELL + ".json")
    lower = limits["set_from"]["lower"]
    for name in ("loss_gap", "grad_gap", "grad_gap_whole", "change_gap"):
        assert 1.8 * lower[name] < limits["limits"][name] < 1.0
        assert name in limits["set_from"]["upper"]
