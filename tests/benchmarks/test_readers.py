"""The readers PR 28 added, on hand-made records: the ``train_window`` span
reduction and the per-kernel readers by kernel name. All plain Python on
dicts; nothing here starts JAX."""

from __future__ import annotations

import glob
import os

import bench_cells
import pytest
from bench_cells import BENCH

from benchmarks import flops, harness, kernel_needs, span_reduce

KIND = "TPU v5 lite"
PEAK = flops.peaks(KIND)
NEW_SPAN_METRICS = ("step.device_step_ms", "loop.host_overhead_ms",
                    "loop.steps_in_flight", "input.fetch_share")
NEW_TRACE_METRICS = ("kernels.flash_attn_fwd_roofline",
                     "kernels.flash_attn_bwd_roofline",
                     "kernels.rms_norm_share")


def _cell(name: str = "internlm2_1p8b.steady") -> dict:
    entry, cfg, traffic = harness.cell(BENCH, name)
    return {"workload": entry, "cfg": cfg, "traffic": traffic,
            "model": harness.model_class(cfg), "device": {"kind": KIND}}


def _span(start, end, **attrs):
    return {"name": "train_window", "start": start, "end": end,
            "duration_s": end - start, "attrs": attrs}


def _window_attrs(steps, wall, completed, in_flight, fetch=0.0, shard=0.0,
                  report=0.0, other=0.0):
    return dict(steps=steps, first_step=1, wall_s=wall, fetch_s=fetch,
                shard_s=shard, dispatch_s=wall - fetch - shard - report
                - other, save_s=0.0, report_s=report, other_s=other,
                completed=completed, in_flight_mean=in_flight,
                in_flight_max=32)


def _run_with_spans() -> dict:
    run = _cell()
    run["window"] = {
        "opened_wall": 1000.0, "seconds": 21.0,
        "spans": [
            # the warm-up's remainder: before the window, not counted
            _span(990.0, 990.4, **_window_attrs(1, 0.4, 0, 1.0)),
            _span(1000.1, 1000.6, **_window_attrs(
                10, 0.5, 0, 5.5, fetch=0.002, shard=0.01, report=0.006,
                other=0.004)),
            _span(1000.6, 1004.1, **_window_attrs(
                10, 3.5, 10, 31.0, fetch=0.003, shard=0.01, report=0.006,
                other=0.004)),
            _span(1004.1, 1006.2, **_window_attrs(
                5, 2.1, 6, 32.0, fetch=1.001, shard=0.005, other=0.002)),
            {"name": "host_sync", "start": 1006.2, "end": 1017.0,
             "duration_s": 10.8, "attrs": {"step": 28}},
            # one that ends after the window closed: not wholly inside
            _span(1020.0, 1021.5, **_window_attrs(3, 1.5, 3, 2.0)),
        ],
        # the feed's hooks took 1.0 s of the third span's fetch (a traced
        # run starts and stops the profiler there)
        "calls": [{"entered": 5.0, "fetch_from": 5.0, "fetch_to": 5.001},
                  {"entered": 9.0, "fetch_from": 10.0, "fetch_to": 10.001}],
    }
    return run


def test_train_windows_takes_only_spans_wholly_inside_the_window():
    windows = span_reduce.train_windows(_run_with_spans())
    assert [w["steps"] for w in windows] == [10, 10, 5]
    assert span_reduce.total(windows, "steps") == 25
    assert span_reduce.total(windows, "wall_s", "completed") == (
        pytest.approx(6.1 + 16))
    assert span_reduce.ratio(windows, ("wall_s",), ("completed",)) == (
        pytest.approx(6.1 / 16))
    assert span_reduce.ratio([], ("wall_s",), ("completed",)) is None
    assert span_reduce.ratio(windows[:1], ("wall_s",),
                             ("completed",)) is None      # none completed
    assert span_reduce.feed_hook_seconds(_run_with_spans()) == (
        pytest.approx(1.0))
    assert span_reduce.train_windows({}) == []


@pytest.mark.parametrize("name,expected", [
    ("step.device_step_ms", 1000.0 * 6.1 / 16),
    ("loop.host_overhead_ms",
     1000.0 * (0.02 + 0.02 + 0.007) / 25),
    ("loop.steps_in_flight", (5.5 * 10 + 31.0 * 10 + 32.0 * 5) / 25),
    ("input.fetch_share", 100.0 * (1.006 - 1.0) / 6.1),
])
def test_span_readers_on_a_hand_made_window(name, expected):
    read = harness.load_module("metrics", name).read
    assert read(_run_with_spans()) == pytest.approx(expected)


@pytest.mark.parametrize("name", NEW_SPAN_METRICS)
def test_span_readers_give_nothing_without_a_train_window_span(name):
    """The parent of the PR that added the span emits none: the reader
    returns None and does not raise, and the line leaves the metric out."""
    read = harness.load_module("metrics", name).read
    run = _run_with_spans()
    run["window"]["spans"] = [s for s in run["window"]["spans"]
                              if s["name"] != "train_window"]
    assert read(run) is None
    run["window"]["spans"] = []
    assert read(run) is None


@pytest.mark.parametrize("layer", [
    {"heads": 16, "kv_heads": 8, "head_dim": 128, "window": None},
    {"heads": 32, "kv_heads": 8, "head_dim": 128, "window": None},
    {"heads": 32, "kv_heads": 4, "head_dim": 128, "window": None},
    {"heads": 64, "kv_heads": 8, "head_dim": 128, "window": 512},
    {"heads": 8, "kv_heads": 2, "head_dim": 32, "window": None},
], ids=["internlm2_1p8b", "mistral_7b", "wide_head_on_hidden_2048",
        "windowed", "twokind_local"])
def test_kernel_needs_forward_plus_backward_is_the_accepted_count(layer):
    """The count PRs 26-30 were measured against, written out on layer
    entries written out (the two accepted configurations', a head of 128 on
    32 heads whatever the hidden size, a windowed one, the tests' own): one
    layer's forward plus backward is 6 matmuls of 2 b heads d over the
    scored pairs, s^2 / 2 under the causal mask; every operand read and
    every result written once in bf16 (forward q, k, v -> o; backward q, k,
    v, o, do -> dq, dk, dv) plus the fp32 row statistics, once each way."""
    heads, kv_heads, d = layer["heads"], layer["kv_heads"], layer["head_dim"]
    for batch, seq in ((2, 2048), (1, 4096), (8, 512)):
        fwd = kernel_needs.flash_attention_fwd(layer, batch, seq)
        bwd = kernel_needs.flash_attention_bwd(layer, batch, seq)
        q_bytes = batch * heads * seq * d * 2
        kv_bytes = batch * kv_heads * seq * d * 2
        stats = batch * heads * seq * 4
        w = layer["window"]
        pairs = (seq * seq / 2.0 if w is None or w >= seq
                 else w * seq - w * w / 2.0)
        assert fwd["flops"] + bwd["flops"] == (
            6.0 * 2.0 * batch * heads * pairs * d)
        assert fwd["bytes"] + bwd["bytes"] == float(
            (q_bytes + 2 * kv_bytes + q_bytes + stats)
            + (3 * q_bytes + 2 * kv_bytes + stats + q_bytes + 2 * kv_bytes))
        assert bwd["flops"] == 2 * fwd["flops"]
    assert flops.roofline_seconds(
        kernel_needs.flash_attention_fwd(layer, 2, 2048), KIND)[1] == "compute"


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_attention_layers_is_the_classs_to_say(config, lookup):
    """Of every configuration, the tests' own among them: one entry a layer
    with the four keys the needs are counted from. That a head is
    ``hidden // heads`` wide and every layer causal to the start is Llama's
    shape, and held of the configurations whose class is ``llama`` only."""
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    cfg = harness.load_json(harness.ROOT, entry["file"])
    layers = harness.model_class(cfg).attention_layers(cfg)
    assert len(layers) == cfg["num_hidden_layers"]
    for layer in layers:
        assert set(layer) == {"heads", "kv_heads", "head_dim", "window"}
        assert layer["heads"] % layer["kv_heads"] == 0
        assert kernel_needs.flash_attention_fwd(layer, 2, 2048)["flops"] > 0
    if cfg["model"] == "llama":
        heads = cfg["num_attention_heads"]
        assert layers[0] == {"heads": heads,
                             "kv_heads": cfg["num_key_value_heads"],
                             "head_dim": cfg["hidden_size"] // heads,
                             "window": None}
    else:       # the tests' own: a head width that hidden does not give
        tied = cfg["hidden_size"] // layers[0]["heads"]
        assert layers[0]["head_dim"] != tied


def test_every_for_every_test_runs_over_the_tests_own_class_too():
    """The guard: ``bench_cells.BENCH``, which has the tests' own class and
    cell entered, is the only benchmark a test file here can get: none asks
    the harness for the accepted one or takes it from ``bench_cells``. So
    whatever a test runs over the configurations or the cells, or holds of
    them all, it runs over and holds of a class that is not Llama's shape."""
    assert bench_cells.OWN_CONFIG in BENCH["configs"]
    assert bench_cells.OWN_CELL in BENCH["workloads"]
    assert len(BENCH["configs"]) >= 3 and len(BENCH["workloads"]) >= 3
    files = glob.glob(os.path.join(bench_cells.HERE, "test_*.py"))
    assert len(files) >= 3
    for path in files:
        with open(path) as f:
            text = f.read()
        assert "harness.benchmark" + "()" not in text, path
        assert "ACC" + "EPTED" not in text, path


# -- device events by name ---------------------------------------------------

def _kernel_event(name):
    """A device event's name as the v5e trace has it (read by hand, PR 28):
    the HLO instruction's text without ``metadata=``."""
    return (f"%{name} = bf16[2,16,2048,128]{{3,2,1,0:T(8,128)(2,1)S(1)}} "
            "custom-call(bf16[2,16,2048,128]{3,2,1,0:T(8,128)(2,1)} %a), "
            'custom_call_target="tpu_custom_call", '
            "frontend_attributes={kernel_metadata={}}")


def _traced(run: dict) -> dict:
    """A trace in which each attention kernel runs at exactly half its
    roofline, three launches of each under two instance names, and the
    norms take 2 % of the device's time."""
    seq = run["traffic"]["seq_len"]
    layer = run["model"].attention_layers(run["cfg"])[0]

    def least(needs):
        return flops.roofline_seconds(needs(layer, 2, seq), KIND)[0]

    fwd = least(kernel_needs.flash_attention_fwd)
    bwd = least(kernel_needs.flash_attention_bwd)
    by_name, counts = {}, {}

    def add(event, seconds, launches):
        by_name[event], counts[event] = seconds, launches

    add(_kernel_event("flash_attn_fwd.1"), 2 * fwd * 2, 2)
    add(_kernel_event("flash_attn_fwd.2.remat"), 2 * fwd, 1)
    for n, launches in ((0, 2), (1, 1)):
        # dQ takes 40 % and dK/dV 60 % of the pair's time
        add(_kernel_event(f"flash_attn_dq.{n}"),
            0.4 * 2 * bwd * launches, launches)
        add(_kernel_event(f"flash_attn_dkv.{n}"),
            0.6 * 2 * bwd * launches, launches)
    # a kernel whose name only starts like one of ours is not ours
    add(_kernel_event("flash_attn_fwdx.1"), 0.001, 1)
    rest = sum(by_name.values())
    add(_kernel_event("rms_norm_fwd.7"), 0.005 * rest / 0.98, 5)
    add(_kernel_event("rms_norm_bwd.7"), 0.015 * rest / 0.98, 5)
    return {"by_name": by_name, "count_by_name": counts}


@pytest.mark.parametrize("name,expected", [
    ("kernels.flash_attn_fwd_roofline", 50.0),
    ("kernels.flash_attn_bwd_roofline", 50.0),
    ("kernels.rms_norm_share", 2.0)])
def test_kernel_readers_find_the_kernels_by_name(name, expected):
    run = _cell()
    run["traced"] = _traced(run)
    read = harness.load_module("metrics", name).read
    assert read(run) == pytest.approx(expected)


def test_fwd_and_bwd_weighted_by_their_needs_give_the_old_whole():
    """``kernels.flash_attn_roofline`` (PRs 26-30, retired in PR 31) held
    all three kernels against forward + backward together; the two shares
    that stay, weighted by their parts of that least time, are that
    number, so its history in the ledger can still be read against them."""
    run = _cell()
    run["traced"] = _traced(run)
    fwd = harness.load_module(
        "metrics", "kernels.flash_attn_fwd_roofline").read(run)
    bwd = harness.load_module(
        "metrics", "kernels.flash_attn_bwd_roofline").read(run)
    seq = run["traffic"]["seq_len"]
    layer = run["model"].attention_layers(run["cfg"])[0]
    part = {k: flops.roofline_seconds(needs(layer, 2, seq), KIND)[0]
            for k, needs in (("fwd", kernel_needs.flash_attention_fwd),
                             ("bwd", kernel_needs.flash_attention_bwd))}
    whole = 100.0 * (part["fwd"] + part["bwd"]) / (
        100.0 * part["fwd"] / fwd + 100.0 * part["bwd"] / bwd)
    assert whole == pytest.approx(50.0)


@pytest.mark.parametrize("name", NEW_TRACE_METRICS)
def test_trace_readers_give_nothing_without_a_named_event(name):
    """The parent of the PR that named the kernels names none: nothing to
    read is None, not an error and not a zero."""
    read = harness.load_module("metrics", name).read
    run = _cell()
    assert read(run) is None                       # an untraced run
    run["traced"] = {}
    assert read(run) is None
    old = ("%custom-call.5 = bf16[2,16,2048,128]{3,2,1,0} custom-call("
           "bf16[2,16,2048,128]{3,2,1,0} %a), "
           'custom_call_target="tpu_custom_call"')
    run["traced"] = {"by_name": {old: 1.0, "%fusion.1 = f32[] fusion()": 2.0},
                     "count_by_name": {old: 3, "%fusion.1 = f32[] fusion()": 1}}
    assert read(run) is None


def test_every_new_metric_is_in_benchmark_json_with_its_reader():
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_SPAN_METRICS + NEW_TRACE_METRICS:
        assert name in listed, name
        assert listed[name]["moves"] == "tokens_per_s"
        # a kernel's metric lists the cells whose program launches it: every
        # cell of class ``llama`` does; a cell on other kernels stays out
        # (the tests' own is entered in ``workloads`` and in neither list)
        if name.startswith("kernels.flash_attn"):
            named = listed[name]["workloads"]
            cells = [w["name"] for w in BENCH["workloads"]]
            assert set(named) <= set(cells)
            assert {c for c in cells if harness.cell(BENCH, c)[1]["model"]
                    == "llama"} <= set(named)
            assert bench_cells.CELL in cells
            assert bench_cells.CELL not in named
        else:
            assert "workloads" not in listed[name]
        assert listed[name]["source"] == (
            "host_clock" if name in NEW_SPAN_METRICS else "device_trace")
