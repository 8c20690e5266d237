"""Window kind ``steady``: the product's loop at full speed for a fixed time.

Also serves a traffic mix with ``checkpoint`` set: the saves are the loop's
own doing and their stall shows in the feed's gaps.

Set-up (not in the window): JAX start, the loop with its trainer, weights on
the device from the seed by ``restore_or_init``, then ``warmup_steps`` steps
taken one ``loop.run`` each through the same feed, whose losses, first
gradient and parameter change the comparison reads. The window: one
``loop.run`` with ``max_steps = 0`` that the feed ends after ``seconds``;
it closes when the returned state is ready on the device.
"""

from __future__ import annotations

import gc
import math
import os
import shutil
import time

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def build_loop(model_module, cfg: dict, traffic: dict, devices,
               client=None, checkpoint_dir: str = ""):
    """The product's loop for this configuration under this traffic mix;
    mesh, batch and checkpointing are the traffic file's data."""
    from dlrover_tpu.parallel.mesh import MeshSpec
    from dlrover_tpu.trainer.elastic_loop import (
        ElasticTrainLoop,
        TrainLoopConfig,
    )

    model, tx, loss_fn = model_module.build(cfg, traffic)
    ckpt = traffic.get("checkpoint") or {}
    return ElasticTrainLoop(
        model, tx, loss_fn,
        TrainLoopConfig(
            global_batch=traffic["global_batch"], seq_len=traffic["seq_len"],
            max_micro_per_replica=traffic["max_micro_per_replica"],
            mesh_spec=MeshSpec(**traffic["mesh"]),
            checkpoint_dir=checkpoint_dir,
            save_interval_steps=ckpt.get("save_interval_steps", 100),
            checkpoint_quantize_bits=ckpt.get("quantize_bits", 0)),
        master_client=client, devices=devices)


def make_feed(seed: int, cfg: dict, traffic: dict, annotate=None):
    """(feed, sampler): rows from the seed -> sampler -> loader -> feed."""
    import numpy as np

    from benchmarks import reference
    from benchmarks.feed import WindowFeed
    from dlrover_tpu.trainer.dataloader import ElasticDataLoader
    from dlrover_tpu.trainer.sampler import ElasticDistributedSampler

    rows = reference.token_rows(seed, cfg["vocab_size"], traffic["rows"],
                                traffic["seq_len"])
    sampler = ElasticDistributedSampler(len(rows), shuffle=traffic["shuffle"],
                                        seed=seed)

    def tokens_and_targets(picked):
        stacked = np.stack(picked)
        return stacked[:, :-1], stacked[:, 1:]

    loader = ElasticDataLoader(rows, traffic["global_batch"], sampler=sampler,
                               collate_fn=tokens_and_targets)
    return WindowFeed(loader, annotate=annotate), sampler


def warm_up(loop, model_module, change_fn, rng, state, feed, sampler,
            steps: int):
    """``steps`` steps, one ``loop.run`` each through the feed the window
    will use; returns the state and what the comparison reads of the
    program: each loss, the first gradient's norms, the change's norms."""
    program = {"losses": []}
    for k in range(steps):
        loop.config.max_steps = 1
        state, metrics = loop.run(state, feed, start_step=k, sampler=sampler)
        program["losses"].append(metrics["loss"])
        if k == 0:
            program["grad_norms"] = model_module.first_grad_norms(state)
    program["change_norms"] = model_module.change_norms(change_fn, rng, state)
    return state, program


def run(ctx) -> int:
    from dlrover_tpu.agent.elastic_agent import init_distributed

    init_distributed()

    import jax

    from benchmarks import check, reference, trace_reduce
    from benchmarks.feed import digest
    from dlrover_tpu import obs

    report, cfg, traffic, seed = ctx.report, ctx.cfg, ctx.traffic, ctx.seed
    compiles = {"in_window": 0, "window_open": False, "total": 0}

    def on_duration(event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            compiles["total"] += 1
            if compiles["window_open"]:
                compiles["in_window"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    devices = jax.devices()
    platform = jax.default_backend()
    report.emit(record="device", platform=platform,
                kind=devices[0].device_kind, count=len(devices),
                jax=jax.__version__)
    if platform != "tpu" and not ctx.rehearse:
        report.emit(record="refused", reason=f"no TPU: found {platform}")
        return 3
    if len(devices) < ctx.chips:
        report.emit(record="refused",
                    reason=f"{len(devices)} chips, cell needs {ctx.chips}")
        return 3

    batch, seq = traffic["global_batch"], traffic["seq_len"]
    ckpt = traffic.get("checkpoint")
    ckpt_dir = ""
    if ckpt:
        ckpt_dir = os.path.join(ctx.workdir, "ckpt")
        free = shutil.disk_usage(ctx.workdir).free
        if free < ckpt.get("min_free_bytes", 0):
            report.emit(record="refused", reason=f"{free} bytes free under "
                        f"{ctx.workdir}, the saves need "
                        f"{ckpt['min_free_bytes']}")
            return 3
    client = None
    if not ctx.in_process:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient.singleton()
    loop = build_loop(ctx.model, cfg, traffic, devices[:ctx.chips], client,
                      ckpt_dir)
    if not ctx.in_process:
        loop.install_signal_handler()

    spans: list = []

    def catch(span) -> None:
        spans.append({"name": span.name, "start": span.start_ts,
                      "end": span.end_ts, "duration_s": span.duration_s,
                      "attrs": {k: v for k, v in span.attrs.items()
                                if isinstance(v, (int, float, str, bool))}})

    obs.add_span_sink(catch)

    feed, sampler = make_feed(seed, cfg, traffic,
                              annotate=jax.profiler.TraceAnnotation)

    rng = jax.random.PRNGKey(seed)
    t0 = time.monotonic()
    state, start = loop.restore_or_init(rng, sampler)
    compiled = getattr(loop.trainer, "_compiled_step", None)
    memory = compiled.memory_analysis() if compiled is not None else None
    seconds = time.monotonic() - t0
    # the step program's text: its kernels and, for a trace's time by scope,
    # its name and each instruction's op_name (host work, before the window)
    text = compiled.as_text() if compiled is not None else ""
    t0 = time.monotonic()
    module = trace_reduce.module_name(text)
    op_name_of = trace_reduce.op_names(text) if ctx.trace else {}
    report.emit(
        record="init", seconds=seconds, start_step=start,
        precompile=dict(loop.trainer.precompile_timings),
        argument_bytes=getattr(memory, "argument_size_in_bytes", None),
        temp_bytes=getattr(memory, "temp_size_in_bytes", None),
        code_bytes=getattr(memory, "generated_code_size_in_bytes", None),
        kernels_in_program=(text.count("tpu_custom_call")
                            if compiled is not None else None),
        op_names_s=time.monotonic() - t0, op_names_read=len(op_name_of))
    del text

    # warm-up: the window's own call and feed, one step each
    change_fn = ctx.model.change_norms_fn(loop.trainer)
    t0 = time.monotonic()
    state, program = warm_up(loop, ctx.model, change_fn, rng, state, feed,
                             sampler, traffic["warmup_steps"])
    jax.block_until_ready(state)
    report.emit(record="warmup", seconds=time.monotonic() - t0,
                losses=program["losses"],
                used_aot=bool(loop.trainer.last_used_aot))

    # the trace: started and stopped from inside the feed
    tracing = {"dir": os.path.join(ctx.workdir, "trace"), "from": None,
               "to": None, "cost_s": 0.0}
    plan = traffic.get("trace") or {}

    def trace_hook(feed_, now: float) -> None:
        since = now - feed_.opened_at
        if tracing["from"] is None:
            if since >= plan.get("start_frac", 0.5) * ctx.seconds:
                jax.profiler.start_trace(tracing["dir"])
                tracing["from"] = time.monotonic()
                tracing["cost_s"] += tracing["from"] - now
        elif tracing["to"] is None and (
                now - tracing["from"] >= plan.get("max_s", 5.0)):
            stop_trace()

    def stop_trace() -> None:
        t = time.monotonic()
        jax.profiler.stop_trace()
        tracing["to"] = time.monotonic()
        tracing["cost_s"] += tracing["to"] - t

    # the window
    start_step = traffic["window_start_step"]
    loop.config.max_steps = 0
    if ctx.trace:
        feed.hooks.append(trace_hook)
    compiles["window_open"] = True
    feed.open(ctx.seconds)
    state, metrics = loop.run(state, feed, start_step=start_step,
                              sampler=sampler)
    jax.block_until_ready(state)
    closed = time.monotonic()
    compiles["window_open"] = False
    if ctx.trace and tracing["from"] is not None and tracing["to"] is None:
        stop_trace()

    calls = feed.window_calls()
    stats = devices[0].memory_stats() or {}
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices[:ctx.chips]), default=0)
    saves = [s for s in spans if s["name"] == "checkpoint_save"
             and s["attrs"].get("saved") and s["start"] >= feed.opened_wall]
    committed = (sorted(loop.checkpointer.all_steps())
                 if loop.checkpointer is not None else [])
    report.emit(
        record="window", opened_wall=feed.opened_wall,
        seconds=closed - feed.opened_at, asked_seconds=ctx.seconds,
        steps=len(calls), tokens_per_step=batch * seq,
        final_step=metrics.get("step"), start_step=start_step,
        final_loss=metrics.get("loss"), final_grad_norm=metrics.get(
            "grad_norm"),
        compiles_in_window=compiles["in_window"],
        compiles_total=compiles["total"],
        memory_peak_bytes=peak, bytes_limit=stats.get("bytes_limit"),
        calls=[{k: c[k] for k in ("entered", "fetch_from", "fetch_to",
                                  "wall")} for c in calls],
        gaps=feed.gaps(), opened_at=feed.opened_at,
        stopped_at=feed.stopped_at,
        closed_at=closed, saves_started=len(saves),
        saves_committed=sum(1 for s in saves
                            if s["attrs"].get("step") in committed),
        trace_cost_s=tracing["cost_s"],
        spans=[s for s in spans if s["start"] >= feed.opened_wall])

    # the program's state goes before anything else is allocated
    loop.close()
    obs.remove_span_sink(catch)
    del state, loop, change_fn
    gc.collect()

    if ctx.trace and tracing["to"] is not None:
        t0 = time.monotonic()
        device_events, host_input, outline, modules = trace_reduce.load(
            tracing["dir"])
        reduced = trace_reduce.reduce(
            device_events, host_input, op_name_of=op_name_of,
            runs=trace_reduce.program_runs(modules, module))
        report.emit(record="traced", reduce_seconds=time.monotonic() - t0,
                    traced_wall_s=tracing["to"] - tracing["from"],
                    outline=outline, **reduced)
        shutil.rmtree(tracing["dir"], ignore_errors=True)

    # the comparison: rows, then the reference over the warm-up's batches
    t0 = time.monotonic()
    truth = reference.Rows(seed, cfg["vocab_size"], traffic["rows"], seq,
                           traffic["shuffle"])
    rows_wrong = sum(
        1 for index, call in enumerate(feed.calls)
        if call["digest"] != digest(truth.batch(index, batch)))
    followed = reference.follow(
        ctx.model_reference, seed, cfg,
        [truth.batch(k, batch) for k in range(traffic["warmup_steps"])])
    compared = check.compare(program, followed, rows_wrong)
    finite = all(map(math.isfinite, program["losses"] + [
        metrics.get("loss", float("nan")),
        metrics.get("grad_norm", float("nan"))]))
    report.emit(record="compared", seconds=time.monotonic() - t0,
                compared=compared, losses_finite=finite,
                program_losses=program["losses"],
                reference_losses=followed["losses"],
                batches_checked=len(feed.calls))
    return 0
