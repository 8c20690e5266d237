"""Chip smoke: the product's main path, once, on the TPU.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # only the four-chip phase + its reference
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal: runs
                                      # every phase at a tiny width, "ok": false

One chip: `python -m dlrover_tpu.run --standalone chip_smoke.py --worker ...`
twice over the same checkpoint and compile-cache directories. Incarnation 1
trains the 1.47B wide Llama (hidden 2048, MLP 8192, 20 layers, seq 2048, bf16,
flash + fused norm, factored-RMS) for 6 steps under ElasticTrainLoop — master
client, sampler-fed dataloader, step reports, async checkpoint at step 4.
Incarnation 2 restores that checkpoint, takes 2 more steps, and its step
program must come out of the compile cache.

Four chips: one process drives all four, state sharded fsdp=4, global batch
8. First the whole 20-layer model for two steps (it lowers, fits, trains).
Then the comparison, at full width and 10 layers — the one-device reference
accumulates four micro-batches, and that program holds the fp32 gradient
sum beside the step's own gradients: at 20 layers the chip's compiler
refuses it (19.96 GiB of 15.75), at 10 it fits. Three steps on fsdp=4, the
same batches on a one-device mesh (accumulation 4); the losses must agree.

The parent NEVER imports jax: a chip belongs to one process at a time. It
learns platform, kind and count from the worker's report. Stdout is one JSON
object per phase, then, as the last line, exactly
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
ok is true only on platform "tpu" with every check passed; anything else —
no chip, no package beside this file, a failed phase — exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
TRAIN_STEPS = 6          # incarnation 1
SAVE_INTERVAL = 4        # one committed checkpoint inside those steps
RESUME_STEPS = 2         # incarnation 2, on top of the restored step
MESH_STEPS = 3
MESH_GLOBAL_BATCH = 8
MESH_COMPARE_LAYERS = 10  # deepest round cut the one-device reference fits
# |loss(fsdp=4) - loss(one device, accum 4)| per step. Same arithmetic in
# another summation order: per-sample logits agree, the batch mean and the
# gradient sum are re-associated. The four-virtual-device CPU rehearsal
# (`--tiny --chips 4`, bf16 compute) measured 2.9e-5, 1.7e-5 and 1.4e-6 over
# the three steps on a loss of 5.56. 1e-3 is 35 times its worst step, about
# 1e-4 of the full model's starting loss (ln 32000 = 10.4): room for the
# chip's own matmul tiling, none for a layout or collective that is wrong.
# (On four v5e chips, PR 22: 0, 1.4e-4, 3.4e-4 — fsdp sums gradients across
# chips in bf16, the one-device reference accumulates them in fp32.)
MESH_LOSS_TOL = 1e-3
# per child; the one-chip run's two stay inside the driver's 1200 s
PHASE_TIMEOUT_S = {"train1": 540, "train2": 540, "mesh": 1500}


# ---------------------------------------------------------------------------
# Workers (own the chip; everything below imports jax)
# ---------------------------------------------------------------------------


class Report:
    """Append-only JSON lines the parent replays to stdout, plus the
    checks that decide this worker's exit code."""

    def __init__(self, path: str):
        self._path = path
        self.failed: list = []

    def emit(self, **record) -> None:
        with open(self._path, "a") as f:
            f.write(json.dumps(record) + "\n")

    def check(self, name: str, ok: bool, **evidence) -> None:
        if not ok:
            self.failed.append(name)
        self.emit(check=name, ok=bool(ok), **evidence)

    def done(self, phase: str) -> int:
        """The worker's last line and its exit code."""
        self.emit(phase="done", incarnation=phase, failed=self.failed)
        return 1 if self.failed else 0


def _model_and_optimizer(tiny: bool, layers: int = 0):
    """layers: 0 = the configuration's own depth; widths never change."""
    import dataclasses

    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models.llama import LlamaConfig

    impl = dict(attn_impl="flash", norm_impl="fused", embed_impl="gather",
                dtype=jnp.bfloat16)
    if tiny:
        cfg = LlamaConfig.tiny(max_seq_len=128, **impl)
    else:
        cfg = LlamaConfig.llama_wide_1b(max_seq_len=2048, **impl)
        if layers:
            cfg = dataclasses.replace(cfg, num_layers=layers)
    tx = optax.chain(optax.scale_by_factored_rms(), optax.scale(-3e-4))
    return cfg, tx


def _device_record(report: Report) -> bool:
    """What jax found; True on a TPU. Off it a full-width worker stops
    right here: the answer is already no, and the Pallas interpreter
    would take hours to say it."""
    import jax

    from dlrover_tpu.native_build import load_native
    from dlrover_tpu.obs import mfu

    device = jax.devices()[0]
    platform = jax.default_backend()
    stats = device.memory_stats() or {}
    report.emit(phase="device", platform=platform,
                kind=device.device_kind, count=len(jax.devices()),
                hbm_limit_bytes=stats.get("bytes_limit"),
                jax=jax.__version__,
                native="built" if load_native() is not None
                else "python-fallback")
    on_chip = platform == "tpu"
    report.check("backend_is_tpu", on_chip, found=platform)
    # an unknown TPU kind raises here, which is the failure to have
    peak = mfu.peak_flops_per_chip(device.device_kind, backend=platform)
    report.check("device_kind_in_peak_table", peak > 0.0,
                 kind=device.device_kind, peak_flops=peak)
    return on_chip


def _compiled_step_record(report: Report, loop, label: str) -> None:
    """The compiled step program is the evidence that the kernels are in
    it: `tpu_custom_call` is a Mosaic kernel; the reference path and the
    Pallas interpreter leave none."""
    compiled = loop.trainer._compiled_step
    report.check(f"{label}_aot_compiled", compiled is not None)
    if compiled is None:
        return
    kernels = compiled.as_text().count("tpu_custom_call")
    memory = compiled.memory_analysis()
    report.check(
        f"{label}_kernels_in_program", kernels > 0,
        tpu_custom_calls=kernels,
        argument_bytes=getattr(memory, "argument_size_in_bytes", None),
        temp_bytes=getattr(memory, "temp_size_in_bytes", None),
        code_bytes=getattr(memory, "generated_code_size_in_bytes", None))


def worker_train(phase: str, workdir: str, tiny: bool) -> int:
    """One incarnation under the agent: build, restore-or-init, train."""
    from dlrover_tpu.agent.elastic_agent import init_distributed

    init_distributed()

    import threading

    import jax
    import numpy as np

    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.models.llama import Llama, cross_entropy_loss
    from dlrover_tpu.trainer.dataloader import ElasticDataLoader
    from dlrover_tpu.trainer.elastic_loop import (
        ElasticTrainLoop,
        TrainLoopConfig,
    )
    from dlrover_tpu.trainer.sampler import ElasticDistributedSampler

    resumed = phase == "train2"
    report = Report(os.path.join(workdir, f"{phase}.jsonl"))
    # persistent-cache lookups, told apart by thread: the loop compiles
    # the step program on a background thread while the main one restores
    # (restore_or_init), so "step_*" is the step program and nothing else
    cache_events = {"hits": 0, "misses": 0, "step_hits": 0,
                    "step_misses": 0}

    def _on_event(event: str, **_) -> None:
        step = ("" if threading.current_thread() is threading.main_thread()
                else "step_")
        if event.endswith("/cache_hits"):
            cache_events[step + "hits"] += 1
        elif event.endswith("/cache_misses"):
            cache_events[step + "misses"] += 1

    jax.monitoring.register_event_listener(_on_event)
    if not _device_record(report) and not tiny:
        return report.done(phase)

    cfg, tx = _model_and_optimizer(tiny)
    global_batch, seq = 2, cfg.max_seq_len
    loop = ElasticTrainLoop(
        Llama(cfg), tx, cross_entropy_loss,
        TrainLoopConfig(
            global_batch=global_batch, seq_len=seq,
            max_micro_per_replica=2,
            checkpoint_dir=os.path.join(workdir, "ckpt"),
            save_interval_steps=SAVE_INTERVAL,
            report_interval_steps=2,
        ),
        master_client=MasterClient.singleton(),
    )
    loop.install_signal_handler()
    # the model-info report swallows its own failure; its outcome is this
    report.check("model_info_resolved",
                 loop._flops_per_token > 0 and loop._peak_flops_total > 0,
                 flops_per_token=loop._flops_per_token,
                 peak_flops_total=loop._peak_flops_total)

    # input pipeline: seeded token stream -> windows -> sampler -> loader
    stream = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, 64 * (seq + 1), dtype=np.int32)
    windows = stream.reshape(64, seq + 1)
    sampler = ElasticDistributedSampler(len(windows), shuffle=True,
                                        seed=SEED)

    def tokens_and_targets(rows):
        batch = np.stack(rows)
        return batch[:, :-1], batch[:, 1:]

    loader = iter(ElasticDataLoader(windows, global_batch, sampler=sampler,
                                    collate_fn=tokens_and_targets))

    t0 = time.monotonic()
    state, start = loop.restore_or_init(jax.random.PRNGKey(SEED), sampler)
    report.emit(phase="restore_or_init", incarnation=phase,
                seconds=round(time.monotonic() - t0, 2), start_step=start,
                last_restore_source=loop.last_restore_source,
                last_restore_timings=loop.last_restore_timings,
                precompile_timings=loop.trainer.precompile_timings,
                compile_cache=dict(cache_events))
    if resumed:
        report.check("restored_from_checkpoint",
                     start == SAVE_INTERVAL
                     and loop.last_restore_source != "init",
                     start_step=start, source=loop.last_restore_source)
        report.check("sampler_position_restored",
                     sampler.completed_num == start * global_batch,
                     completed=sampler.completed_num)
    else:
        report.check("fresh_start",
                     start == 0 and loop.last_restore_source == "init",
                     start_step=start, source=loop.last_restore_source)
    _compiled_step_record(report, loop, "step_program")

    def run(steps: int, step: int):
        loop.config.max_steps = steps
        t = time.monotonic()
        new_state, metrics = loop.run(state, loader, start_step=step,
                                      sampler=sampler)
        return new_state, metrics, time.monotonic() - t

    # first step alone: it is the one that shows whether the AOT program
    # took its arguments or ShardedTrainer.step re-jitted in silence
    state, metrics, first_s = run(1, start)
    report.check("first_step_used_aot", loop.trainer.last_used_aot)
    losses = [metrics["loss"]]
    # the rest in one run: async save overlapping the steps behind it
    rest = (RESUME_STEPS if resumed else TRAIN_STEPS) - 1
    state, metrics, rest_s = run(rest, start + 1)
    losses.append(metrics["loss"])
    final_step = int(metrics["step"])
    report.check("steps_taken", final_step == start + 1 + rest,
                 final_step=final_step)
    report.check("losses_finite",
                 all(map(math.isfinite, losses + [metrics["grad_norm"]])),
                 first_loss=losses[0], last_loss=losses[1],
                 grad_norm=metrics["grad_norm"])
    committed = loop.checkpointer.all_steps()
    report.check("checkpoint_committed", SAVE_INTERVAL in committed,
                 committed_steps=sorted(committed))
    stats = jax.devices()[0].memory_stats() or {}
    # one builder-side reading of one run, for orientation only: the wall
    # time of `rest` steps includes the save's stall when one fell inside
    report.emit(phase="steps", incarnation=phase,
                first_step_wall_s=round(first_s, 3),
                rest_steps=rest, rest_wall_s=round(rest_s, 3),
                info_seconds_per_step=round(rest_s / rest, 4),
                info_tokens_per_second=round(
                    rest * global_batch * seq / rest_s, 1),
                save_inside_rest=not resumed,
                peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    loop.close()
    return report.done(phase)


def worker_mesh(workdir: str, tiny: bool) -> int:
    """fsdp=4 against one device, same seed, same batches, one process."""
    import gc

    import jax
    import numpy as np

    from dlrover_tpu.models.llama import Llama, cross_entropy_loss
    from dlrover_tpu.parallel.mesh import MeshSpec
    from dlrover_tpu.trainer.elastic_loop import (
        ElasticTrainLoop,
        TrainLoopConfig,
    )

    report = Report(os.path.join(workdir, "mesh.jsonl"))
    if not _device_record(report) and not tiny:
        return report.done("mesh")
    devices = jax.devices()
    report.check("four_devices", len(devices) == 4, found=len(devices))
    if len(devices) != 4:
        return report.done("mesh")

    full, tx = _model_and_optimizer(tiny)
    cut, _ = _model_and_optimizer(tiny, MESH_COMPARE_LAYERS)
    seq = full.max_seq_len
    rng = np.random.default_rng(SEED)
    batches = [
        tuple(rng.integers(0, full.vocab_size, (MESH_GLOBAL_BATCH, seq),
                           dtype=np.int32) for _ in range(2))
        for _ in range(MESH_STEPS)]

    def losses_on(label: str, cfg, devs, mesh_spec: MeshSpec,
                  steps: int) -> list:
        loop = ElasticTrainLoop(
            Llama(cfg), tx, cross_entropy_loss,
            TrainLoopConfig(global_batch=MESH_GLOBAL_BATCH, seq_len=seq,
                            max_micro_per_replica=2, mesh_spec=mesh_spec),
            devices=devs,
        )
        state, _ = loop.restore_or_init(jax.random.PRNGKey(SEED))
        _compiled_step_record(report, loop, label)
        if len(devs) > 1:
            leaves = jax.tree.leaves(state.params)
            on_all = all(leaf.sharding.device_set == set(devs)
                         for leaf in leaves)
            total = sum(leaf.nbytes for leaf in leaves)
            on_first = sum(
                shard.data.nbytes for leaf in leaves
                for shard in leaf.addressable_shards
                if shard.device == devs[0])
            # norm scales are whole everywhere; every matrix is split
            report.check(f"{label}_state_sharded",
                         on_all and on_first < 0.3 * total,
                         every_leaf_on_all_devices=on_all,
                         param_bytes=total, bytes_on_device_0=on_first)
        losses = []
        for step, batch in enumerate(batches[:steps]):
            loop.config.max_steps = 1
            t = time.monotonic()
            state, metrics = loop.run(state, [batch], start_step=step)
            losses.append(metrics["loss"])
            report.emit(phase="mesh_step", mesh=label, step=step + 1,
                        loss=metrics["loss"],
                        wall_s=round(time.monotonic() - t, 3),
                        used_aot=loop.trainer.last_used_aot)
        report.check(f"{label}_used_aot", loop.trainer.last_used_aot)
        report.emit(phase="mesh_run", mesh=label, layers=cfg.num_layers,
                    mesh_shape={k: v for k, v in loop.mesh.shape.items()
                                if v > 1},
                    accum=loop.accum, micro_global=loop.micro_global,
                    peak_bytes_in_use=(devs[0].memory_stats() or {}).get(
                        "peak_bytes_in_use"))
        loop.close()
        del state, loop
        gc.collect()
        return losses

    whole = losses_on("fsdp4_full_depth", full, devices, MeshSpec(fsdp=4),
                      steps=2)
    sharded = losses_on("fsdp4", cut, devices, MeshSpec(fsdp=4),
                        steps=MESH_STEPS)
    single = losses_on("one_device", cut, devices[:1], MeshSpec(),
                       steps=MESH_STEPS)
    diffs = [abs(a - b) for a, b in zip(sharded, single)]
    report.check("losses_finite",
                 all(map(math.isfinite, whole + sharded + single)))
    report.check("fsdp4_matches_one_device",
                 all(d <= MESH_LOSS_TOL for d in diffs),
                 layers=cut.num_layers, fsdp4=sharded, one_device=single,
                 abs_diff=diffs, tolerance=MESH_LOSS_TOL)
    return report.done("mesh")


# ---------------------------------------------------------------------------
# Parent (never touches jax)
# ---------------------------------------------------------------------------


def _run_phase(cmd: list, env: dict, log_path: str,
               timeout_s: int) -> int:
    """Run one child in its own process group, its output appended to the
    log; the whole group dies with the phase, whatever the outcome."""
    assert "jax" not in sys.modules, "the parent must stay off jax"
    with open(log_path, "a") as log:
        log.write(f"\n===== {' '.join(cmd)}\n")
        log.flush()
        proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            return proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            log.write(f"\n===== timed out after {timeout_s}s\n")
            return 124
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


def _replay(report_path: str) -> list:
    """Print a worker's report lines; return them parsed."""
    try:
        with open(report_path) as f:
            lines = [line.strip() for line in f if line.strip()]
    except FileNotFoundError:
        return []
    for line in lines:
        print(line)
    return [json.loads(line) for line in lines]


def _finish(ok: bool, device: dict, log_path: str) -> int:
    sys.stdout.flush()
    if not ok:
        try:
            with open(log_path, "rb") as f:
                f.seek(max(0, os.path.getsize(log_path) - 8000))
                sys.stderr.write(f.read().decode("utf-8", "replace"))
        except OSError:
            pass
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


def parent(chips: int, tiny: bool) -> int:
    try:
        from dlrover_tpu.common import compile_cache
    except ImportError as e:
        print(json.dumps({"ok": False, "error": f"no dlrover_tpu beside "
                          f"chip_smoke.py: {e}", "device": {}}))
        return 1

    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, "chip_smoke.log")
    open(log_path, "w").close()
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    env = dict(os.environ)
    env[compile_cache.ENV] = compile_cache.compile_cache_dir()
    # cache every program, not only the slow ones: incarnation 2 must
    # find the step program whatever it cost to compile
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    worker = [os.path.join(HERE, "chip_smoke.py")]
    flags = ["--tiny"] if tiny else []
    device: dict = {}
    ok = True
    try:
        print(json.dumps({
            "phase": "plan", "chips": chips, "tiny": tiny,
            "compile_cache_dir": env[compile_cache.ENV],
            "disk_free_bytes": shutil.disk_usage(workdir).free}))
        if chips == 4:
            phases = [("mesh", [sys.executable] + worker
                       + ["--worker", "mesh", workdir] + flags)]
        else:
            launch = [sys.executable, "-m", "dlrover_tpu.run",
                      "--standalone", "--max-restarts", "0"]
            phases = [(name, launch + worker
                       + ["--worker", name, workdir] + flags)
                      for name in ("train1", "train2")]
        records: dict = {}
        for name, cmd in phases:
            # each launch's agent keeps its files (the peer cache holds a
            # copy of the state) under a TMPDIR this script removes
            env["TMPDIR"] = os.path.join(workdir, f"tmp-{name}")
            os.makedirs(env["TMPDIR"])
            t0 = time.monotonic()
            rc = _run_phase(cmd, env, log_path, PHASE_TIMEOUT_S[name])
            shutil.rmtree(env["TMPDIR"], ignore_errors=True)
            records[name] = _replay(os.path.join(workdir, f"{name}.jsonl"))
            print(json.dumps({"phase": "exit", "incarnation": name,
                              "rc": rc,
                              "wall_s": round(time.monotonic() - t0, 1)}))
            found = next((r for r in records[name]
                          if r.get("phase") == "device"), None)
            if found is not None:
                device = {"platform": found["platform"],
                          "kind": found["kind"], "count": found["count"]}
            finished = any(r.get("phase") == "done" for r in records[name])
            ok = ok and rc == 0
            if not finished or (device.get("platform") != "tpu"
                                and not tiny):
                # crashed before its last line, or no chip under a
                # full-width run: nothing to resume from
                ok = False
                break
        if chips != 4 and len(records) == 2:
            ok = _cache_stayed_put(records) and ok
            ok = _resume_reproduces(records) and ok
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    ok = ok and device.get("platform") == "tpu" and device.get(
        "count") == chips
    return _finish(ok, device, log_path)


def _cache_stayed_put(records: dict) -> bool:
    """Incarnation 2's step program must be a cache load: found by the
    lookup its compile made, and, where incarnation 1 had to compile it
    (a machine that came without a cache), loaded in less time."""
    def compile_s(name: str):
        found = next((r for r in records[name]
                      if r.get("phase") == "restore_or_init"), {})
        return (found.get("precompile_timings", {}).get(
            "compile_or_cache_load_s"),
            found.get("compile_cache", {}))

    first, first_events = compile_s("train1")
    second, events = compile_s("train2")
    first_was_cold = first_events.get("step_misses", 0) > 0
    loaded = (first is not None and second is not None
              and events.get("step_hits", 0) > 0
              and events.get("step_misses", 0) == 0
              and (second < first or not first_was_cold))
    print(json.dumps({"check": "second_compile_is_cache_load",
                      "ok": loaded,
                      "first_compile_or_load_s": first,
                      "first_was_a_cold_compile": first_was_cold,
                      "second_compile_or_load_s": second,
                      "cache_events_incarnation_2": events}))
    return loaded


def _resume_reproduces(records: dict) -> bool:
    """Both incarnations end on the same step: the one that came through
    a checkpoint, a restore and a second process must reach the loss the
    uninterrupted one reached — same state (exact dtypes), same sampler
    position, same program."""
    def last_loss(name: str):
        return next((r for r in records[name]
                     if r.get("check") == "losses_finite"), {}).get(
            "last_loss")

    straight, resumed = last_loss("train1"), last_loss("train2")
    same = (straight is not None and resumed is not None
            and abs(straight - resumed) <= 1e-5 * max(1.0, abs(straight)))
    print(json.dumps({"check": "resumed_run_reproduces_uninterrupted_loss",
                      "ok": same, "step": TRAIN_STEPS,
                      "uninterrupted": straight, "resumed": resumed,
                      "relative_tolerance": 1e-5}))
    return same


def main() -> int:
    parser = argparse.ArgumentParser("chip_smoke")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--tiny", action="store_true",
                        help="tiny model for the CPU rehearsal; ok stays "
                             "false off the TPU")
    parser.add_argument("--worker", nargs=2, metavar=("PHASE", "WORKDIR"),
                        help="internal: train1 | train2 | mesh")
    args = parser.parse_args()
    if args.worker:
        phase, workdir = args.worker
        if phase == "mesh":
            return worker_mesh(workdir, args.tiny)
        return worker_train(phase, workdir, args.tiny)
    return parent(args.chips, args.tiny)


if __name__ == "__main__":
    raise SystemExit(main())
