"""Headline benchmark: Llama train-step throughput + MFU on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline: the driver target of 40% MFU for Llama-class training
(BASELINE.md; reference HFU claim 49.6% on GPU,
docs/blogs/stabilize_llm_training_cn.md:352-353).

On TPU this benches a Llama at seq 2048 in bf16 with the Pallas
flash-attention kernel (1024x1024 blocks, bf16 MXU inputs + fp32
accumulation) and the fused Pallas RMSNorm; the model size is picked to
fit the chip's HBM with adafactor's factored optimizer state (the lean
state is what lets a 16 GB chip train a hidden-2048 model, which is worth
+0.13 MFU over the adamw-sized alternative). It measures on a TPU or not
at all: with no chip every measuring phase fails and the exit code is
non-zero — a CPU time is never printed under this metric's name.

The orchestrating parent never imports jax (a chip belongs to one process
at a time); each measuring phase is a child process of its own.

MFU accounting is conservative: flops/token = 6·params + 6·L·h·s (the
causal-discounted attention term — half the PaLM-style 12·L·h·s — matching
what the kernel actually computes, since blocks above the diagonal are
skipped). Embedding lookup FLOPs are excluded, so the single-chip bench
uses the cheaper gather lookup rather than crediting itself the one-hot
matmul.
"""

from __future__ import annotations

import json
import os
import sys
import time

# bf16 peak FLOP/s per chip by device kind: single-sourced in
# obs/mfu.py (the framework's MFU gauges and this bench must agree);
# jax-free, like everything this module imports at top level
from dlrover_tpu.obs import mfu as mfu_math


def _require_tpu():
    """(first device, its HBM bytes) — or exit: the measuring children
    run on a TPU that reports its memory, never on a guess."""
    import jax

    from dlrover_tpu.ops.backend import on_tpu

    if not on_tpu():
        raise SystemExit(
            f"bench.py measures on a TPU; jax found "
            f"{jax.default_backend()!r}")
    device = jax.devices()[0]
    hbm = (device.memory_stats() or {}).get("bytes_limit")
    if not hbm:
        raise SystemExit(
            f"{device.device_kind} reports no memory_stats bytes_limit; "
            f"the bench sizes its model by it")
    return device, hbm


def peak_flops(device) -> float:
    return mfu_math.peak_flops_per_chip(device.device_kind, backend="tpu")


def _run_json_subprocess(cmd, timeout_s: float, env=None) -> dict:
    """Run cmd in its own process group, parse the last JSON line of
    stdout. On timeout the whole group is SIGKILLed (a worker grandchild
    may hold the chip). Returns {"error": ...} on any failure."""
    import signal
    import subprocess

    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True, env=env,
    )
    stderr = ""
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
        for line in reversed(stdout.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait()
        return {"error": f"timed out after {timeout_s}s"}
    except Exception as e:
        return {"error": str(e)[:200]}
    # the child's last word says why (e.g. "measures on a TPU; found cpu")
    last = (stderr.strip().splitlines() or [""])[-1][:300]
    return {"error": f"no result line (exit {proc.returncode}): {last}"}


def run_restore_bench(timeout_s: float = 480.0,
                      at_scale: bool = False) -> dict:
    """Run bench_restore.py in a subprocess tree. The toy mode is
    CPU-staged (JAX_PLATFORMS=cpu for the whole tree): it measures the
    REAL elastic stack — kill detection, re-rendezvous, respawn, orbax
    restore — at toy size, a control-plane time and not a device one.
    The --at-scale mode runs the 1.47B bench model ON the chip (multi-GB
    restore + re-jit); it must run while no other process holds the
    TPU. Returns the bench's JSON record ("value" =
    seconds, plus the per-phase breakdown and goodput summary); an
    {"error": ...}-shaped dict on failure."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "bench_restore.py")
    env = dict(os.environ)
    cmd = [sys.executable, script, "--timeout", str(timeout_s)]
    if at_scale:
        cmd.append("--at-scale")
    else:
        env["JAX_PLATFORMS"] = "cpu"
    return _run_json_subprocess(cmd, timeout_s + 60, env=env)


def _restore_seconds(restore_result: dict) -> float:
    try:
        return float(restore_result["value"])
    except (KeyError, TypeError, ValueError):
        return -1.0


def _fold_restore_fields(result: dict, restore_result: dict) -> None:
    """Fold the restore bench's per-phase breakdown + goodput summary
    into the scoreboard record (BENCH_r06+ tracks these beside the
    headline seconds): where each restore second went, and how much of
    the episode's rank-time was productive."""
    breakdown = restore_result.get("breakdown") or {}
    for source, target in (
            ("peer_plan_s", "restore_peer_plan_s"),
            ("peer_transfer_s", "restore_peer_transfer_s"),
            ("peer_bandwidth_mbps", "restore_peer_bandwidth_mbps"),
            ("orbax_read_s", "restore_orbax_read_s"),
            ("restore_metadata_read_s", "restore_metadata_read_s"),
            ("restore_tensor_read_s", "restore_tensor_read_s"),
            ("restore_decode_s", "restore_decode_s"),
            ("device_ready_s", "restore_device_put_s"),
            ("post_sync_s", "restore_post_sync_s"),
            ("detect_respawn_s", "restore_detect_respawn_s"),
            ("compile_wait_after_read_s",
             "restore_compile_wait_s"),
            ("first_step_s", "restore_first_step_s"),
            ("restore_read_bandwidth_mbps",
             "restore_read_bandwidth_mbps"),
    ):
        if source in breakdown:
            result[target] = breakdown[source]
    for key in ("phase_sum_s", "phase_coverage", "goodput_fraction",
                "goodput_buckets", "restore_source"):
        if key in restore_result:
            result[key] = restore_result[key]


def _timed_loop(step_fn, state, tok, tgt, warmup=2, steps=5,
                per_step=None):
    """Shared warmup + timed-window protocol. The float() host fetches
    force the full chain to execute.
    ``per_step`` (optional list) collects each timed step's dispatch
    wall time for the critical-path fold — stamps only, no extra host
    syncs, so the headline window is unchanged.
    Returns (state, seconds, warmup_loss, final_loss)."""
    for _ in range(max(warmup, 1)):   # >=1: warmup_loss needs a metrics
        state, metrics = step_fn(state, tok, tgt)
    warmup_loss = float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        t_step = time.perf_counter()
        state, metrics = step_fn(state, tok, tgt)
        if per_step is not None:
            per_step.append(time.perf_counter() - t_step)
    final_loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    return state, dt, warmup_loss, final_loss


def _critical_path_summary(step_times) -> dict:
    """The timed window folded through the fleet's steptrace solver
    (master/steptrace.py pure helpers) — the SAME attribution shape the
    master reports, so the bench JSON and the live dashboard speak one
    vocabulary. One lane here: a single-process bench has no cross-slice
    barrier, and the fold says so (wait fraction 0) instead of omitting
    the field."""
    from dlrover_tpu.master.steptrace import (
        solve_group,
        summarize_solved,
    )

    solved, t0 = [], 0.0
    for step, dt in enumerate(step_times):
        rec = {"step": step, "gen": 0, "slice": 0, "rank": 0,
               "t0": t0, "off": 0.0, "err": 0.0,
               "phases": [["compute", 0.0, float(dt)]], "peers": {}}
        solved.append(solve_group(0, step, {0: rec}))
        t0 += float(dt)
    summary = summarize_solved(solved)
    return {
        "traced_steps": summary["steps"],
        "dominant_gating_phase": summary["dominant_gating_phase"],
        "cross_slice_wait_fraction": summary[
            "cross_slice_wait_fraction"],
    }


def _model_flops_per_token(cfg, seq: int) -> float:
    """obs/mfu.py's conservative accounting: 6·params fwd+bwd matmul
    credit (a gather-lookup embedding table with untied output head
    does no matmul, so those params are not credited) plus the
    causal-discounted attention term — matching what the kernel
    actually computes."""
    uncounted = 0.0
    if cfg.embed_impl == "gather" and not cfg.tie_embeddings:
        uncounted = cfg.vocab_size * cfg.hidden_size
    return mfu_math.flops_per_token(
        cfg.param_count(), num_layers=cfg.num_layers,
        hidden_size=cfg.hidden_size, seq_len=seq,
        uncounted_embed_params=uncounted)


def _oom_report(e: Exception, **extra) -> int:
    """OOM and friends: the reason IS the result, not a failure."""
    reason = str(e)
    key = reason.find("memory space")
    if key >= 0:
        reason = reason[max(0, key - 160):key + 160]
    out = {"error": reason[:400]}
    out.update(extra)
    print(json.dumps(out))
    return 0


def _seven_b_streaming() -> int:
    """Llama-7B on a <20 GB chip via the streaming per-layer trainer
    (trainer/streaming.py): backward is a reverse per-layer loop that
    applies the factored-rms update in place, so only ONE layer's
    gradients are ever live — peak ≈ params + one layer's grads
    ≈ 14 GB, under the 15.75 GB that the dense step's full gradient
    tree (27 GB) overruns.
    AOT-compiles first and reports the XLA memory analysis either way,
    so an OOM comes with the measured budget, not a guess. micro 2
    measures ~6.5% faster than micro 1 (0.586 vs 0.550 MFU on v5e) at
    the same 15.48 GB analyzed peak; micro 1 stays as the fallback so a
    tighter-HBM chip still produces a number instead of an OOM note —
    with the micro-2 failure reason carried in the reported JSON
    (``fallback_note``), not lost on a discarded stderr."""
    try:
        print(json.dumps(_seven_b_streaming_run(2, 2048)))
        return 0
    except Exception as e:
        note = f"micro=2 failed ({str(e)[:300]}); fell back to micro=1"
    try:
        rec = _seven_b_streaming_run(1, 2048)
        rec["fallback_note"] = note
        print(json.dumps(rec))
        return 0
    except Exception as e:
        return _oom_report(e, mode="streaming",
                           memory=getattr(e, "bench_memory", {}),
                           fallback_note=note)


def _seven_b_streaming_run(micro: int, seq: int) -> dict:
    """One streaming-7B attempt. Returns the result record; raises on
    failure with the partial XLA memory analysis attached as
    ``e.bench_memory`` so the caller's report keeps the evidence."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.trainer.streaming import build_streaming_trainer

    # untied embeddings — real Llama-7B has a separate lm_head; tying
    # would shave vocab·hidden params (~2%) and overstate the number
    cfg = LlamaConfig.llama_7b(
        max_seq_len=seq, attn_impl="flash", embed_impl="gather",
        norm_impl="fused", dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    tx = optax.chain(optax.scale_by_factored_rms(),
                     optax.scale(-3e-4))
    mem: dict = {}
    try:
        trainer = build_streaming_trainer(cfg, tx, micro, seq)
        abstract = trainer.abstract_state(jax.random.PRNGKey(0))
        tok_abs = jax.ShapeDtypeStruct((micro, seq), jnp.int32)
        compiled = trainer.step_fn.lower(
            abstract, tok_abs, tok_abs).compile()
        stats = compiled.memory_analysis()
        if stats is not None:
            mem = {
                "args_gb": round(stats.argument_size_in_bytes / 2**30, 2),
                "temp_gb": round(stats.temp_size_in_bytes / 2**30, 2),
                "out_gb": round(stats.output_size_in_bytes / 2**30, 2),
                "alias_gb": round(stats.alias_size_in_bytes / 2**30, 2),
            }
        state = trainer.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(
            0, cfg.vocab_size, (micro, seq), dtype=np.int32))
        # reuse the AOT executable: trainer.step would re-trace and pay
        # the (on-chip, minutes-long) compile a second time
        trainer.step_fn = lambda s, t, tg: compiled(s, t, tg)
        steps = 5
        _, dt, _, _ = _timed_loop(trainer.step, state, tokens, tokens,
                                  warmup=2, steps=steps)
        tokens_per_sec = micro * seq * steps / dt
        mfu = (tokens_per_sec * _model_flops_per_token(cfg, seq)
               / peak_flops(jax.devices()[0]))
        return {"tokens_per_sec": round(tokens_per_sec, 1),
                "mfu": round(mfu, 4), "mode": "streaming",
                "micro_batch": micro, "memory": mem}
    except Exception as e:
        e.bench_memory = mem
        raise


def seven_b_main() -> int:
    """--llama7b subprocess: an honest Llama-7B tokens/sec/chip attempt.
    On <20 GB chips the streaming per-layer
    trainer caps peak memory at params + one layer's grads (see
    _seven_b_streaming); on bigger chips the dense step measures
    directly. On OOM the XLA text is REPORTED as the measured reason
    rather than faked around. Prints one JSON line either way."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import (
        Llama,
        LlamaConfig,
        cross_entropy_loss,
    )
    from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
    from dlrover_tpu.trainer.train_step import build_trainer

    device, hbm = _require_tpu()
    try:
        if hbm < 20 << 30:
            return _seven_b_streaming()
        cfg = LlamaConfig.llama_7b(
            max_seq_len=2048, attn_impl="flash", remat=True,
            embed_impl="gather", norm_impl="fused", dtype=jnp.bfloat16,
            # pure-bf16 params: fp32 masters alone (27 GB) dwarf a 16 GB
            # chip; bf16 halves both params and grads
            param_dtype=jnp.bfloat16)
        tx = optax.chain(optax.scale_by_factored_rms(),
                         optax.scale(-3e-4))
        # data-parallel over every local chip (one sample each); the
        # reported number is per chip
        chips = jax.local_device_count()
        mesh = create_mesh(MeshSpec(), jax.local_devices())
        micro, seq = chips, 2048
        sample = jnp.zeros((micro, seq), jnp.int32)
        trainer = build_trainer(
            Llama(cfg), tx, mesh, sample, cross_entropy_loss,
            accum_steps=1, micro_batch=micro, offload_opt_state=True,
        )
        state = trainer.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, cfg.vocab_size, (micro, seq),
                              dtype=np.int32)
        tok, tgt = trainer.shard_batch(tokens, tokens)
        steps = 5
        _, dt, _, _ = _timed_loop(trainer.step, state, tok, tgt,
                                  warmup=2, steps=steps)
        tokens_per_sec = micro * seq * steps / dt / chips
        mfu = (tokens_per_sec * _model_flops_per_token(cfg, seq)
               / peak_flops(device))
        print(json.dumps({"tokens_per_sec": round(tokens_per_sec, 1),
                          "mfu": round(mfu, 4), "chips": chips,
                          "device_kind": device.device_kind}))
        return 0
    except Exception as e:
        return _oom_report(e)


def run_7b_bench(timeout_s: float = 1800.0) -> dict:
    """Run the --llama7b attempt in its own process (it must own the
    TPU; a failure must not kill the headline bench). The budget is 2x
    the old single-attempt 900 s: a micro-2 attempt that fails late
    (post-compile) plus the full micro-1 fallback is two on-chip
    compiles and two timed runs, each bounded by the old worst case."""
    return _run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--llama7b"],
        timeout_s)


def _measure() -> dict:
    """The headline measurement (owns the accelerator in THIS process)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import (
        Llama,
        LlamaConfig,
        cross_entropy_loss,
    )
    from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
    from dlrover_tpu.trainer.train_step import build_trainer

    device, hbm = _require_tpu()
    # Factored second moments (adafactor family) keep the optimizer
    # state out of HBM so the chip fits a model big enough to saturate
    # the MXU; the optimizer name goes in the metric label. Default
    # "factored_rms" is the adafactor core (scale_by_factored_rms) minus
    # the update-clipping/relative-step passes, which cost ~11 ms/step
    # of pure elementwise HBM traffic (measured 0.689 vs 0.662 MFU).
    # BENCH_OPT=adafactor runs the full optax.adafactor; BENCH_OPT=adamw
    # reverts to the fp32-Adam-sized configs (smaller model, same chip).
    opt_name = os.environ.get("BENCH_OPT", "factored_rms")
    # Model sized by HBM and optimizer state. adafactor (≈0 B/param
    # state; bf16 params + grads = 4 B/param): measured on v5e-16GB,
    # llama_1b at micro 2 no-remat is the MFU sweet spot — 0.63 vs
    # 0.49 for the adamw-sized 0.4B config (bigger matmuls at hidden
    # 2048; micro 4 drops to 0.57 from HBM pressure, a 2.4B config to
    # 0.54 from weight streaming). adamw (~16 B/param fp32 state)
    # needs the next size down at each tier.
    lean = opt_name in ("adafactor", "factored_rms")
    if hbm > 60 << 30:        # v5p-95GB
        size, micro = (LlamaConfig.llama_7b, 2) if lean else (
            LlamaConfig.llama_1b, 8)
    elif hbm > 24 << 30:      # v4-32GB
        size, micro = (LlamaConfig.llama_1b, 4) if lean else (
            LlamaConfig.llama_410m, 8)
    else:                     # v5e/v5lite-16GB
        size, micro = (LlamaConfig.llama_wide_1b, 2) if lean else (
            LlamaConfig.llama_410m, 8)
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    cfg = size(max_seq_len=2048, attn_impl="flash", remat=remat,
               embed_impl="gather", norm_impl="fused",
               dtype=jnp.bfloat16)
    steps, warmup = 10, 2
    micro = int(os.environ.get("BENCH_MICRO_BATCH", micro))
    seq = int(os.environ.get("BENCH_SEQ", 2048))

    # data-parallel over every local chip at `micro` samples each; the
    # reported number is per chip
    chips = jax.local_device_count()
    micro_global = micro * chips
    mesh = create_mesh(MeshSpec(), jax.local_devices())
    model = Llama(cfg)
    if opt_name == "factored_rms":
        tx = optax.chain(optax.scale_by_factored_rms(),
                         optax.scale(-3e-4))
    elif opt_name == "adafactor":
        tx = optax.adafactor(3e-4)
    else:
        tx = optax.adamw(3e-4, weight_decay=0.1)
    sample = jnp.zeros((micro_global, seq), jnp.int32)
    trainer = build_trainer(
        model, tx, mesh, sample, cross_entropy_loss,
        accum_steps=1, micro_batch=micro_global,
    )
    state = trainer.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    shape = (micro_global, seq)
    tokens = rng.integers(0, cfg.vocab_size, shape, dtype=np.int32)
    targets = rng.integers(0, cfg.vocab_size, shape, dtype=np.int32)
    tok, tgt = trainer.shard_batch(tokens, targets)

    per_step: list = []
    _, dt, warmup_loss, final_loss = _timed_loop(
        trainer.step, state, tok, tgt, warmup=warmup, steps=steps,
        per_step=per_step)
    if final_loss != final_loss:
        raise SystemExit("NaN loss in the timed window")
    if final_loss >= warmup_loss:
        # a ~10-step window on synthetic data is noisy; a non-descending
        # loss is a warning, not a bench-killing failure
        print(f"WARNING: loss did not descend over the timed window "
              f"({warmup_loss} -> {final_loss})", file=sys.stderr)

    tokens_per_sec = micro_global * seq * steps / dt / chips
    mfu = (tokens_per_sec * _model_flops_per_token(cfg, seq)
           / peak_flops(device))
    return {
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(mfu, 4),
        "params_b": round(cfg.param_count() / 1e9, 2),
        "seq": seq,
        "opt": opt_name,
        "device": {"platform": device.platform,
                   "kind": device.device_kind, "count": chips},
        "critical_path": _critical_path_summary(per_step),
    }


def measure_main() -> int:
    """--measure subprocess: the headline measurement, isolated so a
    later TPU-owning phase (at-scale restore, 7B attempt) that fails can
    never take the headline metric down with it."""
    print(json.dumps(_measure()))
    return 0


def run_measure_bench(timeout_s: float = 900.0) -> dict:
    return _run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--measure"],
        timeout_s)


def main() -> int:
    skip_restore = os.environ.get("BENCH_SKIP_RESTORE") == "1"
    restore_result = {} if skip_restore else run_restore_bench()
    restore_s = -1.0 if skip_restore else _restore_seconds(restore_result)
    # every TPU phase runs in its OWN subprocess (one process per chip),
    # headline FIRST; no chip, no headline, non-zero exit
    headline = run_measure_bench()
    if "error" in headline:
        print(f"bench.py: the headline measurement failed: "
              f"{headline['error']}", file=sys.stderr)
        return 1
    restore_scale_s = -1.0
    restore_scale_result: dict = {}
    llama7b: dict = {}
    if not skip_restore:
        restore_scale_result = run_restore_bench(
            timeout_s=900.0, at_scale=True)
        restore_scale_s = _restore_seconds(restore_scale_result)
    if os.environ.get("BENCH_SKIP_7B") != "1":
        llama7b = run_7b_bench()

    tokens_per_sec = headline["tokens_per_sec"]
    mfu = headline["mfu"]
    result = {
        "metric": "llama_tokens_per_sec_per_chip",
        "value": tokens_per_sec,
        "unit": f"tokens/s ({headline['params_b']:.2f}B params, "
                f"seq {headline['seq']}, {headline['opt']}, "
                f"MFU {mfu:.3f}, "
                + (f"toy elastic_restore (CPU-staged) {restore_s:.1f}s)"
                   if restore_s >= 0 else "elastic_restore skipped)"),
        "vs_baseline": round(mfu / 0.40, 3),
        "device": headline["device"],
        "elastic_restore_seconds": restore_s,
        "elastic_restore_seconds_at_scale": restore_scale_s,
    }
    if headline.get("critical_path"):
        result["critical_path"] = headline["critical_path"]
    # the at-scale restore is the number the <30 s target is about:
    # its breakdown wins when both ran
    _fold_restore_fields(result, restore_result)
    if restore_scale_result.get("breakdown"):
        _fold_restore_fields(result, restore_scale_result)
    if llama7b:
        result["llama7b_tokens_per_sec_per_chip"] = llama7b.get(
            "tokens_per_sec", -1.0)
        if "mfu" in llama7b:
            result["llama7b_mfu"] = llama7b["mfu"]
        if "micro_batch" in llama7b:
            # a micro-1 value here means the micro-2 default fell back —
            # visible in the scoreboard, not just the subprocess log
            result["llama7b_micro_batch"] = llama7b["micro_batch"]
        notes = [llama7b[k] for k in ("error", "fallback_note")
                 if k in llama7b]
        if notes:   # both attempts failing keeps BOTH reasons visible
            result["llama7b_note"] = " | ".join(notes)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    if "--llama7b" in sys.argv:
        raise SystemExit(seven_b_main())
    if "--measure" in sys.argv:
        raise SystemExit(measure_main())
    raise SystemExit(main())
