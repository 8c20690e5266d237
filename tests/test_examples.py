"""examples/ (nanogpt, longcontext) through the REAL CLI stack: master +
agent + worker subprocesses, with checkpoint-resume (reference parity:
the shell system tests that run the stack outside pytest,
examples/tensorflow/criteo_deeprec/run.sh:15-18)."""

import os
import subprocess
import sys

import pytest

# every test here spawns subprocesses (agents, workers, jax.distributed
# groups) — minutes-slow; excluded from tier-1 (-m "not slow") and from
# the fast unit core (-m "not e2e")
pytestmark = [pytest.mark.e2e, pytest.mark.slow]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN = os.path.join(REPO, "examples", "nanogpt", "train.py")
TRAIN_LONGCTX = os.path.join(REPO, "examples", "longcontext", "train.py")
TRAIN_MOE = os.path.join(REPO, "examples", "moe", "train.py")
TRAIN_SPARSE_MOE = os.path.join(REPO, "examples", "sparse_moe", "train.py")


def run_cli(tmp_path, extra, timeout=240, script=TRAIN):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "dlrover_tpu.run", "--standalone",
         "--devices-per-node", "1", "--monitor-interval", "0.2",
         script] + extra,
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=REPO,
    )


def test_nanogpt_standalone_trains_and_resumes(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    log1 = str(tmp_path / "run1.log")
    proc = run_cli(tmp_path, [
        "--steps", "6", "--save-interval", "3",
        "--global-batch", "8", "--seq", "32",
        "--ckpt-dir", ckpt, "--log-file", log1,
    ])
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = open(log1).read()
    assert "start_step=0" in lines
    assert "done step=6" in lines
    assert os.path.isdir(ckpt) and os.listdir(ckpt)

    # Second run with more steps resumes from the committed checkpoint —
    # the data position travels with the model state.
    log2 = str(tmp_path / "run2.log")
    proc = run_cli(tmp_path, [
        "--steps", "8", "--save-interval", "3",
        "--global-batch", "8", "--seq", "32",
        "--ckpt-dir", ckpt, "--log-file", log2,
    ])
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = open(log2).read()
    assert "start_step=6" in lines
    assert "done step=8" in lines


def test_nanogpt_worker_kill_restarts_and_resumes(tmp_path):
    """SIGKILL the training worker mid-run: the agent respawns it and the
    second incarnation resumes from the checkpoint (the README's kill
    demo, automated)."""
    import signal
    import threading
    import time

    ckpt = str(tmp_path / "ckpt")
    log = str(tmp_path / "kill.log")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.run", "--standalone",
         "--devices-per-node", "1", "--monitor-interval", "0.2",
         TRAIN, "--steps", "200", "--save-interval", "2",
         "--global-batch", "8", "--seq", "32",
         "--ckpt-dir", ckpt, "--log-file", log],
        env=env, cwd=REPO, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        # wait for a committed checkpoint, then kill the WORKER process
        # (the grandchild running train.py)
        deadline = time.time() + 240
        worker_pid = None
        while time.time() < deadline:
            if os.path.isdir(ckpt) and any(
                    name.isdigit() and int(name) >= 2
                    for name in os.listdir(ckpt)):
                out = subprocess.run(
                    ["pgrep", "-f", f"python {TRAIN}"],
                    capture_output=True, text=True)
                pids = [int(p) for p in out.stdout.split()]
                if pids:
                    worker_pid = pids[0]
                    break
            time.sleep(0.2)
        assert worker_pid, "no committed checkpoint / worker found"
        os.kill(worker_pid, signal.SIGKILL)

        # the respawned worker logs a non-zero start step
        def resumed():
            try:
                return any("start_step=" in line
                           and "start_step=0" not in line
                           for line in open(log))
            except FileNotFoundError:
                return False

        deadline = time.time() + 240
        while time.time() < deadline and not resumed():
            time.sleep(0.2)
        assert resumed(), open(log).read()
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait(timeout=30)


def test_longcontext_ring_attention_standalone(tmp_path):
    """The long-context example through the real CLI: ring attention on
    a sequence-sharded mesh (4 virtual CPU devices), checkpoint commit,
    then a resumed run continuing from the saved step."""
    ckpt = str(tmp_path / "ckpt")
    log1 = str(tmp_path / "run1.log")
    proc = run_cli(tmp_path, [
        "--steps", "4", "--save-interval", "2",
        "--global-batch", "2", "--seq", "256", "--seq-shards", "4",
        "--hidden", "128", "--layers", "2",
        "--ckpt-dir", ckpt, "--log-file", log1,
    ], script=TRAIN_LONGCTX, timeout=360)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = open(log1).read()
    assert "start_step=0" in lines and "seq_shards=4" in lines
    assert "done step=4" in lines
    assert os.path.isdir(ckpt) and os.listdir(ckpt)

    log2 = str(tmp_path / "run2.log")
    proc = run_cli(tmp_path, [
        "--steps", "6", "--save-interval", "2",
        "--global-batch", "2", "--seq", "256", "--seq-shards", "4",
        "--hidden", "128", "--layers", "2",
        "--ckpt-dir", ckpt, "--log-file", log2,
    ], script=TRAIN_LONGCTX, timeout=360)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = open(log2).read()
    assert "start_step=4" in lines
    assert "done step=6" in lines


def test_moe_expert_parallel_standalone(tmp_path):
    """The MoE example through the real CLI: expert-sharded mesh (4 of
    the virtual CPU devices), router aux losses through the standard
    trainer, checkpoint commit, then a resumed run continuing from the
    saved step."""
    ckpt = str(tmp_path / "ckpt")
    log1 = str(tmp_path / "run1.log")
    proc = run_cli(tmp_path, [
        "--steps", "4", "--save-interval", "2",
        "--global-batch", "8", "--seq", "64",
        "--experts", "4", "--expert-shards", "4",
        "--hidden", "64", "--layers", "2",
        "--ckpt-dir", ckpt, "--log-file", log1,
    ], script=TRAIN_MOE, timeout=360)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = open(log1).read()
    assert "start_step=0" in lines and "expert_shards=4" in lines
    assert "done step=4" in lines
    assert os.path.isdir(ckpt) and os.listdir(ckpt)

    log2 = str(tmp_path / "run2.log")
    proc = run_cli(tmp_path, [
        "--steps", "6", "--save-interval", "2",
        "--global-batch", "8", "--seq", "64",
        "--experts", "4", "--expert-shards", "4",
        "--hidden", "64", "--layers", "2",
        "--ckpt-dir", ckpt, "--log-file", log2,
    ], script=TRAIN_MOE, timeout=360)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = open(log2).read()
    assert "start_step=4" in lines
    assert "done step=6" in lines


TRAIN_STREAMING = os.path.join(REPO, "examples", "streaming", "train.py")


def test_streaming_standalone_trains_and_resumes(tmp_path):
    """The streaming (>HBM per-layer) example through the real CLI:
    auto_accelerate's `streaming` strategy lowers to the injected
    StreamingTrainer, trains, checkpoints, and a second run resumes
    from the saved step with the sampler position intact."""
    ckpt = str(tmp_path / "ckpt")
    log1 = str(tmp_path / "run1.log")
    proc = run_cli(tmp_path, [
        "--steps", "4", "--save-interval", "2",
        "--batch", "2", "--seq", "64",
        "--hidden", "64", "--layers", "2",
        "--ckpt-dir", ckpt, "--log-file", log1,
    ], script=TRAIN_STREAMING, timeout=360)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = open(log1).read()
    assert "start_step=0" in lines
    assert "done step=4" in lines
    assert os.path.isdir(ckpt) and os.listdir(ckpt)

    log2 = str(tmp_path / "run2.log")
    proc = run_cli(tmp_path, [
        "--steps", "6", "--save-interval", "2",
        "--batch", "2", "--seq", "64",
        "--hidden", "64", "--layers", "2",
        "--ckpt-dir", ckpt, "--log-file", log2,
    ], script=TRAIN_STREAMING, timeout=360)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = open(log2).read()
    assert "start_step=4" in lines
    assert "done step=6" in lines


def test_sparse_moe_tiny_preset_standalone(tmp_path):
    """`models/keye.py`'s tiny preset through the real CLI: attention over
    the keys an indexer selects, its KL term through the standard trainer's
    `losses`, experts held by share with none dropped; the step's metrics
    carry the expert layer's load counter."""
    log = str(tmp_path / "run.log")
    proc = run_cli(tmp_path, ["--steps", "4", "--log-file", log],
                   script=TRAIN_SPARSE_MOE, timeout=360)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = open(log).read()
    assert "start_step=0" in lines and "held=4/8" in lines
    assert "done step=4" in lines
    load = float(lines.split("load=")[1].split()[0])
    assert 1.0 <= load <= 4.0       # max over mean of four held experts
