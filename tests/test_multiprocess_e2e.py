"""TRUE multi-process distributed training through the CLI stack:
a master process + two agent processes, each spawning a JAX worker;
jax.distributed forms the global mesh from the master's rendezvous + KV
coordinator bootstrap (the multi-host story with real process isolation —
reference analogue: the system tests running master + worker processes
sharing DLROVER_MASTER_ADDR, SURVEY §4)."""

import pytest

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every test here spawns subprocesses (agents, workers, jax.distributed
# groups) — minutes-slow; excluded from tier-1 (-m "not slow") and from
# the fast unit core (-m "not e2e")
pytestmark = [pytest.mark.e2e, pytest.mark.slow]

WORKER = """
from dlrover_tpu.agent.elastic_agent import init_distributed
init_distributed()
import jax
import numpy as np, optax
from dlrover_tpu.models.llama import Llama, LlamaConfig, cross_entropy_loss
from dlrover_tpu.trainer.elastic_loop import ElasticTrainLoop, TrainLoopConfig

assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 4, jax.device_count()
cfg = LlamaConfig.tiny(attn_impl="reference", norm_impl="reference")
loop = ElasticTrainLoop(
    Llama(cfg), optax.adam(1e-3), cross_entropy_loss,
    TrainLoopConfig(global_batch=4, seq_len=32, max_steps=2),
)
state, start = loop.restore_or_init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
def gen():
    for _ in range(2):
        t = rng.integers(0, cfg.vocab_size, (4, 32), dtype=np.int32)
        yield t, t
state, metrics = loop.run(state, gen())
print(f"MP-RESULT proc={jax.process_index()} loss={metrics['loss']:.6f}",
      flush=True)
loop.close()
"""


def test_two_process_distributed_training(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)

    master = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.master.job_master",
         "--min-nodes", "2", "--max-nodes", "2"],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    agents = []
    addr_box = {}

    def drain():
        # read master output for the address, then keep draining so the
        # pipe never fills and blocks the master
        for line in master.stdout:
            if "addr" not in addr_box and \
                    "DLROVER_TPU_MASTER_ADDR=" in line:
                addr_box["addr"] = line.split("=", 1)[1].strip()

    import threading

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    try:
        deadline = time.time() + 60
        while time.time() < deadline and "addr" not in addr_box:
            time.sleep(0.2)
        addr = addr_box.get("addr", "")
        assert addr, "master never printed its address"

        for rank in (0, 1):
            agents.append(subprocess.Popen(
                [sys.executable, "-m", "dlrover_tpu.run",
                 "--nnodes", "2", "--node-rank", str(rank),
                 "--master-addr", addr, "--devices-per-node", "2",
                 "--monitor-interval", "0.3", str(worker)],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            ))
        outs = [proc.communicate(timeout=240)[0] for proc in agents]
        assert all(proc.returncode == 0 for proc in agents), outs
        losses = set()
        for out in outs:
            for line in out.splitlines():
                if line.startswith("MP-RESULT"):
                    losses.add(line.split("loss=")[1])
        # both processes computed the SAME global loss (one SPMD program)
        assert len(losses) == 1, outs
    finally:
        for proc in agents:
            proc.kill()
        master.kill()


SCALE_WORKER = """
from dlrover_tpu.agent.elastic_agent import init_distributed
init_distributed()
import jax, sys
import numpy as np, optax
from dlrover_tpu.models.llama import Llama, LlamaConfig, cross_entropy_loss
from dlrover_tpu.trainer.elastic_loop import ElasticTrainLoop, TrainLoopConfig

cfg = LlamaConfig.tiny(attn_impl="reference", norm_impl="reference")
loop = ElasticTrainLoop(
    Llama(cfg), optax.adam(1e-3), cross_entropy_loss,
    TrainLoopConfig(global_batch=4, seq_len=32, max_steps=30,
                    checkpoint_dir=sys.argv[1], save_interval_steps=2),
)
state, start = loop.restore_or_init(jax.random.PRNGKey(0))
print(f"SCALE world={jax.process_count()} start={start}", flush=True)
rng = np.random.default_rng(start)
def gen():
    import time as _t
    while True:
        t = rng.integers(0, cfg.vocab_size, (4, 32), dtype=np.int32)
        yield t, t
        _t.sleep(0.3)   # slow steps: the world=1 phase must outlive the
                        # second agent's arrival
loop.config.max_steps = 30 - start
state, metrics = loop.run(state, gen(), start_step=start)
print(f"SCALE-DONE world={jax.process_count()} "
      f"step={int(metrics['step'])}", flush=True)
loop.close()
"""


def test_scale_down_mid_run_through_cli(tmp_path):
    """Elastic scale-DOWN e2e (the reference's core
    recovery claim, README.md:55-61): two agents train at world=2 (min
    1); one AGENT process group is SIGKILLed (agent + its worker — no
    failure RPC ever reaches the master). The master's liveness reaper
    declares the silent member dead and invalidates the world; the
    survivor's agent restarts its worker, which re-forms at world=1 and
    resumes from the committed checkpoint. The shrink is clocked."""
    import signal
    import threading

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    # tight reaper so the test doesn't wait the production 90 s
    env["DLROVER_TPU_DEAD_NODE_TIMEOUT_S"] = "5"
    worker = tmp_path / "worker.py"
    worker.write_text(SCALE_WORKER)
    ckpt = str(tmp_path / "ckpt")

    master = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.master.job_master",
         "--min-nodes", "1", "--max-nodes", "2"],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    agents, outputs = [], {}
    addr_box = {}

    def drain_master():
        for line in master.stdout:
            if "addr" not in addr_box and \
                    "DLROVER_TPU_MASTER_ADDR=" in line:
                addr_box["addr"] = line.split("=", 1)[1].strip()

    def start_agent(rank):
        proc = subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.run",
             "--nnodes", "1:2", "--node-rank", str(rank),
             "--master-addr", addr_box["addr"],
             "--devices-per-node", "2", "--max-restarts", "3",
             "--monitor-interval", "0.3", str(worker), ckpt],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, start_new_session=True,
        )
        agents.append(proc)
        outputs[rank] = []

        def drain():
            for line in proc.stdout:
                outputs[rank].append(line)

        threading.Thread(target=drain, daemon=True).start()
        return proc

    def saw(rank, needle, timeout=240):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if any(needle in line for line in outputs[rank]):
                return True
            time.sleep(0.3)
        return False

    threading.Thread(target=drain_master, daemon=True).start()
    try:
        deadline = time.time() + 60
        while time.time() < deadline and "addr" not in addr_box:
            time.sleep(0.2)
        assert addr_box.get("addr"), "master never printed its address"

        a0 = start_agent(0)
        a1 = start_agent(1)
        assert saw(0, "SCALE world=2 start=0"), outputs[0]
        assert saw(1, "SCALE world=2 start=0"), outputs[1]
        # wait for a COMMITTED checkpoint so the survivor has something
        # to resume from
        deadline = time.time() + 180
        while time.time() < deadline:
            if os.path.isdir(ckpt) and any(
                    name.isdigit() and int(name) >= 2
                    for name in os.listdir(ckpt)):
                break
            time.sleep(0.3)
        else:
            raise AssertionError(
                f"no committed checkpoint at world=2: {outputs[0]}")

        # SIGKILL agent 1's whole process group: agent AND worker die
        # silently — the master only finds out via the liveness reaper
        t_kill = time.time()
        os.killpg(a1.pid, signal.SIGKILL)
        a1.wait(timeout=30)

        assert saw(0, "SCALE world=1"), outputs[0]
        shrink_s = time.time() - t_kill
        assert a0.wait(timeout=300) == 0, outputs[0]
        resumed = [line for line in outputs[0]
                   if "SCALE world=1 start=" in line]
        assert resumed and int(
            resumed[0].split("start=")[1]) > 0, outputs[0]
        assert saw(0, "SCALE-DONE world=1", timeout=10), outputs[0]
        print(f"SCALE-DOWN kill->world=1 resume in {shrink_s:.1f}s")
    finally:
        for proc in agents:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
        master.kill()


def test_scale_up_mid_run_through_cli(tmp_path):
    """Elastic scale-UP e2e: one agent trains at world=1 (min 1 of
    max 2); a second agent joins mid-run; the master signals the
    membership change, the agent restarts its worker, and both
    incarnations re-form at world=2 resuming from the committed
    checkpoint (start > 0)."""
    import threading

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    worker = tmp_path / "worker.py"
    worker.write_text(SCALE_WORKER)
    ckpt = str(tmp_path / "ckpt")

    master = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.master.job_master",
         "--min-nodes", "1", "--max-nodes", "2"],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    agents, outputs = [], {}
    addr_box = {}

    def drain_master():
        for line in master.stdout:
            if "addr" not in addr_box and \
                    "DLROVER_TPU_MASTER_ADDR=" in line:
                addr_box["addr"] = line.split("=", 1)[1].strip()

    def start_agent(rank):
        proc = subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.run",
             "--nnodes", "1:2", "--node-rank", str(rank),
             "--master-addr", addr_box["addr"],
             "--devices-per-node", "2", "--max-restarts", "3",
             "--monitor-interval", "0.3", str(worker), ckpt],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        agents.append(proc)
        outputs[rank] = []

        def drain():
            for line in proc.stdout:
                outputs[rank].append(line)

        threading.Thread(target=drain, daemon=True).start()
        return proc

    def saw(rank, needle, timeout=240):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if any(needle in line for line in outputs[rank]):
                return True
            time.sleep(0.3)
        return False

    threading.Thread(target=drain_master, daemon=True).start()
    try:
        deadline = time.time() + 60
        while time.time() < deadline and "addr" not in addr_box:
            time.sleep(0.2)
        assert addr_box.get("addr"), "master never printed its address"

        a0 = start_agent(0)
        assert saw(0, "SCALE world=1 start=0"), outputs[0]
        # wait for a COMMITTED checkpoint before the new node arrives
        # (the first step includes the compile, so a fixed sleep races)
        deadline = time.time() + 180
        while time.time() < deadline:
            if os.path.isdir(ckpt) and any(
                    name.isdigit() and int(name) >= 2
                    for name in os.listdir(ckpt)):
                break
            time.sleep(0.3)
        else:
            raise AssertionError(
                f"no committed checkpoint at world=1: {outputs[0]}")
        a1 = start_agent(1)

        assert saw(0, "SCALE world=2"), outputs[0]
        assert saw(1, "SCALE world=2"), outputs[1]
        assert a0.wait(timeout=300) == 0, outputs[0]
        assert a1.wait(timeout=300) == 0, outputs[1]
        # the restarted incarnation resumed from the checkpoint
        resumed = [line for line in outputs[0]
                   if "SCALE world=2 start=" in line]
        assert resumed and int(
            resumed[0].split("start=")[1]) > 0, outputs[0]
        assert saw(0, "SCALE-DONE world=2", timeout=10), outputs[0]
    finally:
        for proc in agents:
            proc.kill()
        master.kill()
