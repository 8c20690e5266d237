"""E2E slice: ElasticTrainLoop with checkpoint-resume across a world resize.

Mirrors the reference e2e story (SURVEY.md §7 step 3 / examples/pytorch/
nanogpt): train, stop, resume on a different mesh with the same global
batch, verify the loss keeps decreasing and data position is restored.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.llama import Llama, LlamaConfig, cross_entropy_loss
from dlrover_tpu.parallel.mesh import MeshSpec
from dlrover_tpu.trainer.elastic_loop import ElasticTrainLoop, TrainLoopConfig
from dlrover_tpu.trainer.sampler import ElasticDistributedSampler


def _make_loop(cpu_devices, tmp_path, n_devices, global_batch=8,
               max_steps=3, **spec_kw):
    cfg = LlamaConfig.tiny(attn_impl="reference")
    model = Llama(cfg)
    tx = optax.adamw(1e-3)
    loop = ElasticTrainLoop(
        model, tx, cross_entropy_loss,
        TrainLoopConfig(
            global_batch=global_batch, seq_len=16,
            max_micro_per_replica=4, max_steps=max_steps,
            checkpoint_dir=str(tmp_path / "ckpt"),
            save_interval_steps=1,
            mesh_spec=MeshSpec(**spec_kw),
        ),
        devices=cpu_devices[:n_devices],
    )
    return cfg, loop


def _batches(cfg, global_batch, seq, count, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(count):
        tokens = rng.integers(0, cfg.vocab_size, (global_batch, seq),
                              dtype=np.int32)
        yield tokens, tokens  # autoregressive dummy


def test_train_checkpoint_resume_resized_world(cpu_devices, tmp_path):
    # Phase 1: 4 devices (dp=2 × tensor=2), 3 steps.
    cfg, loop = _make_loop(cpu_devices, tmp_path, 4, tensor=2)
    assert loop.dp == 2
    sampler = ElasticDistributedSampler(1024, shuffle=False)
    state, start = loop.restore_or_init(jax.random.PRNGKey(0), sampler)
    assert start == 0
    state, metrics = loop.run(
        state, _batches(cfg, 8, 16, 10), start_step=0, sampler=sampler)
    loss_phase1 = metrics["loss"]
    assert np.isfinite(loss_phase1)
    assert sampler.completed_num == 3 * 8
    loop.close()
    del state

    # Phase 2: world resized to 2 devices; same global batch via more accum.
    cfg, loop2 = _make_loop(cpu_devices, tmp_path, 2, max_steps=2)
    assert loop2.dp == 2  # data(2)
    sampler2 = ElasticDistributedSampler(1024, shuffle=False)
    state2, start2 = loop2.restore_or_init(jax.random.PRNGKey(1), sampler2)
    assert start2 == 3
    assert sampler2.completed_num == 24
    state2, metrics2 = loop2.run(
        state2, _batches(cfg, 8, 16, 10, seed=1),
        start_step=start2, sampler=sampler2)
    assert np.isfinite(metrics2["loss"])
    assert loop2.checkpointer.latest_step() == 5
    loop2.close()


def test_stop_request_forces_save(cpu_devices, tmp_path):
    cfg, loop = _make_loop(cpu_devices, tmp_path, 2, max_steps=100)
    loop.config = loop.config  # no-op; keep linters quiet
    loop.checkpointer._save_interval = 1000  # interval never hit
    state, _ = loop.restore_or_init(jax.random.PRNGKey(0))

    def gen():
        for i, batch in enumerate(_batches(cfg, 8, 16, 50)):
            if i == 2:
                loop._stop_requested.set()
            yield batch

    state, metrics = loop.run(state, gen())
    assert loop.checkpointer.latest_step() == 3  # forced save on stop
    loop.close()


def test_global_batch_held_fixed():
    """choose_accumulation keeps global batch constant as dp changes."""
    from dlrover_tpu.trainer.train_step import choose_accumulation

    for dp in (1, 2, 4, 8):
        accum, micro = choose_accumulation(32, dp, max_micro_per_replica=4)
        assert accum * micro == 32
        assert micro // dp <= 4


def test_pipeline_trainer_through_elastic_loop(cpu_devices, tmp_path):
    """PP is elastic too: the loop drives a PipelinedTrainer (external
    trainer surface) with flash checkpointing, and a fresh loop resumes
    from the committed step with resharded state."""
    import optax

    from dlrover_tpu.models.llama import (
        Llama,
        LlamaConfig,
        cross_entropy_loss,
    )
    from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
    from dlrover_tpu.trainer.elastic_loop import (
        ElasticTrainLoop,
        TrainLoopConfig,
    )
    from dlrover_tpu.trainer.pipeline_trainer import build_pipeline_trainer

    cfg = LlamaConfig.tiny(attn_impl="reference", dtype=jnp.float32)
    ckpt = str(tmp_path / "pp-ckpt")

    def make_loop():
        mesh = create_mesh(MeshSpec(data=2, pipe=2), cpu_devices[:4])
        trainer = build_pipeline_trainer(
            cfg, optax.adam(1e-3), mesh, num_microbatches=2,
            micro_batch=4, seq_len=16, loss_fn=cross_entropy_loss)
        return ElasticTrainLoop(
            None, None, None,
            TrainLoopConfig(global_batch=8, seq_len=16,
                            checkpoint_dir=ckpt, save_interval_steps=2),
            trainer=trainer,
        )

    loop = make_loop()
    state, start = loop.restore_or_init(jax.random.PRNGKey(0))
    assert start == 0
    state, metrics = loop.run(state, _batches(cfg, 8, 16, 4))
    loop.close()

    loop2 = make_loop()
    state2, start2 = loop2.restore_or_init(jax.random.PRNGKey(1))
    assert start2 == 4
    # restored chunk params keep their pipe sharding
    leaf = jax.tree.leaves(state2.params["chunks"])[0]
    assert leaf.sharding.spec[1] == "pipe"
    state2, metrics2 = loop2.run(state2, _batches(cfg, 8, 16, 2, seed=1),
                                 start_step=start2)
    assert np.isfinite(metrics2["loss"])
    loop2.close()


def test_profiler_trace_and_model_info(cpu_devices, tmp_path, monkeypatch):
    """The loop writes a jax.profiler trace for the configured window and
    reports ModelInfo to the master (reference: profile_extractor +
    tracing parity, SURVEY §5a)."""
    import optax

    from dlrover_tpu.master.job_master import JobMaster
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.models.llama import (
        Llama,
        LlamaConfig,
        cross_entropy_loss,
    )
    from dlrover_tpu.trainer.elastic_loop import (
        ElasticTrainLoop,
        TrainLoopConfig,
    )

    profile_dir = str(tmp_path / "trace")
    master = JobMaster(min_nodes=1, max_nodes=1, host="127.0.0.1")
    master.prepare()
    client = MasterClient(master.addr, node_id=0, node_rank=0)
    cfg = LlamaConfig.tiny(attn_impl="reference", dtype=jnp.float32)
    try:
        loop = ElasticTrainLoop(
            Llama(cfg), optax.adam(1e-3), cross_entropy_loss,
            TrainLoopConfig(global_batch=8, seq_len=16,
                            profile_dir=profile_dir,
                            profile_start_step=1, profile_num_steps=2),
            master_client=client,
            devices=cpu_devices[:2],
        )
        state, _ = loop.restore_or_init(jax.random.PRNGKey(0))
        state, metrics = loop.run(state, _batches(cfg, 8, 16, 4))
        loop.close()
        # a trace directory with xplane/perfetto output exists
        import glob

        assert glob.glob(profile_dir + "/**/*.xplane.pb", recursive=True) \
            or glob.glob(profile_dir + "/**/*.json.gz", recursive=True)
        # ModelInfo reached the master-side collector (no job manager
        # here, so assert via the servicer path having accepted it)
        info = master.servicer.report(
            __import__("dlrover_tpu.common.messages",
                       fromlist=["x"]).ModelInfo(param_count=1))
        assert info.success
    finally:
        client.close()
        master.stop()


# -- the step seen from inside: marks, train_window, completions -----------


class _Clock:
    """A clock that moves only when something says so."""

    def __init__(self):
        self.now = 100.0

    def monotonic(self):
        return self.now


def test_step_marks_sum_to_the_iterations_wall():
    from dlrover_tpu import obs

    clock = _Clock()
    entered = []
    marks = obs.StepMarks(
        clock.monotonic,
        annotate=lambda label: _Noting(entered, label))
    clock.now += 0.25                     # a poll nothing claims
    for name, label, took in (("fetch", "dlrover/fetch", 1.0),
                              ("shard", "dlrover/shard_batch", 0.5),
                              ("dispatch", "dlrover/dispatch", 2.0),
                              ("save", "dlrover/save", 4.0),
                              ("save", "dlrover/peer_stage", 1.0)):
        with marks.phase(name, label):
            clock.now += took
    clock.now += 0.125                    # bookkeeping nothing claims
    assert marks.close() == clock.now
    assert marks.wall == pytest.approx(8.875)
    assert marks.seconds == {"fetch": 1.0, "shard": 0.5, "dispatch": 2.0,
                             "save": 5.0, "report": 0.0}
    assert marks.other == pytest.approx(0.375)
    assert sum(marks.seconds.values()) + marks.other == pytest.approx(
        marks.wall)
    assert entered == ["dlrover/fetch", "dlrover/shard_batch",
                       "dlrover/dispatch", "dlrover/save",
                       "dlrover/peer_stage"]


class _Noting:
    def __init__(self, log, label):
        self._log, self._label = log, label

    def __enter__(self):
        self._log.append(self._label)

    def __exit__(self, *exc):
        return False


class _Flag:
    """A stand-in for a step's scalar output."""

    def __init__(self):
        self.ready = False

    def is_ready(self):
        return self.ready


def test_steps_in_flight_rise_fall_and_the_step_time_follows_completions():
    from dlrover_tpu import obs

    clock = _Clock()
    flight = obs.StepsInFlight(clock.monotonic)
    assert flight.drain_step_time() == 0.0        # nothing dispatched
    handles = [_Flag() for _ in range(6)]
    for handle in handles[:4]:
        flight.dispatched(handle)
        clock.now += 0.01                          # dispatch runs ahead
        assert flight.poll() == 0
    assert len(flight) == 4 and flight.completed == 0
    # four steps queued in 40 ms and none done: no speed evidence, and
    # above all not the 10 ms a dispatch took
    assert flight.drain_step_time() == 0.0
    clock.now = 100.5
    handles[0].ready = True
    handles[2].ready = True                        # behind an unready one
    assert flight.poll() == 1
    assert len(flight) == 3 and flight.completed == 1
    assert flight.drain_step_time() == pytest.approx(0.5)   # since dispatch 1
    assert flight.drain_step_time() == 0.0         # drained: no new evidence
    clock.now = 101.5
    handles[1].ready = True
    assert flight.poll() == 2                      # 2 and the waiting 3
    assert len(flight) == 1
    flight.dispatched(handles[4])
    flight.dispatched(0.25)                        # a plain float: done
    assert len(flight) == 3
    assert flight.drain_step_time() == pytest.approx(0.5)   # 2 in 1.0 s
    clock.now = 102.0
    handles[3].ready = handles[4].ready = True
    assert flight.poll() == 3 and len(flight) == 0
    assert flight.completed == 6


class _RecordingClient:
    """What the loop asks of a master client, answered locally."""

    node_id = node_rank = 0

    def __init__(self):
        self.step_reports = []
        self.telemetry = []

    def get_shard_plan(self):
        return None

    def report_model_info(self, **kw):
        return True

    def probe_clock(self):
        return 0.0

    def report_global_step(self, step, **kw):
        self.step_reports.append((step, kw))
        return True

    def report_telemetry(self, **kw):
        self.telemetry.append(kw)
        return True


def _catch_train_windows():
    from dlrover_tpu import obs

    caught = []

    def sink(span):
        if span.name == "train_window":
            caught.append(span)

    obs.add_span_sink(sink)
    return caught, lambda: obs.remove_span_sink(sink)


@pytest.mark.parametrize("with_client", [False, True],
                         ids=["no_client", "client"])
def test_train_window_every_report_interval(cpu_devices, with_client):
    """One span per ten steps and one for the remainder, whether or not
    a master is there; every attr a number; each span's marks sum to its
    wall time; the spans tile the steps."""
    cfg = LlamaConfig.tiny(attn_impl="reference")
    client = _RecordingClient() if with_client else None
    loop = ElasticTrainLoop(
        Llama(cfg), optax.adamw(1e-3), cross_entropy_loss,
        TrainLoopConfig(global_batch=8, seq_len=16, max_steps=0),
        master_client=client, devices=cpu_devices[:2])
    caught, release = _catch_train_windows()
    try:
        state, _ = loop.restore_or_init(jax.random.PRNGKey(0))
        state, metrics = loop.run(state, _batches(cfg, 8, 16, 24))
    finally:
        release()
        loop.close()
    assert metrics["step"] == 24
    attrs = [span.attrs for span in caught]
    assert [a["steps"] for a in attrs] == [10, 10, 4]
    assert [a["first_step"] for a in attrs] == [1, 11, 21]
    for span, a in zip(caught, attrs):
        assert set(a) == {
            "steps", "first_step", "wall_s", "fetch_s", "shard_s",
            "dispatch_s", "save_s", "report_s", "other_s", "completed",
            "in_flight_mean", "in_flight_max"}
        assert all(isinstance(v, (int, float))
                   and not isinstance(v, bool) for v in a.values())
        parts = sum(a[k] for k in ("fetch_s", "shard_s", "dispatch_s",
                                   "save_s", "report_s", "other_s"))
        assert parts == pytest.approx(a["wall_s"], rel=1e-6)
        assert span.duration_s == a["wall_s"]
        assert a["in_flight_max"] >= a["in_flight_mean"] >= 0
    # the last span took the iteration that found the data exhausted
    assert sum(a["completed"] for a in attrs) <= 24
    if with_client:
        assert [step <= asked for (step, _), asked in
                zip(client.step_reports, (10, 20))] == [True, True]
        assert all(a["report_s"] > 0 for a in attrs[:2])
        shipped = [s["name"] for batch in client.telemetry
                   for s in batch.get("spans", [])]
        assert "train_window" in shipped
    else:
        assert all(a["report_s"] == 0 for a in attrs)


class _FakeDevice:
    """Runs queued steps one after the other, ``step_s`` each, and lets
    the host queue ``limit`` ahead before a dispatch blocks: a runtime's
    run-ahead, on a clock the test owns."""

    def __init__(self, clock, step_s, limit, dispatch_s):
        self.clock, self.step_s, self.limit = clock, step_s, limit
        self.dispatch_s = dispatch_s
        self.done_at = []

    def submit(self):
        clock = self.clock
        clock.now += self.dispatch_s
        start = max(clock.now, self.done_at[-1] if self.done_at else 0.0)
        self.done_at.append(start + self.step_s)
        if len(self.done_at) > self.limit:
            clock.now = max(clock.now, self.done_at[-self.limit - 1])
        return _Output(clock, self.done_at[-1])


class _Output:
    def __init__(self, clock, done_at):
        self._clock, self._done_at = clock, done_at

    def is_ready(self):
        return self._clock.now >= self._done_at

    def __float__(self):
        return 0.0


class _FakeTrainer:
    grad_fn = None
    accum_steps = 1
    micro_batch = 8

    def __init__(self, mesh, device):
        self.mesh, self.device = mesh, device

    def abstract_state(self, rng):
        raise NotImplementedError("no model behind the fake")

    def shard_batch(self, tokens, targets):
        return tokens, targets

    def step(self, state, tokens, targets):
        return state, {"loss": self.device.submit()}


def test_reports_follow_completions_never_dispatch(cpu_devices,
                                                   monkeypatch):
    """A host that queues 16 steps ahead of a device taking 0.1 s a step:
    the reports carry the steps the device FINISHED, a step time taken
    from completions ("no data" before the first one) and never an MFU
    above 1, where the dispatch clock (1 ms a step) read 50."""
    import types

    from dlrover_tpu.parallel.mesh import create_mesh
    from dlrover_tpu.trainer import elastic_loop

    clock = _Clock()
    monkeypatch.setattr(elastic_loop, "_time", types.SimpleNamespace(
        monotonic=clock.monotonic, time=lambda: 1.7e9 + clock.now))
    device = _FakeDevice(clock, step_s=0.1, limit=16, dispatch_s=0.001)
    client = _RecordingClient()
    loop = ElasticTrainLoop(
        None, None, None,
        TrainLoopConfig(global_batch=8, seq_len=16, max_steps=60),
        master_client=client,
        trainer=_FakeTrainer(create_mesh(MeshSpec(), cpu_devices[:1]),
                             device))
    # a FLOPs model under which 0.1 s a step is an MFU of exactly 0.5
    loop._flops_per_token = 1.0
    loop._peak_flops_total = 8 * 16 / 0.1 / 0.5
    caught, release = _catch_train_windows()
    batches = ((np.zeros((8, 16), np.int32),) * 2 for _ in range(100))
    try:
        loop.run(None, batches)
    finally:
        release()
        loop.close()
    reports = {asked: (step, kw) for asked, (step, kw)
               in zip(range(10, 70, 10), client.step_reports)}
    assert len(reports) == 6
    # step 10 is dispatched 10 ms in: nothing is done, nothing is known
    done, first = reports[10]
    assert done == 0
    assert first["step_time_s"] == 0.0 and first["mfu"] == -1.0
    for asked in (20, 30, 40, 50, 60):
        done, kw = reports[asked]
        assert done == asked - 16            # what the device finished
        assert kw["step_time_s"] == pytest.approx(0.1, rel=0.02)
        assert kw["mfu"] == pytest.approx(0.5, rel=0.02)
    assert all(kw["mfu"] <= 1.0 for _, kw in client.step_reports)
    attrs = [span.attrs for span in caught]
    assert [a["in_flight_max"] for a in attrs] == [10, 16, 16, 16, 16, 16]
    assert [a["completed"] for a in attrs] == [0, 4, 10, 10, 10, 10]
    # the loop's own step time, as the benchmark's reader takes it
    steady = attrs[2:]
    assert (sum(a["wall_s"] for a in steady)
            / sum(a["completed"] for a in steady)) == pytest.approx(0.1)
