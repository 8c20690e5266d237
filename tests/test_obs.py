"""obs/ telemetry layer: exposition golden, span nesting/propagation,
flight-recorder dump-on-signal, the agent↔master telemetry path, the
elastic-loop recompile span after a simulated resize, and the
simulated-failover acceptance (dump contains rendezvous + recompile +
checkpoint-restore spans; exposition carries step-time / tokens-s /
rendezvous-count series). Also gates graftlint clean on obs/."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import optax
import pytest

from dlrover_tpu import obs
from dlrover_tpu.common import messages as msg
from dlrover_tpu.common.constants import RendezvousName
from dlrover_tpu.obs.flight_recorder import FlightRecorder
from dlrover_tpu.obs.metrics import MetricsRegistry

REPO = Path(__file__).resolve().parent.parent


# -- metrics registry ------------------------------------------------------


def test_exposition_golden():
    registry = MetricsRegistry()
    requests = registry.counter("demo_requests_total", "Requests served",
                                labelnames=("code",))
    requests.labels(code="200").inc()
    requests.labels(code="200").inc()
    requests.labels(code="500").inc()
    registry.gauge("demo_temperature_celsius",
                   "Current temperature").set(36.5)
    latency = registry.histogram("demo_latency_seconds", "Latency",
                                 buckets=(0.1, 0.5))
    latency.observe(0.1)    # le="0.1" includes the bound
    latency.observe(0.5)
    latency.observe(2.0)    # lands in +Inf only
    expected = (
        "# HELP demo_latency_seconds Latency\n"
        "# TYPE demo_latency_seconds histogram\n"
        'demo_latency_seconds_bucket{le="0.1"} 1\n'
        'demo_latency_seconds_bucket{le="0.5"} 2\n'
        'demo_latency_seconds_bucket{le="+Inf"} 3\n'
        "demo_latency_seconds_sum 2.6\n"
        "demo_latency_seconds_count 3\n"
        "# HELP demo_requests_total Requests served\n"
        "# TYPE demo_requests_total counter\n"
        'demo_requests_total{code="200"} 2\n'
        'demo_requests_total{code="500"} 1\n'
        "# HELP demo_temperature_celsius Current temperature\n"
        "# TYPE demo_temperature_celsius gauge\n"
        "demo_temperature_celsius 36.5\n"
    )
    assert registry.render() == expected


def test_registry_label_and_type_safety():
    registry = MetricsRegistry()
    registry.counter("a_total", "a", labelnames=("x",))
    with pytest.raises(ValueError, match="re-registered"):
        registry.gauge("a_total", "a", labelnames=("x",))
    with pytest.raises(ValueError, match="declared"):
        registry.counter("a_total", "a", labelnames=("x",)).labels(y="1")
    # malformed names must be rejected at registration (one bad family
    # would break every subsequent scrape of the whole endpoint)
    with pytest.raises(ValueError, match="invalid metric name"):
        registry.gauge("bad name\n", "g")
    with pytest.raises(ValueError, match="invalid label name"):
        registry.gauge("ok_name", "g", labelnames=("bad key",))


def test_servicer_drops_malformed_remote_sample():
    from dlrover_tpu.master.servicer import MasterServicer

    servicer = MasterServicer()
    response = servicer.report(msg.TelemetryReport(
        node_id=1,
        samples=[msg.MetricSample(kind="gauge", name="bad name\n",
                                  value=1.0, labels={"node": "1"}),
                 msg.MetricSample(kind="gauge", name="good_after_bad",
                                  value=2.0, labels={"node": "1"})],
    ))
    assert response.success            # report path survives
    assert servicer.telemetry_queue.flush(timeout_s=5.0)
    rendered = obs.get_registry().render()
    assert "bad name" not in rendered  # malformed family never registered
    assert 'good_after_bad{node="1"} 2' in rendered
    # the endpoint still renders end-to-end
    assert rendered.endswith("\n")


def test_nan_value_renders_instead_of_breaking_scrape():
    registry = MetricsRegistry()
    registry.gauge("maybe_nan", "g").set(float("nan"))
    assert "maybe_nan NaN" in registry.render()


def test_gauge_callback_and_http_exporter():
    import urllib.request

    registry = MetricsRegistry()
    registry.gauge("live_value", "callback-backed").set_function(
        lambda: 7.25)
    server, port = obs.start_http_exporter(registry, host="127.0.0.1")
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    finally:
        server.shutdown()
    assert "live_value 7.25" in body


# -- spans -----------------------------------------------------------------


def test_span_nesting_and_cross_process_propagation():
    with obs.span("parent") as parent:
        ctx = obs.current_context()
        assert ctx == {"trace_id": parent.trace_id,
                       "span_id": parent.span_id}
        with obs.span("child") as child:
            assert child.trace_id == parent.trace_id
            assert child.parent_id == parent.span_id
    assert obs.current_context() is None
    # remote side: the serialized context parents a span in "another
    # process"
    with obs.span("remote_child", parent=ctx) as remote:
        pass
    assert remote.trace_id == parent.trace_id
    assert remote.parent_id == parent.span_id
    assert parent.duration_s >= child.duration_s >= 0.0


def test_span_stacks_are_per_thread():
    seen = {}

    def other_thread():
        with obs.span("other") as s:
            seen["parent_id"] = s.parent_id

    with obs.span("main_span"):
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    assert seen["parent_id"] == ""  # no inherited parent across threads


def test_span_error_status_and_sink():
    captured = []
    obs.add_span_sink(captured.append)
    try:
        with pytest.raises(RuntimeError):
            with obs.span("exploding"):
                raise RuntimeError("boom")
    finally:
        obs.remove_span_sink(captured.append)
    finished = [s for s in captured if s.name == "exploding"]
    assert finished and finished[0].status == "error"


def test_a_process_inherits_its_trace_from_the_environment(monkeypatch):
    """A worker's root spans parent under the agent's rendezvous span
    that the env names; explicit and nested parents still win."""
    monkeypatch.setenv(obs.TRACE_PARENT_ENV, obs.encode_context(
        {"trace_id": "feedbeef", "span_id": "0123abcd"}))
    with obs.span("backend_init") as root:
        with obs.span("nested") as nested:
            pass
    recorded = obs.record_span("train_window", 0.5)
    with obs.span("remote", parent={"trace_id": "t2", "span_id": "s2"}) \
            as remote:
        pass
    assert (root.trace_id, root.parent_id) == ("feedbeef", "0123abcd")
    assert (recorded.trace_id, recorded.parent_id) == ("feedbeef",
                                                       "0123abcd")
    assert (nested.trace_id, nested.parent_id) == ("feedbeef", root.span_id)
    assert (remote.trace_id, remote.parent_id) == ("t2", "s2")
    for garbage in ("", "no-colon", ":no-trace"):
        monkeypatch.setenv(obs.TRACE_PARENT_ENV, garbage)
        with obs.span("fresh") as fresh:
            pass
        assert fresh.parent_id == "" and fresh.trace_id != "feedbeef"


def test_attach_hands_a_thread_its_starters_context():
    seen = {}

    def compile_thread(context):
        with obs.attach(context):
            with obs.span("recompile") as s:
                seen["ids"] = (s.trace_id, s.parent_id)
        with obs.span("after") as s:
            seen["after"] = s.parent_id

    with obs.span("restore_or_init") as restore:
        t = threading.Thread(target=compile_thread,
                             args=(restore.context(),))
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert seen["ids"] == (restore.trace_id, restore.span_id)
    assert seen["after"] == ""      # detached once the block is left


def test_join_rendezvous_span_parents_under_agent_trace():
    from dlrover_tpu.master.servicer import MasterServicer

    servicer = MasterServicer()
    captured = []
    obs.add_span_sink(captured.append)
    try:
        with obs.span("rendezvous") as agent_span:
            result = servicer.report(msg.JoinRendezvousRequest(
                node_id=0, node_rank=0, local_world_size=1,
                rdzv_name=RendezvousName.TRAINING,
                trace=obs.current_context(),
            ))
        assert isinstance(result, msg.JoinRendezvousResult)
    finally:
        obs.remove_span_sink(captured.append)
    joins = [s for s in captured if s.name == "rendezvous_join"]
    assert joins, "master never recorded the join span"
    assert joins[0].trace_id == agent_span.trace_id
    assert joins[0].parent_id == agent_span.span_id


# -- flight recorder -------------------------------------------------------


def test_flight_recorder_ring_is_bounded():
    recorder = FlightRecorder(capacity=4, role="t")
    for i in range(10):
        recorder.record_event("e", i=i)
    events = recorder.snapshot()
    assert len(events) == 4
    assert [e["attrs"]["i"] for e in events] == [6, 7, 8, 9]


def test_flight_recorder_dump_on_sigterm_chains_previous(tmp_path):
    recorder = FlightRecorder(role="sigtest", dump_dir=str(tmp_path))
    recorder.record_event("before_signal", detail=1)
    chained = []
    prev = signal.signal(signal.SIGTERM,
                         lambda signum, frame: chained.append(signum))
    try:
        recorder.install_signal_handlers()
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5
        while not chained and time.time() < deadline:
            time.sleep(0.01)
    finally:
        recorder.uninstall_signal_handlers()
        signal.signal(signal.SIGTERM, prev)
    assert chained == [signal.SIGTERM], "previous handler not chained"
    path = tmp_path / f"flight-sigtest-{os.getpid()}.json"
    payload = json.loads(path.read_text())
    assert payload["reason"] == f"signal-{int(signal.SIGTERM)}"
    names = [e["name"] for e in payload["events"]]
    assert "before_signal" in names
    assert "signal" in names


def test_obs_dump_tool_renders_timeline(tmp_path):
    recorder = FlightRecorder(role="tool", dump_dir=str(tmp_path))
    recorder.record_event("worker_spawn", pid=1)
    with obs.span("demo_span"):
        pass
    recorder.record_span(obs.record_span("measured", 0.25,
                                         attrs={"round": 1}))
    path = recorder.dump(reason="test")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "obs_dump.py"), path],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "worker_spawn" in proc.stdout
    assert "measured" in proc.stdout
    assert "SPAN" in proc.stdout and "EVENT" in proc.stdout
    # filters work and report counts
    proc2 = subprocess.run(
        [sys.executable, str(REPO / "tools" / "obs_dump.py"),
         "--spans-only", "--name", "measured", path],
        capture_output=True, text=True, timeout=60)
    assert proc2.returncode == 0
    assert "worker_spawn" not in proc2.stdout


# -- agent↔master telemetry path ------------------------------------------


def test_servicer_ingests_telemetry_report():
    from dlrover_tpu.master.servicer import MasterServicer

    servicer = MasterServicer()
    spans = [{"kind": "span", "name": "remote_restore", "ts": 1.0,
              "end_ts": 3.5, "duration_s": 2.5, "trace_id": "t",
              "span_id": "s", "parent_id": "", "status": "ok",
              "pid": 1, "attrs": {}}]
    response = servicer.report(msg.TelemetryReport(
        node_id=7,
        samples=[
            msg.MetricSample(kind="gauge", name="obs_test_worker_gauge",
                             value=1.5, labels={"node": "7"}),
            msg.MetricSample(kind="counter", name="obs_test_total",
                             value=2.0, labels={"node": "7"}),
        ],
        spans_json=json.dumps(spans),
    ))
    assert response.success
    # ingestion rides a bounded queue + drainer thread since the
    # control-plane split; flush before asserting on the registry
    assert servicer.telemetry_queue.flush(timeout_s=5.0)
    rendered = obs.get_registry().render()
    assert 'obs_test_worker_gauge{node="7"} 1.5' in rendered
    assert 'obs_test_total{node="7"} 2' in rendered
    names = [e.get("name") for e in obs.get_flight_recorder().snapshot()]
    assert "remote_restore" in names
    assert ('dlrover_tpu_span_duration_seconds_bucket{span="remote_'
            'restore"' in rendered)


def test_master_client_report_telemetry_roundtrip(free_port):
    """Worker-side client → real gRPC → servicer → master registry."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.common.comm import build_server
    from dlrover_tpu.master.servicer import MasterServicer

    servicer = MasterServicer()
    server, port = build_server(servicer.get_bytes, servicer.report_bytes,
                                port=free_port, host="127.0.0.1")
    server.start()
    try:
        client = MasterClient(f"127.0.0.1:{port}", node_id=3)
        assert client.report_telemetry(
            samples=[msg.MetricSample(kind="gauge",
                                      name="obs_rpc_gauge", value=9.0,
                                      labels={"node": "3"})],
            spans=[{"kind": "span", "name": "rpc_span", "ts": 0.0,
                    "duration_s": 0.1, "attrs": {}}],
        )
        client.close()
    finally:
        server.stop(0.1)
    assert servicer.telemetry_queue.flush(timeout_s=5.0)
    rendered = obs.get_registry().render()
    assert 'obs_rpc_gauge{node="3"} 9' in rendered


# -- speed monitor exposition ---------------------------------------------


def _series_value(rendered: str, series: str) -> float:
    import re

    match = re.search(rf"^{re.escape(series)} (\S+)$", rendered,
                      re.MULTILINE)
    assert match, f"{series} missing from exposition"
    return float(match.group(1))


def test_speed_monitor_publishes_step_time_and_tokens_per_second():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor

    # the registry is process-global and other tests feed the same
    # histogram — assert on the delta, not absolutes
    before = obs.get_registry().render()
    count_before = (
        _series_value(before, "dlrover_tpu_train_step_time_seconds_count")
        if "dlrover_tpu_train_step_time_seconds_count" in before else 0)
    monitor = SpeedMonitor()
    monitor.set_tokens_per_step(8 * 16)
    t0 = time.time()
    monitor.collect_global_step(1, t0)
    monitor.collect_global_step(2, t0 + 0.5)
    monitor.collect_global_step(4, t0 + 1.0)
    assert monitor.running_speed() == pytest.approx(3.0, rel=0.01)
    assert monitor.tokens_per_second() == pytest.approx(
        3.0 * 128, rel=0.01)
    rendered = obs.get_registry().render()
    assert _series_value(
        rendered, "dlrover_tpu_training_steps_per_second"
    ) == pytest.approx(3.0, rel=0.01)
    assert _series_value(
        rendered, "dlrover_tpu_training_tokens_per_second"
    ) == pytest.approx(384.0, rel=0.01)
    # two deltas observed: 0.5s/step and 0.25s/step
    assert _series_value(
        rendered, "dlrover_tpu_train_step_time_seconds_count"
    ) == count_before + 2


# -- elastic loop integration ---------------------------------------------


def _make_loop(cpu_devices, tmp_path, n_devices, max_steps=2):
    import jax

    from dlrover_tpu.models.llama import (
        Llama,
        LlamaConfig,
        cross_entropy_loss,
    )
    from dlrover_tpu.parallel.mesh import MeshSpec
    from dlrover_tpu.trainer.elastic_loop import (
        ElasticTrainLoop,
        TrainLoopConfig,
    )

    cfg = LlamaConfig.tiny(attn_impl="reference")
    loop = ElasticTrainLoop(
        Llama(cfg), optax.adamw(1e-3), cross_entropy_loss,
        TrainLoopConfig(
            global_batch=8, seq_len=16, max_micro_per_replica=4,
            max_steps=max_steps, checkpoint_dir=str(tmp_path / "ckpt"),
            save_interval_steps=1, mesh_spec=MeshSpec(),
        ),
        devices=cpu_devices[:n_devices],
    )
    return cfg, loop, jax


def _batches(cfg, count, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(count):
        tokens = rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32)
        yield tokens, tokens


def test_recompile_span_recorded_after_simulated_resize(cpu_devices,
                                                        tmp_path):
    captured = []
    obs.add_span_sink(captured.append)
    try:
        cfg, loop, jax_mod = _make_loop(cpu_devices, tmp_path, 2)
        state, start = loop.restore_or_init(jax_mod.random.PRNGKey(0))
        state, _ = loop.run(state, _batches(cfg, 4), start_step=start)
        loop.close()
        del state
        captured.clear()
        # simulated elastic resize: the agent restarts the worker, which
        # rebuilds the loop for the new world (2 → 4 devices)
        cfg, loop2, jax_mod = _make_loop(cpu_devices, tmp_path, 4)
        state2, start2 = loop2.restore_or_init(jax_mod.random.PRNGKey(1))
        loop2.close()
    finally:
        obs.remove_span_sink(captured.append)
    assert start2 == 2, "resize must resume from the checkpoint"
    recompiles = [s for s in captured if s.name == "recompile"]
    assert recompiles, "no recompile span after the resize"
    relower = [s for s in recompiles
               if s.attrs.get("phase") == "relower"]
    assert relower and relower[0].attrs["devices"] == 4
    assert relower[0].duration_s > 0
    restores = [s for s in captured if s.name == "checkpoint_restore"]
    assert restores and restores[0].attrs["step"] == 2


def test_fresh_weights_and_the_aot_compile_nest_under_restore_or_init(
        cpu_devices, tmp_path):
    """``state_init`` on the main thread and the AOT ``recompile`` on the
    compile thread both parent under ``restore_or_init``; the compile says
    how the persistent cache met it."""
    captured = []
    obs.add_span_sink(captured.append)
    try:
        _, loop, jax_mod = _make_loop(cpu_devices, tmp_path, 2)
        state, start = loop.restore_or_init(jax_mod.random.PRNGKey(0))
        loop.close()
    finally:
        obs.remove_span_sink(captured.append)
    assert start == 0
    by_name = {}
    for s in captured:
        by_name.setdefault((s.name, s.attrs.get("phase")), s)
    restore = by_name[("restore_or_init", None)]
    init = by_name[("state_init", None)]
    aot = by_name[("recompile", "aot")]
    assert init.parent_id == restore.span_id
    assert aot.parent_id == restore.span_id
    assert init.trace_id == aot.trace_id == restore.trace_id
    assert init.attrs["bytes"] == sum(
        leaf.nbytes for leaf in jax_mod.tree_util.tree_leaves(state)) > 0
    assert aot.attrs["cache"] in ("hit", "miss", "off")
    assert {"cache_hits", "cache_misses"} <= set(aot.attrs)


def _fresh_program():
    import jax

    def doubled_plus_one(x):        # one name: one cache key across calls
        return x * 2.0 + 1.0

    return jax.jit(doubled_plus_one)


def test_compile_cache_reads_a_miss_then_a_hit(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        x = jnp.arange(7.0)
        reads = []
        for _ in range(2):
            with obs.device.compile_cache_reads() as cache:
                _fresh_program().lower(x).compile()
            reads.append(cache)
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    assert reads == [{"cache": "miss", "cache_hits": 0, "cache_misses": 1},
                     {"cache": "hit", "cache_hits": 1, "cache_misses": 0}]


def test_compile_cache_reads_count_the_compiling_thread_alone():
    """The AOT compile runs on its own thread while the main thread
    compiles the state's init: only the block's own thread counts."""
    import jax

    prefix = "/jax/compilation_cache/"
    go, done = threading.Event(), threading.Event()

    def other_thread():
        go.wait(timeout=10)
        for name in ("compile_requests_use_cache", "cache_misses"):
            jax.monitoring.record_event(prefix + name)
        done.set()

    t = threading.Thread(target=other_thread)
    t.start()
    with obs.device.compile_cache_reads() as off:
        go.set()
        assert done.wait(timeout=10)
    t.join(timeout=10)
    assert not t.is_alive()
    with obs.device.compile_cache_reads() as hit:
        for name in ("compile_requests_use_cache", "cache_hits") * 2:
            jax.monitoring.record_event(prefix + name)
    with obs.device.compile_cache_reads() as miss:
        for name in ("compile_requests_use_cache", "cache_hits",
                     "compile_requests_use_cache", "cache_misses"):
            jax.monitoring.record_event(prefix + name)
    assert off == {"cache": "off", "cache_hits": 0, "cache_misses": 0}
    assert hit == {"cache": "hit", "cache_hits": 2, "cache_misses": 0}
    assert miss == {"cache": "miss", "cache_hits": 1, "cache_misses": 1}


_WORKER = """
from dlrover_tpu import obs
from dlrover_tpu.agent.elastic_agent import init_distributed

init_distributed()
obs.get_flight_recorder().dump(reason="worker-exit")
"""


@pytest.mark.parametrize("source", ["env", "probe"])
def test_launcher_and_worker_dumps_hold_the_set_up_spans(tmp_path, source):
    """A standalone launch: the launcher's dump holds ``device_probe`` (by
    the env's count, or by the probe child's) and ``master_prepare``; the
    worker's ``backend_init`` is in the agent's rendezvous trace."""
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu",
               DLROVER_TPU_FLIGHT_DIR=str(tmp_path / "flight"))
    env.pop(obs.TRACE_PARENT_ENV, None)
    for name in ("DLROVER_TPU_DEVICES_PER_NODE", "XLA_FLAGS"):
        env.pop(name, None)     # one CPU device, however it is counted
    if source == "env":
        env["DLROVER_TPU_DEVICES_PER_NODE"] = "1"
    proc = subprocess.run(
        [sys.executable, "-m", "dlrover_tpu.run", "--standalone",
         "--max-restarts", "0", "--monitor-interval", "0.2", str(script)],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    spans, events = {}, {}
    for path in (tmp_path / "flight").glob("flight-*.json"):
        for record in json.loads(path.read_text())["events"]:
            if record.get("kind") == "span":
                spans.setdefault(record["name"], record)
            elif record.get("kind") == "event":
                events.setdefault(record["name"], record)
    # the agent reached the spawn without the accelerator stack
    assert events["worker_spawn"]["attrs"]["agent_jax_loaded"] is False
    probe = spans["device_probe"]
    assert probe["attrs"] == {"devices": 1, "source": source}
    assert probe["duration_s"] >= 0.0 and "master_prepare" in spans
    rendezvous, backend = spans["rendezvous"], spans["backend_init"]
    assert backend["pid"] != rendezvous["pid"]
    assert backend["trace_id"] == rendezvous["trace_id"]
    assert backend["parent_id"] == rendezvous["span_id"]
    assert backend["attrs"] == {"platform": "cpu", "devices": 1}


# -- acceptance: simulated failover ---------------------------------------


def test_simulated_failover_dump_and_master_exposition(
        cpu_devices, tmp_path, monkeypatch):
    """The PR's acceptance scenario end-to-end in one process: a worker
    dies after round 0, the survivors re-rendezvous, the respawned
    worker re-lowers and restores — the flight dump must show the whole
    timeline (rendezvous, recompile, checkpoint-restore spans with
    durations) and the master exposition the headline series."""
    from dlrover_tpu.master.rendezvous import (
        ElasticTrainingRendezvousManager,
        RendezvousParameters,
    )
    from dlrover_tpu.master.speed_monitor import SpeedMonitor

    monkeypatch.setenv(obs.FLIGHT_DIR_ENV, str(tmp_path / "flight"))

    # ---- master: rendezvous round 0 with ranks {0, 1} ----
    mgr = ElasticTrainingRendezvousManager(
        RendezvousParameters(min_nodes=2, max_nodes=2))
    mgr.join_rendezvous(0, 1)
    mgr.join_rendezvous(1, 1)
    _, _, world = mgr.get_comm_world(0)
    assert world == {0: 1, 1: 1}

    # ---- master: speed monitor sees step progress ----
    monitor = SpeedMonitor()
    monitor.set_tokens_per_step(8 * 16)
    t0 = time.time()
    for i, ts in enumerate((t0, t0 + 0.2, t0 + 0.4), start=1):
        monitor.collect_global_step(i, ts)

    # ---- worker trains + checkpoints, then "dies" ----
    cfg, loop, jax_mod = _make_loop(cpu_devices, tmp_path, 2)
    state, _ = loop.restore_or_init(jax_mod.random.PRNGKey(0))
    state, _ = loop.run(state, _batches(cfg, 4), start_step=0)
    loop.close()
    del state, loop

    # ---- master: rank 1 dies → world invalidated → re-rendezvous ----
    mgr.remove_alive_node(1, graceful=False)
    assert mgr.num_nodes_waiting() > 0
    mgr.join_rendezvous(0, 1)
    mgr.join_rendezvous(2, 1)   # the replacement
    _, _, world2 = mgr.get_comm_world(0)
    assert world2 == {0: 1, 2: 1}

    # ---- respawned worker: re-lower + restore on the new world ----
    cfg, loop2, jax_mod = _make_loop(cpu_devices, tmp_path, 4)
    state2, start2 = loop2.restore_or_init(jax_mod.random.PRNGKey(1))
    assert start2 == 2
    loop2.close()
    del state2, loop2

    # ---- the postmortem dump ----
    path = obs.get_flight_recorder().dump(reason="failover-test")
    payload = json.loads(Path(path).read_text())
    spans = [e for e in payload["events"] if e.get("kind") == "span"]
    names = {s["name"] for s in spans}
    assert {"rendezvous_round", "recompile",
            "checkpoint_restore"} <= names, names
    for name in ("rendezvous_round", "recompile", "checkpoint_restore"):
        timed = [s for s in spans if s["name"] == name]
        assert all(s["duration_s"] >= 0.0 for s in timed)
        assert all(s["end_ts"] >= s["ts"] for s in timed)
    rounds = [s for s in spans if s["name"] == "rendezvous_round"]
    assert len(rounds) >= 2    # round 0 and the post-failover round
    events = {e["name"] for e in payload["events"]
              if e.get("kind") == "event"}
    assert "world_invalidated" in events

    # ---- the master exposition ----
    rendered = obs.get_registry().render()
    assert "dlrover_tpu_train_step_time_seconds" in rendered
    assert "dlrover_tpu_training_tokens_per_second" in rendered
    assert ('dlrover_tpu_rendezvous_rounds_total{rdzv="elastic-'
            'training"}' in rendered)
    assert ('dlrover_tpu_rendezvous_world_invalidations_total{rdzv='
            '"elastic-training"}' in rendered)


# -- tooling gate ----------------------------------------------------------


def test_graftlint_clean_on_obs():
    from dlrover_tpu.analysis import run_analysis

    result = run_analysis([str(REPO / "dlrover_tpu" / "obs")])
    assert result.findings == [], [str(f) for f in result.findings]
