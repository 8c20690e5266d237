"""Tests for the common layer: node state machine, message serialization,
global context (reference analogues: test_node.py / grpc message tests)."""

import os
import pickle
import re
from pathlib import Path

import pytest

from dlrover_tpu.common.config import Context
from dlrover_tpu.common.constants import (
    DefaultValues,
    NodeEventType,
    NodeExitReason,
    NodeStatus,
    NodeType,
)
from dlrover_tpu.common.messages import (
    CommWorld,
    JoinRendezvousRequest,
    Task,
    deserialize_message,
    serialize_message,
)
from dlrover_tpu.common.node import (
    Node,
    NodeResource,
    get_node_state_flow,
)


class TestNodeStateFlow:
    def test_pending_to_running(self):
        flow = get_node_state_flow(
            NodeStatus.PENDING, NodeEventType.MODIFIED, NodeStatus.RUNNING
        )
        assert flow is not None and not flow.should_relaunch

    def test_running_failure_relaunches(self):
        flow = get_node_state_flow(
            NodeStatus.RUNNING, NodeEventType.MODIFIED, NodeStatus.FAILED
        )
        assert flow is not None and flow.should_relaunch

    def test_same_status_is_noop(self):
        assert (
            get_node_state_flow(
                NodeStatus.RUNNING, NodeEventType.MODIFIED, NodeStatus.RUNNING
            )
            is None
        )

    def test_delete_after_success_no_relaunch(self):
        flow = get_node_state_flow(
            NodeStatus.SUCCEEDED, NodeEventType.DELETED, NodeStatus.DELETED
        )
        assert flow is not None and not flow.should_relaunch

    def test_delete_while_running_relaunches(self):
        flow = get_node_state_flow(
            NodeStatus.RUNNING, NodeEventType.DELETED, NodeStatus.DELETED
        )
        assert flow is not None and flow.should_relaunch


class TestNode:
    def test_relaunch_inherits_rank_and_counts(self):
        node = Node(NodeType.WORKER, 3, rank_index=1,
                    config_resource=NodeResource(cpu=4, chips=4))
        node.exit_reason = NodeExitReason.KILLED
        new = node.get_relaunch_node(new_id=7)
        assert new.rank_index == 1
        assert new.relaunch_count == 1
        assert new.config_resource.chips == 4

    def test_unrecoverable_on_fatal_or_budget(self):
        node = Node(NodeType.WORKER, 0, max_relaunch_count=2)
        assert not node.is_unrecoverable_failure()
        node.exit_reason = NodeExitReason.FATAL_ERROR
        assert node.is_unrecoverable_failure()
        node2 = Node(NodeType.WORKER, 1, max_relaunch_count=2)
        node2.relaunch_count = 2
        assert node2.is_unrecoverable_failure()

    def test_update_status_records_times(self):
        node = Node(NodeType.WORKER, 0)
        node.update_status(NodeStatus.RUNNING)
        assert node.start_time is not None
        node.update_status(NodeStatus.SUCCEEDED)
        assert node.finish_time is not None


class TestMessages:
    def test_roundtrip(self):
        msg = JoinRendezvousRequest(node_id=2, node_rank=2,
                                    local_world_size=4,
                                    rdzv_name="elastic-training")
        out = deserialize_message(serialize_message(msg))
        assert out == msg

    def test_nested_dataclass_roundtrip(self):
        world = CommWorld(rdzv_name="x", round=3, world={0: 4, 1: 4})
        assert deserialize_message(serialize_message(world)) == world

    def test_forbidden_class_rejected(self):
        payload = pickle.dumps(os.system)
        with pytest.raises(Exception):
            deserialize_message(payload)

    def test_empty_task(self):
        assert Task().is_empty
        assert not Task(task_id=0).is_empty


class TestContext:
    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_RPC_RETRIES", "9")
        Context.reset()
        try:
            assert Context.singleton().rpc_retries == 9
        finally:
            Context.reset()

    def test_update(self):
        Context.reset()
        ctx = Context.singleton()
        ctx.update(hang_seconds=123.0, nonexistent_key=1)
        assert ctx.hang_seconds == 123.0
        assert not hasattr(ctx, "nonexistent_key")
        Context.reset()


class TestSettingsRule:
    """A setting exists only while something reads it AND something
    sets it; a number with one value in use is a ``DefaultValues``
    constant its reader takes directly (ROADMAP D5)."""

    PACKAGE = Path(__file__).resolve().parent.parent / "dlrover_tpu"

    def _sources(self, skip):
        return {path: path.read_text()
                for path in sorted(self.PACKAGE.rglob("*.py"))
                if path.name != skip or path.parent.name != "common"}

    def _fields(self):
        return [name for name in vars(Context()) if not name.startswith("_")]

    def test_every_field_is_read(self):
        text = "\n".join(self._sources("config.py").values())
        unread = [name for name in self._fields()
                  if not re.search(rf"\.{name}\b", text)]
        assert not unread, (
            f"Context fields no code under dlrover_tpu/ reads: {unread}")

    def test_field_count_does_not_grow(self):
        assert len(self._fields()) <= 50

    def test_every_constant_is_read(self):
        text = "\n".join(self._sources("constants.py").values())
        names = [name for name in vars(DefaultValues) if name.isupper()]
        assert len(names) > 50
        orphaned = [name for name in names
                    if not re.search(rf"\bDefaultValues\.{name}\b", text)]
        assert not orphaned, (
            f"DefaultValues constants nothing reads: {orphaned}")


class TestMessageSecurity:
    def test_builtins_callables_rejected(self):
        """builtins.eval / os.system via __reduce__ must not deserialize."""
        payload = pickle.dumps(eval)
        with pytest.raises(Exception):
            deserialize_message(payload)

    def test_reduce_gadget_rejected(self):
        class Gadget:
            def __reduce__(self):
                return (eval, ("1+1",))

        with pytest.raises(Exception):
            deserialize_message(pickle.dumps(Gadget()))

    def test_dotted_name_bypass_rejected(self):
        """STACK_GLOBAL of ('dlrover_tpu.common.messages', 'pickle.loads')
        must not resolve (dotted-name attribute chain bypass)."""
        payload = (
            b"\x80\x04\x95.\x00\x00\x00\x00\x00\x00\x00"
            b"\x8c\x1cdlrover_tpu.common.messages\x8c\x0cpickle.loads\x93."
        )
        with pytest.raises(Exception):
            deserialize_message(payload)

    def test_non_message_class_in_module_rejected(self):
        """Classes in the messages module that are not Message subclasses
        (e.g. the unpickler itself) must not resolve."""
        payload = (
            b"\x80\x04\x95:\x00\x00\x00\x00\x00\x00\x00"
            b"\x8c\x1cdlrover_tpu.common.messages\x8c\x15_RestrictedUnpickler\x93."
        )
        with pytest.raises(Exception):
            deserialize_message(payload)


class TestCompileCacheDir:
    """One rule for where the persistent compile cache lives
    (common/compile_cache.py): the path is part of every entry's key, so
    it must not move between launches."""

    def test_env_var_wins(self, monkeypatch, tmp_path):
        from dlrover_tpu.common import compile_cache

        monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "given"))
        assert compile_cache.compile_cache_dir() == str(tmp_path / "given")

    def test_fixed_path_inside_checkout(self, monkeypatch):
        import tempfile

        from dlrover_tpu.common import compile_cache

        monkeypatch.delenv(compile_cache.ENV, raising=False)
        first = compile_cache.compile_cache_dir()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(repo, ".jax_cache")
        assert first == compile_cache.compile_cache_dir()
        # never a temp name, a pid or a time
        assert not first.startswith(tempfile.gettempdir() + os.sep)
        assert str(os.getpid()) not in first
        # ... and git ignores it
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    @pytest.mark.parametrize("preset", [False, True])
    def test_agent_hands_it_to_the_worker(self, monkeypatch, tmp_path,
                                          preset):
        """The worker's environment carries the helper's path — the same
        one on every launch — unless the launcher's environment already
        names a cache, which then wins untouched."""
        import sys

        from dlrover_tpu.agent.elastic_agent import ElasticAgent, WorkerSpec
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.common import compile_cache
        from dlrover_tpu.master.job_master import JobMaster

        given = str(tmp_path / "given")
        if preset:
            monkeypatch.setenv(compile_cache.ENV, given)
        else:
            monkeypatch.delenv(compile_cache.ENV, raising=False)
        expected = given if preset else compile_cache.compile_cache_dir()
        master = JobMaster(min_nodes=1, max_nodes=1, host="127.0.0.1")
        master.prepare()
        seen = []
        try:
            for launch in range(2):
                out = tmp_path / f"seen-{launch}"
                client = MasterClient(master.addr, node_id=0, node_rank=0)
                agent = ElasticAgent(client, WorkerSpec(
                    entrypoint=[
                        sys.executable, "-c",
                        "import os; open(%r, 'w').write(os.environ[%r])"
                        % (str(out), compile_cache.ENV)],
                    monitor_interval_s=0.1, enable_monitors=False))
                try:
                    assert agent.run() == 0
                finally:
                    agent.shutdown()
                    client.close()
                seen.append(out.read_text())
        finally:
            master.stop()
        assert seen == [expected, expected]

    def test_network_check_probe_uses_it(self, monkeypatch):
        from dlrover_tpu.common import compile_cache
        from dlrover_tpu.diagnostics import network_check

        monkeypatch.delenv(compile_cache.ENV, raising=False)

        class _Client:
            node_rank = 0

            def join_rendezvous(self, *a):
                return 0

            def get_comm_world(self, name):
                return 0, 0, {0: 1}

            def kv_set(self, key, value):
                pass

        captured = {}

        def _run(cmd, env=None, timeout=None):
            captured.update(env)
            raise network_check.subprocess.TimeoutExpired(cmd, timeout)

        monkeypatch.setattr(network_check.subprocess, "run", _run)
        normal, _ = network_check._probe_round(_Client(), 1, timeout_s=5.0)
        assert not normal
        assert captured[compile_cache.ENV] == \
            compile_cache.compile_cache_dir()
