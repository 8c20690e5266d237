"""Slice-scoped failure domains: multi-slice hierarchical DP.

The ISSUE 10 acceptance story: losing a slice must not lose the fleet —
per-slice rendezvous worlds with per-slice generation tokens, a
hierarchical gradient sync (in-slice over ICI, cross-slice over DCN)
that tolerates an absent slice for ``slice_absent_max_steps`` steps
(renormalized mean, degraded accounting, hard stall past the budget),
slice-unit drains, and a restore plan preferring same-slice donors.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from dlrover_tpu import obs
from dlrover_tpu.common.config import Context
from dlrover_tpu.common.constants import NodeEnv, RendezvousName
from dlrover_tpu.master.rendezvous import (
    ElasticTrainingRendezvousManager,
    NetworkCheckRendezvousManager,
    RendezvousParameters,
)
from dlrover_tpu.parallel.dcn_sync import (
    GRAD_KEY_PREFIX,
    REJOIN_KEY,
    STATE_KEY,
    SliceGradSync,
    decode_payload,
    encode_leaves,
    peek_step,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_context():
    Context.reset()
    yield
    Context.reset()


def _params(**kw):
    kw.setdefault("min_nodes", 1)
    kw.setdefault("max_nodes", 16)
    kw.setdefault("wait_new_node_s", 30.0)
    return RendezvousParameters(**kw)


def _join_all(mgr, slices):
    """slices: {rank: slice_id}; joins then polls every rank once so
    ready slices cut."""
    for rank, sid in slices.items():
        mgr.join_rendezvous(rank, 1, slice_id=sid)
    worlds = {}
    for rank in slices:
        worlds[rank] = mgr.get_comm_world(rank)
    return worlds


# ---------------------------------------------------------------------------
# hierarchical mesh + train step
# ---------------------------------------------------------------------------


class TestHierarchicalMesh:
    def test_dcn_axis_outermost_and_sized(self):
        from dlrover_tpu.parallel.mesh import MeshSpec

        spec = MeshSpec(dcn=2).with_total_devices(8)
        sizes = spec.axis_sizes()
        assert sizes[0] == ("dcn", 2)
        assert spec.data == 4          # inferred within the slices
        assert spec.total == 8

    def test_explicit_dcn_split_pins_the_dcn_axis(self):
        from dlrover_tpu.parallel.mesh import MeshSpec, _dcn_split

        spec = MeshSpec(data=2, dcn=2)
        shape = _dcn_split(spec, 2)
        assert shape is not None
        assert shape[0] == 2 and all(s == 1 for s in shape[1:])
        # granule count the dcn axis cannot carry → no split
        assert _dcn_split(MeshSpec(data=3, dcn=3), 2) is None

    def test_create_mesh_dcn(self, cpu_devices):
        from dlrover_tpu.parallel.mesh import (
            MeshSpec,
            create_mesh,
            data_axes,
            dcn_size,
            dp_size,
        )

        mesh = create_mesh(MeshSpec(dcn=2), cpu_devices[:4])
        assert mesh.shape["dcn"] == 2
        assert dcn_size(mesh) == 2
        assert dp_size(mesh) == 4
        assert data_axes(mesh)[0] == "dcn"

    def test_quant_collectives_accept_exact_bits(self):
        from dlrover_tpu.parallel.quant_collectives import quantized_pmean

        with pytest.raises(ValueError):
            quantized_pmean({}, "dcn", 2, bits=16)
        # bits=0 is the exact escape hatch (no raise)
        quantized_pmean({}, "dcn", 2, bits=0)


class TestHierarchicalTrainStep:
    @staticmethod
    def _toy():
        import flax.linen as nn
        import optax

        class Toy(nn.Module):
            @nn.compact
            def __call__(self, x):
                emb = self.param("emb", nn.initializers.normal(),
                                 (64, 32))
                return emb[x] @ emb.T

        def loss_fn(logits, tgt):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt).mean()

        return Toy(), optax.sgd(0.1), loss_fn

    def _run_step(self, mesh, bits=0, split=False):
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.trainer.train_step import build_trainer

        model, tx, loss_fn = self._toy()
        sample = jnp.zeros((4, 6), jnp.int32)
        trainer = build_trainer(model, tx, mesh, sample, loss_fn,
                                accum_steps=1, micro_batch=4,
                                grad_reduce_bits=bits,
                                split_grad_apply=split)
        rng = np.random.default_rng(0)
        tok = rng.integers(0, 64, (4, 6)).astype(np.int32)
        state = trainer.init(jax.random.PRNGKey(0))
        t, g = trainer.shard_batch(tok, tok)
        return trainer, state, t, g

    def test_manual_dcn_reduce_matches_flat_reference(self, cpu_devices):
        import jax

        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh

        # dcn-only mesh: the manual cross-slice reduce runs even on a
        # jax without partial-auto shard_map (full-manual program)
        mesh = create_mesh(MeshSpec(data=1, dcn=4), cpu_devices[:4])
        trainer, state, t, g = self._run_step(mesh)
        s2, m2 = trainer.step(state, t, g)
        ref_mesh = create_mesh(MeshSpec(), cpu_devices[:1])
        rtrainer, rstate, rt, rg = self._run_step(ref_mesh)
        s1, m1 = rtrainer.step(rstate, rt, rg)
        assert float(m2["loss"]) == pytest.approx(float(m1["loss"]),
                                                  rel=1e-5)
        p2 = np.asarray(jax.tree.leaves(s2.params)[0])
        p1 = np.asarray(jax.tree.leaves(s1.params)[0])
        np.testing.assert_allclose(p2, p1, atol=1e-6)

    def test_quantized_dcn_reduce_close_to_exact(self, cpu_devices):
        import jax

        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh

        mesh = create_mesh(MeshSpec(data=1, dcn=4), cpu_devices[:4])
        trainer, state, t, g = self._run_step(mesh, bits=8)
        s2, _ = trainer.step(state, t, g)
        etrainer, estate, et, eg = self._run_step(mesh)
        s1, _ = etrainer.step(estate, et, eg)
        p2 = np.asarray(jax.tree.leaves(s2.params)[0])
        p1 = np.asarray(jax.tree.leaves(s1.params)[0])
        np.testing.assert_allclose(p2, p1, atol=1e-4)

    def test_split_grad_apply_equals_fused_step(self, cpu_devices):
        import jax

        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh

        mesh = create_mesh(MeshSpec(), cpu_devices[:2])
        trainer, state, t, g = self._run_step(mesh, split=True)
        fused, _ = trainer.step(state, t, g)
        trainer2, state2, t2, g2 = self._run_step(mesh, split=True)
        grads, gm = trainer2.grad_step(state2, t2, g2)
        assert "loss" in gm
        split_state, am = trainer2.apply_grads(state2, grads)
        assert "grad_norm" in am
        p_f = np.asarray(jax.tree.leaves(fused.params)[0])
        p_s = np.asarray(jax.tree.leaves(split_state.params)[0])
        np.testing.assert_allclose(p_f, p_s, atol=1e-6)

    def test_unsplit_trainer_refuses_grad_step(self, cpu_devices):
        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh

        mesh = create_mesh(MeshSpec(), cpu_devices[:1])
        trainer, state, t, g = self._run_step(mesh)
        with pytest.raises(RuntimeError):
            trainer.grad_step(state, t, g)


# ---------------------------------------------------------------------------
# DCN wire codec
# ---------------------------------------------------------------------------


class TestWireCodec:
    def test_exact_roundtrip(self):
        leaves = [np.arange(12, dtype=np.float32).reshape(3, 4),
                  np.array([7], dtype=np.int32)]
        payload = encode_leaves(leaves, 42)
        assert peek_step(payload) == 42
        header, out = decode_payload(payload)
        assert header["step"] == 42
        np.testing.assert_array_equal(out[0], leaves[0])
        np.testing.assert_array_equal(out[1], leaves[1])
        assert out[0].flags.writeable

    def test_quantized_roundtrip_error_bound(self):
        rng = np.random.default_rng(0)
        leaf = rng.standard_normal(4096).astype(np.float32)
        payload = encode_leaves([leaf], 1, quant_bits=8)
        _, (out,) = decode_payload(payload)
        # groupwise symmetric int8: |err| <= absmax/127 per group
        assert np.abs(out - leaf).max() <= np.abs(leaf).max() / 127 + 1e-7
        # and the wire is meaningfully smaller than exact
        assert len(payload) < leaf.nbytes * 0.6

    def test_small_or_integer_leaves_ship_exact(self):
        small = np.ones(8, np.float32)
        ints = np.arange(4096, dtype=np.int32)
        payload = encode_leaves([small, ints], 1, quant_bits=8)
        _, (a, b) = decode_payload(payload)
        np.testing.assert_array_equal(a, small)
        np.testing.assert_array_equal(b, ints)

    def test_garbage_reads_as_absent(self):
        assert decode_payload(b"") is None
        assert decode_payload(b"not json\nxx") is None
        assert peek_step(b"torn{") == -1


# ---------------------------------------------------------------------------
# slice-scoped rendezvous
# ---------------------------------------------------------------------------


class TestSliceRendezvous:
    def test_per_slice_worlds_and_groups(self):
        mgr = ElasticTrainingRendezvousManager(_params())
        worlds = _join_all(mgr, {0: 0, 1: 0, 2: 1, 3: 1})
        assert worlds[0] == (0, 0, {0: 1, 1: 1})
        assert worlds[2] == (0, 1, {2: 1, 3: 1})
        # the fleet view is the union
        assert mgr.latest_world == {0: 1, 1: 1, 2: 1, 3: 1}
        status = mgr.slice_status()
        assert status["total"] == 2
        assert status["slices"]["0"]["formed"]
        assert status["slices"]["1"]["generation"] == 1

    def test_slice_death_never_touches_the_survivor(self):
        mgr = ElasticTrainingRendezvousManager(_params())
        _join_all(mgr, {0: 0, 1: 0, 2: 1, 3: 1})
        before = obs.get_flight_recorder().snapshot()
        mgr.remove_alive_node(0)
        # victim slice: world gone, survivor of the slice must re-join
        assert mgr.get_comm_world(1)[2] == {}
        assert mgr.num_nodes_waiting(1) >= 1
        # SURVIVING slice: world, round, generation, waiting all
        # untouched — the failure-domain contract
        assert mgr.get_comm_world(2) == (0, 1, {2: 1, 3: 1})
        assert mgr.num_nodes_waiting(2) == 0
        assert mgr.num_nodes_waiting(3) == 0
        status = mgr.slice_status()
        assert not status["slices"]["0"]["formed"]
        assert status["slices"]["1"]["formed"]
        assert status["slices"]["1"]["generation"] == 1
        events = [e for e in obs.get_flight_recorder().snapshot()
                  if e not in before]
        invalidated = [e for e in events
                       if e.get("name") == "slice_world_invalidated"]
        assert invalidated and invalidated[-1]["attrs"]["slice"] == 0

    def test_victim_slice_reforms_alone_with_bumped_generation(self):
        mgr = ElasticTrainingRendezvousManager(_params())
        _join_all(mgr, {0: 0, 1: 0, 2: 1})
        mgr.remove_alive_node(0)
        # survivors of slice 0 re-join; slice 1 does nothing
        mgr.join_rendezvous(0, 1, slice_id=0)
        mgr.join_rendezvous(1, 1, slice_id=0)
        round_idx, group, world = mgr.get_comm_world(0)
        assert (round_idx, group, world) == (1, 0, {0: 1, 1: 1})
        status = mgr.slice_status()
        assert status["slices"]["0"]["generation"] == 2
        assert status["slices"]["1"]["generation"] == 1
        # the waiting signal clears for the re-formed slice
        assert mgr.num_nodes_waiting(0) == 0
        assert mgr.num_nodes_waiting(1) == 0

    def test_world_and_round_for_are_slice_scoped(self):
        mgr = ElasticTrainingRendezvousManager(_params())
        _join_all(mgr, {0: 0, 2: 1})
        mgr.remove_alive_node(0)
        mgr.join_rendezvous(0, 1, slice_id=0)
        mgr.get_comm_world(0)
        assert mgr.round_for(0) == 1
        assert mgr.round_for(2) == 0
        assert mgr.world_for(2) == {2: 1}

    def test_slice_state_survives_export_restore(self):
        mgr = ElasticTrainingRendezvousManager(_params())
        _join_all(mgr, {0: 0, 1: 1})
        mgr.remove_alive_node(0)
        mgr.join_rendezvous(0, 1, slice_id=0)
        mgr.get_comm_world(0)
        state = mgr.export_state()
        restored = ElasticTrainingRendezvousManager(_params())
        restored.restore_state(state)
        assert restored.slice_status() == mgr.slice_status()
        assert restored.world_for(1) == {1: 1}
        assert restored.round_for(0) == 1

    def test_grace_window_not_reset_by_rank_zero_waiting(self):
        """Regression: the slice grace timer must be keyed on waiting
        MEMBERSHIP, not rank truthiness — with rank 0 already waiting,
        a later join must not re-arm the window (it would livelock the
        re-formation of a slice with a dead member)."""
        mgr = ElasticTrainingRendezvousManager(
            _params(wait_new_node_s=0.3))
        # rank 2 is a known slice-0 member that is alive but never
        # joins (wedged host): the grace expiry is the only way out
        mgr.record_slice(2, 0)
        mgr.add_alive_node(2)
        mgr.join_rendezvous(0, 1, slice_id=0)
        time.sleep(0.35)
        mgr.join_rendezvous(1, 1, slice_id=0)
        # the window expired relative to rank 0's join: the slice cuts
        # NOW — a timer reset on rank 1's join would return {} here
        _, _, world = mgr.get_comm_world(0)
        assert world == {0: 1, 1: 1}, world

    def test_drain_plans_the_slice_world(self):
        mgr = ElasticTrainingRendezvousManager(_params())
        _join_all(mgr, {0: 0, 1: 0, 2: 1})
        planned = mgr.mark_draining(0, time.time() + 30.0)
        # the planned post-departure world is the SLICE's, minus the
        # draining rank — not the whole fleet
        assert planned == {1: 1}

    def test_sliceless_joins_keep_fleet_behavior(self):
        mgr = ElasticTrainingRendezvousManager(_params())
        mgr.join_rendezvous(0, 1)
        mgr.join_rendezvous(1, 1)
        round_idx, group, world = mgr.get_comm_world(0)
        assert (round_idx, group, world) == (0, 0, {0: 1, 1: 1})
        assert mgr.slice_status() == {"total": 0, "slices": {},
                              "epoch": 0}

    def test_network_check_ignores_slices(self):
        mgr = NetworkCheckRendezvousManager(_params())
        mgr.join_rendezvous(0, 1, slice_id=0)
        mgr.join_rendezvous(1, 1, slice_id=1)
        _, group, world = mgr.get_comm_world(0)
        # fleet-wide pairing: both ranks in one probe group despite
        # different slices (DCN links are what the probe checks)
        assert world == {0: 1, 1: 1}


# ---------------------------------------------------------------------------
# restore-plan donor preference (satellite)
# ---------------------------------------------------------------------------


class TestRestorePlanSlicePreference:
    def _mgr_with_stores(self):
        mgr = ElasticTrainingRendezvousManager(_params())
        _join_all(mgr, {0: 0, 1: 0, 4: 0, 2: 1, 3: 1})
        keys = ["shard/a", "shard/b", "shard/c", "shard/d"]
        for rank in (1, 4, 2, 3):
            mgr.register_peer_store(rank, f"10.0.0.{rank}:9", 5, keys)
        return mgr, keys

    def test_same_slice_donors_win_round_robin(self):
        mgr, keys = self._mgr_with_stores()
        plan = mgr.compute_restore_plan(0)
        assert plan["step"] == 5
        donors = [plan["entries"][k]["rank"] for k in sorted(keys)]
        tiers = {plan["entries"][k]["tier"] for k in keys}
        # every shard from the requester's own slice (ranks 1 and 4),
        # round-robin between them
        assert set(donors) == {1, 4}
        assert donors == [1, 4, 1, 4]
        assert tiers == {"same-slice"}

    def test_cross_slice_fallback_when_no_same_slice_donor(self):
        mgr, keys = self._mgr_with_stores()
        # the requester's whole slice died with it: only cross-slice
        # donors remain
        mgr.register_peer_store(1, "", -1, [])
        mgr.register_peer_store(4, "", -1, [])
        plan = mgr.compute_restore_plan(0)
        donors = [plan["entries"][k]["rank"] for k in sorted(keys)]
        assert set(donors) == {2, 3}
        assert donors == [2, 3, 2, 3]
        assert {plan["entries"][k]["tier"]
                for k in keys} == {"cross-slice"}

    def test_requester_own_store_still_wins(self):
        mgr, keys = self._mgr_with_stores()
        mgr.register_peer_store(0, "10.0.0.0:9", 5, ["shard/a"])
        plan = mgr.compute_restore_plan(0)
        assert plan["entries"]["shard/a"]["rank"] == 0
        assert plan["entries"]["shard/a"]["tier"] == "local"

    def test_sliceless_fleet_keeps_flat_round_robin(self):
        mgr = ElasticTrainingRendezvousManager(_params())
        for rank in (0, 1, 2):
            mgr.join_rendezvous(rank, 1)
        mgr.get_comm_world(0)
        for rank in (1, 2):
            mgr.register_peer_store(rank, f"10.0.0.{rank}:9", 3,
                                    ["a", "b"])
        plan = mgr.compute_restore_plan(0)
        assert [plan["entries"][k]["rank"] for k in ("a", "b")] == [1, 2]


# ---------------------------------------------------------------------------
# slice-unit drain (servicer)
# ---------------------------------------------------------------------------


class TestSliceUnitDrain:
    def test_notice_drains_the_slice_and_checkpoints_the_rest(self):
        from dlrover_tpu.common import messages as msg
        from dlrover_tpu.master.diagnosis.manager import DiagnosisManager
        from dlrover_tpu.master.servicer import MasterServicer
        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        speed = SpeedMonitor()
        servicer = MasterServicer(
            speed_monitor=speed,
            diagnosis_manager=DiagnosisManager(speed))
        for rank, sid in {0: 0, 1: 0, 2: 1, 3: 1}.items():
            servicer.report(msg.JoinRendezvousRequest(
                node_id=rank, node_rank=rank, local_world_size=1,
                rdzv_name=RendezvousName.TRAINING, slice_id=sid))
        result = servicer.report(msg.DrainReport(
            node_id=0, node_rank=0, deadline=time.time() + 30.0,
            reason="spot reclaim", phase="notice"))
        # checkpoint fan-out only to ranks OUTSIDE the draining slice
        assert sorted(result.checkpoint_ranks) == [2, 3]
        dm = servicer.diagnosis_manager
        drain_actions = dm.poll_actions(1)
        assert [a["kind"] for a in drain_actions] == ["drain"]
        assert all(a["kind"] == "checkpoint"
                   for a in dm.poll_actions(2))
        # the notifier itself drains locally — no action queued for it
        assert dm.poll_actions(0) == []
        # the WHOLE slice is marked draining (blown-deadline reap
        # removes it as a unit)
        mgr = servicer.rdzv_managers[RendezvousName.TRAINING]
        assert set(mgr.draining) == {0, 1}

    def test_action_grammar_knows_drain(self):
        from dlrover_tpu.master.diagnosis.rules import parse_action

        assert parse_action("drain:3") == {"kind": "drain", "rank": 3}


# ---------------------------------------------------------------------------
# SliceGradSync: degraded mode, budget stall, rejoin catch-up
# ---------------------------------------------------------------------------


class _FakeSyncClient:
    """The MasterClient surface SliceGradSync needs, backed by a shared
    dict (the 'KV store') and a mutable status (the 'slice registry')."""

    def __init__(self, kv, status):
        self.kv = kv
        self.status = status

    def kv_set(self, key, value):
        self.kv[key] = value
        return True

    def kv_get(self, key):
        return self.kv.get(key, b"")

    def get_slice_status(self):
        return json.loads(json.dumps(self.status))


def _grads(value):
    return [np.full((8,), value, np.float32)]


class TestSliceGradSync:
    def _pair(self, **ctx):
        Context.singleton().update(
            dcn_sync_timeout_s=ctx.pop("timeout", 0.5),
            dcn_sync_poll_s=0.01, **ctx)
        kv = {}
        status = {"total": 2, "fleet_step": 0,
                  "slices": {"0": {"formed": True},
                             "1": {"formed": True}}}
        c0 = _FakeSyncClient(kv, status)
        c1 = _FakeSyncClient(kv, status)
        return SliceGradSync(c0, 0), SliceGradSync(c1, 1), kv, status

    def test_whole_fleet_exact_mean(self):
        s0, s1, kv, _ = self._pair()
        out = {}

        def run(sync, grads, key):
            out[key] = sync.reduce(grads, 1)

        threads = [threading.Thread(target=run, args=(s0, _grads(1.0),
                                                      "a")),
                   threading.Thread(target=run, args=(s1, _grads(3.0),
                                                      "b"))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
        for key in ("a", "b"):
            reduced, info = out[key]
            np.testing.assert_allclose(reduced[0], 2.0)
            assert not info["degraded"]
            assert info["present"] == [0, 1]

    def test_absent_slice_renormalizes_and_counts_degraded(self):
        s0, _, _, status = self._pair()
        status["slices"]["1"]["formed"] = False
        reduced, info = s0.reduce(_grads(5.0), 1)
        # mean over the present slice only — 5.0 stays 5.0, not 2.5
        np.testing.assert_allclose(reduced[0], 5.0)
        assert info["degraded"] and info["absent"] == [1]
        assert s0.consecutive_degraded == 1
        assert s0.drain_unreported() == 1
        assert s0.drain_unreported() == 0

    def test_formed_but_silent_peer_is_absent_for_the_step(self):
        s0, _, _, _ = self._pair(timeout=0.3)
        reduced, info = s0.reduce(_grads(4.0), 1)
        # slice 1 is formed in the registry but posted nothing inside
        # the window: absent for THIS step, loudly degraded
        np.testing.assert_allclose(reduced[0], 4.0)
        assert info["degraded"] and 1 in info["absent"]

    def test_budget_blown_stalls_until_fleet_whole(self):
        s0, _, kv, status = self._pair(slice_absent_max_steps=2)
        status["slices"]["1"]["formed"] = False
        for step in (1, 2):
            s0.reduce(_grads(1.0), step)
        assert s0.consecutive_degraded == 2
        done = {}

        def run():
            done["result"] = s0.reduce(_grads(1.0), 3)

        thread = threading.Thread(target=run)
        thread.start()
        time.sleep(0.4)
        # still stalled: budget blown and the slice is still absent
        assert thread.is_alive(), "must hard-stall past the budget"
        events = [e.get("name") for e in
                  obs.get_flight_recorder().snapshot()]
        assert "slice_absent_budget_blown" in events
        # the slice re-forms and posts: the stall ends
        kv[f"{GRAD_KEY_PREFIX}1"] = encode_leaves(_grads(3.0), 3)
        status["slices"]["1"]["formed"] = True
        thread.join(timeout=10.0)
        assert not thread.is_alive()
        reduced, info = done["result"]
        np.testing.assert_allclose(reduced[0], 2.0)
        assert not info["degraded"]
        assert info["stalled_s"] > 0
        assert s0.consecutive_degraded == 0

    def test_abort_breaks_the_stall(self):
        stop = threading.Event()
        Context.singleton().update(dcn_sync_timeout_s=0.2,
                                   dcn_sync_poll_s=0.01,
                                   slice_absent_max_steps=1)
        kv = {}
        status = {"total": 2, "fleet_step": 0,
                  "slices": {"0": {"formed": True},
                             "1": {"formed": False}}}
        sync = SliceGradSync(_FakeSyncClient(kv, status), 0,
                             abort_fn=stop.is_set)
        sync.reduce(_grads(1.0), 1)

        def run():
            sync.reduce(_grads(1.0), 2)

        thread = threading.Thread(target=run)
        thread.start()
        time.sleep(0.3)
        assert thread.is_alive()
        stop.set()
        thread.join(timeout=5.0)
        assert not thread.is_alive()

    def test_rejoin_handoff_and_catch_up(self):
        s0, s1, kv, status = self._pair()
        status["fleet_step"] = 9
        # the re-formed slice 1 restored at step 2; the fleet is at 9
        catcher = {}

        def catch():
            catcher["result"] = s1.catch_up(2, timeout_s=10.0)

        thread = threading.Thread(target=catch)
        thread.start()
        time.sleep(0.1)
        # the fleet leader (slice 0) services the rejoin inside its
        # next sync, publishing its pre-update state for step 9
        state_leaves = [np.arange(8, dtype=np.float32)]
        s0.reduce(_grads(1.0), 10,
                  state_leaves_fn=lambda: state_leaves)
        thread.join(timeout=10.0)
        assert catcher.get("result") is not None
        leaves, fleet_step = catcher["result"]
        assert fleet_step == 9
        np.testing.assert_array_equal(leaves[0], state_leaves[0])
        # the request was consumed
        assert kv.get(REJOIN_KEY, b"") == b""
        events = [e.get("name") for e in
                  obs.get_flight_recorder().snapshot()]
        assert "slice_state_handoff" in events
        assert "slice_rejoin_catchup" in events

    def test_rejoin_handoff_when_rejoiner_has_the_lowest_slice_id(self):
        """Regression: the leader election must EXCLUDE the requesting
        slice — by handoff time the rejoiner is formed again, and when
        it holds the lowest id the survivor must still answer (it must
        never be its own donor)."""
        s0, s1, kv, status = self._pair()
        status["fleet_step"] = 9
        catcher = {}

        def catch():
            catcher["result"] = s0.catch_up(2, timeout_s=10.0)

        thread = threading.Thread(target=catch)
        thread.start()
        time.sleep(0.1)
        state_leaves = [np.full((4,), 5.0, np.float32)]
        # slice 1 (the only survivor, NOT the lowest id) services it
        s1.reduce(_grads(1.0), 10,
                  state_leaves_fn=lambda: state_leaves)
        thread.join(timeout=10.0)
        assert catcher.get("result") is not None
        leaves, fleet_step = catcher["result"]
        assert fleet_step == 9
        np.testing.assert_array_equal(leaves[0], state_leaves[0])

    def test_catch_up_ignores_stale_state_from_a_previous_episode(self):
        """Regression: dcn/state is never cleared — a payload left by
        an OLDER handoff (step > restored step but behind the fleet
        head) must not be adopted, or the slice resumes months behind
        the survivors."""
        s0, s1, kv, status = self._pair()
        status["fleet_step"] = 9
        # a previous episode's answer at step 5: newer than the
        # restored step (2) but older than the fleet head (9)
        kv[STATE_KEY] = encode_leaves([np.zeros(4, np.float32)], 5,
                                      extra={"kind": "state"})
        catcher = {}

        def catch():
            catcher["result"] = s1.catch_up(2, timeout_s=10.0)

        thread = threading.Thread(target=catch)
        thread.start()
        time.sleep(0.3)
        assert thread.is_alive(), "stale step-5 state was adopted"
        fresh = [np.full((4,), 7.0, np.float32)]
        s0.reduce(_grads(1.0), 10, state_leaves_fn=lambda: fresh)
        thread.join(timeout=10.0)
        leaves, fleet_step = catcher["result"]
        assert fleet_step == 9
        np.testing.assert_array_equal(leaves[0], fresh[0])

    def test_status_outage_still_counts_degraded(self):
        """Regression: a failed slice-status RPC (master outage) in a
        fleet known to be multi-slice must count the local-only step as
        DEGRADED — and the budget must eventually stall it, not let it
        train solo forever."""
        Context.singleton().update(dcn_sync_timeout_s=0.3,
                                   dcn_sync_poll_s=0.01,
                                   slice_absent_max_steps=2)
        kv = {}
        status = {"total": 2, "fleet_step": 0,
                  "slices": {"0": {"formed": True},
                             "1": {"formed": True}}}
        client = _FakeSyncClient(kv, status)
        fail = {"on": False}
        good_status = client.get_slice_status

        def flaky_status():
            if fail["on"]:
                raise RuntimeError("master down")
            return good_status()

        client.get_slice_status = flaky_status
        sync = SliceGradSync(client, 0)
        # prime the known fleet size (peer posts so the step is whole)
        kv[f"{GRAD_KEY_PREFIX}1"] = encode_leaves(_grads(1.0), 1)
        _, info = sync.reduce(_grads(1.0), 1)
        assert not info["degraded"]
        fail["on"] = True
        for step in (2, 3):
            _, info = sync.reduce(_grads(1.0), step)
            assert info["degraded"], "outage step must read degraded"
        assert sync.consecutive_degraded == 2
        # past the budget the outage stalls; the master returning with
        # a whole fleet (and a posted peer) unblocks it
        done = {}

        def run():
            done["result"] = sync.reduce(_grads(1.0), 4)

        thread = threading.Thread(target=run)
        thread.start()
        time.sleep(0.3)
        assert thread.is_alive(), "must stall past the budget"
        kv[f"{GRAD_KEY_PREFIX}1"] = encode_leaves(_grads(3.0), 4)
        fail["on"] = False
        thread.join(timeout=10.0)
        assert not thread.is_alive()
        reduced, info = done["result"]
        np.testing.assert_allclose(reduced[0], 2.0)
        assert not info["degraded"]

    def test_catch_up_noop_when_fleet_not_ahead(self):
        _, s1, _, status = self._pair()
        status["fleet_step"] = 2
        assert s1.catch_up(5, timeout_s=0.2) is None

    def test_single_slice_fleet_is_a_noop(self):
        Context.singleton().update(dcn_sync_timeout_s=0.2,
                                   dcn_sync_poll_s=0.01)
        kv = {}
        status = {"total": 1, "slices": {"0": {"formed": True}}}
        sync = SliceGradSync(_FakeSyncClient(kv, status), 0)
        reduced, info = sync.reduce(_grads(7.0), 1)
        np.testing.assert_allclose(reduced[0], 7.0)
        assert not info["degraded"]
        assert not kv, "nothing should hit the wire with one slice"


# ---------------------------------------------------------------------------
# chaos grammar: slice-targeted faults (satellite)
# ---------------------------------------------------------------------------


class TestChaosSliceGrammar:
    def test_parse_slice_faults(self):
        from dlrover_tpu.diagnostics.chaos import parse_chaos

        faults = parse_chaos("kill:slice:0@5;preempt:slice:1@4:20")
        assert faults[0].role == "slice" and faults[0].rank == 0
        assert faults[1].action == "preempt"
        assert faults[1].duration == 20.0

    def test_injector_matches_own_slice_only(self, monkeypatch):
        from dlrover_tpu.diagnostics.chaos import ChaosInjector

        spec = "kill:slice:1@5"
        monkeypatch.setenv(NodeEnv.NODE_RANK, "7")
        armed = ChaosInjector(spec=spec, slice_id=1)
        assert len(armed.faults) == 1
        other = ChaosInjector(spec=spec, slice_id=0)
        assert other.faults == []
        sliceless = ChaosInjector(spec=spec, slice_id=-1)
        assert sliceless.faults == []

    def test_slice_markers_are_per_node(self, tmp_path, monkeypatch):
        from dlrover_tpu.diagnostics.chaos import ChaosInjector

        monkeypatch.setenv("DLROVER_TPU_CHAOS_STATE", str(tmp_path))
        spec = "preempt:slice:0@3:5"
        a = ChaosInjector(spec=spec, rank=0, slice_id=0)
        b = ChaosInjector(spec=spec, rank=1, slice_id=0)
        assert a._marker(a.faults[0]) != b._marker(b.faults[0])

    def test_preempt_slice_fans_notices(self, tmp_path, monkeypatch):
        from dlrover_tpu.diagnostics.chaos import ChaosInjector

        monkeypatch.setenv("DLROVER_TPU_CHAOS_STATE", str(tmp_path))
        notices = {}
        for rank in (0, 1):
            notice = tmp_path / f"notice{rank}.json"
            monkeypatch.setenv(NodeEnv.PREEMPTION_NOTICE_FILE,
                               str(notice))
            injector = ChaosInjector(spec="preempt:slice:0@3:9",
                                     rank=rank, slice_id=0)
            injector.maybe_inject(3)
            notices[rank] = notice
        for rank, notice in notices.items():
            payload = json.loads(notice.read_text())
            assert payload["grace_s"] == 9.0, f"rank {rank} missed"


# ---------------------------------------------------------------------------
# observability: degraded accounting + per-slice sections (satellite)
# ---------------------------------------------------------------------------


class TestSliceObservability:
    def test_goodput_ledger_counts_degraded_steps(self):
        from dlrover_tpu.obs.goodput import GoodputLedger
        from dlrover_tpu.obs.metrics import MetricsRegistry

        ledger = GoodputLedger(registry=MetricsRegistry())
        ledger.set_slice_map({0: 0, 1: 1})
        ledger.observe_step_report(0, 10, step_time_s=0.1)
        ledger.observe_degraded_steps(0, 7)
        snap = ledger.snapshot()
        assert snap["degraded_steps_total"] == 7
        assert snap["per_rank"]["0"]["degraded_steps"] == 7
        assert snap["per_rank"]["0"]["slice"] == 0
        from dlrover_tpu.obs.goodput import render_snapshot

        rendered = render_snapshot(snap)
        assert "per slice:" in rendered
        assert "degraded_steps=7" in rendered

    def test_degraded_survives_ledger_state_roundtrip(self):
        from dlrover_tpu.obs.goodput import GoodputLedger
        from dlrover_tpu.obs.metrics import MetricsRegistry

        ledger = GoodputLedger(registry=MetricsRegistry())
        ledger.set_slice_map({3: 1})
        ledger.observe_degraded_steps(3, 4)
        restored = GoodputLedger(registry=MetricsRegistry())
        restored.restore_state(ledger.export_state())
        snap = restored.snapshot()
        assert snap["degraded_steps_total"] == 4

    def test_servicer_publishes_degraded_counter(self):
        from dlrover_tpu.common import messages as msg
        from dlrover_tpu.master.servicer import MasterServicer
        from dlrover_tpu.obs.goodput import GoodputLedger
        from dlrover_tpu.obs.metrics import MetricsRegistry

        registry = MetricsRegistry()
        servicer = MasterServicer(
            goodput_ledger=GoodputLedger(registry=registry))
        servicer.report(msg.JoinRendezvousRequest(
            node_id=0, node_rank=0, local_world_size=1,
            rdzv_name=RendezvousName.TRAINING, slice_id=2))
        servicer.report(msg.GlobalStepReport(
            node_id=0, node_rank=0, step=10, timestamp=time.time(),
            step_time_s=0.1, degraded_steps=3))
        rendered = obs.get_registry().render()
        assert ('dlrover_tpu_slice_degraded_steps_total{slice="2"} 3'
                in rendered)
        assert servicer.goodput_ledger.snapshot()[
            "degraded_steps_total"] == 3

    def test_speed_monitor_slice_rollup(self):
        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        monitor = SpeedMonitor()
        monitor.set_slice_map({0: 0, 1: 0, 2: 1})
        for rank in (0, 1, 2):
            monitor.collect_worker_step(rank, 10, step_time_s=0.5,
                                        mfu=0.4)
        rendered = obs.get_registry().render()
        assert 'dlrover_tpu_slice_steps_per_second{slice="0"} 2' in rendered
        assert 'dlrover_tpu_slice_workers{slice="0"} 2' in rendered
        assert 'dlrover_tpu_slice_mfu{slice="1"} 0.4' in rendered
        # whole-slice eviction: slice 1's only member departs
        monitor.evict_departed({0, 1})
        rendered = obs.get_registry().render()
        assert 'dlrover_tpu_slice_workers{slice="1"}' not in rendered
        assert 'dlrover_tpu_slice_workers{slice="0"} 2' in rendered

    def test_diagnose_tool_renders_slice_section(self, capsys, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import diagnose
        finally:
            sys.path.pop(0)
        payload = {"events": [
            {"kind": "event", "name": "slice_world_cut", "ts": 1.0,
             "attrs": {"slice": 0, "round": 1, "generation": 2,
                       "world": [0, 1]}},
            {"kind": "event", "name": "slice_world_invalidated",
             "ts": 2.0, "attrs": {"slice": 0, "dead_rank": 1}},
            {"kind": "event", "name": "train_degraded_step", "ts": 3.0,
             "attrs": {"step": 7, "present": [1], "absent": [0]}},
            {"kind": "event", "name": "slice_absent_budget_blown",
             "ts": 4.0, "attrs": {"slice": 1, "degraded_steps": 100}},
        ]}
        rendered = diagnose.render_slices(payload)
        assert "slice_world_cut" in rendered
        assert "generation=2" in rendered
        assert "slice_absent_budget_blown" in rendered
        assert "1 degraded step(s)" in rendered
        assert "slice failure-domain events: 4" in rendered


# ---------------------------------------------------------------------------
# in-process acceptance: losing a slice does not lose the fleet
# ---------------------------------------------------------------------------


_SLICE_WORKER = """
import os, sys, time
sys.path.insert(0, {repo!r})
from dlrover_tpu.agent.preemption import DrainRequestSource

out_path = {out!r}
with open(out_path, "a") as f:
    f.write("spawn pid=%d slice=%s world=%s\\n" % (
        os.getpid(), os.environ.get("DLROVER_TPU_SLICE_ID"),
        os.environ.get("DLROVER_TPU_WORLD_SIZE")))
drain = DrainRequestSource()
for _ in range(100000):
    req = drain.poll()
    if req is not None and req.get("exit", True):
        sys.exit(76)
    time.sleep(0.05)
"""


def _wait_until(predicate, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


def test_slice_loss_acceptance_in_process(tmp_path):
    """Acceptance (ISSUE 10): kill an entire slice (its agents go
    silent, as when the platform reclaims the slice's VMs) — the
    surviving slice's world, generation token and worker pid never
    move; the real cross-slice sync takes a renormalized degraded step;
    the victim slice re-forms alone with a bumped generation, all well
    inside the liveness timeout of a SECOND failure."""
    from dlrover_tpu.agent.elastic_agent import ElasticAgent, WorkerSpec
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.master.job_master import JobMaster

    Context.singleton().update(dead_node_timeout_s=3.0,
                               dcn_sync_timeout_s=1.0,
                               dcn_sync_poll_s=0.02)
    test_start_ts = time.time()
    master = JobMaster(min_nodes=1, max_nodes=4, host="127.0.0.1")
    master.prepare()
    outs = {r: str(tmp_path / f"worker{r}.log") for r in (0, 1, 2)}
    slices = {0: 0, 1: 0, 2: 1}
    clients, agents, threads = {}, {}, {}

    def _spawn_agent(rank):
        clients[rank] = MasterClient(master.addr, node_id=rank,
                                     node_rank=rank,
                                     slice_id=slices[rank])
        script = _SLICE_WORKER.format(repo=REPO, out=outs[rank])
        agents[rank] = ElasticAgent(clients[rank], WorkerSpec(
            entrypoint=[sys.executable, "-c", script],
            monitor_interval_s=0.3, rdzv_timeout_s=30.0,
            shutdown_grace_s=2.0, enable_monitors=False))
        threads[rank] = threading.Thread(
            target=agents[rank].run, daemon=True)
        threads[rank].start()

    try:
        for rank in (0, 1, 2):
            _spawn_agent(rank)
        # both slice worlds form independently
        _wait_until(lambda: sorted(agents[0].last_world) == [0, 1]
                    and sorted(agents[2].last_world) == [2],
                    30.0, "both slice worlds to form")
        mgr = master.rdzv_managers[RendezvousName.TRAINING]
        assert mgr.slice_status()["slices"]["1"]["generation"] == 1
        # the world forms a moment before the agent spawns its worker
        _wait_until(lambda: agents[2]._proc is not None, 10.0,
                    "the survivor's worker to spawn")
        survivor_pid = agents[2]._proc.pid
        kill_ts = time.time()

        # the whole of slice 0 disappears: agents stop polling (the
        # platform took the VMs), workers killed
        for rank in (0, 1):
            agents[rank].shutdown()
        # the master reaps the silent slice on the survivor's polls;
        # ONLY slice 0's world is invalidated
        _wait_until(lambda: not mgr.slice_status()["slices"]["0"]
                    ["formed"], 15.0, "slice 0 to be reaped")
        reap_s = time.time() - kill_ts

        # the REAL sync against the REAL master: the survivor's slice
        # takes a renormalized degraded step while slice 0 is gone
        sync = SliceGradSync(clients[2], 1)
        reduced, info = sync.reduce([np.full((4,), 6.0, np.float32)], 1)
        np.testing.assert_allclose(reduced[0], 6.0)
        assert info["degraded"] and 0 in info["absent"]

        # survivor untouched: same pid, same world, token unchanged,
        # no membership-restart signal ever raised for its slice
        status = mgr.slice_status()
        assert status["slices"]["1"]["formed"]
        assert status["slices"]["1"]["generation"] == 1
        assert agents[2]._proc.pid == survivor_pid
        assert mgr.num_nodes_waiting(2) == 0

        # the victim slice re-forms ALONE (replacement agents)
        for rank in (0, 1):
            threads[rank].join(timeout=10.0)
            clients[rank].close()
            _spawn_agent(rank)
        _wait_until(lambda: sorted(agents[0].last_world) == [0, 1],
                    30.0, "slice 0 to re-form")
        reform_s = time.time() - kill_ts
        status = mgr.slice_status()
        # the bump is >= 2, not == 2: the two replacement agents race
        # the round cut, and the first may form a 1-node world that the
        # second's arrival immediately re-cuts (an extra generation)
        assert status["slices"]["0"]["generation"] >= 2
        assert status["slices"]["1"]["generation"] == 1
        assert agents[2]._proc.pid == survivor_pid

        # flight-event evidence: invalidation named slice 0 only; the
        # surviving slice's world was cut exactly once, ever
        snapshot = obs.get_flight_recorder().snapshot()
        invalidated = [e for e in snapshot
                       if e.get("name") == "slice_world_invalidated"
                       and e["ts"] >= kill_ts]
        assert invalidated
        assert {e["attrs"]["slice"] for e in invalidated} == {0}
        cuts_slice1 = [e for e in snapshot
                       if e.get("name") == "slice_world_cut"
                       and e["attrs"].get("slice") == 1
                       and e["ts"] >= test_start_ts]
        assert len(cuts_slice1) == 1
        # survivor never respawned its worker
        survivor_log = open(outs[2]).read()
        assert survivor_log.count("spawn") == 1
        # and the whole loss→re-form cycle beat the liveness timeout
        # headroom (reap itself is bounded by dead_node_timeout_s)
        assert reap_s < 10.0
        assert reform_s < 30.0
    finally:
        for rank, agent in agents.items():
            agent.shutdown()
        for thread in threads.values():
            thread.join(timeout=10.0)
        for client in clients.values():
            try:
                client.close()
            except Exception:  # noqa: BLE001 — already closed
                pass
        master.stop(grace_s=0.1)


# ---------------------------------------------------------------------------
# slow 2-slice e2e: chaos kills a slice mid-training (satellite:
# multi-process DCN acceptance)
# ---------------------------------------------------------------------------


_TRAIN_WORKER = """
import json, os, sys, time
sys.path.insert(0, {repo!r})
from dlrover_tpu.agent.elastic_agent import apply_jax_platform_env
apply_jax_platform_env()
import jax
import numpy as np
import optax

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.models.llama import Llama, LlamaConfig, \\
    cross_entropy_loss
from dlrover_tpu.trainer.elastic_loop import ElasticTrainLoop, \\
    TrainLoopConfig

events_file = {events!r}
total = {total}


def emit(event):
    with open(events_file, "a") as f:
        f.write(json.dumps(event) + "\\n")


client = MasterClient.singleton()
cfg = LlamaConfig.tiny(attn_impl="reference", norm_impl="reference")
loop = ElasticTrainLoop(
    Llama(cfg), optax.adamw(3e-4), cross_entropy_loss,
    TrainLoopConfig(global_batch=8, seq_len=64,
                    checkpoint_dir=os.environ["TEST_SLICE_CKPT_DIR"],
                    save_interval_steps=3, report_interval_steps=1),
    master_client=client)
loop.install_signal_handler()
state, start = loop.restore_or_init(jax.random.PRNGKey(0))
catch_up = int(loop.last_restore_timings.get("catch_up_steps", 0))
emit({{"event": "restored", "rank": client.node_rank,
      "slice": client.slice_id, "pid": os.getpid(),
      "step": start, "restored_step": start - catch_up,
      "source": loop.last_restore_source, "catch_up": catch_up}})
rng = np.random.default_rng(start)
step = start
while step < total:
    tokens = rng.integers(0, cfg.vocab_size, (8, 64), dtype=np.int32)
    state, _ = loop.run(state, [(tokens, tokens)], start_step=step)
    step += 1
    emit({{"event": "step", "step": step, "rank": client.node_rank,
          "slice": client.slice_id}})
    if loop._stop_requested.is_set():
        break
loop.close()
emit({{"event": "done", "rank": client.node_rank, "step": step}})
"""


def _read_events(path):
    try:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]
    except FileNotFoundError:
        return []


@pytest.mark.slow
def test_two_slice_chaos_kill_e2e(tmp_path):
    """The full chain over real agent/worker processes: 2 slices train
    in lockstep through the DCN sync; chaos SIGKILLs slice 0's worker
    mid-run. Flight events must show the surviving slice never left its
    world (one slice_world_cut, no respawn), DEGRADED steps were taken,
    and the victim resumed at the checkpointed step via PEER restore
    then caught up to the fleet over the DCN state handoff."""
    from dlrover_tpu.agent.elastic_agent import ElasticAgent, WorkerSpec
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.master.job_master import JobMaster

    test_start_ts = time.time()
    total_steps = 14
    master = JobMaster(min_nodes=1, max_nodes=2, host="127.0.0.1")
    master.prepare()
    events_files = {r: str(tmp_path / f"events{r}.jsonl")
                    for r in (0, 1)}
    common_env = {
        "DLROVER_TPU_CHAOS": "kill:slice:0@8",
        "DLROVER_TPU_CHAOS_STATE": str(tmp_path / "chaos"),
        "DLROVER_TPU_DCN_SYNC_TIMEOUT_S": "3.0",
        "DLROVER_TPU_DCN_SYNC_POLL_S": "0.05",
    }
    clients, agents, threads, results = {}, {}, {}, {}
    try:
        for rank in (0, 1):
            clients[rank] = MasterClient(master.addr, node_id=rank,
                                         node_rank=rank, slice_id=rank)
            script = _TRAIN_WORKER.format(repo=REPO,
                                          events=events_files[rank],
                                          total=total_steps)
            env = dict(common_env)
            env["TEST_SLICE_CKPT_DIR"] = str(tmp_path / f"ckpt{rank}")
            agents[rank] = ElasticAgent(clients[rank], WorkerSpec(
                entrypoint=[sys.executable, "-c", script],
                monitor_interval_s=0.5, rdzv_timeout_s=120.0,
                shutdown_grace_s=10.0, env=env,
                enable_monitors=False))

            def _run(rank=rank):
                results[rank] = agents[rank].run()

            threads[rank] = threading.Thread(target=_run, daemon=True)
            threads[rank].start()
            time.sleep(0.2)
        for rank in (0, 1):
            threads[rank].join(timeout=420.0)
            assert not threads[rank].is_alive(), (
                f"agent {rank} never finished; events so far: "
                f"{_read_events(events_files[rank])[-5:]}")
            assert results[rank] == 0

        victim = _read_events(events_files[0])
        survivor = _read_events(events_files[1])
        # both slices finished the full run
        assert any(e["event"] == "done" and e["step"] >= total_steps
                   for e in victim)
        assert any(e["event"] == "done" and e["step"] >= total_steps
                   for e in survivor)
        # the victim's SECOND incarnation resumed at the checkpointed
        # step via PEER restore (staged host cache, not Orbax), then
        # caught up to the fleet over the DCN state handoff
        restores = [e for e in victim if e["event"] == "restored"]
        assert len(restores) == 2, restores
        assert restores[0]["source"] == "init"
        assert restores[1]["source"] == "peer", restores[1]
        # a staged checkpoint cut — possibly the SURVIVOR's newer one
        # (cross-slice donors serve the newest common step, which beats
        # the victim's own pre-kill stage and shrinks the catch-up)
        assert restores[1]["restored_step"] >= 3, restores[1]
        # the survivor never respawned: exactly one incarnation
        assert len([e for e in survivor
                    if e["event"] == "restored"]) == 1

        snapshot = obs.get_flight_recorder().snapshot()
        recent = [e for e in snapshot if e.get("ts", 0) >= test_start_ts]
        # the surviving slice's world was cut exactly once — its
        # generation token never moved across the victim's failure
        cuts = {}
        for event in recent:
            if event.get("name") == "slice_world_cut":
                sid = event["attrs"].get("slice")
                cuts[sid] = cuts.get(sid, 0) + 1
        assert cuts.get(1) == 1, cuts
        assert cuts.get(0, 0) >= 2, cuts   # victim re-formed
        # degraded steps were taken while the victim was down — the
        # survivors' step reports carried them to the master's counter
        # and ledger (worker flight rings don't cross the process
        # boundary; the master-side accounting is the durable evidence)
        ledger_snap = master.goodput_ledger.snapshot()
        assert ledger_snap["degraded_steps_total"] > 0, ledger_snap
        assert ledger_snap["per_rank"]["1"]["degraded_steps"] > 0
        rendered = obs.get_registry().render()
        assert ('dlrover_tpu_slice_degraded_steps_total{slice="1"}'
                in rendered)
        # the victim resumed at (or caught up to) the fleet head: via
        # the DCN state handoff, or directly from a cross-slice donor's
        # stage newer than its own pre-kill checkpoint
        resumed_at_head = restores[1]["restored_step"] >= 8
        assert restores[1]["catch_up"] > 0 or resumed_at_head, restores
    finally:
        for agent in agents.values():
            agent.shutdown()
        for thread in threads.values():
            thread.join(timeout=10.0)
        for client in clients.values():
            try:
                client.close()
            except Exception:  # noqa: BLE001 — already closed
                pass
        master.stop(grace_s=0.1)


# ---------------------------------------------------------------------------
# graftlint gate on the new/changed slice modules (satellite)
# ---------------------------------------------------------------------------


def test_graftlint_clean_on_slice_modules():
    from dlrover_tpu.analysis import run_analysis

    result = run_analysis([
        os.path.join(REPO, "dlrover_tpu", "parallel", "dcn_sync.py"),
        os.path.join(REPO, "dlrover_tpu", "master", "rendezvous.py"),
        os.path.join(REPO, "dlrover_tpu", "master", "servicer.py"),
        os.path.join(REPO, "dlrover_tpu", "master", "speed_monitor.py"),
        os.path.join(REPO, "dlrover_tpu", "obs", "goodput.py"),
        os.path.join(REPO, "dlrover_tpu", "trainer", "elastic_loop.py"),
    ])
    assert result.findings == [], [str(f) for f in result.findings]
