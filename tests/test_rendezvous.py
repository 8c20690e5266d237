"""In-memory rendezvous tests (reference analogue:
dlrover/python/tests/test_rdzv_manager.py)."""

import time

from dlrover_tpu.master.rendezvous import (
    ElasticTrainingRendezvousManager,
    NetworkCheckRendezvousManager,
    RendezvousParameters,
)


def make_mgr(min_nodes, max_nodes, wait=0.0, unit=1):
    return ElasticTrainingRendezvousManager(
        RendezvousParameters(min_nodes, max_nodes, wait, unit)
    )


class TestElasticTrainingRendezvous:
    def test_round_completes_when_all_join(self):
        mgr = make_mgr(2, 4, wait=3600.0)
        mgr.join_rendezvous(0, 4)
        _, _, world = mgr.get_comm_world(0)
        assert world == {}  # node 1 is alive? no — only node 0 alive, joined
        mgr.join_rendezvous(1, 4)
        rnd, group, world = mgr.get_comm_world(0)
        assert world == {0: 4, 1: 4}
        assert rnd == 0 and group == 0

    def test_single_node_world(self):
        mgr = make_mgr(1, 1)
        mgr.join_rendezvous(0, 8)
        _, _, world = mgr.get_comm_world(0)
        assert world == {0: 8}

    def test_waits_for_alive_nodes(self):
        """If 3 nodes are alive but only 2 joined, and the grace window has
        not expired, the round must not cut."""
        mgr = make_mgr(2, 3, wait=3600.0)
        mgr.add_alive_node(0)
        mgr.add_alive_node(1)
        mgr.add_alive_node(2)
        mgr.join_rendezvous(0, 4)
        mgr.join_rendezvous(1, 4)
        _, _, world = mgr.get_comm_world(0)
        assert world == {}
        mgr.join_rendezvous(2, 4)
        _, _, world = mgr.get_comm_world(0)
        assert set(world) == {0, 1, 2}

    def test_grace_window_cut_without_stragglers(self):
        mgr = make_mgr(2, 4, wait=0.05)
        mgr.add_alive_node(9)  # alive but never joins
        mgr.join_rendezvous(0, 4)
        mgr.join_rendezvous(1, 4)
        _, _, world = mgr.get_comm_world(0)
        assert world == {}
        time.sleep(0.06)
        _, _, world = mgr.get_comm_world(0)
        assert set(world) == {0, 1}

    def test_node_unit_rounding(self):
        """5 joiners with node_unit=2 → world of 4; 1 left waiting."""
        mgr = make_mgr(2, 8, wait=0.0, unit=2)
        for rank in range(5):
            mgr.join_rendezvous(rank, 4)
        _, _, world = mgr.get_comm_world(0)
        assert len(world) == 4
        assert mgr.num_nodes_waiting() == 1

    def test_dead_node_removed_before_round(self):
        mgr = make_mgr(2, 4, wait=3600.0)
        for rank in range(3):
            mgr.join_rendezvous(rank, 4)
        mgr.remove_alive_node(2)
        _, _, world = mgr.get_comm_world(0)
        assert set(world) == {0, 1}

    def test_membership_change_signal(self):
        mgr = make_mgr(1, 4, wait=0.0)
        mgr.join_rendezvous(0, 4)
        mgr.get_comm_world(0)
        assert mgr.num_nodes_waiting() == 0
        mgr.join_rendezvous(1, 4)  # a new node appears
        assert mgr.num_nodes_waiting() > 0

    def test_next_round_after_restart(self):
        mgr = make_mgr(2, 2, wait=3600.0)
        mgr.join_rendezvous(0, 4)
        mgr.join_rendezvous(1, 4)
        rnd0, _, world0 = mgr.get_comm_world(0)
        assert world0 and rnd0 == 0
        # both re-join (worker restart)
        mgr.join_rendezvous(0, 4)
        mgr.join_rendezvous(1, 4)
        rnd1, _, world1 = mgr.get_comm_world(1)
        assert world1 == {0: 4, 1: 4}
        assert rnd1 == 1


class TestNetworkCheckRendezvous:
    def _join_all(self, mgr, n):
        for rank in range(n):
            mgr.join_rendezvous(rank, 4)

    def test_round0_adjacent_pairs(self):
        mgr = NetworkCheckRendezvousManager(
            RendezvousParameters(4, 4, 0.0)
        )
        self._join_all(mgr, 4)
        _, g0, w0 = mgr.get_comm_world(0)
        _, g2, w2 = mgr.get_comm_world(2)
        assert set(w0) == {0, 1} and set(w2) == {2, 3}
        assert g0 != g2

    def test_round1_pairs_fast_with_slow(self):
        mgr = NetworkCheckRendezvousManager(
            RendezvousParameters(4, 4, 0.0)
        )
        self._join_all(mgr, 4)
        for rank in range(4):
            mgr.get_comm_world(rank)
        # report round-0 results: node 3 very slow
        times = {0: 1.0, 1: 1.1, 2: 1.2, 3: 50.0}
        for rank, t in times.items():
            mgr.report_network_status(rank, True, t)
        self._join_all(mgr, 4)
        _, _, world_fast = mgr.get_comm_world(0)
        # fastest (0) paired with slowest (3)
        assert set(world_fast) == {0, 3}

    def test_fault_node_must_fail_both_rounds(self):
        mgr = NetworkCheckRendezvousManager(
            RendezvousParameters(2, 2, 0.0)
        )
        self._join_all(mgr, 2)
        mgr.get_comm_world(0)
        mgr.report_network_status(0, False, 0.0)
        mgr.report_network_status(1, True, 1.0)
        fault, rounds = mgr.check_fault_node()
        assert fault == [0] and rounds == 1
        # round 2: node 0 now passes → not faulty
        self._join_all(mgr, 2)
        mgr.get_comm_world(0)
        mgr.report_network_status(0, True, 1.0)
        mgr.report_network_status(1, True, 1.0)
        fault, rounds = mgr.check_fault_node()
        assert fault == [] and rounds == 2
        assert mgr.network_check_success()

    def test_straggler_two_x_median(self):
        mgr = NetworkCheckRendezvousManager(
            RendezvousParameters(4, 4, 0.0)
        )
        self._join_all(mgr, 4)
        mgr.get_comm_world(0)
        for rank, t in {0: 20.0, 1: 21.0, 2: 20.5, 3: 150.0}.items():
            mgr.report_network_status(rank, True, t)
        assert mgr.detect_stragglers() == [3]

    def test_member_death_drops_stale_groups(self):
        """A post-cut member death must not leave the check groups keyed on
        the emptied world (survivor polls raised KeyError)."""
        mgr = NetworkCheckRendezvousManager(
            RendezvousParameters(1, 2, 0.0)
        )
        self._join_all(mgr, 2)
        _, _, world = mgr.get_comm_world(0)
        assert set(world) == {0, 1}
        mgr.remove_alive_node(1)
        rnd, _, world = mgr.get_comm_world(0)   # must not raise
        assert world == {}

    def test_odd_node_count_merges_singleton(self):
        mgr = NetworkCheckRendezvousManager(
            RendezvousParameters(3, 3, 0.0)
        )
        self._join_all(mgr, 3)
        worlds = [set(mgr.get_comm_world(r)[2]) for r in range(3)]
        # everyone belongs to a group of >= 2
        assert all(len(w) >= 2 for w in worlds)


class TestRendezvousOverflow:
    def test_more_joiners_than_max_still_cuts(self):
        """len(waiting) > max_nodes must cut a max_nodes round, not deadlock."""
        mgr = make_mgr(2, 2, wait=3600.0)
        for rank in range(3):
            mgr.join_rendezvous(rank, 4)
        _, _, world = mgr.get_comm_world(0)
        assert len(world) == 2
        assert mgr.num_nodes_waiting() == 1

    def test_member_death_invalidates_cut_world(self):
        """A member dying AFTER the round was cut must invalidate the world:
        a survivor that never re-joined would otherwise be handed a world
        containing the dead peer and only find out at
        jax.distributed.initialize timeout."""
        mgr = make_mgr(1, 3, wait=0.0)
        mgr.join_rendezvous(0, 4)
        mgr.join_rendezvous(1, 4)
        rnd0, _, world0 = mgr.get_comm_world(0)
        assert set(world0) == {0, 1}
        mgr.remove_alive_node(1)         # node 1 dies after the cut
        # Survivor 0 (which has NOT re-joined) must not see the stale world.
        rnd, _, world = mgr.get_comm_world(0)
        assert world == {}
        # Healthy survivors are told to restart (membership change signal)
        # even before anyone reaches the waiting list.
        assert mgr.num_nodes_waiting() > 0
        # The poll reported a round beyond the one node 0 joined — the agent
        # re-joins and a fresh round cuts with the survivor only.
        assert rnd > rnd0
        mgr.join_rendezvous(0, 4)
        rnd1, _, world1 = mgr.get_comm_world(0)
        assert world1 == {0: 4} and rnd1 == rnd0 + 1
        # Signal clears once the fresh round is cut.
        assert mgr.num_nodes_waiting() == 0

    def test_restart_signal_is_level_triggered_per_survivor(self):
        """A survivor whose num_nodes_waiting poll misses the first window
        must STILL see the restart signal after a fresh round was cut by
        faster survivors — otherwise its worker hangs on the dead world."""
        mgr = make_mgr(1, 3, wait=0.0)
        for rank in range(3):
            mgr.join_rendezvous(rank, 4)
        _, _, world = mgr.get_comm_world(0)
        assert set(world) == {0, 1, 2}
        mgr.remove_alive_node(2)          # node 2 dies
        mgr.join_rendezvous(0, 4)         # fast survivor re-joins…
        _, _, w = mgr.get_comm_world(0)   # …and a fresh round cuts
        assert set(w) == {0}
        # Slow survivor 1 polls only now: the signal must still be raised.
        assert mgr.num_nodes_waiting() > 0
        mgr.join_rendezvous(1, 4)         # it re-joins → signal clears
        mgr.join_rendezvous(0, 4)
        _, _, w = mgr.get_comm_world(1)
        assert set(w) == {0, 1}
        assert mgr.num_nodes_waiting() == 0

    def test_reaper_declares_silent_node_dead(self):
        """An agent whose PROCESS died (SIGKILL — no failure RPC, no node
        manager watching) must still be detected: reap_dead_nodes expires
        ranks whose RPC liveness went silent, invalidating the world so
        survivors re-form (the scale-DOWN path)."""
        import time as _time

        mgr = make_mgr(1, 2, wait=0.0)
        mgr.join_rendezvous(0, 4)
        mgr.join_rendezvous(1, 4)
        _, _, world = mgr.get_comm_world(0)
        assert set(world) == {0, 1}
        # node 1's process is SIGKILLed: no RPC ever reports it. Survivor
        # 0 keeps polling (touches); node 1's last_seen goes stale.
        _time.sleep(0.15)
        mgr.touch(0)
        mgr.reap_dead_nodes(timeout_s=0.1)
        assert mgr.num_nodes_waiting() > 0      # restart signal raised
        _, _, world = mgr.get_comm_world(0)
        assert world == {}                      # stale world invalidated
        mgr.join_rendezvous(0, 4)
        _, _, world = mgr.get_comm_world(0)
        assert world == {0: 4}                  # re-formed at world=1
        # disabled timeout is a no-op; a live node is never reaped
        mgr.reap_dead_nodes(timeout_s=0)
        mgr.touch(0)
        mgr.reap_dead_nodes(timeout_s=10.0)
        assert 0 in mgr._alive_nodes

    def test_leave_waiting_withdraws_abandoned_join(self):
        """A joiner that gives up polling an uncompleted round must be
        able to withdraw: its stale entry would otherwise let a LATE
        partner complete the round against a peer that already left and
        hang waiting for that peer's coordinator (the network-check
        flake's root cause under load)."""
        mgr = make_mgr(2, 2, wait=3600.0)
        mgr.join_rendezvous(0, 1)
        # node 0's poll deadline expires; it withdraws
        mgr.leave_waiting(0)
        # node 1 arrives late: the round must NOT complete with node 0
        mgr.join_rendezvous(1, 1)
        _, _, world = mgr.get_comm_world(1)
        assert world == {}
        # node 0 re-joins -> the round completes for real
        mgr.join_rendezvous(0, 1)
        _, _, world = mgr.get_comm_world(1)
        assert sorted(world) == [0, 1]
        # leaving after the cut is a no-op (the world stands)
        mgr.leave_waiting(0)
        _, _, world = mgr.get_comm_world(1)
        assert sorted(world) == [0, 1]

    def test_graceful_exit_keeps_world_valid(self):
        """A node finishing cleanly must NOT invalidate the world: the
        survivors are finishing their own work and must not be told to
        restart into a rendezvous that can never complete."""
        mgr = make_mgr(2, 2, wait=3600.0)
        mgr.join_rendezvous(0, 4)
        mgr.join_rendezvous(1, 4)
        _, _, world = mgr.get_comm_world(0)
        assert set(world) == {0, 1}
        mgr.remove_alive_node(1, graceful=True)   # node 1 finished
        _, _, world = mgr.get_comm_world(0)
        assert set(world) == {0, 1}               # world still valid
        assert mgr.num_nodes_waiting() == 0       # no restart signal

    def test_rejoined_node_sees_forming_not_stale_world(self):
        """A node that re-joined for the next round must not receive the
        previous round's world (it may contain dead peers)."""
        mgr = make_mgr(2, 2, wait=3600.0)
        mgr.join_rendezvous(0, 4)
        mgr.join_rendezvous(1, 4)
        _, _, world0 = mgr.get_comm_world(0)
        assert world0
        mgr.remove_alive_node(1)     # node 1 died
        mgr.join_rendezvous(0, 4)    # node 0 restarts, re-joins
        _, _, world = mgr.get_comm_world(0)
        assert world == {}           # round 1 still forming
