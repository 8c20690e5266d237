"""Master-side rendezvous managers.

Capability parity: dlrover/python/master/elastic_training/rdzv_manager.py —
min/max-node rendezvous with a waiting list and `node_unit` rounding
(`_check_rdzv_completed` rdzv_manager.py:104, `join_rendezvous` :146), plus
the 2-round network-check rendezvous with pair grouping, fault isolation and
2×median straggler verdicts (`_group_nodes` :299, `check_fault_node` :399,
`_detect_stragglers` :446).

TPU framing: a "node" is one TPU host (one JAX process); ``local_world_size``
is the host's chip count. A completed rendezvous round yields the world map
{node_rank → chips} from which agents derive ``jax.distributed`` process
count/index and the coordinator, then training re-lowers onto the new mesh.
"""

from __future__ import annotations

import statistics
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from dlrover_tpu import obs
from dlrover_tpu.common.constants import DefaultValues, RendezvousName
from dlrover_tpu.common.log import default_logger as logger


@dataclass
class RendezvousParameters:
    min_nodes: int = 1
    max_nodes: int = 1
    # After min_nodes have joined, wait this long for late nodes up to max.
    wait_new_node_s: float = 30.0
    # World size is rounded down to a multiple of node_unit (e.g. a pipeline
    # stage count or a DCN slice granule).
    node_unit: int = 1


@dataclass
class _WaitingNode:
    node_rank: int
    local_world_size: int
    join_time: float = field(default_factory=time.time)


def plan_restore_entries(stores: Dict[int, Dict], node_rank: int,
                         slices: Dict[int, int],
                         stripe: bool = False) -> Dict:
    """The pure donor-selection core of ``compute_restore_plan``:
    ``stores`` must already be filtered to alive, non-draining donors.
    Shared by the single-lock manager (which calls it under its lock)
    and the sharded router (which calls it with aggregated copies —
    master/rendezvous_shards.py). Returns {"step", "entries", "donors"}
    (epoch stamping is the caller's)."""
    if not stores:
        return {"step": -1, "entries": {}, "donors": {}}
    step = max(store["step"] for store in stores.values())
    at_step = {rank: store for rank, store in stores.items()
               if store["step"] == step}
    requester_slice = slices.get(node_rank, -1)
    holders: Dict[str, List[int]] = {}
    for rank in sorted(at_step):
        for key in at_step[rank]["keys"]:
            holders.setdefault(key, []).append(rank)
    entries: Dict[str, Dict] = {}
    # independent round-robin cursors per tier, so the ICI tier
    # spreads across same-slice donors and the DCN tier across the
    # rest — one shared cursor would skew whichever tier the other
    # consumed from
    spread_same = 0
    spread_cross = 0
    for key in sorted(holders):
        ranks = holders[key]
        if node_rank in ranks:
            donor, tier = node_rank, "local"
        elif stripe and len(ranks) > 1:
            # resharding migration: order every holder same-slice
            # first, then the rest — the receiver stripes the shard's
            # bytes across them in parallel
            same = [r for r in ranks
                    if requester_slice >= 0
                    and slices.get(r, -1) == requester_slice]
            ordered = same + [r for r in ranks if r not in same]
            entries[key] = {
                "ranks": ordered,
                "addrs": [at_step[r]["addr"] for r in ordered],
                "tier": "striped"}
            continue
        else:
            same = [r for r in ranks
                    if requester_slice >= 0
                    and slices.get(r, -1) == requester_slice]
            if same:
                donor = same[spread_same % len(same)]
                spread_same += 1
                tier = "same-slice"
            else:
                donor = ranks[spread_cross % len(ranks)]
                spread_cross += 1
                tier = "cross-slice"
        entries[key] = {"rank": donor,
                        "addr": at_step[donor]["addr"],
                        "tier": tier}
    return {
        "step": step, "entries": entries,
        "donors": {rank: at_step[rank]["addr"] for rank in at_step},
    }


class RendezvousManager:
    """Base rendezvous: collect joiners, cut a round when complete.

    Slice-scoped mode (multi-slice hierarchical DP): when joins carry a
    slice id (and the manager class opts in via ``slice_scoped``), the
    SLICE is the failure domain — each slice cuts its own world with its
    own round counter and generation token, a member death invalidates
    only that slice's world, and the surviving slices' worlds (and
    tokens, and worker pids) are untouched. The fleet-level structures
    (_latest_world/_rdzv_round) stay idle in slice mode; the fleet view
    is the union of slice worlds."""

    name = "base"
    # slice-scoped worlds apply to training rendezvous; the 2-round
    # network-check pairing is deliberately fleet-wide (the probe WANTS
    # cross-slice pairs — DCN links are exactly what it checks)
    slice_scoped = True

    def __init__(self, params: Optional[RendezvousParameters] = None):
        # graftlint: ephemeral(re-derived via update_rdzv_params)
        self._params = params or RendezvousParameters()
        self._lock = threading.Lock()
        self._waiting: Dict[int, _WaitingNode] = {}
        self._alive_nodes: set = set()
        self._rdzv_round = 0
        self._latest_world: Dict[int, int] = {}   # node_rank -> local_world
        self._latest_round_start = 0.0
        self._node_ips: Dict[int, str] = {}
        # Survivors of an invalidated world that have not yet re-joined.
        # The membership-change signal stays raised (level-triggered) until
        # every one of them re-joins or dies — a survivor whose poll missed
        # the first window must still be told to restart.
        self._pending_rejoin: set = set()
        # rank -> last RPC touch (join / comm-world / waiting-num polls):
        # the liveness source for reap_dead_nodes in topologies with no
        # node manager (standalone/CLI masters — reference analogue: the
        # torch rendezvous backend expiring silent members,
        # elastic_agent/torch/training.py:483-521)
        self._last_seen: Dict[int, float] = {}
        # bumped on every mutation of EXPORTED state (joins, leaves,
        # round cuts, membership changes — NOT liveness touches): lets
        # the servicer skip the full state export+hash on the
        # steady-state polls, which mutate nothing almost always
        # graftlint: ephemeral(dirty counter; the new incarnation restarts at 0)
        self._mutations = 0
        # rank -> departure deadline (unix ts): ranks that announced a
        # preemption drain. Still alive (training until departure), but
        # the post-departure world is already planned — on
        # complete_drain (or a blown deadline) the world re-forms in
        # ONE round instead of waiting out the liveness timeout.
        self._draining: Dict[int, float] = {}
        # peer-to-peer restore (checkpoint/peer_restore.py): rank ->
        # {addr, step, keys, bytes, ts} of the staged state its agent's
        # donor server can serve to a replacement rank
        self._peer_stores: Dict[int, Dict] = {}
        # bumped on EVERY membership loss (death, reap, drain
        # completion): restore plans are stamped with it, and a plan
        # whose epoch no longer matches must not commit — a second
        # failure mid-transfer may have taken the donor (or made the
        # planned world itself stale)
        self._world_epoch = 0
        # -- online parallelism re-planning (parallel/planner.py) ------
        # model profile fields fed from ModelInfo reports + chip-stats
        # HBM totals: what the planner scores candidates against. Empty
        # until the first worker reports — plans computed before that
        # rank on topology alone (still deterministic).
        self._model_profile: Dict[str, float] = {}
        self._chip_hbm_bytes: int = 0
        # the last stamped plan (fleet-wide — in slice mode the plan
        # spans every formed slice with dcn = slice count): its mesh
        # feeds the migration term of the NEXT plan, and a change
        # against it is what counts as a REAL re-plan. The inputs it
        # was computed from memoize the planner: every join and every
        # worker's ShardPlanRequest asks, and re-enumerating the mesh
        # space under the manager lock for identical inputs would
        # serialize liveness-critical RPCs behind pure recomputation.
        self._last_plan: Optional[Dict] = None
        self._last_plan_inputs: Optional[Tuple] = None
        # learned per-axis efficiency discounts from the calibration
        # loop (parallel/calibration.py, pushed by the servicer):
        # part of every plan's deterministic inputs. Deliberately NOT
        # exported — the calibration itself persists and re-pushes
        # after a restore, so the discounts can never outlive their
        # evidence.
        # graftlint: ephemeral(re-pushed via push_axis_discounts)
        self._axis_discounts: Dict[str, float] = {}
        # rank -> chips, remembered across world invalidations: the
        # planner must see the EXPECTED post-re-formation world at the
        # FIRST survivor's join (cut worlds are emptied on a death and
        # the waiting list fills one join at a time — planning only
        # from those would stamp a transient partial-world plan per
        # join and fire N-1 spurious re-plan events)
        self._known_chips: Dict[int, int] = {}
        # -- slice-scoped failure domains ------------------------------
        # rank -> slice id, learned from joins/peer-store reports; any
        # entry (with slice_scoped) switches the manager to per-slice
        # worlds
        self._slices: Dict[int, int] = {}
        self._slice_worlds: Dict[int, Dict[int, int]] = {}
        # per-slice round counters (what join/get_comm_world speak in
        # slice mode) and generation tokens — the PER-SLICE layer over
        # PR 3's global master generation: bumped each time THAT slice's
        # world cuts, provably untouched when a DIFFERENT slice fails
        self._slice_rounds: Dict[int, int] = {}
        self._slice_generation: Dict[int, int] = {}
        self._slice_round_start: Dict[int, float] = {}

    # -- membership (driven by the node manager / event callbacks) --------
    def update_rdzv_params(self, min_nodes: int, max_nodes: int,
                           wait_new_node_s: float = 30.0,
                           node_unit: int = 1) -> None:
        with self._lock:
            self._params = RendezvousParameters(
                min_nodes, max_nodes, wait_new_node_s, node_unit
            )

    @property
    def mutation_count(self) -> int:
        with self._lock:
            return self._mutations

    @property
    def alive_nodes(self) -> set:
        """Ranks currently believed alive (the membership the speed
        monitor / diagnosis engine must not outrank)."""
        with self._lock:
            return set(self._alive_nodes)

    def add_alive_node(self, node_rank: int) -> None:
        with self._lock:
            self._alive_nodes.add(node_rank)
            self._last_seen[node_rank] = time.time()
            self._mutations += 1

    def touch(self, node_rank: int) -> None:
        """Record liveness for a rank (any agent RPC qualifies)."""
        if node_rank < 0:
            return
        with self._lock:
            self._last_seen[node_rank] = time.time()

    # -- slice membership (multi-slice hierarchical DP) --------------------
    def _slice_mode_locked(self) -> bool:
        """(lock held)"""
        return self.slice_scoped and bool(self._slices)

    def _record_slice_locked(self, node_rank: int, slice_id: int) -> None:
        """(lock held)"""
        if slice_id >= 0 and self.slice_scoped:
            if self._slices.get(node_rank) != slice_id:
                self._slices[node_rank] = slice_id
                self._mutations += 1

    def record_slice(self, node_rank: int, slice_id: int) -> None:
        """Teach the registry a rank's slice outside the join path
        (reconnects, peer-store reports that precede the first join)."""
        with self._lock:
            self._record_slice_locked(node_rank, slice_id)

    def slice_of(self, node_rank: int) -> int:
        with self._lock:
            return self._slices.get(node_rank, -1)

    @property
    def slice_map(self) -> Dict[int, int]:
        with self._lock:
            return dict(self._slices)

    def slice_members(self, slice_id: int) -> List[int]:
        with self._lock:
            return sorted(r for r, s in self._slices.items()
                          if s == slice_id)

    def slice_status(self) -> Dict:
        """The registry view the cross-slice gradient sync divides by
        (parallel/dcn_sync.py): which slices are formed right now, with
        their generation tokens. JSON-safe."""
        with self._lock:
            sids = sorted(set(self._slices.values()))
            slices = {}
            for sid in sids:
                members = sorted(r for r, s in self._slices.items()
                                 if s == sid)
                world = self._slice_worlds.get(sid, {})
                slices[str(sid)] = {
                    "formed": bool(world),
                    "ranks": sorted(world) if world else members,
                    "generation": self._slice_generation.get(sid, 0),
                    "draining": any(r in self._draining
                                    for r in members),
                }
            # the world epoch namespaces the hot dcn/ coordination keys
            # (parallel/dcn_sync.py + kv_store episode hygiene): every
            # membership loss moves the fleet to a fresh key namespace
            return {"total": len(sids), "slices": slices,
                    "epoch": self._world_epoch}

    def world_for(self, node_rank: int) -> Dict[int, int]:
        """The world ``node_rank`` belongs to: its slice's world in
        slice mode, the fleet world otherwise (the reconnect handler's
        intact check must compare against the right scope)."""
        with self._lock:
            if self._slice_mode_locked() and node_rank in self._slices:
                return dict(self._slice_worlds.get(
                    self._slices[node_rank], {}))
            return dict(self._latest_world)

    def round_for(self, node_rank: int) -> int:
        """The latest completed round in ``node_rank``'s scope."""
        with self._lock:
            if self._slice_mode_locked() and node_rank in self._slices:
                return self._slice_rounds.get(
                    self._slices[node_rank], 0) - 1
            return self._rdzv_round - 1

    def _slice_ready_locked(self, sid: int) -> bool:
        """A slice's round completes when every alive member joined, or
        the late-node grace expired with at least one waiting (lock
        held). node_unit deliberately does not apply: a slice cuts
        whole — partial slices are what the failure domain forbids."""
        waiting = {r for r in self._waiting
                   if self._slices.get(r) == sid}
        if not waiting:
            return False
        alive = {r for r in self._alive_nodes
                 if self._slices.get(r) == sid}
        if alive and alive.issubset(set(self._waiting)):
            return True
        started = self._slice_round_start.get(sid)
        return (started is not None
                and time.time() - started >= self._params.wait_new_node_s)

    def _cut_slice_locked(self, sid: int):
        """Cut ``sid``'s world from its waiting members (lock held).
        Returns (sid, round, generation, world, duration) for the
        caller's obs emission outside the lock."""
        members = sorted(r for r in self._waiting
                         if self._slices.get(r) == sid)
        world = {r: self._waiting[r].local_world_size for r in members}
        for rank in members:
            del self._waiting[rank]
        self._slice_worlds[sid] = world
        self._slice_rounds[sid] = self._slice_rounds.get(sid, 0) + 1
        self._slice_generation[sid] = (
            self._slice_generation.get(sid, 0) + 1)
        self._mutations += 1
        started = self._slice_round_start.pop(sid, None)
        duration = (max(0.0, time.time() - started)
                    if started is not None else 0.0)
        logger.info(
            "%s rendezvous: slice %d round %d cut (generation %d): "
            "world=%s", self.name, sid, self._slice_rounds[sid] - 1,
            self._slice_generation[sid], sorted(world))
        return (sid, self._slice_rounds[sid] - 1,
                self._slice_generation[sid], dict(world), duration)

    def _emit_slice_cut_obs(self, cut) -> None:
        """Flight + metrics for a just-cut slice world (called OUTSIDE
        the manager lock)."""
        sid, round_idx, generation, world, duration = cut
        obs.get_flight_recorder().record_event(
            "slice_world_cut", rdzv=self.name, slice=sid,
            round=round_idx, generation=generation,
            world=sorted(world))
        obs.record_span(
            "rendezvous_round", duration,
            attrs={"rdzv": self.name, "round": round_idx, "slice": sid,
                   "world_size": len(world)})
        registry = obs.get_registry()
        registry.counter(
            "dlrover_tpu_rendezvous_rounds_total",
            "Completed rendezvous rounds", labelnames=("rdzv",),
        ).labels(rdzv=self.name).inc()
        registry.gauge(
            "dlrover_tpu_slice_generation",
            "Per-slice generation token: bumped each time THAT slice's "
            "world re-forms (a peer slice's failure must not move it)",
            labelnames=("slice",)).labels(slice=str(sid)).set(generation)
        registry.gauge(
            "dlrover_tpu_slice_world_size",
            "Node count of the slice's latest cut world",
            labelnames=("slice",)).labels(slice=str(sid)).set(len(world))

    # -- preemption drain --------------------------------------------------
    def _publish_draining_gauge(self) -> None:
        """Republished by EVERY path that mutates the draining set
        (notice, completion, blown-deadline reap, re-join cancel,
        death, state restore) — updating it only on the drain RPC
        would leave phantom perpetually-draining ranks on the others.
        Called OUTSIDE the manager lock (obs takes its own)."""
        if self.name != RendezvousName.TRAINING:
            return
        with self._lock:
            count = len(self._draining)
        obs.get_registry().gauge(
            "dlrover_tpu_draining_nodes",
            "Ranks currently draining (announced, not yet departed)",
        ).set(count)

    def mark_draining(self, node_rank: int, deadline: float
                      ) -> Dict[int, int]:
        """A preemption notice for ``node_rank``: it keeps training
        until departure, but the post-departure world is planned NOW.
        Returns that planned world (latest world minus every draining
        rank) so the caller can log/verify the one-round target."""
        with self._lock:
            if node_rank in self._alive_nodes:
                self._draining[node_rank] = deadline
                self._mutations += 1
            if (self._slice_mode_locked()
                    and node_rank in self._slices):
                base_world = self._slice_worlds.get(
                    self._slices[node_rank], {})
            else:
                base_world = self._latest_world
            planned = {rank: n for rank, n in base_world.items()
                       if rank not in self._draining}
        logger.info(
            "%s rendezvous: node %d DRAINING (deadline %.0fs away); "
            "planned post-departure world %s", self.name, node_rank,
            max(0.0, deadline - time.time()), sorted(planned))
        self._publish_draining_gauge()
        return planned

    def complete_drain(self, node_rank: int) -> bool:
        """The drained worker exited clean: remove the rank immediately
        (planned departure — no liveness timeout) so survivors re-form
        in one round. Returns whether the rank was known draining."""
        with self._lock:
            was_draining = self._draining.pop(node_rank, None) is not None
        # NOT graceful: the cut world contained the drained rank, so
        # survivors must re-join for the planned smaller world — and
        # with the rank out of the alive set the new round cuts as soon
        # as the last survivor joins (no wait_new_node_s stall)
        self.remove_alive_node(node_rank, graceful=False)
        obs.get_flight_recorder().record_event(
            "node_drained", rdzv=self.name, rank=node_rank,
            announced=was_draining)
        return was_draining

    @property
    def draining(self) -> Dict[int, float]:
        with self._lock:
            return dict(self._draining)

    # -- peer-to-peer restore (checkpoint/peer_restore.py) -----------------
    @property
    def world_epoch(self) -> int:
        with self._lock:
            return self._world_epoch

    def register_peer_store(self, node_rank: int, addr: str, step: int,
                            keys, total_bytes: int = 0,
                            slice_id: int = -1) -> None:
        """An agent advertising (or withdrawing: step < 0 / no keys) the
        staged state its donor server can serve. ``slice_id`` also
        teaches the slice registry — store reports land BEFORE the
        join, and a restarted master must know the donor's slice to
        tier the plan."""
        with self._lock:
            self._record_slice_locked(node_rank, slice_id)
            if step < 0 or not keys:
                if self._peer_stores.pop(node_rank, None) is not None:
                    self._mutations += 1
                return
            self._peer_stores[node_rank] = {
                "addr": addr, "step": int(step), "keys": list(keys),
                "bytes": int(total_bytes), "ts": time.time(),
            }
            self._mutations += 1

    @property
    def peer_stores(self) -> Dict[int, Dict]:
        with self._lock:
            return {rank: dict(s) for rank, s in self._peer_stores.items()}

    # -- online parallelism re-planning (parallel/planner.py) --------------
    def set_model_profile(self, param_count: int = 0,
                          param_bytes: int = 0,
                          flops_per_token: float = 0.0,
                          peak_flops_per_chip: float = 0.0,
                          seq_len: int = 0,
                          global_batch: int = 0,
                          tensor_divisor: int = 0,
                          fsdp_divisor: int = 0) -> None:
        """Teach the planner the model's shape (fed from ModelInfo
        reports by the servicer). Zero fields leave the previous value
        standing — a cross-check re-report that only updates the FLOPs
        model must not erase the batch."""
        updates = {"param_count": param_count, "param_bytes": param_bytes,
                   "flops_per_token": flops_per_token,
                   "peak_flops_per_chip": peak_flops_per_chip,
                   "seq_len": seq_len, "global_batch": global_batch,
                   "tensor_divisor": tensor_divisor,
                   "fsdp_divisor": fsdp_divisor}
        with self._lock:
            for key, value in updates.items():
                if value and value > 0:
                    if self._model_profile.get(key) != value:
                        self._model_profile[key] = value
                        self._mutations += 1

    def set_chip_hbm(self, hbm_bytes: int) -> None:
        """Observed per-chip HBM total (from NodeResourceStats chip
        stats): the planner's memory-fit budget. 0 stays 0 —
        unconstrained (CPU harnesses)."""
        with self._lock:
            if hbm_bytes > 0 and self._chip_hbm_bytes != int(hbm_bytes):
                self._chip_hbm_bytes = int(hbm_bytes)
                self._mutations += 1

    def set_axis_discounts(self, discounts: Dict[str, float]) -> None:
        """Learned per-axis efficiency corrections from the calibration
        loop (parallel/calibration.py, pushed by the servicer when the
        learned table changes): scoring input for every later plan.
        Changing them invalidates the plan memo (they are part of its
        inputs) but deliberately does not bump the mutation counter —
        derived state, re-pushed from the persisted calibration."""
        with self._lock:
            self._axis_discounts = {str(k): float(v)
                                    for k, v in (discounts or {}).items()
                                    if v and v > 0}

    def _plan_world_locked(self) -> Dict[int, int]:
        """(lock held) The world the next plan must cover: every alive,
        non-draining rank — cut worlds and the waiting list give the
        freshest chip counts, the remembered ``_known_chips`` covers
        survivors that have not re-joined yet (their world was
        invalidated an instant ago, but they ARE part of the world
        that is about to form). Planning from the full expected set
        means the FIRST join after a membership change already sees
        the final plan — one re-plan per resize, not one per joiner."""
        chips: Dict[int, int] = dict(self._known_chips)
        if self._slice_mode_locked():
            for world in self._slice_worlds.values():
                chips.update(world)
        else:
            chips.update(self._latest_world)
        for rank, waiting in self._waiting.items():
            chips[rank] = waiting.local_world_size
        return {rank: int(n) for rank, n in chips.items()
                if rank in self._alive_nodes
                and rank not in self._draining}

    def compute_shard_plan(self, node_rank: int) -> Tuple[Dict, bool]:
        """The deterministic parallelism plan for the (forming) world
        ``node_rank`` belongs to (parallel/planner.py): DP×TP×PP(×DCN)
        mesh + batch/accumulation shape, stamped with the rendezvous
        generation token and the world epoch (same staleness
        discipline as restore plans). Returns (plan, changed) —
        ``changed`` is True when the plan's execution shape differs
        from the last stamped one (a REAL re-plan, not a re-stamp for
        a late joiner)."""
        from dlrover_tpu.parallel import planner

        with self._lock:
            world = self._plan_world_locked()
            slices = (len({self._slices.get(r, -1) for r in world})
                      if self._slice_mode_locked() and world else 1)
            profile = planner.ModelProfile(
                param_count=int(self._model_profile.get(
                    "param_count", 0)),
                param_bytes=int(self._model_profile.get(
                    "param_bytes", 0)),
                flops_per_token=float(self._model_profile.get(
                    "flops_per_token", 0.0)),
                peak_flops_per_chip=float(self._model_profile.get(
                    "peak_flops_per_chip", 0.0)),
                seq_len=int(self._model_profile.get("seq_len", 0)),
                global_batch=int(self._model_profile.get(
                    "global_batch", 0)),
                hbm_bytes_per_chip=self._chip_hbm_bytes,
                tensor_divisor=int(self._model_profile.get(
                    "tensor_divisor", 0)),
                fsdp_divisor=int(self._model_profile.get(
                    "fsdp_divisor", 0)),
            )
            if self._slice_mode_locked() and node_rank in self._slices:
                sid = self._slices[node_rank]
                generation = self._slice_generation.get(sid, 0)
                round_ = self._slice_rounds.get(sid, 0)
            else:
                generation = self._rdzv_round
                round_ = self._rdzv_round
            discounts = dict(self._axis_discounts)
            inputs = (tuple(sorted(world.items())), profile,
                      max(1, slices), generation, self._world_epoch,
                      round_, tuple(sorted(discounts.items())))
            if (self._last_plan is not None
                    and inputs == self._last_plan_inputs):
                # identical inputs → identical (deterministic) plan:
                # answer the memo instead of re-enumerating the mesh
                # space under the lock for every join/plan poll
                return dict(self._last_plan), False
            plan = planner.plan_parallelism(
                world, profile, slices=max(1, slices),
                prev_plan=self._last_plan, generation=generation,
                epoch=self._world_epoch, round_=round_,
                axis_discounts=discounts or None)
            self._last_plan_inputs = inputs
            equivalent = planner.plans_equivalent(self._last_plan, plan)
            # a REAL re-plan needs a previous plan to differ from AND a
            # world that has ever formed — bootstrap joins refine the
            # first plan as members arrive, which is formation, not a
            # resize (no replan events, no MFU re-anchor churn)
            has_cut = (any(self._slice_rounds.values())
                       if self._slice_mode_locked()
                       else self._rdzv_round > 0)
            changed = (self._last_plan is not None and has_cut
                       and not equivalent)
            prev = None
            if not equivalent:
                prev = self._last_plan
                self._last_plan = plan
                self._mutations += 1
        if changed and prev is not None:
            obs.get_flight_recorder().record_event(
                "replan_stamped", rdzv=self.name,
                world_size=plan.get("world_size"),
                devices=plan.get("total_devices"),
                mesh=plan.get("mesh"), prev_mesh=prev.get("mesh"),
                global_batch=plan.get("global_batch"),
                batch_adjusted=plan.get("batch_adjusted"),
                resharded=plan.get("resharded"),
                generation=plan.get("generation"),
                epoch=plan.get("epoch"))
        return plan, changed

    @property
    def last_shard_plan(self) -> Optional[Dict]:
        with self._lock:
            return dict(self._last_plan) if self._last_plan else None

    def compute_restore_plan(self, node_rank: int,
                             stripe: bool = False) -> Dict:
        """For each staged shard a restoring rank may need, which
        surviving donor serves it. Donors: alive, not draining, staged
        at the newest common step (mixing steps would assemble a state
        that never existed). The requester's own store wins for shards
        it holds (a local read beats the network); the rest prefer
        SAME-SLICE donors (ICI bandwidth) before cross-slice (DCN)
        ones, round-robin within each tier. Stamped with the world
        epoch — the staleness guard. Pure dict work under the lock;
        JSON encoding is the caller's business.

        ``stripe`` (the re-plan migration mode): each entry lists EVERY
        same-step holder (same-slice donors first) so the receiver can
        fetch contiguous byte RANGES of one shard from several donors
        in parallel — who sends which shard slice to whom when the
        target sharding differs from the source
        (checkpoint/peer_restore.py ``fetch_shards``)."""
        with self._lock:
            stores = {
                rank: store
                for rank, store in self._peer_stores.items()
                if rank in self._alive_nodes
                and rank not in self._draining
            }
            plan = plan_restore_entries(stores, node_rank, self._slices,
                                        stripe=stripe)
            plan["epoch"] = self._world_epoch
            if stripe:
                plan["mode"] = "stripe"
            return plan

    def export_protocol_view(self) -> Dict:
        """One-lock-cut view of the protocol membership (the sharded
        router aggregates these per shard for fleet-wide planning —
        master/rendezvous_shards.py)."""
        with self._lock:
            return {
                "world": dict(self._latest_world),
                "waiting": {r: w.local_world_size
                            for r, w in self._waiting.items()},
                "alive": set(self._alive_nodes),
                "draining": dict(self._draining),
            }

    def reap_dead_nodes(self, timeout_s: float) -> None:
        """Declare ranks silent for > timeout_s dead (world invalidation
        via remove_alive_node). 0/negative disables. Runs on live agents'
        polls — no master thread needed, and with no live agents there is
        nobody left to tell anyway.

        Draining ranks whose departure deadline passed are reaped
        regardless of the liveness timeout: the platform took the VM at
        the deadline even if the drain-complete RPC was lost."""
        now = time.time()
        with self._lock:
            overdue = [rank for rank, deadline in self._draining.items()
                       if now > deadline + 5.0]
            for rank in overdue:
                del self._draining[rank]
        for rank in overdue:
            logger.warning(
                "%s rendezvous: draining node %d blew its departure "
                "deadline without reporting completion; removing it",
                self.name, rank)
            self.remove_alive_node(rank, graceful=False)
        if timeout_s <= 0:
            return
        with self._lock:
            dead = [rank for rank in self._alive_nodes
                    if now - self._last_seen.get(rank, now) > timeout_s]
        for rank in dead:
            logger.warning(
                "%s rendezvous: node %d silent for > %.0fs; declaring it "
                "dead", self.name, rank, timeout_s)
            self.remove_alive_node(rank, graceful=False)

    def remove_alive_node(self, node_rank: int,
                          graceful: bool = False) -> None:
        """Drop a node from membership. ``graceful`` marks a clean exit
        (worker finished): survivors keep running, so the cut world stays
        valid for them and must NOT be invalidated — only a death does."""
        invalidated_round = None
        slice_invalidated = None
        with self._lock:
            in_slice_world = any(
                node_rank in world
                for world in self._slice_worlds.values())
            if (node_rank in self._alive_nodes
                    or node_rank in self._latest_world
                    or in_slice_world):
                # a real membership loss: any restore plan computed
                # before this instant may name the departed rank as a
                # donor — the epoch bump invalidates it at commit time
                self._world_epoch += 1
            self._alive_nodes.discard(node_rank)
            self._waiting.pop(node_rank, None)
            self._pending_rejoin.discard(node_rank)
            self._draining.pop(node_rank, None)
            # the host's staged state goes with the host
            self._peer_stores.pop(node_rank, None)
            self._mutations += 1
            if self._slice_mode_locked():
                # SLICE-SCOPED cut: only the dead rank's slice loses
                # its world. Every other slice's world, round counter
                # and generation token are deliberately untouched —
                # that is the failure-domain contract. (The rank keeps
                # its slice-map entry: it re-joins as the same slice.)
                sid = self._slices.get(node_rank, -1)
                world = self._slice_worlds.get(sid, {})
                if not graceful and node_rank in world:
                    logger.info(
                        "%s rendezvous: node %d died after slice %d "
                        "round %d was cut; invalidating ONLY that "
                        "slice's world (fleet unaffected)", self.name,
                        node_rank, sid,
                        self._slice_rounds.get(sid, 1) - 1)
                    self._pending_rejoin |= set(world) - {node_rank}
                    self._slice_worlds[sid] = {}
                    slice_invalidated = (
                        sid, self._slice_rounds.get(sid, 1) - 1)
            elif not graceful and node_rank in self._latest_world:
                # A member of the cut round died: any survivor handed this
                # world would only find out at jax.distributed.initialize
                # timeout. Empty it so polls report "still forming" and
                # survivors re-join for a fresh round.
                logger.info(
                    "%s rendezvous: node %d died after round %d was cut; "
                    "invalidating the world", self.name, node_rank,
                    self._rdzv_round - 1,
                )
                self._pending_rejoin |= (
                    set(self._latest_world) - {node_rank}
                )
                self._latest_world = {}
                self._on_world_invalidated()
                invalidated_round = self._rdzv_round - 1
        # obs sinks run OUTSIDE the manager lock (they take their own)
        self._publish_draining_gauge()
        if slice_invalidated is not None:
            sid, round_idx = slice_invalidated
            obs.get_flight_recorder().record_event(
                "slice_world_invalidated", rdzv=self.name, slice=sid,
                dead_rank=node_rank, round=round_idx)
            obs.get_registry().counter(
                "dlrover_tpu_rendezvous_world_invalidations_total",
                "Cut worlds invalidated by a member death",
                labelnames=("rdzv",),
            ).labels(rdzv=self.name).inc()
        if invalidated_round is not None:
            self._emit_invalidation_obs(node_rank, invalidated_round)

    def _emit_invalidation_obs(self, node_rank: int,
                               invalidated_round: int) -> None:
        """Flight + metrics for an invalidated cut world (called OUTSIDE
        the manager lock; shard inners override it to emit the
        slice-labeled variant — master/rendezvous_shards.py)."""
        obs.get_flight_recorder().record_event(
            "world_invalidated", rdzv=self.name,
            dead_rank=node_rank, round=invalidated_round)
        obs.get_registry().counter(
            "dlrover_tpu_rendezvous_world_invalidations_total",
            "Cut worlds invalidated by a member death",
            labelnames=("rdzv",),
        ).labels(rdzv=self.name).inc()

    def _on_world_invalidated(self) -> None:
        """Hook for subclasses holding state keyed on the cut world
        (lock held)."""

    # -- agent-facing protocol --------------------------------------------
    def join_rendezvous(self, node_rank: int, local_world_size: int,
                        node_ip: str = "", slice_id: int = -1) -> int:
        """Register a joiner; returns the round it will be placed in
        (its SLICE's round in slice mode)."""
        with self._lock:
            self._record_slice_locked(node_rank, slice_id)
            self._waiting[node_rank] = _WaitingNode(node_rank,
                                                    local_world_size)
            # the planner's expected-world chip map (kept across world
            # invalidations; see _plan_world_locked)
            self._known_chips[node_rank] = local_world_size
            self._alive_nodes.add(node_rank)
            self._last_seen[node_rank] = time.time()
            self._pending_rejoin.discard(node_rank)
            # a re-joining rank is no longer departing (drain cancelled
            # operator-side, or the platform gave the VM back)
            self._draining.pop(node_rank, None)
            if node_ip:
                self._node_ips[node_rank] = node_ip
            if len(self._waiting) == 1:
                self._latest_round_start = time.time()
            self._mutations += 1
            if (self._slice_mode_locked()
                    and node_rank in self._slices):
                sid = self._slices[node_rank]
                # the slice's grace window is timed from ITS first
                # waiting member, not the fleet's (test membership,
                # not rank truthiness — rank 0 is falsy)
                others_waiting = any(
                    r != node_rank and self._slices.get(r) == sid
                    for r in self._waiting)
                if not others_waiting:
                    self._slice_round_start[sid] = time.time()
                joined_round = self._slice_rounds.get(sid, 0)
            else:
                joined_round = self._rdzv_round
        obs.get_registry().counter(
            "dlrover_tpu_rendezvous_joins_total",
            "join_rendezvous RPCs accepted", labelnames=("rdzv",),
        ).labels(rdzv=self.name).inc()
        self._publish_draining_gauge()
        return joined_round

    def leave_waiting(self, node_rank: int) -> None:
        """A joiner abandoning an UNCOMPLETED round (its poll deadline
        expired). Its entry must not linger: a late partner would
        otherwise complete the round against a peer that already left
        and hang waiting for that peer's coordinator. The node stays
        alive (it may re-join); a no-op after the round cut."""
        with self._lock:
            if self._waiting.pop(node_rank, None) is not None:
                self._mutations += 1
                logger.info(
                    "%s rendezvous: node %d left the waiting list "
                    "(gave up on the forming round)", self.name,
                    node_rank)

    def get_comm_world(self, node_rank: int
                       ) -> Tuple[int, int, Dict[int, int]]:
        """Poll for the completed world. Returns (round, group, world) —
        empty world while the round is still forming. In slice mode the
        world is the polling rank's SLICE world and ``group`` carries
        the slice id."""
        cut_info = None
        slice_cut = None
        with self._lock:
            self._last_seen[node_rank] = time.time()
            if (self._slice_mode_locked()
                    and node_rank in self._slices):
                sid = self._slices[node_rank]
                if self._slice_ready_locked(sid):
                    slice_cut = self._cut_slice_locked(sid)
                world = self._slice_worlds.get(sid, {})
                if (node_rank in world
                        and node_rank not in self._waiting):
                    result = (self._slice_rounds.get(sid, 1) - 1, sid,
                              dict(world))
                else:
                    result = self._slice_rounds.get(sid, 0), sid, {}
            else:
                if self._check_rdzv_completed():
                    cut_info = self._cut_round()
                # A node still in the waiting list has re-joined for the
                # NEXT round — the latest world is stale for it (it may
                # contain dead peers), so report "still forming".
                if (node_rank in self._latest_world
                        and node_rank not in self._waiting):
                    result = (self._rdzv_round - 1, 0,
                              dict(self._latest_world))
                else:
                    result = self._rdzv_round, 0, {}
        if slice_cut is not None:
            self._emit_slice_cut_obs(slice_cut)
        if cut_info is not None:
            self._emit_round_obs(cut_info)
        return result

    def num_nodes_waiting(self, node_rank: int = -1) -> int:
        """Agents restart workers when >0 while healthy (membership change;
        reference: training.py:483-486). In slice mode the signal is
        scoped to the POLLING rank's slice: a peer slice re-forming must
        not restart this slice's worker — that is the failure domain."""
        with self._lock:
            if (self._slice_mode_locked() and node_rank >= 0
                    and node_rank in self._slices):
                sid = self._slices[node_rank]
                members = {r for r, s in self._slices.items()
                           if s == sid}
                waiting = set(self._waiting) & members
                if self._pending_rejoin & members:
                    return max(1, len(waiting))
                if not self._slice_worlds.get(sid):
                    return 0
                return len(waiting)
            if self._pending_rejoin:
                # A world member died: every survivor must restart and
                # re-join; keep the signal raised until each has done so
                # (or died), however late its poll arrives.
                return max(1, len(self._waiting))
            # Before the first round there is no world to change.
            if not self._latest_world:
                return 0
            return len(self._waiting)

    # -- internals ---------------------------------------------------------
    def _check_rdzv_completed(self) -> bool:
        """Round completes when every alive node joined, or min_nodes joined
        and the late-node grace window expired (lock held)."""
        if not self._waiting:
            return False
        if self._slice_mode_locked() and all(
                rank in self._slices for rank in self._waiting):
            # slice mode: every waiting rank belongs to a slice — the
            # per-slice cut path owns them; a sliceless poller must not
            # sweep them into a fleet round
            return False
        num = min(len(self._waiting), self._params.max_nodes)
        if num < self._params.min_nodes:
            return False
        alive_all_joined = (
            self._alive_nodes
            and self._alive_nodes.issubset(set(self._waiting))
        )
        if num == self._params.max_nodes or alive_all_joined:
            return self._rounded_size(num) >= self._params.min_nodes
        waited = time.time() - self._latest_round_start
        if waited >= self._params.wait_new_node_s:
            return self._rounded_size(num) >= self._params.min_nodes
        return False

    def _rounded_size(self, num: int) -> int:
        unit = max(1, self._params.node_unit)
        return (num // unit) * unit

    def _cut_round(self):
        """Select the world for this round (lock held). Returns
        (duration_s, round_idx, world_size, world_ranks) for the caller
        to pass to `_emit_round_obs` once the lock is released."""
        size = self._rounded_size(
            min(len(self._waiting), self._params.max_nodes)
        )
        # Keep the lowest-ranked `size` nodes; the rest stay waiting for the
        # next round (node_unit remainder).
        chosen = sorted(self._waiting)[:size]
        self._latest_world = {
            rank: self._waiting[rank].local_world_size for rank in chosen
        }
        for rank in chosen:
            del self._waiting[rank]
        self._rdzv_round += 1
        self._mutations += 1
        logger.info(
            "%s rendezvous round %d completed: world=%s",
            self.name, self._rdzv_round - 1, sorted(self._latest_world),
        )
        duration = max(0.0, time.time() - self._latest_round_start)
        if self._waiting:
            # a node_unit remainder stays waiting: it opens the NEXT
            # forming round now (the len==1 transition in join_rendezvous
            # will never fire for it, so the next round's span/grace
            # window must not be timed from the OLD round's first join)
            self._latest_round_start = time.time()
        return (duration, self._rdzv_round - 1, len(self._latest_world),
                sorted(self._latest_world))

    def _emit_round_obs(self, cut_info) -> None:
        """Round span + counters for a just-cut round. Called AFTER the
        manager lock is released — span sinks and registry children take
        their own locks and must never nest under it."""
        duration_s, round_idx, world_size, _ = cut_info
        obs.record_span(
            "rendezvous_round", duration_s,
            attrs={"rdzv": self.name, "round": round_idx,
                   "world_size": world_size},
        )
        registry = obs.get_registry()
        registry.counter(
            "dlrover_tpu_rendezvous_rounds_total",
            "Completed rendezvous rounds", labelnames=("rdzv",),
        ).labels(rdzv=self.name).inc()
        registry.gauge(
            "dlrover_tpu_rendezvous_world_size",
            "Node count of the latest cut world", labelnames=("rdzv",),
        ).labels(rdzv=self.name).set(world_size)

    @property
    def latest_world(self) -> Dict[int, int]:
        """The fleet view: the union of slice worlds in slice mode."""
        with self._lock:
            if self._slice_mode_locked():
                merged: Dict[int, int] = {}
                for world in self._slice_worlds.values():
                    merged.update(world)
                return merged
            return dict(self._latest_world)

    @property
    def rdzv_round(self) -> int:
        with self._lock:
            return self._rdzv_round

    # -- crash-consistent state (master/state_backend.py) ------------------
    def export_state(self) -> dict:
        """JSON-safe snapshot of the rendezvous protocol state. Liveness
        clocks (_last_seen) are NOT exported: wall time on the restarted
        master restarts them, and exporting stale clocks would reap every
        member the instant the new master serves its first poll."""
        with self._lock:
            state = {
                "round": self._rdzv_round,
                "latest_world": {str(r): n
                                 for r, n in self._latest_world.items()},
                "waiting": {str(r): w.local_world_size
                            for r, w in self._waiting.items()},
                "alive": sorted(self._alive_nodes),
                "pending_rejoin": sorted(self._pending_rejoin),
                "node_ips": {str(r): ip
                             for r, ip in self._node_ips.items()},
                "draining": {str(r): deadline
                             for r, deadline in self._draining.items()},
                "world_epoch": self._world_epoch,
                "peer_stores": {
                    str(r): {"addr": s["addr"], "step": s["step"],
                             "keys": list(s["keys"]),
                             "bytes": s.get("bytes", 0)}
                    for r, s in self._peer_stores.items()
                },
                # slice-scoped failure domains: membership, per-slice
                # worlds and the generation tokens must survive a
                # master failover — a restarted master that forgot the
                # tokens would hand every slice a fresh generation and
                # erase the "untouched survivor" evidence
                "slices": {str(r): s for r, s in self._slices.items()},
                "slice_worlds": {
                    str(sid): {str(r): n for r, n in world.items()}
                    for sid, world in self._slice_worlds.items()
                },
                "slice_rounds": {str(sid): r for sid, r
                                 in self._slice_rounds.items()},
                "slice_generation": {
                    str(sid): g for sid, g
                    in self._slice_generation.items()},
                # online re-planning: the model profile and the last
                # stamped plan must survive a master failover — a
                # restarted master that forgot them would stamp a
                # migration-blind plan (and mis-detect a "re-plan")
                # on the first join it serves
                "model_profile": dict(self._model_profile),
                "chip_hbm_bytes": self._chip_hbm_bytes,
                "last_plan": (dict(self._last_plan)
                              if self._last_plan else None),
                "known_chips": {str(r): n for r, n
                                in self._known_chips.items()},
            }
            # subclass fields join the SAME cut: one lock acquisition,
            # never two cuts with a mutation in between
            self._export_extra(state)
            return state

    def _export_extra(self, state: dict) -> None:
        """Subclass hook appending extra exported fields (lock held)."""

    def restore_state(self, state: dict) -> None:
        if "shards" in state:
            # a SHARDED master wrote this lineage; flatten its per-shard
            # partitions instead of silently restoring an empty protocol
            # state (the rdzv_sharded=0 escape hatch must keep working
            # over an existing sharded state-dir)
            from dlrover_tpu.master.rendezvous_shards import (
                flatten_sharded_state,
            )

            state = flatten_sharded_state(state)
        now = time.time()
        with self._lock:
            self._rdzv_round = int(state.get("round", 0))
            self._latest_world = {
                int(r): int(n)
                for r, n in state.get("latest_world", {}).items()
            }
            self._waiting = {
                int(r): _WaitingNode(int(r), int(n), join_time=now)
                for r, n in state.get("waiting", {}).items()
            }
            self._alive_nodes = {int(r) for r in state.get("alive", ())}
            self._pending_rejoin = {
                int(r) for r in state.get("pending_rejoin", ())
            }
            self._node_ips = {int(r): ip
                              for r, ip in state.get("node_ips",
                                                     {}).items()}
            # absolute deadlines survive the restart as-is: a drain
            # announced before the master died is still a drain, and a
            # blown deadline is reaped on the first poll
            self._draining = {int(r): float(d)
                              for r, d in state.get("draining",
                                                    {}).items()}
            # a restored plan epoch keeps in-flight plans valid across a
            # master failover — the membership they were computed from
            # was restored with them; peer stores re-register within a
            # monitor tick anyway, but restoring them means a restore
            # landing mid-failover still gets a plan
            self._world_epoch = int(state.get("world_epoch", 0))
            self._peer_stores = {
                int(r): {"addr": s.get("addr", ""),
                         "step": int(s.get("step", -1)),
                         "keys": list(s.get("keys", ())),
                         "bytes": int(s.get("bytes", 0)),
                         "ts": now}
                for r, s in state.get("peer_stores", {}).items()
            }
            self._slices = {int(r): int(s) for r, s in
                            (state.get("slices") or {}).items()}
            self._slice_worlds = {
                int(sid): {int(r): int(n) for r, n in world.items()}
                for sid, world in
                (state.get("slice_worlds") or {}).items()
            }
            self._slice_rounds = {
                int(sid): int(r) for sid, r in
                (state.get("slice_rounds") or {}).items()}
            self._slice_generation = {
                int(sid): int(g) for sid, g in
                (state.get("slice_generation") or {}).items()}
            self._model_profile = {
                str(k): float(v) for k, v in
                (state.get("model_profile") or {}).items()}
            self._chip_hbm_bytes = int(state.get("chip_hbm_bytes", 0))
            last_plan = state.get("last_plan")
            self._last_plan = (dict(last_plan)
                               if isinstance(last_plan, dict) else None)
            self._known_chips = {
                int(r): int(n) for r, n in
                (state.get("known_chips") or {}).items()}
            # the memo key is not exported: the first post-restore ask
            # recomputes (and, being deterministic, re-stamps the same
            # plan without a spurious changed flag)
            self._last_plan_inputs = None
            self._slice_round_start = {}
            # every restored member gets a fresh liveness clock: agents
            # re-register within their poll interval, the genuinely dead
            # age out through the normal reap path
            self._last_seen = {rank: now for rank in self._alive_nodes}
            self._latest_round_start = now
            self._restore_extra(state)
        self._publish_draining_gauge()

    def _restore_extra(self, state: dict) -> None:
        """Subclass hook restoring extra exported fields (lock held)."""


class ElasticTrainingRendezvousManager(RendezvousManager):
    name = "elastic-training"


class NetworkCheckRendezvousManager(RendezvousManager):
    """2-round diagnostic rendezvous (reference: rdzv_manager.py:248-461).

    Deliberately NOT slice-scoped (``slice_scoped = False``): the probe
    pairs across the whole fleet — cross-slice DCN links are part of
    what it checks.

    Round 0 groups adjacent pairs; round 1 re-pairs fastest-with-slowest so a
    node that failed round 0 is re-tested against a known-good partner. On a
    TPU slice the pair maps to a 2-host sub-mesh probe program (allgather over
    ICI/DCN); see dlrover_tpu/diagnostics/network_check.py.
    """

    name = "network-check"
    slice_scoped = False

    def __init__(self, params: Optional[RendezvousParameters] = None):
        super().__init__(params)
        # round -> {node_rank: (normal, elapsed_time)}
        self._reports: Dict[int, Dict[int, Tuple[bool, float]]] = {}
        self._check_round = 0
        self._groups: Dict[int, List[List[int]]] = {}

    def get_comm_world(self, node_rank: int
                       ) -> Tuple[int, int, Dict[int, int]]:
        cut_info = None
        result = None
        with self._lock:
            self._last_seen[node_rank] = time.time()
            if self._check_rdzv_completed():
                cut_info = self._cut_round()
                self._groups[self._rdzv_round - 1] = self._group_nodes(
                    self._check_round
                )
                self._check_round += 1
            round_idx = self._rdzv_round - 1
            groups = self._groups.get(round_idx, [])
            for gi, group in enumerate(groups):
                if (node_rank in group
                        and all(r in self._latest_world for r in group)):
                    world = {r: self._latest_world[r] for r in group}
                    result = round_idx, gi, world
                    break
            if result is None:
                result = self._rdzv_round, 0, {}
        if cut_info is not None:
            self._emit_round_obs(cut_info)
        return result

    def _on_world_invalidated(self) -> None:
        # Groups are keyed on the cut world; a member death makes the
        # latest round's grouping stale (lock held).
        self._groups.pop(self._rdzv_round - 1, None)

    def _group_nodes(self, check_round: int) -> List[List[int]]:
        """Pair nodes for the probe (lock held). Round 0: adjacent pairs.
        Round ≥1: sort by last round's elapsed time, pair fastest with
        slowest (reference: rdzv_manager.py:299-346)."""
        ranks = sorted(self._latest_world)
        if check_round == 0 or not self._reports.get(check_round - 1):
            pairs = [ranks[i:i + 2] for i in range(0, len(ranks), 2)]
        else:
            prev = self._reports[check_round - 1]
            by_time = sorted(
                ranks, key=lambda r: prev.get(r, (False, float("inf")))[1]
            )
            pairs = []
            lo, hi = 0, len(by_time) - 1
            while lo < hi:
                pairs.append([by_time[lo], by_time[hi]])
                lo += 1
                hi -= 1
            if lo == hi:
                pairs.append([by_time[lo]])
        # Merge a trailing singleton into the previous pair so it has a peer.
        if pairs and len(pairs[-1]) == 1 and len(pairs) > 1:
            pairs[-2].extend(pairs.pop())
        return pairs

    def report_network_status(self, node_rank: int, normal: bool,
                              elapsed_time: float) -> None:
        with self._lock:
            round_reports = self._reports.setdefault(
                self._check_round - 1 if self._check_round else 0, {}
            )
            round_reports[node_rank] = (normal, elapsed_time)

    def join_rendezvous(self, node_rank: int, local_world_size: int,
                        node_ip: str = "", slice_id: int = -1) -> int:
        with self._lock:
            if not self._waiting and self._check_round >= 2:
                # A full 2-round check cycle was consumed; a new joiner starts
                # a fresh cycle with a clean slate of verdicts.
                self._reports.clear()
                self._groups.clear()
                self._check_round = 0
        return super().join_rendezvous(node_rank, local_world_size, node_ip,
                                       slice_id)

    def check_fault_node(self) -> Tuple[List[int], int]:
        """Nodes abnormal in ALL reported rounds are faulty (reference:
        check_fault_node rdzv_manager.py:399). Returns (fault_nodes,
        rounds_reported)."""
        with self._lock:
            if not self._reports:
                return [], 0
            fault: Optional[set] = None
            for round_reports in self._reports.values():
                bad = {r for r, (ok, _) in round_reports.items() if not ok}
                fault = bad if fault is None else (fault & bad)
            return sorted(fault or ()), len(self._reports)

    def detect_stragglers(self) -> List[int]:
        """elapsed > ratio × median in the latest round (reference:
        _detect_stragglers rdzv_manager.py:446)."""
        ratio = DefaultValues.STRAGGLER_MEDIAN_RATIO
        with self._lock:
            if not self._reports:
                return []
            latest = self._reports[max(self._reports)]
            times = [t for ok, t in latest.values() if t > 0]
            if len(times) < 2:
                return []
            median = statistics.median(times)
            return sorted(
                r for r, (ok, t) in latest.items() if t > ratio * median
            )

    def network_check_success(self) -> bool:
        fault, rounds = self.check_fault_node()
        return rounds > 0 and not fault

    def _export_extra(self, state: dict) -> None:
        """Check-cycle fields join the base export's cut (lock held)."""
        state["check_round"] = self._check_round
        state["reports"] = {
            str(rnd): {str(r): [ok, t]
                       for r, (ok, t) in reports.items()}
            for rnd, reports in self._reports.items()
        }
        state["groups"] = {
            str(rnd): groups
            for rnd, groups in self._groups.items()
        }

    def _restore_extra(self, state: dict) -> None:
        """(lock held)"""
        self._check_round = int(state.get("check_round", 0))
        self._reports = {
            int(rnd): {int(r): (bool(v[0]), float(v[1]))
                       for r, v in reports.items()}
            for rnd, reports in state.get("reports", {}).items()
        }
        self._groups = {
            int(rnd): [[int(r) for r in group] for group in groups]
            for rnd, groups in state.get("groups", {}).items()
        }
