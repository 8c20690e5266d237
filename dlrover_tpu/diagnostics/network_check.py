"""Network check: 2-round paired ICI/DCN probe + straggler detection.

Capability parity: dlrover's `--network-check` path — the agent runs a
diagnostic task before training (elastic_agent/torch/training.py:681-874
NetworkCheckElasticAgent; probe task trainer/torch/run_network_check.py:30-92
does matmul + repeated allgather and writes elapsed time to a file); the
master groups nodes in pairs (round 0 adjacent, round 1
fastest-with-slowest), isolates nodes that fail BOTH rounds as faulty, and
flags elapsed > 2×median as stragglers (rdzv_manager.py:299-461).

TPU re-design: the probe is a fresh JAX subprocess per round (a JAX process
can only initialize one distributed runtime, and each round re-forms the
group). Within the pair group it runs a bf16 matmul burst (MXU sanity) and
repeated `jax.lax.all_gather` over every chip of the pair (ICI/DCN sanity)
under `shard_map`, then writes elapsed seconds to a result file the agent
reports to the master.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Tuple

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common import compile_cache
from dlrover_tpu.common.bootstrap import publish_or_wait_coordinator
from dlrover_tpu.common.constants import NodeEnv, RendezvousName
from dlrover_tpu.common.log import default_logger as logger

_RESULT_FILE_ENV = "DLROVER_TPU_NC_RESULT_FILE"
_MATMUL_SIZE = 4096
_ALLGATHER_FLOATS = 1 << 20
_ROUNDS = 2
_REPEATS = 10


# ---------------------------------------------------------------------------
# Probe subprocess
# ---------------------------------------------------------------------------


def probe_main() -> int:
    """Entry for `python -m dlrover_tpu.diagnostics.network_check`.

    Initializes jax.distributed within the pair group from the agent env
    contract, runs the probe, writes `{"elapsed": s}` to the result file.
    """
    result_file = os.environ[_RESULT_FILE_ENV]
    from dlrover_tpu.agent.elastic_agent import init_distributed

    init_distributed()
    import jax
    import jax.numpy as jnp

    # The matmul burst is MXU sanity — on the CPU backend (tests, dev
    # boxes) a 4096^3 bf16 burst is ~400 GFLOPs of pure execution that
    # starves a loaded host and flakes the pair's coordination-service
    # deadlines; small there, full-size on real chips.
    size = _MATMUL_SIZE if jax.default_backend() == "tpu" else 512
    x = jnp.ones((size, size), jnp.bfloat16)

    @jax.jit
    def matmul_burst(x):
        for _ in range(3):
            x = jnp.tanh(x @ x * 1e-4)
        return x

    n = jax.device_count()
    gather_sum = None
    data = None
    if n > 1:
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(jax.devices(), ("probe",))
        data = jnp.ones((n, _ALLGATHER_FLOATS), jnp.float32)

        @jax.jit
        def gather_sum(arr):
            def inner(block):
                gathered = jax.lax.all_gather(block, "probe")
                return jnp.sum(gathered, dtype=jnp.float32)[None]

            return jax.shard_map(
                inner, mesh=mesh, in_specs=P("probe"), out_specs=P("probe")
            )(arr)

    # compile OUTSIDE the timed window: the elapsed that feeds straggler
    # detection (2x median) must compare EXECUTION, and a peer stuck in
    # a cold compile mid-collective is what tripped the coordination
    # service's deadline under load (round-3 flake)
    matmul_exec = matmul_burst.lower(x).compile()
    gather_exec = (gather_sum.lower(data).compile()
                   if gather_sum is not None else None)

    t0 = time.perf_counter()
    jax.block_until_ready(matmul_exec(x))
    if gather_exec is not None:
        # ICI/DCN sanity: repeated all-gather across the group's chips
        for _ in range(_REPEATS):
            out = gather_exec(data)
        jax.block_until_ready(out)
        expected = float(n * _ALLGATHER_FLOATS)
        if abs(float(out[0]) - expected) > 1e-3 * expected:
            raise RuntimeError(
                f"allgather result {float(out[0])} != {expected}"
            )
    elapsed = time.perf_counter() - t0
    with open(result_file, "w") as f:
        json.dump({"elapsed": elapsed}, f)
    return 0


# ---------------------------------------------------------------------------
# Agent-side driver
# ---------------------------------------------------------------------------


def _probe_round(client: MasterClient, devices_per_node: int,
                 timeout_s: float) -> Tuple[bool, float]:
    """Join one NETWORK_CHECK round, run the probe in the pair group,
    return (normal, elapsed)."""
    rdzv = RendezvousName.NETWORK_CHECK
    client.join_rendezvous(devices_per_node, rdzv)
    deadline = time.time() + timeout_s
    while True:
        rdzv_round, group, world = client.get_comm_world(rdzv)
        if world and client.node_rank in world:
            break
        if time.time() > deadline:
            # withdraw the stale join: a late partner must not complete
            # this round against a peer that already gave up (it would
            # hang waiting for a coordinator that never publishes).
            # Best-effort: a master hiccup here must stay a round
            # failure, not escalate into an exception that fails the
            # whole health check.
            try:
                client.leave_rendezvous(rdzv)
            except Exception:
                logger.warning("network check: leave_rendezvous failed; "
                               "continuing with round failure",
                               exc_info=True)
            return False, 0.0
        time.sleep(0.5)

    ranks = sorted(world)
    process_id = ranks.index(client.node_rank)
    try:
        coord = publish_or_wait_coordinator(
            client, f"coord/{rdzv}/{rdzv_round}/{group}", process_id,
            timeout_s,
        )
    except TimeoutError:
        # the pair's rank 0 never published (it may have abandoned the
        # round under load): this ROUND failed for us; the verdict layer
        # decides faultiness from both rounds
        logger.warning("network check: no coordinator for round %d "
                       "group %d; counting the round as failed",
                       rdzv_round, group)
        return False, 0.0

    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        result_file = f.name
    env = dict(os.environ)
    env.update({
        NodeEnv.WORLD_SIZE: str(len(ranks)),
        NodeEnv.PROCESS_ID: str(process_id),
        NodeEnv.COORDINATOR_ADDR: coord,
        _RESULT_FILE_ENV: result_file,
    })
    # Round 1 re-runs the same probe program in a fresh process; a shared
    # persistent compile cache lets it skip the cold compile that makes a
    # loaded 1-core host starve the coordination-service deadline.
    env.setdefault(compile_cache.ENV, compile_cache.compile_cache_dir())
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, "-m",
             "dlrover_tpu.diagnostics.network_check"],
            env=env, timeout=timeout_s,
        )
        normal = proc.returncode == 0
    except subprocess.TimeoutExpired:
        normal = False
    elapsed = time.perf_counter() - t0
    try:
        with open(result_file) as f:
            elapsed = json.load(f)["elapsed"]
    except Exception:
        normal = False
    finally:
        try:
            os.unlink(result_file)
        except OSError:
            pass
    return normal, elapsed


def run_network_check(client: MasterClient, devices_per_node: int = 1,
                      exclude_straggler: bool = False,
                      timeout_s: float = 300.0) -> bool:
    """Run the 2-round probe and ask the master for the verdict. Returns
    whether this node may join training (reference: training.py:681-733)."""
    for check_round in range(_ROUNDS):
        normal, elapsed = _probe_round(client, devices_per_node, timeout_s)
        logger.info("network check round %d: normal=%s elapsed=%.2fs",
                    check_round, normal, elapsed)
        client.report_network_status(normal, elapsed)
    verdict = client.get_network_check_verdict()
    if not verdict.normal:
        logger.error("network check: this node is FAULTY (%s)",
                     verdict.reason)
        return False
    if verdict.is_straggler:
        logger.warning("network check: this node is a STRAGGLER")
        if exclude_straggler:
            return False
    return True


if __name__ == "__main__":
    raise SystemExit(probe_main())
