"""Mixture-of-experts elastic training example: expert parallelism over
the `expert` mesh axis.

Capability parity: the reference's MoE path (atorch modules/moe —
MOELayer with expert-parallel groups injected into transformer blocks,
moe/inject.py). TPU re-design: `LlamaMoE` is a first-class model family
(Mixtral shape — Llama attention + sparse expert MLPs with capacity-based
top-k routing); expert weights carry the `expert` logical axis, so on an
expert-sharded mesh XLA places one dispatch all-to-all per MoE layer and
each device holds 1/E of the expert parameters. Router load-balancing
aux losses ride the mutable 'losses' collection and are folded into the
objective by the standard trainer — no bespoke loop.

Run on one host over all local devices (expert axis = device count):
    python -m dlrover_tpu.run --standalone examples/moe/train.py \
        --experts 4 --expert-shards 4 --steps 50 --ckpt-dir /tmp/moe-ckpt
Multi-node: as examples/nanogpt, one agent per host.

Elastic restart, checkpoint + sampler resume, and speed reports all
apply unchanged — same ElasticTrainLoop; only the mesh and model differ.
strategy="auto" on an MoE model picks the expert axis by itself (the
planner forces an expert_parallel candidate; see
tests/test_auto_accelerate.py::test_auto_on_moe_picks_expert_axis) —
this example pins it explicitly for clarity.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np


def parse_args(argv=None):
    parser = argparse.ArgumentParser("moe-train")
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--global-batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=256)
    parser.add_argument("--experts", type=int, default=4)
    parser.add_argument("--top-k", type=int, default=2)
    parser.add_argument("--expert-shards", type=int, default=0,
                        help="expert-axis size (0 = all local devices, "
                             "capped at --experts)")
    parser.add_argument("--hidden", type=int, default=256)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--ckpt-dir", default="")
    parser.add_argument("--save-interval", type=int, default=20)
    parser.add_argument("--log-file", default="",
                        help="append step logs here (tests parse it)")
    return parser.parse_args(argv)


def token_batches(vocab_size, sampler, global_batch, seq):
    """Synthetic documents: per-index seeded, so a resumed sampler
    regenerates identical data."""
    batch = []
    for idx in sampler:
        rng = np.random.default_rng(idx)
        batch.append(
            rng.integers(0, vocab_size, seq + 1).astype(np.int32))
        if len(batch) == global_batch:
            chunk = np.stack(batch)
            batch = []
            yield chunk[:, :-1], chunk[:, 1:]


def main(argv=None) -> int:
    args = parse_args(argv)

    from dlrover_tpu.agent.elastic_agent import init_distributed

    init_distributed()

    import jax
    import optax

    from dlrover_tpu.models.llama import cross_entropy_loss
    from dlrover_tpu.models.llama_moe import LlamaMoE, LlamaMoEConfig
    from dlrover_tpu.ops.backend import on_tpu
    from dlrover_tpu.parallel.mesh import MeshSpec
    from dlrover_tpu.trainer.elastic_loop import (
        ElasticTrainLoop,
        TrainLoopConfig,
    )
    from dlrover_tpu.trainer.sampler import ElasticDistributedSampler

    if args.hidden < 64 or args.hidden % 64:
        raise SystemExit(
            f"--hidden {args.hidden} must be a multiple of 64 "
            f"(64-dim attention heads)")
    if args.expert_shards:
        expert_shards = args.expert_shards
        if args.experts % expert_shards:
            raise SystemExit(
                f"--experts {args.experts} must divide by expert "
                f"shards {expert_shards}")
    else:
        # auto: the largest device count that divides the expert count
        # (the analyser's own sizing policy, auto/engine/analyser.py)
        n_dev = max(1, len(jax.devices()))
        expert_shards = max(
            d for d in range(1, n_dev + 1) if args.experts % d == 0)
    cfg = LlamaMoEConfig(
        vocab_size=1024, hidden_size=args.hidden,
        num_layers=args.layers, num_heads=args.hidden // 64,
        num_kv_heads=args.hidden // 64,
        intermediate_size=args.hidden * 2,
        max_seq_len=args.seq,
        num_experts=args.experts, top_k=args.top_k,
        attn_impl="flash" if on_tpu() else "reference",
    )

    client = None
    if os.environ.get("DLROVER_TPU_MASTER_ADDR"):
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient.singleton()

    loop = ElasticTrainLoop(
        # deterministic=False = TRAINING routing semantics (train
        # capacity factor + router jitter when configured); the trainer
        # supplies the per-step gating rng stream
        LlamaMoE(cfg, deterministic=False),
        optax.adafactor(args.lr),
        cross_entropy_loss,
        TrainLoopConfig(
            global_batch=args.global_batch,
            seq_len=args.seq,
            max_steps=args.steps,
            checkpoint_dir=args.ckpt_dir,
            save_interval_steps=args.save_interval,
            report_interval_steps=10,
            mesh_spec=MeshSpec(expert=expert_shards),
        ),
        master_client=client,
    )
    loop.install_signal_handler()

    sampler = ElasticDistributedSampler(
        dataset_size=10 ** 6, shuffle=True, seed=0)
    state, start_step = loop.restore_or_init(jax.random.PRNGKey(0),
                                             sampler)

    def log(message: str) -> None:
        print(message, flush=True)
        if args.log_file:
            with open(args.log_file, "a") as f:
                f.write(message + "\n")

    active = cfg.active_param_count() / 1e6
    total = cfg.param_count() / 1e6
    log(f"moe: start_step={start_step} experts={args.experts} "
        f"expert_shards={expert_shards} params={total:.1f}M "
        f"active={active:.1f}M backend={jax.default_backend()}")
    if args.steps <= start_step:
        log("moe: nothing to do")
        loop.close()
        return 0

    data = token_batches(cfg.vocab_size, sampler, args.global_batch,
                         args.seq)
    loop.config.max_steps = args.steps - start_step
    state, metrics = loop.run(state, data, start_step=start_step,
                              sampler=sampler)
    final_step = int(metrics.get("step", start_step))
    log(f"moe: done step={final_step} "
        f"loss={metrics.get('loss', -1):.4f}")
    loop.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
