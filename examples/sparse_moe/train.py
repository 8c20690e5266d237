"""Sparse-attention mixture-of-experts example: `models/keye.py`'s tiny
preset through the product's path.

`Keye` is Keye-VL-2.0's language model: each query attends the keys a
learned indexer selects (`ops/sparse_attention.py`), the indexer learns
from the attention by a KL term the block sows into `losses`, and the MLP
is a top-k mixture of SwiGLU experts of which this process holds a share
and drops none (`parallel/moe.py:HeldExpertsLayer`: the assignments sorted
by expert, the held experts' first, and worked through a chunk of the even
routing's share at a time; a chunk no held expert reaches is skipped, so the
cost follows the rows held and not tokens x top_k). The trainer adds the
sown term to the loss it reports; nothing here is bespoke.

    python -m dlrover_tpu.run --standalone examples/sparse_moe/train.py \
        --steps 20

`--experts-held` / `--first-expert` pick the share of the `--experts`
routed experts this process holds (what one chip of an expert-parallel
group would); what the absent experts would add is left out.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np


def parse_args(argv=None):
    parser = argparse.ArgumentParser("sparse-moe-train")
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--global-batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=64)
    parser.add_argument("--topk", type=int, default=16,
                        help="keys a query attends, of those before it")
    parser.add_argument("--experts", type=int, default=8)
    parser.add_argument("--experts-held", type=int, default=4)
    parser.add_argument("--first-expert", type=int, default=2)
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--log-file", default="",
                        help="append step logs here (tests parse it)")
    return parser.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)

    from dlrover_tpu.agent.elastic_agent import init_distributed

    init_distributed()

    import jax
    import optax

    from dlrover_tpu.models.keye import Keye, KeyeConfig
    from dlrover_tpu.models.llama import cross_entropy_loss
    from dlrover_tpu.trainer.elastic_loop import (
        ElasticTrainLoop,
        TrainLoopConfig,
    )
    from dlrover_tpu.trainer.sampler import ElasticDistributedSampler

    cfg = KeyeConfig.tiny(
        max_seq_len=args.seq, index_topk=args.topk,
        num_experts=args.experts, experts_held=args.experts_held,
        first_expert=args.first_expert, embed_impl="gather")
    client = None
    if os.environ.get("DLROVER_TPU_MASTER_ADDR"):
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient.singleton()
    loop = ElasticTrainLoop(
        Keye(cfg), optax.adafactor(args.lr), cross_entropy_loss,
        TrainLoopConfig(global_batch=args.global_batch, seq_len=args.seq,
                        max_steps=args.steps, report_interval_steps=10),
        master_client=client)
    loop.install_signal_handler()
    sampler = ElasticDistributedSampler(dataset_size=10 ** 6, shuffle=True,
                                        seed=0)
    state, start_step = loop.restore_or_init(jax.random.PRNGKey(0), sampler)

    def log(message: str) -> None:
        print(message, flush=True)
        if args.log_file:
            with open(args.log_file, "a") as f:
                f.write(message + "\n")

    def batches():
        rows = []
        for index in sampler:       # seeded by index: a resume replays
            rows.append(np.random.default_rng(index).integers(
                0, cfg.vocab_size, args.seq + 1).astype(np.int32))
            if len(rows) == args.global_batch:
                chunk, rows = np.stack(rows), []
                yield chunk[:, :-1], chunk[:, 1:]

    log(f"sparse_moe: start_step={start_step} params="
        f"{cfg.param_count() / 1e6:.2f}M held={args.experts_held}/"
        f"{args.experts} topk={args.topk} backend={jax.default_backend()}")
    state, metrics = loop.run(state, batches(), start_step=start_step,
                              sampler=sampler)
    log(f"sparse_moe: done step={int(metrics['step'])} "
        f"loss={metrics['loss']:.4f} "
        f"load={metrics.get('moe_load_max_over_mean', -1):.2f} "
        f"chunks_run={metrics.get('moe_chunks_run', -1):.2f}")
    loop.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
