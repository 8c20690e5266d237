"""nanoGPT-style elastic training example — the doc-of-record that the
whole stack composes outside pytest.

Capability parity: the reference's `examples/pytorch/nanogpt/train.py`
(trained via ElasticTrainer, :289) — TPU re-design on this framework's
stack: `dlrover-tpu-run --standalone` spawns a local master + agent; this
worker joins the process set, builds the model through `auto_accelerate`,
and trains with the elastic loop (checkpoint + sampler resume, step
reports to the master's SpeedMonitor).

Run single-host:
    python -m dlrover_tpu.run --standalone examples/nanogpt/train.py \
        --steps 200 --ckpt-dir /tmp/nanogpt-ckpt
Multi-node (per node):
    python -m dlrover_tpu.run --nnodes 2:4 --node-rank $RANK \
        --master-addr $DLROVER_TPU_MASTER_ADDR examples/nanogpt/train.py
On k8s, see manifests/samples/elasticjob_llama.yaml.

A SIGKILL mid-run (or a node loss) restarts the worker through the agent;
this script then resumes from the latest committed checkpoint with the
data position intact.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np


def parse_args(argv=None):
    parser = argparse.ArgumentParser("nanogpt-train")
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--global-batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=64)
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--ckpt-dir", default="")
    parser.add_argument("--save-interval", type=int, default=20)
    parser.add_argument("--log-file", default="",
                        help="append step logs here (tests parse it)")
    return parser.parse_args(argv)


def synthetic_corpus(vocab_size: int, length: int = 2 ** 15) -> np.ndarray:
    """A deterministic token stream with local structure (random walk),
    standing in for the reference's shakespeare download."""
    rng = np.random.default_rng(1234)
    steps = rng.integers(-3, 4, length)
    return np.cumsum(steps).astype(np.int32) % vocab_size


def batches(corpus, sampler, global_batch, seq):
    """Yield (tokens, targets) global batches by sampler order."""
    starts_per_sample = len(corpus) - seq - 1
    batch = []
    for idx in sampler:
        start = idx % starts_per_sample
        batch.append(corpus[start:start + seq + 1])
        if len(batch) == global_batch:
            chunk = np.stack(batch)
            batch = []
            yield chunk[:, :-1], chunk[:, 1:]


def main(argv=None) -> int:
    args = parse_args(argv)

    from dlrover_tpu.agent.elastic_agent import init_distributed

    init_distributed()   # joins the round's process set; no-op single host

    import jax
    import optax

    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.models.llama import cross_entropy_loss
    from dlrover_tpu.ops.backend import on_tpu
    from dlrover_tpu.trainer.elastic_loop import (
        ElasticTrainLoop,
        TrainLoopConfig,
    )
    from dlrover_tpu.trainer.sampler import ElasticDistributedSampler

    cfg = GPTConfig.nano(attn_impl="flash" if on_tpu() else "reference")
    model = GPT(cfg)

    client = None
    if os.environ.get("DLROVER_TPU_MASTER_ADDR"):
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient.singleton()

    loop = ElasticTrainLoop(
        model,
        optax.adamw(args.lr, weight_decay=0.1),
        cross_entropy_loss,
        TrainLoopConfig(
            global_batch=args.global_batch,
            seq_len=args.seq,
            max_steps=args.steps,
            checkpoint_dir=args.ckpt_dir,
            save_interval_steps=args.save_interval,
            report_interval_steps=10,
        ),
        master_client=client,
    )
    loop.install_signal_handler()

    corpus = synthetic_corpus(cfg.vocab_size)
    sampler = ElasticDistributedSampler(
        dataset_size=10 ** 6, shuffle=True, seed=0)
    state, start_step = loop.restore_or_init(jax.random.PRNGKey(0),
                                             sampler)

    def log(message: str) -> None:
        print(message, flush=True)
        if args.log_file:
            with open(args.log_file, "a") as f:
                f.write(message + "\n")

    log(f"nanogpt: start_step={start_step} "
        f"dp={loop.dp} accum={loop.accum} backend={jax.default_backend()}")
    if args.steps <= start_step:
        log("nanogpt: nothing to do")
        loop.close()
        return 0

    data = batches(corpus, sampler, args.global_batch, args.seq)
    loop.config.max_steps = args.steps - start_step
    state, metrics = loop.run(state, data, start_step=start_step,
                              sampler=sampler)
    final_step = int(metrics.get("step", start_step))
    log(f"nanogpt: done step={final_step} loss={metrics.get('loss', -1):.4f}")
    loop.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
