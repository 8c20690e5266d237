"""Streaming per-layer training example: models bigger than one chip's
HBM, through the elastic CLI.

Capability parity: the reference trains >memory models via FSDP
param/grad sharding (atorch/distributed/zero_optimization.py:215) and
CPU-offloaded Adam (atorch/optim/adam_offload.py). TPU re-design for
ONE chip: the `streaming` strategy pass (auto/opt_lib/library.py)
lowers to the per-layer streaming trainer (trainer/streaming.py) —
backward runs as a reverse per-layer loop that applies a per-leaf
optimizer (factored-rms here) in place, so peak memory is params + one
layer's gradients instead of the full gradient tree: the path for a
model whose bf16 params fit a chip but whose gradient tree does not
(Llama-7B, 13.5 GB of bf16 params, on a 15.75 GB v5e).

Run on one host (the streaming trainer is single-device by design;
multi-chip scale-out composes the ordinary trainers with fsdp/PP):
    python -m dlrover_tpu.run --standalone examples/streaming/train.py \
        --steps 50 --ckpt-dir /tmp/streaming-ckpt

Elastic restart, checkpoint + sampler resume, restore-compile overlap,
and speed reports all apply unchanged — StreamingTrainer exposes the
ShardedTrainer surface, so the same ElasticTrainLoop drives it as an
injected trainer.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np


def parse_args(argv=None):
    parser = argparse.ArgumentParser("streaming-train")
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--batch", type=int, default=2,
                        help="micro batch == global batch (streaming "
                             "does not gradient-accumulate)")
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--hidden", type=int, default=128)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--ckpt-dir", default="")
    parser.add_argument("--save-interval", type=int, default=20)
    parser.add_argument("--log-file", default="",
                        help="append step logs here (tests parse it)")
    return parser.parse_args(argv)


def token_batches(vocab_size, sampler, batch_size, seq):
    """Synthetic documents: per-index seeded, so a resumed sampler
    regenerates identical data."""
    batch = []
    for idx in sampler:
        rng = np.random.default_rng(idx)
        batch.append(
            rng.integers(0, vocab_size, seq + 1).astype(np.int32))
        if len(batch) == batch_size:
            chunk = np.stack(batch)
            batch = []
            yield chunk[:, :-1], chunk[:, 1:]


def main(argv=None) -> int:
    args = parse_args(argv)

    from dlrover_tpu.agent.elastic_agent import init_distributed

    init_distributed()

    import jax
    import optax

    from dlrover_tpu.auto import auto_accelerate
    from dlrover_tpu.models.llama import (
        Llama,
        LlamaConfig,
        cross_entropy_loss,
    )
    from dlrover_tpu.ops.backend import on_tpu
    from dlrover_tpu.trainer.elastic_loop import (
        ElasticTrainLoop,
        TrainLoopConfig,
    )
    from dlrover_tpu.trainer.sampler import ElasticDistributedSampler

    if args.hidden < 64 or args.hidden % 64:
        raise SystemExit(
            f"--hidden {args.hidden} must be a multiple of 64 "
            f"(64-dim attention heads)")
    cfg = LlamaConfig(
        vocab_size=1024, hidden_size=args.hidden,
        num_layers=args.layers, num_heads=args.hidden // 64,
        num_kv_heads=args.hidden // 64,
        intermediate_size=args.hidden * 2,
        max_seq_len=args.seq,
        tie_embeddings=False,
        attn_impl="flash" if on_tpu() else "reference",
    )

    result = auto_accelerate(
        Llama(cfg),
        optim_factory=lambda: optax.chain(
            optax.scale_by_factored_rms(), optax.scale(-args.lr)),
        loss_fn=cross_entropy_loss,
        sample_batch=np.zeros((args.batch, args.seq), np.int32),
        strategy=["half", ("streaming", {})],
        micro_batch=args.batch,
        # the streaming trainer is the ONE-chip >HBM path by definition
        # (auto_accelerate rejects more); across chips use fsdp/pipeline
        devices=jax.devices()[:1],
    )

    client = None
    if os.environ.get("DLROVER_TPU_MASTER_ADDR"):
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient.singleton()

    loop = ElasticTrainLoop(
        result.model,
        None,                      # tx lives inside the injected trainer
        cross_entropy_loss,
        TrainLoopConfig(
            global_batch=args.batch,
            seq_len=args.seq,
            max_steps=args.steps,
            checkpoint_dir=args.ckpt_dir,
            save_interval_steps=args.save_interval,
            report_interval_steps=10,
        ),
        master_client=client,
        trainer=result.trainer,
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

    log(f"streaming: start_step={start_step} "
        f"params={cfg.param_count() / 1e6:.1f}M "
        f"backend={jax.default_backend()}")
    if args.steps <= start_step:
        log("streaming: nothing to do")
        loop.close()
        return 0

    data = token_batches(cfg.vocab_size, sampler, args.batch, args.seq)
    loop.config.max_steps = args.steps - start_step
    state, metrics = loop.run(state, data, start_step=start_step,
                              sampler=sampler)
    final_step = int(metrics.get("step", start_step))
    log(f"streaming: done step={final_step} "
        f"loss={metrics.get('loss', -1):.4f}")
    loop.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
