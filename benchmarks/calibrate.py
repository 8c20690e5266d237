"""Readings the limits in ``benchmarks/limits/`` are set from, taken on the
chip at a cell's own sizes. Not part of a benchmark run.

    python3 benchmarks/calibrate.py --workload <name> --what program --seeds 1,2,3
    python3 benchmarks/calibrate.py --workload <name> --what control --seeds 1,2,3

``program``: the program's first steps against the reference, seed after seed
in one process (the loop and its compiled step built once, the state made
anew from each seed by ``restore_or_init``): the lower readings.
``control``: the reference in the nearest precision below the configuration's
(int8 operands) and the reference with half of each batch left out, each
against the float32 reference: the upper readings. One JSON line a reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import check, harness, reference  # noqa: E402


def emit(out, **record) -> None:
    print(json.dumps({k: v for k, v in record.items() if k != "leaves"}),
          flush=True)
    line = json.dumps(record)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def summary(compared: dict) -> dict:
    return {k: v["value"] for k, v in compared.items()} | {
        k + "_at": v["where"] for k, v in compared.items() if v["where"]}


def leaves(got: dict, truth: dict) -> dict:
    """Every leaf's signed relative gap, for a look at where a number comes
    from (written to --out only)."""
    return {kind: {name: (got[kind][name] - ref) / ref
                   for name, ref in truth[kind].items()}
            for kind in ("grad_norms", "change_norms")}


def batches_of(seed, cfg, traffic):
    truth = reference.Rows(seed, cfg["vocab_size"], traffic["rows"],
                           traffic["seq_len"], traffic["shuffle"])
    return [truth.batch(k, traffic["global_batch"])
            for k in range(traffic["warmup_steps"])]


def control(args, cfg, traffic) -> None:
    plain = harness.model_reference(cfg)
    for seed in args.seeds:
        batches = batches_of(seed, cfg, traffic)
        t0 = time.monotonic()
        truth = reference.follow(plain, seed, cfg, batches)
        emit(args.out, what="reference", workload=args.workload, seed=seed,
             seconds=time.monotonic() - t0, losses=truth["losses"])
        readings = {
            "int8": lambda: reference.follow(plain, seed, cfg, batches,
                                             "int8"),
            "bf16": lambda: reference.follow(plain, seed, cfg, batches,
                                             "bf16"),
            "half_batch": lambda: reference.follow(
                plain, seed, cfg, batches,
                keep_rows=traffic["global_batch"] // 2),
        }
        for name in args.controls:
            t0 = time.monotonic()
            got = readings[name]()
            emit(args.out, what=name, workload=args.workload, seed=seed,
                 seconds=time.monotonic() - t0, leaves=leaves(got, truth),
                 **summary(check.compare(got, truth, 0)))
            del got
            gc.collect()
        del truth


def program(args, cfg, traffic, entry) -> None:
    import jax

    from benchmarks.windows import steady

    model_module = harness.model_class(cfg)
    plain = harness.model_reference(cfg)
    loop = steady.build_loop(model_module, cfg, traffic,
                             jax.devices()[:entry["chips"]])
    change_fn = model_module.change_norms_fn(loop.trainer)
    for seed in args.seeds:
        feed, sampler = steady.make_feed(seed, cfg, traffic)
        rng = jax.random.PRNGKey(seed)
        t0 = time.monotonic()
        state, _ = loop.restore_or_init(rng, sampler)
        state, got = steady.warm_up(loop, model_module, change_fn, rng, state,
                                    feed, sampler, traffic["warmup_steps"])
        program_s = time.monotonic() - t0
        del state
        gc.collect()
        t0 = time.monotonic()
        truth = reference.follow(plain, seed, cfg,
                                 batches_of(seed, cfg, traffic))
        emit(args.out, what="program", workload=args.workload, seed=seed,
             program_seconds=program_s,
             reference_seconds=time.monotonic() - t0,
             program_losses=got["losses"], reference_losses=truth["losses"],
             leaves=leaves(got, truth), **summary(check.compare(got, truth, 0)))
        del truth
        gc.collect()
    loop.close()


def main() -> int:
    parser = argparse.ArgumentParser("benchmarks.calibrate")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--what", choices=("program", "control"),
                        required=True)
    parser.add_argument("--seeds", required=True,
                        type=lambda s: [int(x) for x in s.split(",")])
    parser.add_argument("--controls", default="int8,half_batch",
                        type=lambda s: s.split(","))
    parser.add_argument("--out", default="")
    parser.add_argument("--tiny", action="store_true",
                        help="the rehearsal's widths, off the chip")
    args = parser.parse_args()
    entry, cfg, traffic = harness.cell(harness.benchmark(), args.workload)
    if args.tiny:
        cfg, traffic = harness.model_class(cfg).tiny(cfg, traffic)
    else:
        import jax

        if jax.default_backend() != "tpu":
            print("no TPU: readings are taken on the chip", file=sys.stderr)
            return 1
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    if args.what == "control":
        control(args, cfg, traffic)
    else:
        program(args, cfg, traffic, entry)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
