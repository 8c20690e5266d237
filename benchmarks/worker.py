"""The process that holds the chip: started by ``run.py`` under
``python -m dlrover_tpu.run --standalone``, it finds the cell's configuration,
traffic mix, model class (a class that lacks one of the contract's functions
fails here, by the function's name) and window kind by name and runs the
window."""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness  # noqa: E402


@dataclasses.dataclass
class Context:
    workload: str
    seed: int
    seconds: float
    trace: bool
    chips: int
    cfg: dict
    traffic: dict
    model: object               # the model class, ``models/<key>.py``
    model_reference: object     # its plain reference, ``<key>_reference.py``
    report: harness.Report
    workdir: str
    rehearse: bool = False      # off the TPU, at a tiny width: never a result
    in_process: bool = False    # no launcher above: no master client


def context(workload: str, seed: int, seconds: float, trace: bool,
            report_path: str, workdir: str, rehearse: bool = False,
            in_process: bool = False) -> Context:
    entry, cfg, traffic = harness.cell(harness.benchmark(), workload)
    model = harness.model_class(cfg)
    if rehearse:
        cfg, traffic = model.tiny(cfg, traffic)
    return Context(workload=workload, seed=seed, seconds=seconds, trace=trace,
                   chips=entry["chips"], cfg=cfg, traffic=traffic,
                   model=model,
                   model_reference=harness.model_reference(cfg),
                   report=harness.Report(report_path), workdir=workdir,
                   rehearse=rehearse, in_process=in_process)


def main() -> int:
    parser = argparse.ArgumentParser("benchmarks.worker")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--report", required=True)
    parser.add_argument("--workdir", required=True)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    ctx = context(args.workload, args.seed, args.seconds, bool(args.trace),
                  args.report, args.workdir, args.rehearse)
    window = harness.load_module("windows", ctx.traffic["window"])
    code = window.run(ctx)
    ctx.report.emit(record="done", code=code)
    return code


if __name__ == "__main__":
    raise SystemExit(main())
