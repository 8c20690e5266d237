"""The benchmark's command:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent never imports JAX (a chip belongs to one process): it starts
``python -m dlrover_tpu.run --standalone --max-restarts 0 benchmarks/worker.py``
so that the timed path is the product's own (launcher, agent, worker, master
client, ``ElasticTrainLoop``), reads the worker's records back, lets each
metric's reader (``benchmarks/metrics/<name>.py``) take its number from them,
decides ``correct`` from the compared numbers and their limits, and prints one
JSON object as the last line of standard output. Off the TPU it prints no
result and exits non-zero; ``--rehearse`` runs a tiny width on whatever JAX
finds, says so, and exits non-zero too.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import check, harness  # noqa: E402

CHILD_TIMEOUT_S = 1100     # a cell's first run in a checkout compiles


def launch(args, workdir: str, report_path: str, log_path: str) -> int:
    """Run the worker under the product's launcher, in a process group of
    its own that is gone when this returns."""
    from dlrover_tpu.common import compile_cache

    assert "jax" not in sys.modules, "the parent must stay off jax"
    env = dict(os.environ)
    env[compile_cache.ENV] = compile_cache.compile_cache_dir()
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env["TMPDIR"] = os.path.join(workdir, "tmp")
    env.pop("BENCH_RUN", None)
    os.makedirs(env["TMPDIR"])
    cmd = [sys.executable, "-m", "dlrover_tpu.run", "--standalone",
           "--max-restarts", "0", os.path.join(HERE, "worker.py"),
           "--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace),
           "--report", report_path, "--workdir", workdir]
    if args.rehearse:
        cmd.append("--rehearse")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            return proc.wait(timeout=CHILD_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            return 124
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


def gather(records: list, bench: dict, workload: str, started_wall: float,
           seconds: float, trace: bool) -> dict:
    """Everything a metric's reader may look at, under one roof."""
    entry, cfg, traffic = harness.cell(bench, workload)
    run = {"bench": bench, "workload": entry, "cfg": cfg, "traffic": traffic,
           "model": harness.model_class(cfg), "started_wall": started_wall,
           "asked_seconds": seconds, "traced_run": trace}
    for record in records:
        run[record["record"]] = record
    return run


def conclude(run: dict, limits: dict, lenient: bool = False) -> dict:
    """The result line from a finished run's records. ``lenient`` (the
    rehearsal): a reader that raises, as the peak lookup does off the TPU,
    is noted and left out."""
    bench, name = run["bench"], run["workload"]["name"]
    window, device = run["window"], run["device"]
    group = "per_layer" if run["traced_run"] else "end_to_end"
    metrics = {}
    for metric in harness.metrics_of(bench, name, group):
        try:
            value = harness.load_module("metrics", metric["name"]).read(run)
        except Exception as e:  # noqa: BLE001 - only the rehearsal forgives
            if not lenient:
                raise
            print(f"reader {metric['name']} raised: {e}", file=sys.stderr)
            value = None
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    compared = dict(run["compared"]["compared"])
    compared["compiles_in_window"] = {"value": window["compiles_in_window"]}
    compared["saves_uncommitted"] = {
        "value": window["saves_started"] - window["saves_committed"]}
    correct, table = check.verdict(compared, limits)
    correct = correct and bool(run["compared"]["losses_finite"])
    failed = ((0 if run["compared"]["losses_finite"] else 1)
              + window["compiles_in_window"]
              + window["saves_started"] - window["saves_committed"])
    out_device = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": window["memory_peak_bytes"]}
    line = {"correct": correct,
            "attempted": window["steps"] + window["saves_started"],
            "failed": failed, "metrics": metrics, "device": out_device}
    traced = run.get("traced")
    if run["traced_run"] and traced and traced.get("busy_s"):
        out_device["busy_s"] = traced["busy_s"]
        out_device["window_s"] = traced["window_s"]
        line["breakdown"] = {"device_ops": traced["device_ops"],
                             "idle_gaps": traced["idle_gaps"]}
    line["compared"] = table
    return line


def main() -> int:
    started_wall = time.time()
    parser = argparse.ArgumentParser("benchmarks.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny widths on whatever JAX finds; prints the "
                             "platform, never a result, exits non-zero")
    args = parser.parse_args()
    bench = harness.benchmark()
    harness.cell(bench, args.workload)      # fails here on an unknown name
    try:
        import dlrover_tpu.common.compile_cache  # noqa: F401
    except ImportError as e:
        print(f"no dlrover_tpu beside benchmarks/: {e}", file=sys.stderr)
        return 1

    workdir = tempfile.mkdtemp(prefix="bench-")
    report_path = os.path.join(workdir, "report.jsonl")
    log_path = os.path.join(workdir, "worker.log")
    try:
        code = launch(args, workdir, report_path, log_path)
        records = harness.Report(report_path).read()
        keep = os.environ.get("BENCH_KEEP_DIR")
        if keep:        # a builder's look at a run; the driver never sets it
            os.makedirs(keep, exist_ok=True)
            tag = f"{args.workload}.{args.seed}.{args.trace}"
            for path in (report_path, log_path):
                if os.path.exists(path):
                    shutil.copy(path, os.path.join(
                        keep, f"{tag}.{os.path.basename(path)}"))
        run = gather(records, bench, args.workload, started_wall,
                     args.seconds, bool(args.trace))
        for record in records:
            slim = {k: v for k, v in record.items()
                    if k not in ("calls", "spans", "outline", "by_name",
                                 "count_by_name")}
            print(json.dumps(slim))
        device = run.get("device", {})
        finished = code == 0 and "compared" in run and "window" in run
        if not finished or (device.get("platform") != "tpu"
                            and not args.rehearse):
            print(f"no result: worker exit {code}, platform "
                  f"{device.get('platform')}, refused: "
                  f"{run.get('refused', {}).get('reason')}", file=sys.stderr)
            try:
                with open(log_path, "rb") as f:
                    f.seek(max(0, os.path.getsize(log_path) - 6000))
                    sys.stderr.write(f.read().decode("utf-8", "replace"))
            except OSError:
                pass
            return 1
        limits = check.limits_for(args.workload)
        line = conclude(run, limits, lenient=args.rehearse)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if args.rehearse:
        # no time from this run goes out under a metric's name
        line["metrics"] = {name: "not measured" for name in line["metrics"]}
        print(json.dumps({"rehearsal_on": device.get("platform"),
                          "would_print": line}))
        print(f"rehearsal on {device.get('platform')}: not a result",
              file=sys.stderr)
        return 2
    if device.get("count") != run["workload"]["chips"]:
        print(f"found {device.get('count')} chips, the cell needs "
              f"{run['workload']['chips']}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
