"""The whole step's share of the chip's peak: model FLOPs the window's steps
need (forward and backward, nothing recomputed; the model class's own count,
``models/<key>.py:flops_per_token``) over the window's seconds x chips x peak
bf16 FLOP/s of the device kind. In a traced run the profiler's own start and
stop lie inside the window, so this reads a little under an untraced run's
tokens_per_s would give."""

from benchmarks import flops


def read(run: dict):
    window, device = run["window"], run["device"]
    if not window["steps"]:
        return None
    per_token = run["model"].flops_per_token(run["cfg"],
                                             run["traffic"]["seq_len"])
    peak = flops.peaks(device["kind"])["bf16_flops_per_s"]
    needed = window["steps"] * window["tokens_per_step"] * per_token
    return 100.0 * needed / (
        window["seconds"] * run["workload"]["chips"] * peak)
