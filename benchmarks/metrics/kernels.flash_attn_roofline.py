"""Flash attention's share of its roofline, from the device trace.

Today's trace gives no kernel a name (the program passes none to
``pl.pallas_call``): a Pallas kernel is a ``custom-call`` whose HLO text holds
``tpu_custom_call``, and the three flash kernels (forward, dQ, dK/dV) are the
ones with an operand of the query's shape ``[batch, heads, seq, head_dim]``
in the compute dtype; the norm kernels never have one. One layer of one step
launches the three once, so their summed device time over a third of their
launches is the time of one layer-step, held against the larger of needed
FLOPs over peak FLOP/s and needed bytes over peak HBM bytes/s
(benchmarks/flops.py; compute-bound at these shapes). A trace with no such
event gives nothing.
"""

from benchmarks import flops

_SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def read(run: dict):
    traced = run.get("traced") or {}
    by_name, counts = traced.get("by_name"), traced.get("count_by_name")
    if not by_name:
        return None
    cfg, traffic = run["cfg"], run["traffic"]
    batch = traffic["global_batch"] // max(1, run["workload"]["chips"])
    query = "{}[{},{},{},{}]".format(
        _SHORT[cfg["compute_dtype"]], batch, cfg["num_attention_heads"],
        traffic["seq_len"], flops.head_dim(cfg))
    kernels = [n for n in by_name if "tpu_custom_call" in n and query in n]
    launches = sum(counts[n] for n in kernels)
    if launches < 3:
        return None
    per_layer_step = sum(by_name[n] for n in kernels) / (launches / 3.0)
    least, _ = flops.roofline_seconds(
        flops.flash_attention_needs(cfg, batch, traffic["seq_len"]),
        run["device"]["kind"])
    return 100.0 * least / per_layer_step
