"""Model-FLOPs / MFU accounting: one formula, every consumer.

The conservative accounting (6·params matmul credit plus the
causal-discounted attention term) lives here and nowhere else in the
program, so the worker's step reports and the master's gauges can never
drift apart (``benchmarks/`` counts on its own, on purpose: the
yardstick must not move with the program). The analytic model is
cross-checkable against what XLA actually compiled via
:func:`cost_analysis_flops` (``jax.jit(...).lower(...).compile()
.cost_analysis()``) — callers pass the compiled object in, so this
module stays import-light (no jax dependency).

stdlib-only by design (imported by the master and tools without jax).
"""

from __future__ import annotations

from typing import Optional

# bf16 peak FLOP/s per chip by device kind (public specs). Longest
# matching prefix wins ("TPU v5 lite" must not resolve as "TPU v5").
PEAK_FLOPS_BY_KIND = {
    "TPU v4": 275e12,
    "TPU v5": 459e12,          # v5p
    "TPU v5e": 197e12,
    "TPU v5 lite": 197e12,
    "TPU v6e": 918e12,
    "TPU v6 lite": 918e12,
}



def peak_flops_per_chip(device_kind: str = "",
                        backend: str = "") -> float:
    """Peak bf16 FLOP/s for one chip of ``device_kind`` (longest-prefix
    table match). There is no default peak: a TPU whose kind is not in
    the table is an error (add the kind with its public spec), and any
    other backend has no peak — 0.0, which :func:`achieved_mfu` reports
    as -1, "no evidence", rather than a utilization of an invented
    chip."""
    best = 0.0
    best_len = -1
    for name, flops in PEAK_FLOPS_BY_KIND.items():
        if device_kind.startswith(name) and len(name) > best_len:
            best, best_len = flops, len(name)
    if not best and backend == "tpu":
        raise ValueError(
            f"no peak FLOP/s on record for TPU device kind "
            f"{device_kind!r}; add it to PEAK_FLOPS_BY_KIND")
    return best


def flops_per_token(param_count: float, num_layers: int = 0,
                    hidden_size: int = 0, seq_len: int = 0,
                    uncounted_embed_params: float = 0.0) -> float:
    """Model FLOPs per trained token (fwd+bwd), conservatively.

    ``6·params`` credits the matmul FLOPs of forward (2·params) plus
    backward (4·params). ``uncounted_embed_params`` subtracts parameters
    that do no matmul (a gather-lookup embedding table with untied
    output head). The attention term is QK^T + PV = 4·h·s FLOPs/token
    forward, ×3 for fwd+bwd, ÷2 causal — matching what a
    block-skipping flash kernel actually computes. With
    ``num_layers``/``hidden_size``/``seq_len`` unknown (0), the formula
    degrades to the bare 6·params floor.
    """
    counted = max(0.0, float(param_count) - float(uncounted_embed_params))
    attention = 6.0 * num_layers * hidden_size * seq_len
    return 6.0 * counted + attention


def model_flops_per_token(config, param_count: float,
                          seq_len: int) -> float:
    """Model FLOPs per trained token of the model whose config this is.

    A model that says which of its parameters a token multiplies and
    which pairs its attention scores answers itself: a config with a
    ``flops_per_token(seq_len)`` (``models/keye.py``: of the routed
    experts the ones a token reaches, the selected pairs and not the
    causal ones) is asked, and nothing is guessed. For any other config
    the shape is read off attribute names (``num_layers``,
    ``hidden_size``, ``embed_impl``, ``tie_embeddings``; none of them:
    the bare 6 x params floor), every parameter counted as active."""
    own = getattr(config, "flops_per_token", None)
    if callable(own):
        return float(own(seq_len))
    # a gather-lookup embedding table with an untied head does no matmul:
    # crediting it would report a higher MFU than the benchmark's own
    # count gives the identical model
    uncounted = 0.0
    if (getattr(config, "embed_impl", "") == "gather"
            and not getattr(config, "tie_embeddings", True)):
        uncounted = (getattr(config, "vocab_size", 0)
                     * getattr(config, "hidden_size", 0))
    return flops_per_token(
        param_count, num_layers=getattr(config, "num_layers", 0),
        hidden_size=getattr(config, "hidden_size", 0), seq_len=seq_len,
        uncounted_embed_params=uncounted)


def achieved_mfu(tokens_per_second: float, flops_per_token_: float,
                 peak_flops_total: float) -> float:
    """Achieved / peak model-FLOPs utilization; -1.0 when the FLOPs
    model or the peak is unknown (callers must not mistake "no
    evidence" for "0 % utilized")."""
    if flops_per_token_ <= 0.0 or peak_flops_total <= 0.0:
        return -1.0
    if tokens_per_second < 0.0:
        return -1.0
    return tokens_per_second * flops_per_token_ / peak_flops_total


def cost_analysis_flops(compiled) -> float:
    """FLOPs per execution of an XLA-compiled program, from
    ``compiled.cost_analysis()`` — the cross-check for the analytic
    model. Returns 0.0 whenever the backend/object cannot answer (cost
    analysis is advisory; it must never break reporting)."""
    if compiled is None:
        return 0.0
    try:
        analysis = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend support varies
        return 0.0
    # jax has returned both a dict and a one-element list of dicts
    # across versions
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return 0.0
    try:
        return float(analysis.get("flops", 0.0) or 0.0)
    except (TypeError, ValueError):
        return 0.0


def cross_check(analytic_per_token: float, measured_per_execution: float,
                tokens_per_execution: float,
                tolerance_ratio: float = 2.0) -> Optional[float]:
    """Compare the analytic FLOPs/token against a cost-analysis
    measurement. Returns the measured FLOPs/token when it diverges from
    the analytic model by more than ``tolerance_ratio`` in either
    direction (the measurement should then be adopted), else None (the
    analytic model stands). A 0/unknown measurement always returns
    None."""
    if measured_per_execution <= 0.0 or tokens_per_execution <= 0.0:
        return None
    measured_per_token = measured_per_execution / tokens_per_execution
    if analytic_per_token <= 0.0:
        return measured_per_token
    ratio = measured_per_token / analytic_per_token
    if ratio > tolerance_ratio or ratio < 1.0 / tolerance_ratio:
        return measured_per_token
    return None
