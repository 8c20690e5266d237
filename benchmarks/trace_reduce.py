"""From a profiler trace to numbers: device busy time, idle gaps, the time of
named kernels. The interval arithmetic is plain Python on (start, end) pairs
in seconds and is tested on hand-made lists; ``load`` is the only part that
needs JAX (``jax.profiler.ProfileData``) and the only part that knows how
today's trace names things.

What a TPU v5e trace looks like today (read by hand, PRs 26 and 28): one
plane per chip, ``/device:TPU:<n>``, with the lines ``Steps``, ``XLA Modules``
(one event per program run), ``XLA Ops`` (one event per executed HLO op, start
and duration; ~9,600 distinct ops and ~9,500 events a step for the 24-layer
model) and ``Async XLA Ops``. An op's name is its whole HLO text without
``metadata=`` (so no ``op_name``). A Pallas kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"`` whose instruction name is the name
the program gave its ``pallas_call`` (``%flash_attn_fwd.3 = ...``; since PR
28), which is how ``kernel_needs.kernel_events`` finds it. Host threads are
lines of ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals) -> list:
    """Merged, sorted (start, end) pairs covering the same points."""
    merged: list = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def covered(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def gaps(intervals, span: tuple) -> list:
    """The parts of ``span`` no interval covers."""
    out, at = [], span[0]
    for start, end in union(intervals):
        if end <= span[0] or start >= span[1]:
            continue
        if start > at:
            out.append((at, min(start, span[1])))
        at = max(at, end)
    if at < span[1]:
        out.append((at, span[1]))
    return out


def overlap(interval: tuple, others) -> float:
    a, b = interval
    return sum(max(0.0, min(b, d) - max(a, c)) for c, d in union(others))


def total_by_name(events) -> dict:
    """name -> summed duration of (name, start, end) events."""
    out: dict = {}
    for name, start, end in events:
        out[name] = out.get(name, 0.0) + (end - start)
    return out


def count_by_name(events) -> dict:
    out: dict = {}
    for name, _, _ in events:
        out[name] = out.get(name, 0) + 1
    return out


_HLO = re.compile(r"^%?(\S+) = (\(.*?\)|\S+) ([\w\-]+)\(")


def short_name(name: str, limit: int = 160) -> str:
    """An HLO instruction's text cut to its class: opcode, result shape and
    fusion kind (instance name, layouts and operands dropped), so that the
    same op of every layer falls under one name."""
    flat = re.sub(r"\{[^{}]*\}", "", name)
    found = _HLO.match(flat)
    if not found:
        return flat[:limit]
    text = f"{found.group(3)} -> {found.group(2)}"
    kind = re.search(r"kind=\w+", flat)
    if kind:
        text += " " + kind.group(0)
    if "tpu_custom_call" in flat:
        text += " tpu_custom_call"
    return text[:limit]


# ---------------------------------------------------------------------------
# the reduction every traced run makes
# ---------------------------------------------------------------------------


def reduce(devices: dict, host_input: list, top: int = 10) -> dict:
    """``devices``: plane name -> [(op name, start s, end s)];
    ``host_input``: [(start, end)] of the benchmark's own ``input``
    annotations. Returns busy and window seconds averaged over the chips, the
    idle gaps and the heaviest ops of the first chip, and every chip's
    events by name for the kernel readers."""
    if not devices:
        return {}
    per_chip = []
    for name in sorted(devices):
        events = devices[name]
        if not events:
            continue
        span = (min(e[1] for e in events), max(e[2] for e in events))
        spans = [(e[1], e[2]) for e in events]
        per_chip.append({"plane": name, "span": span,
                         "busy_s": covered(spans),
                         "gaps": gaps(spans, span),
                         "by_name": total_by_name(events),
                         "count_by_name": count_by_name(events)})
    if not per_chip:
        return {}
    first = per_chip[0]
    idle = []
    for gap in sorted(first["gaps"], key=lambda g: g[0] - g[1])[:top]:
        inside = overlap(gap, host_input)
        cause = "input" if inside >= 0.5 * (gap[1] - gap[0]) else "other host"
        idle.append([cause, gap[1] - gap[0]])
    classes: dict = {}
    for name, seconds in first["by_name"].items():
        entry = classes.setdefault(short_name(name), [0.0, 0])
        entry[0] += seconds
        entry[1] += first["count_by_name"][name]
    ops = sorted(classes.items(), key=lambda kv: -kv[1][0])[:top]
    return {
        "busy_s": sum(c["busy_s"] for c in per_chip) / len(per_chip),
        "window_s": sum(c["span"][1] - c["span"][0]
                        for c in per_chip) / len(per_chip),
        "chips": len(per_chip),
        "device_ops": [[f"{name} x{count}", seconds]
                       for name, (seconds, count) in ops],
        "idle_gaps": idle,
        "by_name": first["by_name"],
        "count_by_name": first["count_by_name"],
    }


# ---------------------------------------------------------------------------
# reading the file (needs jax)
# ---------------------------------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: str) -> tuple:
    """(devices, host_input, outline): device op events by plane, the
    ``input`` annotations' intervals, and a plain outline of planes and lines
    for a reader who wants to look at the trace by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    devices: dict = {}
    host_input: list = []
    outline: list = []
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            outline.append({"plane": plane.name, "line": line.name,
                            "events": len(events)})
            if plane.name.startswith(DEVICE_PLANE) and line.name == OPS_LINE:
                devices.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9) for e in events)
            elif plane.name.startswith(HOST_PLANE):
                host_input.extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in events if e.name == "input")
    return devices, host_input, outline
