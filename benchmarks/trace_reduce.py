"""From a profiler trace to numbers: device busy time, idle gaps, the time of
named kernels and the time under each scope of the program. The interval
arithmetic is plain Python on (start, end) pairs in seconds and is tested on
hand-made lists; ``load`` is the only part that needs JAX
(``jax.profiler.ProfileData``) and the only part that knows how today's trace
names things.

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

What XLA compiles has no name of the program's own, but the compiled step's
text has every instruction's ``op_name``: the path of JAX transformations,
Flax modules and ``jax.named_scope`` names it was traced under
(``jit(_train_step)/grad_accum/while/body/closed_call/transpose(jvp(Llama))/
layer_3/mlp/down_proj/dot_general``). ``op_names`` reads that text into
instruction name -> ``op_name``, and ``reduce`` joins it with the events'
instruction names into ``by_scope`` (``kernel_needs.scope_share`` reads it).
An instruction's name (``fusion.1``) is unique in its program only, so the
join holds to the events inside that program's runs on the ``XLA Modules``
line, which are named ``<module>(<fingerprint>)`` (``program_runs``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
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
# device time by the program's scopes
# ---------------------------------------------------------------------------

UNSCOPED = "unscoped"
OUTSIDE = "outside_program"     # beside UNSCOPED: how much of it is this
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def op_names(hlo_text: str) -> dict:
    """instruction name -> ``op_name`` over a compiled program's text
    (``compiled.as_text()``). An instruction without one of its own that
    calls a computation, as a fusion does, takes that computation's root's
    (where the root is a tuple or a bitcast and has none: that of the last
    instruction before it that has); one that finds none is left out."""
    named, calls, last_named = {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if not found:
            opened = _COMPUTATION.match(line)
            if opened:
                computation = opened.group(1)
            continue
        name = found.group(1)
        op_name = _OP_NAME.search(line)
        if op_name:
            named[name] = last_named[computation] = op_name.group(1)
        else:
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
    for name, computation in calls.items():
        if computation in last_named:
            named[name] = last_named[computation]
    return named


def scopes_of(op_name: str) -> list:
    """The names one ``op_name`` counts under, outermost first, each once:
    every component of its path, a component that a transformation wraps
    (``transpose(jvp(Llama))``) as the transformation alone
    (``transpose(jvp(``: the backward) and as what it wraps (``Llama``).
    The ``jit(...)`` the path starts with is the program's name and is
    left out; the last component is the primitive."""
    parts = op_name.split("/")
    while parts and parts[0].startswith(("jit(", "pjit(")):
        parts.pop(0)
    out: list = []
    for part in parts:
        opened = part.rfind("(") + 1
        inner = part[opened:].rstrip(")")
        for name in ((part[:opened], inner) if opened and inner else (part,)):
            if name and name not in out:
                out.append(name)
    return out


_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)


def module_name(hlo_text: str):
    """The name a compiled program's text opens with (``jit__train_step``)."""
    found = _MODULE.search(hlo_text)
    return found.group(1) if found else None


def program_runs(modules: dict, module) -> dict:
    """plane name -> the sorted (start, end) of the runs of the program
    named ``module`` among that chip's ``XLA Modules`` events (name, start,
    end), which are named ``<module>(<fingerprint>)``."""
    return {plane: sorted((start, end) for name, start, end in events
                          if module and name.startswith(module + "("))
            for plane, events in modules.items()}


def time_by_scope(events, op_name_of: dict, runs=None) -> dict:
    """Seconds of one chip's (name, start, end) events under every name of
    ``scopes_of`` the event's instruction has. An event whose instruction
    the program's text does not name, or names without an ``op_name``,
    counts as ``unscoped``; so does one that starts outside ``runs``
    (that chip's ``program_runs``; None: not held to any), whatever its
    name: it is another program's instruction, and counts as
    ``outside_program`` too."""
    by_scope: dict = {UNSCOPED: 0.0}
    scopes_by_event: dict = {}
    starts = [run[0] for run in runs or ()]
    for event, start, end in events:
        scopes = scopes_by_event.get(event)
        if scopes is None:
            instruction = event.lstrip("%").split(" ", 1)[0]
            scopes = scopes_by_event[event] = scopes_of(
                op_name_of.get(instruction, "")) or [UNSCOPED]
        if runs is not None:
            at = bisect.bisect_right(starts, start) - 1
            if at < 0 or start >= runs[at][1]:
                scopes = [UNSCOPED, OUTSIDE]
        for scope in scopes:
            by_scope[scope] = by_scope.get(scope, 0.0) + (end - start)
    return by_scope


# ---------------------------------------------------------------------------
# the reduction every traced run makes
# ---------------------------------------------------------------------------


def reduce(devices: dict, host_input: list, top: int = 10,
           op_name_of: dict | None = None, runs=None) -> dict:
    """``devices``: plane name -> [(op name, start s, end s)];
    ``host_input``: [(start, end)] of the benchmark's own ``input``
    annotations; ``op_name_of``: ``op_names`` of the traced program, where
    the window has it, and ``runs``: plane name -> that program's
    ``program_runs``. Returns busy and window seconds averaged over the
    chips, the idle gaps and the heaviest ops of the first chip, that chip's
    events by name for the kernel readers and, with ``op_name_of``, its
    time by scope."""
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
    reduced = {
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
    if op_name_of:
        reduced["by_scope"] = time_by_scope(
            devices[first["plane"]], op_name_of,
            None if runs is None else runs.get(first["plane"], []))
    return reduced


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
    """(devices, host_input, outline, modules): device op events by plane,
    the ``input`` annotations' intervals, a plain outline of planes and lines
    for a reader who wants to look at the trace by hand, and the programs'
    runs by plane (the ``XLA Modules`` line, as ``devices`` has the ops)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    devices: dict = {}
    modules: dict = {}
    host_input: list = []
    outline: list = []
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            outline.append({"plane": plane.name, "line": line.name,
                            "events": len(events)})
            kept = {OPS_LINE: devices, MODULES_LINE: modules}.get(line.name)
            if plane.name.startswith(DEVICE_PLANE) and kept is not None:
                kept.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9) for e in events)
            elif plane.name.startswith(HOST_PLANE):
                host_input.extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in events if e.name == "input")
    return devices, host_input, outline, modules
