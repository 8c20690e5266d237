"""Set-up seen from inside: the program's spans from the command's start to
the window's opening, from all three of its processes, to numbers. Plain
Python on span records, tested on hand-made lists; stdlib only, so the
parent may use it.

A span record has the shape ``obs.Span.to_dict()`` gives it (``name``,
``ts``, ``end_ts``, ``duration_s``, ``trace_id``, ``span_id``,
``parent_id``, ``pid``, ``attrs``), on the host's wall clock, which the
launcher, the worker and ``run.py``'s ``started_wall`` share. Two sources,
one span in both counts once (by ``span_id``):

- ``run["setup"]["spans"]``: the worker's own flight recorder as the window
  finds it once it has closed, the spans that ended before it opened
  (``setup_record``). ``backend_init`` and the relower ``recompile`` start
  before the window's span sink exists, and only the recorder holds them;
- ``run["flight"]``: every flight dump the launcher's process writes at its
  exit (``read_flight``): the launcher's ``device_probe`` and
  ``master_prepare``, the agent's ``rendezvous``, the master's, and the
  worker's spans that its telemetry carried there.

The spans and what reads each (docs/observability.md, "Set-up"):
``device_probe`` -> ``setup.device_probe_s``, ``backend_init`` ->
``setup.backend_init_s``, the first ``recompile`` ``phase=relower`` ->
``setup.relower_s``, ``recompile`` ``phase=aot`` -> ``setup.compile_s``
(its ``cache`` attr says whether it compiled), ``state_init`` ->
``setup.state_init_s``; ``setup.unattributed_s`` is what no span covers.
Where a span is missing (a program without it, or a run with no launcher
above the worker) its reader gives nothing.
"""

from __future__ import annotations

import glob
import json
import os

from benchmarks import trace_reduce

FIELDS = ("name", "ts", "end_ts", "duration_s", "trace_id", "span_id",
          "parent_id", "pid")


def setup_record(snapshot: list, opened_wall: float) -> dict:
    """The ``setup`` record from a flight recorder's snapshot: its spans
    that ended before the window opened, with their number and string
    attrs."""
    return {"record": "setup", "spans": [
        dict({k: r.get(k) for k in FIELDS}, attrs={
            k: v for k, v in (r.get("attrs") or {}).items()
            if isinstance(v, (int, float, str, bool))})
        for r in snapshot
        if r.get("kind") == "span" and r.get("end_ts", 0.0) < opened_wall]}


def read_flight(directory: str) -> list:
    """The span records of every ``flight-*.json`` dump in ``directory``."""
    out = []
    for path in sorted(glob.glob(os.path.join(directory, "flight-*.json"))):
        with open(path) as f:
            out.extend(r for r in json.load(f).get("events", [])
                       if r.get("kind") == "span")
    return out


def spans(run: dict) -> list:
    """The span records of both sources, each once, that ended before the
    window opened."""
    opened = (run.get("window") or {}).get("opened_wall")
    seen, out = set(), []
    for record in ((run.get("setup") or {}).get("spans") or []) + (
            run.get("flight") or []):
        key = record.get("span_id")
        if key in seen or (opened is not None
                           and record["end_ts"] >= opened):
            continue
        seen.add(key)
        out.append(record)
    return out


def find(records: list, name: str, **attrs):
    """The earliest span named ``name`` whose attrs hold ``attrs``; None."""
    return min((r for r in records if r.get("name") == name and all(
        (r.get("attrs") or {}).get(k) == v for k, v in attrs.items())),
        key=lambda r: r["ts"], default=None)


def duration(run: dict, name: str, **attrs):
    """The seconds of the earliest such span before the window; None
    without one."""
    found = find(spans(run), name, **attrs)
    return None if found is None else found["duration_s"]


def covered(records: list, start: float, end: float) -> float:
    """Seconds of ``[start, end]`` inside at least one span."""
    return trace_reduce.covered(
        (max(start, r["ts"]), min(end, r["end_ts"])) for r in records)
