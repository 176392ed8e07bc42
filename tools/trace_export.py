"""Perfetto/Chrome-trace exporter for QueryTrace snapshots.

One timeline per query, spanning serving admission -> driver dispatch ->
per-rank executor task spans -> shuffle fetch/pipeline producer spans,
loadable in ui.perfetto.dev or chrome://tracing.  The input is the
JSON-safe snapshot shape ``utils/obs.QueryTrace.snapshot()`` produces
(or the trace object itself); the output is the Chrome Trace Event
Format (the JSON dialect Perfetto ingests natively):

  * one PROCESS per track — ``serving`` (admission/control plane),
    ``driver`` (dispatch + await), one per executor rank (``rank0``,
    ``rank1``, ...), plus any other track spans were recorded under —
    named via ``process_name`` metadata events;
  * every span is a complete "X" event (ts/dur in MICROSECONDS of epoch
    time; spans from different processes align because QueryTrace
    records epoch timestamps); a span that names its ``parent`` carries
    ``span_id``/``parent`` in ``args``.  On one thread a child nests
    under its parent by time; a child on ANOTHER thread (a task thread
    under ``query.collect``, a decode under the ``scan.wait`` that
    started it) is tied to its parent by a flow arrow;
  * the query's attributed counter snapshot rides as ``args`` on a
    process-wide summary event, so the numbers travel with the
    timeline.

Usage:
    python tools/trace_export.py <snapshot.json> [out.trace.json]
or programmatically:
    from tools.trace_export import export_trace
    export_trace(trace_or_snapshot, "/tmp/query_7.trace.json")
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

#: stable pids for the well-known tracks; rank tracks and strays are
#: assigned deterministically after these
_FIXED_PIDS = {"serving": 1, "driver": 2}
_RANK_PID_BASE = 10


def _snapshot_of(trace_or_snapshot) -> dict:
    snap = getattr(trace_or_snapshot, "snapshot", None)
    return snap() if callable(snap) else dict(trace_or_snapshot)


def _track_pids(spans: List[dict]) -> Dict[str, int]:
    tracks = sorted({s.get("track") or "local" for s in spans})
    pids: Dict[str, int] = {}
    stray = _RANK_PID_BASE + 1000
    for t in tracks:
        if t in _FIXED_PIDS:
            pids[t] = _FIXED_PIDS[t]
        elif t.startswith("rank") and t[4:].isdigit():
            pids[t] = _RANK_PID_BASE + int(t[4:])
        else:
            pids[t] = stray
            stray += 1
    return pids


def trace_events(trace_or_snapshot) -> List[dict]:
    """Chrome trace events for one query's snapshot (see module doc)."""
    snap = _snapshot_of(trace_or_snapshot)
    spans = list(snap.get("spans") or ())
    pids = _track_pids(spans)
    qid = snap.get("query_id")
    events: List[dict] = []
    for track, pid in sorted(pids.items(), key=lambda kv: kv[1]):
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0,
                       "args": {"name": f"{track} (query {qid})"}})
    #: thread ids per (track, thread name), stable within the export
    tids: Dict[tuple, int] = {}
    placed: Dict[int, dict] = {}    # span id -> its "X" event
    for s in spans:
        track = s.get("track") or "local"
        pid = pids[track]
        key = (track, s.get("thread") or "")
        tid = tids.get(key)
        if tid is None:
            tid = len([k for k in tids if k[0] == track]) + 1
            tids[key] = tid
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": key[1] or track}})
        ev = {"ph": "X", "name": s["name"], "cat": track,
              "pid": pid, "tid": tid,
              "ts": s["t0"] * 1e6,
              "dur": max((s["t1"] - s["t0"]) * 1e6, 1.0)}
        if s.get("tags"):
            ev["args"] = dict(s["tags"])
        if s.get("id") is not None:
            ev.setdefault("args", {}).update(
                span_id=s["id"], parent=s.get("parent"))
            placed[s["id"]] = ev
        events.append(ev)
    events.extend(_flow_arrows(placed))
    # the per-query counter attribution travels with the timeline
    counters = {k: v for k, v in (snap.get("counters") or {}).items()
                if v}
    if counters or snap.get("duration_s") is not None:
        anchor = snap.get("t_submit") or (
            min((s["t0"] for s in spans), default=0.0))
        pid = pids.get("serving") or pids.get("driver") or (
            next(iter(pids.values())) if pids else 1)
        events.append({
            "ph": "X", "name": f"query {qid} summary", "cat": "summary",
            "pid": pid, "tid": 0, "ts": anchor * 1e6,
            "dur": max((snap.get("duration_s") or 0.0) * 1e6, 1.0),
            "args": {"counters": counters,
                     "dropped_spans": snap.get("dropped_spans", 0)}})
    return events


def _flow_arrows(placed: Dict[int, dict]) -> List[dict]:
    """A flow arrow from each parent to each child that ran on another
    thread, bound to the slices that enclose its two ends."""
    arrows: List[dict] = []
    for sid, child in placed.items():
        parent = placed.get(child["args"].get("parent"))
        if parent is None or (parent["pid"], parent["tid"]) == (
                child["pid"], child["tid"]):
            continue
        start = min(max(child["ts"], parent["ts"]),
                    parent["ts"] + parent["dur"])
        common = {"name": "spawn", "cat": "parent", "id": sid}
        arrows.append({**common, "ph": "s", "pid": parent["pid"],
                       "tid": parent["tid"], "ts": start})
        arrows.append({**common, "ph": "f", "bp": "e", "pid": child["pid"],
                       "tid": child["tid"], "ts": child["ts"]})
    return arrows


def export_trace(trace_or_snapshot, path: str) -> str:
    """Write one query's Perfetto-loadable trace JSON; returns path."""
    doc = {"traceEvents": trace_events(trace_or_snapshot),
           "displayTimeUnit": "ms"}
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def main(argv) -> int:
    if not argv or len(argv) > 2:
        print(__doc__)
        return 2
    with open(argv[0]) as f:
        snap = json.load(f)
    out = argv[1] if len(argv) > 1 else (
        os.path.splitext(argv[0])[0] + ".trace.json")
    export_trace(snap, out)
    print(out)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
