"""What the host does while the device waits, from the program's own spans.

    python tools/host_timeline.py --cell q6_parquet_sf10 --seed 7
                                  [--queries 3] [--rows N] [--out FILE]

Writes the cell's table as the benchmark does (``benchmark/run.py``'s data,
client and query files), warms the query up, runs ``--queries`` of it with
every sink off and then as many with ``tracing.span_log`` on (no profiler),
and reduces ``span_log.snapshot()`` and ``tracing.late_ticks()`` to:

  * per span name: count, seconds, and per scan chunk (one ``scan.upload``)
    the milliseconds of ``scan.decode``, ``scan.wait``, ``scan.upload`` and,
    of that, ``upload.put``; and the task thread's cycle from one upload's
    start to the next inside a query, with what of it no scan span owns;
  * the ``host.lock_wait`` seconds by the span names open at the same time
    ("late while a ``scan.decode`` and a ``scan.upload`` were both open" is
    a row), beside how long each set of names was open at all;
  * the late ticks over 20 ms grouped by the innermost frames of each other
    thread: the thread that held the lock stands at the line of its C call.

The interpreter's garbage collections of the traced queries are added to the
spans as ``host.gc`` (the tool's own name, from ``gc.callbacks``): a
collection keeps the lock and leaves no frame behind, so a late tick with
every thread in a wait is read against the ``host.gc`` row.

Host numbers on the ``time.perf_counter`` clock: they say where the host's
time went on the machine they were read on, nothing of the device.  ``--rows``
is a rehearsal at another table size.  ``reduce()`` takes any spans and late
ticks (an operator's own: ``docs/observability.md``).
"""
import argparse
import collections
import gc
import json
import os
import re
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.trace_digest import clip  # noqa: E402

SCAN_SPANS = ("scan.decode", "scan.wait", "scan.upload")
ROOT_SPAN, LOCK_SPAN, GC_SPAN = "query.collect", "host.lock_wait", "host.gc"
ROWS_SHOWN = 12


def by_name(spans) -> dict:
    out = collections.defaultdict(list)
    for name, t0, t1 in spans:
        out[name].append((t0, t1))
    return out


def span_table(named: dict, queries: int) -> dict:
    """name -> count, seconds, seconds a query, and the spans' own
    distribution in milliseconds."""
    table = {}
    for name, ivs in sorted(named.items()):
        ms = sorted(1e3 * (t1 - t0) for t0, t1 in ivs)
        table[name] = {
            "count": len(ms), "seconds": sum(ms) / 1e3,
            "s_per_query": sum(ms) / 1e3 / max(queries, 1),
            "mean_ms": statistics.fmean(ms), "p50_ms": ms[len(ms) // 2],
            "p95_ms": ms[min(len(ms) - 1, int(0.95 * len(ms)))],
            "max_ms": ms[-1]}
    return table


def per_chunk(named: dict) -> dict:
    """Milliseconds of each scan span per chunk (one ``scan.upload`` a
    chunk), ``upload.put`` counted only inside an upload."""
    uploads = named.get("scan.upload", ())
    if not uploads:
        return {}
    out = {"chunks": len(uploads)}
    for name in SCAN_SPANS:
        out[name + "_ms"] = 1e3 * sum(
            t1 - t0 for t0, t1 in named.get(name, ())) / len(uploads)
    puts = named.get("upload.put", ())
    inside = sum(t1 - t0 for u0, u1 in uploads for t0, t1 in clip(puts, u0, u1))
    out["upload.put_ms"] = 1e3 * inside / len(uploads)
    out["puts_per_chunk"] = len(puts) / len(uploads)
    return out


def chunk_cycles(named: dict) -> dict:
    """The task thread's cycle from one ``scan.upload``'s start to the next
    inside one ``query.collect``, and what of it ``scan.upload``,
    ``scan.wait`` and ``fused.batch`` cover; the rest has no span of the
    scan's or the dispatch's.  Meant for a plan with one scanning task
    thread at a time."""
    cycles, covered = [], collections.Counter()
    parts = {n: named.get(n, ())
             for n in ("scan.upload", "scan.wait", "fused.batch")}
    for q0, q1 in named.get(ROOT_SPAN, ()):
        starts = sorted(t0 for t0, t1 in parts["scan.upload"]
                        if q0 <= t0 < q1)
        for a, b in zip(starts, starts[1:]):
            cycles.append(b - a)
            for n, ivs in parts.items():
                covered[n] += sum(t1 - t0 for t0, t1 in clip(ivs, a, b))
    if not cycles:
        return {}
    out = {"cycles": len(cycles), "cycle_ms": 1e3 * statistics.fmean(cycles),
           "cycle_p50_ms": 1e3 * statistics.median(cycles)}
    for n in parts:
        out[n + "_ms"] = 1e3 * covered[n] / len(cycles)
    out["unowned_ms"] = out["cycle_ms"] - sum(out[n + "_ms"] for n in parts)
    return out


def lock_wait_by_open_spans(spans) -> list:
    """Rows of (the span names open, seconds they were open together,
    seconds of ``host.lock_wait`` in that time), by a sweep over every
    span's two ends.  ``query.collect`` is left out of the names."""
    events = []
    for name, t0, t1 in spans:
        if name != ROOT_SPAN and t1 > t0:
            events.append((t0, 1, name))
            events.append((t1, -1, name))
    events.sort(key=lambda e: (e[0], e[1]))
    open_now, late_now = collections.Counter(), 0
    open_s, late_s = collections.Counter(), collections.Counter()
    last = None
    for t, step, name in events:
        if last is not None and t > last:
            key = tuple(sorted(n for n, c in open_now.items() if c > 0))
            open_s[key] += t - last
            if late_now:
                late_s[key] += t - last
        last = t
        if name == LOCK_SPAN:
            late_now += step
        else:
            open_now[name] += step
    rows = [{"open": list(key), "open_s": open_s[key],
             "lock_wait_s": late_s[key],
             "late_share_pct": 100.0 * late_s[key] / open_s[key]}
            for key in open_s if open_s[key] > 0]
    rows.sort(key=lambda r: (-r["lock_wait_s"], -r["open_s"]))
    return rows


def holders(late_ticks) -> list:
    """The late ticks grouped by (thread, innermost two frames) of every
    other thread, thread names with their numbers struck out."""
    groups = collections.defaultdict(lambda: [0, 0.0])
    for due, ran, threads in late_ticks:
        for name, frames in threads.items():
            key = (re.sub(r"\d+", "N", name), tuple(frames[:2]))
            groups[key][0] += 1
            groups[key][1] += ran - due
    rows = [{"thread": thread, "frames": list(frames), "ticks": n,
             "late_s": late} for (thread, frames), (n, late) in groups.items()]
    rows.sort(key=lambda r: -r["late_s"])
    return rows


def reduce(spans, late_ticks, queries: int) -> dict:
    named = by_name(spans)
    waits = named.get(LOCK_SPAN, [])
    lo = min((t0 for _n, t0, _t1 in spans), default=0.0)
    hi = max((t1 for _n, _t0, t1 in spans), default=0.0)
    return {
        "queries": queries, "traced_s": hi - lo,
        "spans": span_table(named, queries),
        "per_chunk": per_chunk(named), "chunk_cycle": chunk_cycles(named),
        "lock_wait": {
            "spans": len(waits), "seconds": sum(b - a for a, b in waits),
            "share_of_traced_pct": 100.0 * sum(b - a for a, b in waits)
            / (hi - lo) if hi > lo else 0.0,
            "over_20ms": sum(b - a > 0.020 for a, b in waits),
            "longest_ms": 1e3 * max((b - a for a, b in waits), default=0.0)},
        "lock_wait_by_open_spans": lock_wait_by_open_spans(spans),
        "late_ticks": len(late_ticks), "holders": holders(late_ticks),
        "late_tick_frames": [
            {"late_ms": 1e3 * (ran - due), "threads": threads}
            for due, ran, threads in late_ticks]}


def render(report: dict) -> str:
    lines = []
    for key in ("per_chunk", "chunk_cycle", "lock_wait"):
        lines.append(f"{key}: " + json.dumps(report[key]))
    lines.append("lock_wait by open spans (open_s, lock_wait_s, share %):")
    for r in report["lock_wait_by_open_spans"][:ROWS_SHOWN]:
        lines.append(f"  {r['open_s']:9.4f} {r['lock_wait_s']:9.4f} "
                     f"{r['late_share_pct']:6.2f}  "
                     f"{' + '.join(r['open']) or '(none)'}")
    lines.append(f"late ticks over 20 ms: {report['late_ticks']}; by thread "
                 "and innermost frames (ticks, late_s):")
    for r in report["holders"][:ROWS_SHOWN]:
        lines.append(f"  {r['ticks']:4d} {r['late_s']:8.4f}  {r['thread']}: "
                     f"{' <- '.join(r['frames'])}")
    return "\n".join(lines)


class GcSpans:
    """The interpreter's collections as ``(host.gc, start, end)`` spans on
    the span log's clock, while it is among ``gc.callbacks``."""

    def __init__(self):
        self.spans, self._t0 = [], None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.spans.append((GC_SPAN, self._t0, time.perf_counter()))
            self._t0 = None


def drive(args) -> dict:
    """The cell's query: warmed up, ``--queries`` times with every sink off,
    then as many with the span log on."""
    from benchmark import run
    cell = run.load_cell(args.cell)
    import jax
    data = run.start_data(cell, args)
    try:
        files, rows, _seconds = data[1].result()
        from spark_rapids_tpu.utils import tracing
        client = run.Client(cell, files, rows)
        qname, = cell.queries

        def collect_s() -> float:
            # not Client.run: its stall dump is the benchmark's own
            t0 = time.perf_counter()
            client.frame(qname).collect()
            return time.perf_counter() - t0

        collect_s()                                 # warm-up
        off = [collect_s() for _ in range(args.queries)]
        tracing.span_log.clear()
        tracing.sampler.clear()
        collections_seen = GcSpans()
        gc.callbacks.append(collections_seen)
        tracing.span_log.enabled = True
        try:
            on = [collect_s() for _ in range(args.queries)]
        finally:
            tracing.span_log.enabled = False
            gc.callbacks.remove(collections_seen)
        report = reduce(tracing.span_log.snapshot() + collections_seen.spans,
                        tracing.late_ticks(), args.queries)
    finally:
        run.drop_data(data)
    report.update(
        cell=args.cell, seed=args.seed, rows=rows,
        platform=jax.devices()[0].platform,
        query_s_sinks_off=off, query_s_span_log_on=on,
        threads=sorted(re.sub(r"\d+", "N", t.name)
                       for t in threading.enumerate()))
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", type=int, default=3)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    t = time.perf_counter()
    report = drive(args)
    report["tool_s"] = time.perf_counter() - t
    print(render(report))
    print(json.dumps({k: report[k] for k in (
        "cell", "seed", "platform", "queries", "query_s_sinks_off",
        "query_s_span_log_on")}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
