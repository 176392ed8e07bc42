"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children, no fallback: without an accelerator (or with fewer
chips than the cell asks for) it prints no result line and exits non-zero.
``--rows N`` rehearses the control flow at another table size on whatever
JAX finds; it says so on every line and still exits non-zero.

Everything that belongs to one cell is data found by name from
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``tables/<table>.py``, ``queries/<query>.py``, ``metrics/<metric>.py``.
See ``README.md``.
"""
import argparse
import concurrent.futures
import faulthandler
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import types

_T0 = time.perf_counter()       # set-up is counted from here: before numpy,
#                                 pyarrow, pandas, JAX and the program load

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datagen, trace_digest, traffic  # noqa: E402

WINDOW_ANNOTATION = "bench.window"
SLICE_MIN_QUERIES = 2
SLICE_MIN_SECONDS = 10.0
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
MAX_CONSECUTIVE_FAILURES = 3
STALL_FACTOR = 3.0      # a query this many times the fastest so far stalls


def say(**fields) -> None:
    """A progress line on standard error (standard output carries only the
    result line)."""
    print(json.dumps(fields), file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by the name a data file gives."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"run.py: no benchmark/{kind}/{name}.py")
    mod_name = f"benchmark.{kind}.{name}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration, traffic, tables,
    queries and the metrics it reports, all read from files."""

    def __init__(self, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"run.py: no workload {name!r} in "
                             f"BENCHMARK.json; known: {sorted(cells)}")
        self.name = name
        self.chips = cells[name]["chips"]
        cfg = {c["name"]: c for c in bench["configs"]}[cells[name]["config"]]
        self.config = load_json(os.path.join(ROOT, cfg["file"]))
        self.traffic = traffic.load(os.path.join(
            HERE, "traffic", cells[name]["traffic"] + ".json"))
        self.queries = {q: load_module("queries", q)
                        for q in traffic.query_names(self.traffic)}
        self.tables = {t: load_module("tables", t)
                       for t in {m.TABLE for m in self.queries.values()}}
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def load_cell(name: str) -> Cell:
    return Cell(load_json(os.path.join(ROOT, "BENCHMARK.json")), name)


class CompileWatch:
    """XLA backend compiles of the process, as ``jax.monitoring`` reports
    them (copied from ``chip_smoke.CompileWatch``)."""

    def __init__(self):
        import jax.monitoring
        self.requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == BACKEND_COMPILE_EVENT:
            self.requests += 1


class GcWatch:
    """Seconds the interpreter's garbage collector ran, and its longest
    collection, so that a slow query can be held against them."""

    def __init__(self):
        self.seconds, self.longest, self._t = 0.0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.seconds += d
            self.longest = max(self.longest, d)


class Query(types.SimpleNamespace):
    """One ``collect()`` of the loop: name, table, input rows, start, end
    (``time.perf_counter``), the rows it returned or the error it raised."""


class Client:
    """The system under test behind one entry: ``read_parquet`` -> the
    query file's builder -> ``collect()``."""

    def __init__(self, cell: Cell, files: dict, rows: dict):
        from spark_rapids_tpu.api.session import TpuSession
        self.cell, self.files, self.rows = cell, files, rows
        self.session = TpuSession(dict(cell.config["session_conf"]))
        self.fastest = {}           # query name -> its fastest collect()

    def frame(self, qname: str):
        mod = self.cell.queries[qname]
        return mod.build(self.session.read_parquet(*self.files[mod.TABLE]))

    def run(self, qname: str) -> Query:
        mod = self.cell.queries[qname]
        q = Query(name=qname, table=mod.TABLE, rows=self.rows[mod.TABLE],
                  answer=None, error=None, t0=time.perf_counter())
        # a query that takes STALL_FACTOR times the fastest one before it
        # has every thread's stack written to standard error while it hangs;
        # one that does not pays for a timer it cancels.  The timer is the
        # process's one: with several clients the last to start holds it
        fastest = self.fastest.get(qname)
        if fastest:
            faulthandler.dump_traceback_later(STALL_FACTOR * fastest,
                                              file=sys.stderr)
        try:
            q.answer = self.frame(qname).collect()
        except Exception as e:      # noqa: BLE001 — a failed query is counted
            q.error = f"{type(e).__name__}: {e}"[:300]
        finally:
            if fastest:
                faulthandler.cancel_dump_traceback_later()
        q.t1 = time.perf_counter()
        if q.error is None:
            self.fastest[qname] = min(q.t1 - q.t0, fastest or q.t1 - q.t0)
        return q


def closed_loop(client: Client, spec: dict, seed: int, stop) -> tuple:
    """Every client sends its next query when its last returned, until
    ``stop(elapsed, completed)`` says so after a completion.  Returns
    (queries of all clients in completion order, start, end)."""
    done, lock = [], threading.Lock()
    t_start = time.perf_counter()

    def one_client(idx: int):
        mine, failures = 0, 0
        for qname in traffic.client_stream(spec, seed, idx):
            q = client.run(qname)
            with lock:
                done.append(q)
            mine += 1
            failures = failures + 1 if q.error else 0
            if (failures >= MAX_CONSECUTIVE_FAILURES
                    or stop(q.t1 - t_start, mine)):
                return

    threads = [threading.Thread(target=one_client, args=(i,))
               for i in range(int(spec["clients"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    done.sort(key=lambda q: q.t1)
    return done, t_start, max([q.t1 for q in done], default=t_start)


def percentile(values, p: float) -> float:
    """Nearest-rank percentile of all the values."""
    xs = sorted(values)
    return xs[max(0, -(-len(xs) * p // 100) - 1)] if xs else float("nan")


def memory_peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def traced_slice(client: Client, cell: Cell, seed: int, seconds: float,
                 trace_dir: str) -> dict:
    """A slice of whole queries under the profiler (Python tracer off), the
    program's span log and a per-query trace scope for its retry counts."""
    import jax.profiler

    from spark_rapids_tpu.memory import arena
    from spark_rapids_tpu.plan.execs.base import launch_stats
    from spark_rapids_tpu.utils import tracing
    from spark_rapids_tpu.utils.obs import QueryTrace, trace_scope

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    qtrace = QueryTrace("bench_slice", enabled=True)
    l0, oom0 = launch_stats()["launches"], arena.GLOBAL_DEVICE_OOM_COUNT
    tracing.span_log.clear()
    tracing.span_log.enabled = True
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION), \
                trace_scope(qtrace):
            queries, t0, t1 = closed_loop(
                client, cell.traffic, seed + 1,
                lambda elapsed, n: (n >= SLICE_MIN_QUERIES
                                    and elapsed >= seconds))
    finally:
        jax.profiler.stop_trace()
        tracing.span_log.enabled = False
    qtrace.finish()
    counters = qtrace.counters_snapshot()
    return {
        "queries": queries, "interval": (t0, t1),
        "spans": tracing.span_log.snapshot(),
        "launches": launch_stats()["launches"] - l0,
        "oom": arena.GLOBAL_DEVICE_OOM_COUNT - oom0,
        "retries": (counters.get("task_retry_count", 0)
                    + counters.get("task_split_retry_count", 0)),
    }


def read_per_layer(cell: Cell, readers: dict, ctx, rehearsal: bool,
                   tag: dict) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    metrics = {}
    for m in cell.per_layer:
        try:
            value = readers[m["name"]].read(ctx)
        except KeyError as e:
            # a rehearsal on the CPU has no entry in the table of peaks
            if not rehearsal:
                raise
            say(phase="reader", **tag, metric=m["name"], skipped=str(e))
            continue
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def start_data(cell: Cell, args):
    """The cell's tables, written on threads of their own from now on:
    host work that needs nothing of JAX, so it runs while JAX starts the
    chip.  Returns (scratch directory, future of (files,
    rows, seconds))."""
    scratch = tempfile.mkdtemp(prefix="bench_data_")

    def write():
        t, files, rows = time.perf_counter(), {}, {}
        for tname, tmod in cell.tables.items():
            spec = cell.config["tables"][tname]
            rows[tname] = args.rows or spec["rows"]
            files[tname] = datagen.write_table(
                scratch, tmod, tname, rows[tname], spec["files"],
                spec["row_group_rows"], args.seed,
                spec["scale_factor"] * rows[tname] / spec["rows"])
        return files, rows, time.perf_counter() - t

    pool = concurrent.futures.ThreadPoolExecutor(1)
    future = pool.submit(write)
    pool.shutdown(wait=False)
    return scratch, future


def drop_data(data) -> None:
    scratch, future = data
    concurrent.futures.wait([future])
    shutil.rmtree(scratch, ignore_errors=True)


def run(args, rehearsal: bool, cell: Cell = None, data=None) -> dict:
    """Set-up, window, (traced slice,) comparison.  Returns the result
    object.  ``main`` has already started the data and looked for the
    chip."""
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    tag = {"platform": device["platform"], "rehearsal": rehearsal}
    cell = cell or load_cell(args.workload)
    scratch, data_ready = data or start_data(cell, args)
    readers = ({m["name"]: load_module("metrics", m["name"])
                for m in cell.per_layer} if args.trace else {})
    watch = CompileWatch()
    say(phase="device", **tag, kind=device["kind"], count=device["count"],
        since_start_s=time.perf_counter() - _T0)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        # -- set-up: data, session, plans, one warm-up of every query shape
        t = time.perf_counter()
        files, rows, data_s = data_ready.result()
        say(phase="data", **tag, seed=args.seed, rows=rows,
            parquet_bytes=sum(os.path.getsize(p)
                              for ps in files.values() for p in ps),
            seconds=data_s, waited_s=time.perf_counter() - t)

        t = time.perf_counter()
        client = Client(cell, files, rows)
        say(phase="session", **tag, seconds=time.perf_counter() - t)
        from benchmark.plan_check import fallback_nodes, plan_nodes
        fallbacks = 0
        for qname in cell.queries:
            t, c0 = time.perf_counter(), watch.requests
            plan = client.frame(qname).physical_plan()
            bad = fallback_nodes(plan)
            fallbacks += len(bad)
            warm = client.run(qname)
            say(phase="warm_up", **tag, query=qname, plan=plan_nodes(plan),
                fallback_nodes=bad, error=warm.error,
                compiles=watch.requests - c0,
                seconds=time.perf_counter() - t)
        del warm

        # -- the measured window
        c0 = watch.requests
        gcw = GcWatch()
        setup_s = time.perf_counter() - _T0
        window, w0, w1 = closed_loop(
            client, cell.traffic, args.seed,
            lambda elapsed, n: elapsed >= args.seconds)
        window_s = w1 - w0
        compiles_in_window = watch.requests - c0
        peak = memory_peak_bytes(devices)
        ok = [q for q in window if q.error is None]
        lat = sorted(q.t1 - q.t0 for q in ok)
        say(phase="window", **tag, seconds=window_s, queries=len(window),
            failed=len(window) - len(ok),
            compiles_in_window=compiles_in_window,
            latency_s={"min": lat[0], "median": lat[len(lat) // 2],
                       "max": lat[-1]} if lat else None,
            stalled=sum(x > STALL_FACTOR * lat[len(lat) // 2] for x in lat),
            gc_s=gcw.seconds, gc_longest_s=gcw.longest,
            memory_peak_bytes=peak)

        sliced, digest = None, None
        if args.trace:
            sliced = traced_slice(
                client, cell, args.seed,
                min(SLICE_MIN_SECONDS, float(args.seconds)), trace_dir)
            span_names = {s for r in readers.values()
                          for s in getattr(r, "SPANS", ())}
            t = time.perf_counter()
            digest = trace_digest.digest(trace_dir, WINDOW_ANNOTATION,
                                         span_names, device["platform"])
            say(phase="trace", **tag, queries=len(sliced["queries"]),
                slice_s=sliced["interval"][1] - sliced["interval"][0],
                busy_s=digest["busy_s"], window_s=digest["window_s"],
                digest_seconds=time.perf_counter() - t)

        # -- free the program's state, then the plain reference on the host
        every = window + (sliced["queries"] if sliced else [])
        answers = [(q.name, q.answer) for q in every if q.error is None]
        errors = [q.error for q in every if q.error is not None]
        client = None
        gc.collect()
        t = time.perf_counter()
        references = {
            qname: mod.reference(datagen.read_frame(
                files[mod.TABLE], mod.COLUMNS))
            for qname, mod in cell.queries.items()}
        compared = compare.compare(
            answers, references, cell.config["limits"], fallbacks,
            len(errors), {qname for qname, mod in cell.queries.items()
                          if getattr(mod, "ORDERED", False)})
        say(phase="reference", **tag, answers=len(answers),
            seconds=time.perf_counter() - t, first_error=errors[:1])
    finally:
        drop_data((scratch, data_ready))
        shutil.rmtree(trace_dir, ignore_errors=True)

    measured = {
        "rows_per_s": sum(q.rows for q in ok) / window_s if ok else 0.0,
        "query_p95_ms": 1e3 * percentile([q.t1 - q.t0 for q in ok], 95),
        "setup_s": setup_s,
    }
    device["memory_peak_bytes"] = peak
    metrics, breakdown = {}, None
    if not args.trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    else:
        device["busy_s"] = digest["busy_s"]
        device["window_s"] = digest["window_s"]
        ctx = types.SimpleNamespace(
            cell=cell, queries=cell.queries, tables=cell.tables,
            device_kind=device["kind"], window_queries=ok,
            window_s=window_s, compiles_in_window=compiles_in_window,
            memory_peak_bytes=peak, trace=digest,
            slice_queries=[q for q in sliced["queries"] if q.error is None],
            slice_interval=sliced["interval"], spans=sliced["spans"],
            slice_launches=sliced["launches"], slice_oom=sliced["oom"],
            slice_retries=sliced["retries"])
        metrics = read_per_layer(cell, readers, ctx, rehearsal, tag)
        breakdown = {"device_ops": digest["device_ops"],
                     "idle_gaps": digest["idle_gaps"]}
    result = {
        "correct": compare.is_correct(compared),
        "attempted": len(every), "failed": len(errors),
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="rehearsal only: another table size; the run says "
                    "so, prints no result line and exits non-zero")
    return ap.parse_args(argv)


def print_compared(compared: dict) -> None:
    for name, c in compared.items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = load_cell(args.workload)
    import jax                  # before the data's threads: an import
    #                             beside them waits for the interpreter
    t_imports = time.perf_counter()
    data = start_data(cell, args)
    try:
        devices = jax.devices()
    except BaseException:
        drop_data(data)
        raise
    on_chip = devices[0].platform != "cpu"
    rehearsal = args.rows is not None or not on_chip
    if not on_chip and args.rows is None:
        drop_data(data)
        print("run.py: JAX found no accelerator (platform cpu); --rows N "
              "rehearses the control flow and exits non-zero too",
              file=sys.stderr)
        return 3
    if on_chip and len(devices) < cell.chips:
        drop_data(data)
        print(f"run.py: the cell asks for {cell.chips} chip(s), JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    say(phase="start", imports_s=t_imports - _T0,
        devices_s=time.perf_counter() - t_imports)
    result = run(args, rehearsal, cell, data)
    if rehearsal:
        say(phase="rehearsal_result", **result)
        print_compared(result["compared"])
        print("run.py: rehearsal finished; no result line",
              file=sys.stderr)
        return 4
    print_compared(result["compared"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
