"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children, no fallback: without an accelerator (or with fewer
chips than the cell asks for) it prints no result line and exits non-zero.
``--rows N`` rehearses the control flow at another table size on whatever
JAX finds; it says so on every line and still exits non-zero.

Everything that belongs to one cell is data found by name from
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``tables/<table>.py``, ``queries/<query>.py``, ``metrics/<metric>.py``.
A traffic file's ``loop`` is ``closed`` (clients that each
wait for their last answer) or ``open`` (arrivals on a schedule, served
through ``serving.QueryQueue``).  See ``README.md``.
"""
import argparse
import concurrent.futures
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
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
STALL_FACTOR = 3.0      # a query this many times the fastest so far stalls,
STALL_FLOOR_S = 10.0    # and never under this many seconds
WATCH_TICK_S = 1.0      # how often the watchdog looks
DRAIN_SECONDS = 60.0    # an open window waits this long past its last due
#                         time for the answers still out
JOIN_SECONDS = 30.0     # and this long more for a cancelled arrival to end


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
        self.traffic = traffic.load(
            traffic_path(cells[name]["traffic"]),
            lambda q: len(substitution_space(load_module("queries", q))))
        self.open = self.traffic["loop"] == "open"
        self.queries = {q: load_module("queries", q)
                        for q in traffic.query_names(self.traffic)}
        self.spaces = {q: substitution_space(mod)
                       for q, mod in self.queries.items()}
        self.tables = {t: load_module("tables", t)
                       for t in {m.TABLE for m in self.queries.values()}}
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def traffic_path(name: str) -> str:
    return os.path.join(HERE, "traffic", name + ".json")


def substitution_space(mod) -> list:
    """Every substitution set a query file's ``SUBSTITUTIONS`` allows."""
    return traffic.substitution_space(getattr(mod, "SUBSTITUTIONS", None))


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
    """One query of a loop: name, table, input rows, substitution set
    (``sub``), due time ``t0`` and end ``t1`` (``time.perf_counter``), the
    rows it returned, or the error it raised, or why it was ``refused``
    (admission's reason, or ``unfinished`` when the window was drained).
    ``service_s`` is how long it ran: None for an open loop's answer from
    the result cache, which ran nothing.  In a closed loop a query is due
    when it is sent; an open loop's arrival also has its ``tenant`` and when
    it was ``fired``."""

    def key(self) -> tuple:
        """(query, substitution set): what its answer is compared by."""
        return self.name, traffic.sub_key(self.sub)


class Watchdog:
    """One daemon thread for the process that looks at every running query
    each ``tick_s`` and, for each one that runs past its limit, writes every
    thread's stack to ``out``, once a query.  It reads the stacks with
    ``sys._current_frames()`` under the interpreter lock, so it reads no
    frame that another thread is changing.  A query's limit is stretched by
    the number of queries running beside it, which share the chip."""

    def __init__(self, out=None, tick_s: float = None):
        self.out = out or sys.stderr
        self.tick_s = tick_s or WATCH_TICK_S
        self.dumps = 0
        self._running, self._lock = {}, threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="bench-watchdog", daemon=True)
        self._thread.start()

    @contextlib.contextmanager
    def watch(self, label: str, limit_s: float):
        """Watch the query that runs inside the block."""
        entry = types.SimpleNamespace(label=label, limit=limit_s,
                                      start=time.perf_counter(), fired=False)
        with self._lock:
            self._running[id(entry)] = entry
        try:
            yield
        finally:
            with self._lock:
                del self._running[id(entry)]

    def _loop(self) -> None:
        while not self._stop.wait(self.tick_s):
            self.scan(time.perf_counter())

    def scan(self, now: float) -> None:
        with self._lock:
            n = len(self._running)
            over = [e for e in self._running.values()
                    if not e.fired and now - e.start > e.limit * n]
            for e in over:
                e.fired = True
        for e in over:
            self._dump(e, now, n)

    def _dump(self, e, now: float, running: int) -> None:
        names = {t.ident: t.name for t in threading.enumerate()}
        text = [f"run.py: stall: {e.label} has run {now - e.start:.2f} s, "
                f"over {e.limit:.2f} s x {running} running; every thread's "
                "stack follows\n"]
        for ident, frame in sys._current_frames().items():
            text.append(f"-- thread {names.get(ident, '?')} ({ident})\n")
            text.extend(traceback.format_stack(frame))
        self.dumps += 1
        print("".join(text), end="", file=self.out, flush=True)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=self.tick_s + 5.0)


def stall_limit(fastest) -> float:
    return max(STALL_FACTOR * fastest, STALL_FLOOR_S)


class Client:
    """The system under test behind one entry: ``read_parquet`` -> the
    query file's builder -> ``collect()``."""

    def __init__(self, cell: Cell, files: dict, rows: dict,
                 watchdog: Watchdog = None):
        from spark_rapids_tpu.api.session import TpuSession
        self.cell, self.files, self.rows = cell, files, rows
        self.session = TpuSession(dict(cell.config["session_conf"]))
        self.watchdog = watchdog
        self.fastest = {}           # query name -> its fastest run
        self._fastest_lock = threading.Lock()

    def frame(self, qname: str, sub: dict = None):
        mod = self.cell.queries[qname]
        return mod.build(self.session.read_parquet(*self.files[mod.TABLE]),
                         **(sub or {}))

    def query(self, qname: str, sub: dict, t0: float, **more) -> Query:
        mod = self.cell.queries[qname]
        return Query(name=qname, table=mod.TABLE, rows=self.rows[mod.TABLE],
                     sub=sub, answer=None, error=None, refused=None, t0=t0,
                     **more)

    def watch(self, qname: str, label: str):
        """The watchdog's block for one run of ``qname``: none before the
        query has a fastest run, or without a watchdog."""
        fastest = self.fastest.get(qname)
        if self.watchdog is None or not fastest:
            return contextlib.nullcontext()
        return self.watchdog.watch(label, stall_limit(fastest))

    def note(self, qname: str, seconds: float) -> None:
        with self._fastest_lock:
            self.fastest[qname] = min(seconds,
                                      self.fastest.get(qname, seconds))

    def run(self, qname: str, sub: dict = None) -> Query:
        q = self.query(qname, sub or {}, time.perf_counter())
        try:
            with self.watch(qname, f"query {qname}"):
                q.answer = self.frame(qname, sub).collect()
        except Exception as e:      # noqa: BLE001 — a failed query is counted
            q.error = f"{type(e).__name__}: {e}"[:300]
        q.t1 = time.perf_counter()
        q.service_s = q.t1 - q.t0       # it ran as soon as it was sent
        if q.error is None:
            self.note(qname, q.service_s)
        return q


class Served:
    """The serving tier under test for an open loop: ``QueryQueue`` over a
    runner, under the configuration's ``session_conf``, with an empty
    result cache.  ``serve`` runs one arrival on the calling thread."""

    def __init__(self, client: Client, runner):
        from spark_rapids_tpu.serving.admission import QueryQueue
        self.client, self.runner = client, runner
        self.queue = QueryQueue(self._run,
                                conf=dict(client.cell.config["session_conf"]))
        self._arrivals = {}        # thread ident -> the arrival it serves

    def _run(self, plan, ctx):
        """The runner, timed and watched: admission has let the query in."""
        q = self._arrivals[threading.get_ident()]
        t = time.perf_counter()
        try:
            with self.client.watch(q.name, f"arrival {q.qid} ({q.name})"):
                return self.runner(plan, ctx)
        finally:
            q.service_s = time.perf_counter() - t

    def serve(self, a: traffic.Arrival, due: float, qid: str) -> Query:
        from spark_rapids_tpu.serving.admission import AdmissionRejected
        from spark_rapids_tpu.utils.cancel import QueryCancelled
        q = self.client.query(a.query, a.sub, due, tenant=a.tenant, qid=qid,
                              fired=time.perf_counter(), service_s=None)
        self._arrivals[threading.get_ident()] = q
        try:
            q.answer = self.queue.submit(
                self.client.frame(a.query, a.sub).plan, tenant=a.tenant,
                priority=a.priority, query_id=qid)
        except AdmissionRejected as e:
            q.refused = e.reason
        except QueryCancelled:
            q.refused = "unfinished"
        except Exception as e:      # noqa: BLE001 — a failed query is counted
            q.error = f"{type(e).__name__}: {e}"[:300]
        finally:
            del self._arrivals[threading.get_ident()]
        q.t1 = time.perf_counter()
        if q.service_s is not None and q.error is None and q.refused is None:
            self.client.note(q.name, q.service_s)
        return q

    def close(self) -> None:
        self.queue.close()


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


def open_loop(served: Served, schedule: list, seconds: float) -> tuple:
    """Fires each arrival of ``schedule`` at its due time on a thread of its
    own, whatever is still in flight, and waits for the answers until
    ``DRAIN_SECONDS`` past ``seconds``.  An arrival still out then is
    cancelled and counts as refused, ``unfinished``.  Returns (arrivals in
    completion order, start, end).  The window ends at the last completion,
    or at the drain's limit where an arrival was still out, and never before
    ``seconds``: load was offered for that long."""
    done, lock, closed = [], threading.Lock(), []

    def one(a, due, qid):
        q = served.serve(a, due, qid)
        with lock:
            if not closed:
                done.append(q)

    t_start = time.perf_counter()
    threads = []
    for i, a in enumerate(schedule):
        due = t_start + a.due
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=one, args=(a, due, f"a{i}"),
                              name=f"bench-arrival-{i}", daemon=True)
        th.start()
        threads.append((th, a, due, f"a{i}"))
    limit = t_start + seconds + DRAIN_SECONDS
    for th, *_ in threads:
        th.join(max(0.0, limit - time.perf_counter()))
    with lock:
        closed.append(True)
        out = [(th, a, due, qid) for th, a, due, qid in threads
               if th.is_alive()]
    for th, a, due, qid in out:
        served.queue.cancel(qid, "the window was drained")
        q = served.client.query(a.query, a.sub, due, tenant=a.tenant,
                                qid=qid, fired=None, service_s=None)
        q.refused, q.t1 = "unfinished", limit
        done.append(q)
    for th, *_ in out:
        th.join(JOIN_SECONDS)
    still = sum(th.is_alive() for th, *_ in out)
    if out:
        say(phase="drain", unfinished=len(out), still_running=still)
    done.sort(key=lambda q: q.t1)
    return done, t_start, max([q.t1 for q in done] + [t_start + seconds])


def percentile(values, p: float) -> float:
    """Nearest-rank percentile of all the values."""
    xs = sorted(values)
    return xs[max(0, -(-len(xs) * p // 100) - 1)] if xs else float("nan")


def memory_peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def traced_slice(loop, trace_dir: str) -> dict:
    """A slice of whole queries, ``loop()``'s (queries, start, end), under
    the profiler (Python tracer off), the program's span log and a
    per-query trace scope for its retry counts."""
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
            queries, t0, t1 = loop()
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


def shapes_of(cell: Cell, args, slice_seconds: float):
    """What a run drives: the window's loop and the traced slice's, as
    functions of the client, and every (query, substitution set) that they
    use, which the warm-up runs once each.  A closed loop runs each query's
    validation values; an open one the sets its schedules draw."""
    if not cell.open:
        def closed(seed, stop):
            return lambda client: closed_loop(client, cell.traffic, seed,
                                              stop)
        return ([(q, {}) for q in cell.queries],
                closed(args.seed, lambda elapsed, n: elapsed >= args.seconds),
                closed(args.seed + 1, lambda elapsed, n: (
                    n >= SLICE_MIN_QUERIES and elapsed >= slice_seconds)))
    sets = traffic.draw_sets(cell.traffic, args.seed, cell.spaces)
    window = traffic.open_schedule(cell.traffic, args.seed, args.seconds,
                                   sets)
    sliced = (traffic.open_schedule(cell.traffic, args.seed + 1,
                                    slice_seconds, sets)
              if args.trace else [])
    shapes = {(a.query, traffic.sub_key(a.sub)): (a.query, a.sub)
              for a in window + sliced}

    def opened(schedule, seconds):
        def loop(client):
            from spark_rapids_tpu.serving.admission import LocalSessionRunner
            served = Served(client, LocalSessionRunner(
                dict(cell.config["session_conf"])))
            try:
                return open_loop(served, schedule, seconds)
            finally:
                served.close()
        return loop
    return (list(shapes.values()), opened(window, args.seconds),
            opened(sliced, slice_seconds))


def warm_up(client: Client, shapes: list, watch, tag: dict) -> int:
    """Each (query, substitution set) once, outside any loop; a query's
    first set is planned and looked at for CPU fallbacks.  Returns the
    number of fallback nodes found."""
    from benchmark.plan_check import fallback_nodes, plan_nodes
    fallbacks, planned = 0, set()
    for qname, sub in shapes:
        t, c0 = time.perf_counter(), watch.requests
        line = {}
        if qname not in planned:
            planned.add(qname)
            plan = client.frame(qname, sub).physical_plan()
            line["fallback_nodes"] = bad = fallback_nodes(plan)
            line["plan"] = plan_nodes(plan)
            fallbacks += len(bad)
        warm = client.run(qname, sub)
        say(phase="warm_up", **tag, query=qname, sub=sub, **line,
            error=warm.error, compiles=watch.requests - c0,
            seconds=time.perf_counter() - t)
    return fallbacks


def window_line(window: list, served: bool) -> dict:
    """What the window's progress line says of its queries: counts,
    latency from the due time, and for an open loop how late the generator
    ran, the service times, cache hits (answers that did not run), and
    refusals by reason and by tenant."""
    ok = [q for q in window if q.error is None and q.refused is None]
    lat = sorted(q.t1 - q.t0 for q in ok)
    line = {
        "queries": len(window), "failed": len(window) - len(ok),
        "latency_s": {"min": lat[0], "median": lat[len(lat) // 2],
                      "max": lat[-1]} if lat else None,
        "stalled": sum(x > STALL_FACTOR * lat[len(lat) // 2] for x in lat)}
    if not served:
        return line
    late = [q.fired - q.t0 for q in window if q.fired is not None]
    service = [q.service_s for q in ok if q.service_s is not None]
    refused, tenants = {}, {}
    for q in window:
        t = tenants.setdefault(q.tenant, {"arrivals": 0, "answered": 0,
                                          "cache_hits": 0, "refused": {}})
        t["arrivals"] += 1
        if q.refused:
            refused[q.refused] = refused.get(q.refused, 0) + 1
            t["refused"][q.refused] = t["refused"].get(q.refused, 0) + 1
        elif q.error is None:
            t["answered"] += 1
            t["cache_hits"] += q.service_s is None
    line.update(
        arrivals=len(window), answered=len(ok), executed=len(service),
        cache_hits=len(ok) - len(service), refused=refused,
        errors=sum(q.error is not None for q in window),
        lateness_s={"p50": percentile(late, 50), "p99": percentile(late, 99),
                    "max": max(late, default=float("nan"))},
        due_latency_s={"p50": percentile(lat, 50), "p95": percentile(lat, 95)},
        service_s={"p50": percentile(service, 50),
                   "p95": percentile(service, 95)},
        by_tenant=tenants)
    return line


def reference_answers(cell: Cell, files: dict, keys) -> dict:
    """The plain reference of each (query, substitution set) in ``keys``;
    each table's frame is read once, with the columns of all its
    queries."""
    columns = {}
    for mod in cell.queries.values():
        columns.setdefault(mod.TABLE, {}).update(dict.fromkeys(mod.COLUMNS))
    frames = {t: datagen.read_frame(files[t], list(c))
              for t, c in columns.items()}
    return {(qname, sk): cell.queries[qname].reference(
                frames[cell.queries[qname].TABLE], **dict(sk))
            for qname, sk in keys}


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
    dog = Watchdog()
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
        slice_seconds = min(SLICE_MIN_SECONDS, float(args.seconds))
        shapes, window_loop, slice_loop = shapes_of(cell, args, slice_seconds)
        fallbacks = warm_up(client, shapes, watch, tag)
        # the loops are watched; a warm-up that compiles is no stall
        client.watchdog = dog

        # -- the measured window
        c0 = watch.requests
        gcw = GcWatch()
        setup_s = time.perf_counter() - _T0
        window, w0, w1 = window_loop(client)
        window_s = w1 - w0
        compiles_in_window = watch.requests - c0
        peak = memory_peak_bytes(devices)
        ok = [q for q in window if q.error is None and q.refused is None]
        say(phase="window", **tag, seconds=window_s,
            **window_line(window, cell.open),
            stall_dumps=dog.dumps, compiles_in_window=compiles_in_window,
            gc_s=gcw.seconds, gc_longest_s=gcw.longest,
            memory_peak_bytes=peak)

        sliced, digest = None, None
        if args.trace:
            sliced = traced_slice(lambda: slice_loop(client), trace_dir)
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
        answers = [(q.key(), q.answer) for q in every
                   if q.error is None and q.refused is None]
        errors = [q.error for q in every if q.error is not None]
        refused = sum(q.refused is not None for q in every)
        client = None
        gc.collect()
        t = time.perf_counter()
        references = reference_answers(cell, files,
                                       dict.fromkeys(k for k, _ in answers))
        compared = compare.compare(
            answers, references, cell.config["limits"], fallbacks,
            len(errors), {qname for qname, mod in cell.queries.items()
                          if getattr(mod, "ORDERED", False)})
        say(phase="reference", **tag, answers=len(answers),
            references=len(references), seconds=time.perf_counter() - t,
            first_error=errors[:1])
    finally:
        dog.close()
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
            slice_queries=[q for q in sliced["queries"]
                           if q.error is None and q.refused is None
                           and q.service_s is not None],
            slice_interval=sliced["interval"], spans=sliced["spans"],
            slice_launches=sliced["launches"], slice_oom=sliced["oom"],
            slice_retries=sliced["retries"])
        metrics = read_per_layer(cell, readers, ctx, rehearsal, tag)
        breakdown = {"device_ops": digest["device_ops"],
                     "idle_gaps": digest["idle_gaps"]}
    result = {
        "correct": compare.is_correct(compared),
        "attempted": len(every), "failed": len(errors) + refused,
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
