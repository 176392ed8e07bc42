"""One span tree per ``collect()``: every layer's span is recorded where its
work happens, names its parent up to ``query.collect``, shares the query's id,
and covers its own work and not its child's (the engine is a pull model).

q6- and q1-shaped queries (testing/tpch.py) over two small Parquet files, on
the CPU backend; times here are only compared with each other.
"""
import ast
import os
import subprocess
import sys
import time
import types

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.plan.execs.base import (launch_stats,
                                              reset_launch_stats)
from spark_rapids_tpu.testing import tpch
from spark_rapids_tpu.utils import obs, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, ROW_GROUP = 24_000, 4_096
CONF = {"spark.rapids.sql.enabled": "true",
        "spark.rapids.sql.batchSizeRows": str(ROW_GROUP),
        "spark.rapids.sql.reader.batchSizeRows": str(ROW_GROUP)}

Q6_SPANS = ("query.collect", "query.plan", "query.finish", "query.fetch",
            "scan.open", "scan.decode", "scan.wait", "scan.upload",
            "upload.put", "fused.batch", "fused.feedback")
Q1_SPANS = Q6_SPANS + ("exchange.write", "exchange.read", "sort.range")


def write_lineitem(root: str) -> list:
    """testing/tpch.py's lineitem as two Parquet files of three row groups."""
    from spark_rapids_tpu.columnar.arrow import (
        decimal_array_from_unscaled, sql_type_to_arrow)
    paths = []
    chunks = tpch.lineitem_host_chunks(ROWS, seed=11, batch_rows=ROWS // 2)
    for i, cols in enumerate(chunks):
        arrays = {}
        for name, dt in zip(tpch.LINEITEM_SCHEMA.names,
                            tpch.LINEITEM_SCHEMA.dtypes):
            if isinstance(dt, T.DecimalType):
                arrays[name] = decimal_array_from_unscaled(
                    cols[name], dt.precision, dt.scale, None)
            elif isinstance(dt, T.DateType):
                arrays[name] = pa.array(cols[name], type=pa.int32()).cast(
                    sql_type_to_arrow(dt))
            else:
                arrays[name] = pa.array(cols[name],
                                        type=sql_type_to_arrow(dt))
        paths.append(os.path.join(root, f"lineitem-{i}.parquet"))
        pq.write_table(pa.table(arrays), paths[-1],
                       row_group_size=ROW_GROUP)
    return paths


def build(sess, paths, query: str):
    df = sess.read_parquet(*paths)
    if query == "q6":
        return tpch.q6(df)
    return tpch.q1(df).order_by("l_linenumber")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """query -> (QueryTrace of one traced collect(), launch_stats after it,
    span_log snapshot), each query warmed up first."""
    paths = write_lineitem(str(tmp_path_factory.mktemp("lineitem")))
    sess = TpuSession(dict(CONF))
    out = {}
    for query in ("q6", "q1"):
        df = build(sess, paths, query)
        assert df.collect()
        assert sess.last_query_trace is None    # no sink on, no trace
        reset_launch_stats()
        tracing.span_log.clear()
        tracing.span_log.enabled = True
        try:
            assert df.collect()
        finally:
            tracing.span_log.enabled = False
        out[query] = (sess.last_query_trace, launch_stats(),
                      tracing.span_log.snapshot())
    return out


def ancestors(span: dict, by_id: dict) -> list:
    names = []
    while span.get("parent") is not None:
        span = by_id[span["parent"]]
        names.append(span["name"])
    return names


@pytest.mark.parametrize("query,name", [("q6", n) for n in Q6_SPANS]
                         + [("q1", n) for n in Q1_SPANS])
def test_span_is_recorded_under_the_query_root(traced, query, name):
    trace = traced[query][0]
    assert trace.query_id.startswith("collect-") and not trace.dropped_spans
    spans = trace.spans_snapshot()
    by_id = {s["id"]: s for s in spans}
    assert len(by_id) == len(spans), "span ids repeat"
    roots = [s for s in spans if s["parent"] is None]
    assert [s["name"] for s in roots] == ["query.collect"]
    mine = [s for s in spans if s["name"] == name]
    assert mine, f"{name} was never recorded in {query}"
    for s in mine:
        chain = ancestors(s, by_id)
        assert (chain[-1:] == ["query.collect"]
                or s["name"] == "query.collect"), (name, chain)
    # the log a metric reader sees holds the same spans, as 3-tuples
    logged = [n for n, _t0, _t1 in traced[query][2]]
    assert logged.count(name) == len(mine)


def test_each_collect_has_a_query_id_of_its_own(traced):
    assert traced["q6"][0].query_id != traced["q1"][0].query_id


def test_where_each_span_sits(traced):
    spans = traced["q1"][0].spans_snapshot()
    by_id = {s["id"]: s for s in spans}
    parents = {(s["name"], by_id[s["parent"]]["name"])
               for s in spans if s["parent"] is not None}
    assert ("scan.open", "scan.decode") in parents
    assert ("fused.feedback", "fused.batch") in parents
    assert ("query.plan", "query.collect") in parents
    assert ("query.fetch", "query.collect") in parents
    # a file is opened once, inside the first decode of its partition
    assert sum(s["name"] == "scan.open" for s in spans) == 2


@pytest.mark.parametrize("name", ["exchange.write", "exchange.read",
                                  "sort.range", "fused.batch"])
def test_span_is_self_time_not_the_childs(traced, name):
    """A span that stayed open while its layer pulled from its child would
    contain the child's uploads, which run on the same task thread."""
    spans = traced["q1"][0].spans_snapshot()
    uploads = [s for s in spans if s["name"] == "scan.upload"]
    assert uploads
    for s in (s for s in spans if s["name"] == name):
        for u in uploads:
            if u["thread"] == s["thread"]:
                assert u["t1"] <= s["t0"] or u["t0"] >= s["t1"], (s, u)


def test_launches_by_program_sum_to_launches(traced):
    for query, kinds in (("q6", {"fused_agg_mfilter", "agg_combine"}),
                         ("q1", {"fused_agg_mfilter_slice", "agg_combine",
                                 "sort_local"})):
        stats = traced[query][1]
        assert sum(stats["by_program"].values()) == stats["launches"] > 0
        assert {n.rpartition("_")[0] for n in stats["by_program"]} == kinds


def test_scan_decode_excludes_the_wait_on_a_full_queue():
    """The producer blocks on ``q.put`` while the consumer stalls; that wait
    is the consumer's pace and must not read as decode time."""
    from spark_rapids_tpu.io.reader_pool import prefetched
    stall = 0.25
    tracing.span_log.clear()
    tracing.span_log.enabled = True
    try:
        got = []
        for item in prefetched(lambda: iter(range(6)), 2, capacity=1):
            got.append(item)
            time.sleep(stall)
    finally:
        tracing.span_log.enabled = False
    assert got == list(range(6))
    decodes = [t1 - t0 for n, t0, t1 in tracing.span_log.snapshot()
               if n == "scan.decode"]
    assert len(decodes) == 7                    # six items and the end
    assert max(decodes) < stall / 5, decodes


def test_a_scan_puts_two_planes_a_fixed_width_column_and_three_a_string(
        tmp_path):
    """``upload.put`` is one hand-over of a host plane to the runtime: data
    and validity, and a string's offsets, nested in the batch's
    ``scan.upload``; with the log off nothing is written."""
    groups, rows = 3, 1000
    path = os.path.join(str(tmp_path), "t.parquet")
    pq.write_table(pa.table({
        "k": pa.array(range(groups * rows), type=pa.int64()),
        "s": pa.array([f"v{i % 7}" for i in range(groups * rows)])}),
        path, row_group_size=rows)
    sess = TpuSession({"spark.rapids.sql.enabled": "true",
                       "spark.rapids.sql.batchSizeRows": str(rows),
                       "spark.rapids.sql.reader.batchSizeRows": str(rows)})
    df = sess.read_parquet(path)
    tracing.span_log.clear()
    assert len(df.collect()) == groups * rows
    assert tracing.span_log.snapshot() == []
    tracing.span_log.enabled = True
    try:
        assert len(df.collect()) == groups * rows
    finally:
        tracing.span_log.enabled = False
    spans = tracing.span_log.snapshot()
    uploads = [(t0, t1) for n, t0, t1 in spans if n == "scan.upload"]
    puts = [(t0, t1) for n, t0, t1 in spans if n == "upload.put"]
    assert len(uploads) == groups
    assert len(puts) == (2 + 3) * groups
    for t0, t1 in puts:
        assert sum(u0 <= t0 and t1 <= u1 for u0, u1 in uploads) == 1
    for u0, u1 in uploads:
        assert sum(u0 <= t0 and t1 <= u1 for t0, t1 in puts) == 2 + 3


@pytest.mark.parametrize("name", ["upload.put", "host.lock_wait"])
def test_the_host_spans_are_registered_and_documented(name):
    assert name in tracing.static_ranges()
    with open(os.path.join(REPO, "docs", "trace_ranges.md")) as f:
        assert f"| `{name}` |" in f.read()


def test_upload_put_is_a_child_of_scan_upload_in_the_query_trace(traced):
    spans = traced["q6"][0].spans_snapshot()
    by_id = {s["id"]: s for s in spans}
    puts = [s for s in spans if s["name"] == "upload.put"]
    assert puts
    for s in puts:
        assert by_id[s["parent"]]["name"] == "scan.upload"
        assert s["thread"] == by_id[s["parent"]]["thread"]
    # the sampler's span is written after the fact, outside any query
    assert not [s for s in spans if s["name"] == "host.lock_wait"]


def test_span_log_is_a_bounded_ring():
    log = tracing.SpanLog(capacity=4)
    log.enabled = True
    for i in range(10):
        log.record(f"s{i}", i, i + 1)
    assert log.snapshot() == [(f"s{i}", i, i + 1) for i in range(6, 10)]


def test_worker_spans_descend_from_the_spawning_threads_span():
    from spark_rapids_tpu.utils.ambient import spawn_with_ambients
    trace = obs.QueryTrace("t")

    def work():
        with tracing.trace_range("scan.decode"):
            pass

    with obs.trace_scope(trace), tracing.trace_range("scan.wait"):
        t = spawn_with_ambients(work)
        t.join(timeout=30)
        assert not t.is_alive()
    wait, = (s for s in trace.spans_snapshot() if s["name"] == "scan.wait")
    decode, = (s for s in trace.spans_snapshot()
               if s["name"] == "scan.decode")
    assert decode["parent"] == wait["id"] and wait["parent"] is None
    assert decode["thread"] != wait["thread"]
    # and the export ties the two threads together with a flow arrow
    from tools.trace_export import trace_events
    flows = [e for e in trace_events(trace) if e["ph"] in ("s", "f")]
    assert sorted(e["ph"] for e in flows) == ["f", "s"]
    assert {e["id"] for e in flows} == {decode["id"]}


_NAMES_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.plan.execs.base import launch_stats
from spark_rapids_tpu.testing import tpch
sess = TpuSession({{"spark.rapids.sql.enabled": "true"}})
df = sess.create_dataframe(tpch.gen_lineitem(2048, seed=3), num_partitions=2)
tpch.q6(df).collect()
tpch.q1(df).order_by("l_linenumber").collect()
print(json.dumps(sorted(launch_stats()["by_program"])))
"""


def test_program_names_are_the_same_in_every_process():
    """A name salted per process (``hash()``) would change the persistent
    compile cache's key, and every run would compile cold."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _NAMES_SCRIPT.format(repo=REPO)],
        env=dict(env, PYTHONHASHSEED=seed), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True) for seed in ("1", "2")]
    outs = [p.communicate(timeout=600) for p in procs]
    for p, (_out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-2000:]
    names = [out.strip().splitlines()[-1] for out, _err in outs]
    assert names[0] == names[1]
    assert "fused_agg_mfilter" in names[0] and "sort_local" in names[0]


def test_drift_lint_sees_span_names_passed_to_timed():
    from tools.tpulint import drift
    src = ('from spark_rapids_tpu.plan.execs.base import timed\n'
           'with timed(m, "exchange.write"):\n    pass\n'
           'with timed(m, "not.registered"):\n    pass\n'
           'with timed(m, span="nor.this"):\n    pass\n')
    fake = types.SimpleNamespace(path="spark_rapids_tpu/fake.py",
                                 tree=ast.parse(src))
    found = [v for v in drift._check_trace_ranges(REPO, [fake])
             if "not registered" in str(v) or "_STATIC_RANGES" in str(v)]
    text = " ".join(str(v) for v in found)
    assert len(found) == 2 and "not.registered" in text and "nor.this" in text


def test_trace_ranges_doc_matches_the_registry():
    from tools.tpulint import drift
    assert drift._check_trace_ranges(REPO) == []
