"""The readers of the program's own spans (``metrics/*_per_query.py``,
``fused_host_ms_per_batch.py``) on hand-written spans: the arithmetic of
each, ``None`` where its span was never recorded (a program without the
span, as the parent of the PR that added them), and all of them in the line
of a traced run of the engine on the CPU."""
import types

import pytest

from benchmark import run

S = 100.0       # the slice starts here on the spans' clock


def ctx(spans, queries=2, interval=(S, S + 10.0)):
    return types.SimpleNamespace(
        spans=spans, slice_interval=interval,
        slice_queries=[object()] * queries)


def reader(name):
    return run.load_module("metrics", name)


SPANS = [
    ("query.plan", S + 0.0, S + 0.004), ("query.plan", S + 5.0, S + 5.006),
    # two files opening at once, then one alone
    ("scan.open", S + 0.010, S + 0.030), ("scan.open", S + 0.020, S + 0.050),
    ("scan.open", S + 5.010, S + 5.020),
    ("scan.decode", S + 0.010, S + 0.510), ("scan.decode", S + 0.020, S + 0.320),
    ("scan.decode", S + 5.0, S + 5.2),
    ("fused.batch", S + 1.0, S + 1.030), ("fused.feedback", S + 1.005, S + 1.029),
    ("fused.batch", S + 2.0, S + 2.010), ("fused.feedback", S + 2.002, S + 2.009),
    ("fused.batch", S + 6.0, S + 6.020), ("fused.feedback", S + 6.001, S + 6.016),
    ("exchange.write", S + 3.0, S + 3.002), ("exchange.write", S + 7.0, S + 7.004),
    ("exchange.read", S + 3.5, S + 3.501), ("exchange.read", S + 7.5, S + 7.503),
    ("sort.range", S + 4.0, S + 4.008), ("sort.range", S + 4.1, S + 4.102),
    ("sort.range", S + 8.0, S + 8.010),
    ("query.fetch", S + 4.5, S + 4.503), ("query.fetch", S + 9.0, S + 9.005),
    ("query.finish", S + 4.4, S + 4.45), ("query.finish", S + 8.9, S + 8.93),
    # before the slice: counted by none
    ("query.plan", S - 3.0, S - 2.0), ("scan.decode", S - 3.0, S - 1.0),
    ("fused.batch", S - 1.5, S - 1.0), ("query.fetch", S - 0.5, S - 0.4),
    ("scan.wait", S + 0.0, S + 0.5), ("scan.upload", S + 0.5, S + 0.6),
]

WANT = {
    "plan_ms_per_query": (4 + 6) / 2,
    "scan_open_ms_per_query": (40 + 10) / 2,            # a union
    "scan_decode_s_per_query": (0.5 + 0.3 + 0.2) / 2,   # a sum over threads
    "fused_host_ms_per_batch": ((30 + 10 + 20) - (24 + 7 + 15)) / 3,
    "exchange_ms_per_query": (2 + 4 + 1 + 3) / 2,
    "range_sort_ms_per_query": (8 + 2 + 10) / 2,
    "result_fetch_ms_per_query": (3 + 5) / 2,
    "query_finish_ms_per_query": (50 + 30) / 2,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_hand_written_spans(name):
    assert reader(name).read(ctx(SPANS)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_where_the_span_was_never_recorded(name):
    """The parent commit has ``scan.wait``/``scan.upload`` and none of the
    new spans: the metric is left out of the line, and nothing raises."""
    old = [s for s in SPANS if s[0] in ("scan.wait", "scan.upload")]
    assert reader(name).read(ctx(old)) is None
    assert reader(name).read(ctx([])) is None


@pytest.mark.parametrize("name", ["plan_ms_per_query",
                                  "scan_decode_s_per_query",
                                  "exchange_ms_per_query",
                                  "range_sort_ms_per_query",
                                  "result_fetch_ms_per_query",
                                  "query_finish_ms_per_query",
                                  "scan_open_ms_per_query"])
def test_per_query_reader_without_a_completed_query(name):
    assert reader(name).read(ctx(SPANS, queries=0)) is None


def test_gaps_are_named_only_after_spans_on_the_thread_the_device_waits_for():
    """``scan.open``/``scan.decode`` run beside ``scan.wait`` on the reader
    pool and would take over the gaps it names; ``query.collect`` covers
    every gap."""
    named = {s for name in WANT for s in getattr(reader(name), "SPANS", ())}
    assert named == {"query.plan", "fused.batch", "exchange.write",
                     "exchange.read", "sort.range", "query.fetch",
                     "query.finish"}


def test_a_traced_run_of_the_engine_reports_all_eight():
    # several batches from two files: the fused plan with its exchange and
    # range sort, as in the cell (one batch plans neither)
    args = types.SimpleNamespace(workload="q1_parquet_sf1", seed=2**31 + 9,
                                 seconds=0.1, trace=1,
                                 rows=3 * 1_048_576 + 17)
    r = run.run(args, rehearsal=True)
    assert r["correct"] is True, r["compared"]
    assert set(WANT) <= set(r["metrics"]), sorted(r["metrics"])
    for name in WANT:
        assert r["metrics"][name]["value"] > 0, name
    # and every metric the benchmark had before still reads
    assert {"queries_completed", "scan_wait_pct", "scan_upload_pct",
            "launches_per_query", "compiles_in_window", "oom_retries",
            "device_idle_pct"} <= set(r["metrics"])
    cell = run.load_cell("q6_parquet_sf1")
    assert {"exchange_ms_per_query", "range_sort_ms_per_query"}.isdisjoint(
        m["name"] for m in cell.per_layer)
