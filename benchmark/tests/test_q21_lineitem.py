"""``q21_lineitem_parquet_sf1`` rehearsed on the CPU (``run.run`` with the
look for a chip skipped): ``correct`` at one batch and at several, where the
plan holds both joins and three exchanges under the cell's conf scaled to
the rows; the plain reference against a brute-force nested loop; the three
readers this cell brought, on hand-written spans and in the line of a traced
run; and the three controls that stand where the float32 control cannot
(``q21_control_readings.py``) coming out as not ``correct``."""
import copy
import json
import types

import pytest

from benchmark import datagen, run
from benchmark.tests import q21_control_readings as controls
from benchmark.tests.test_span_readers import S, ctx, reader

CELL = "q21_lineitem_parquet_sf1"
NEW = ("join_ms_per_query", "join_retries_per_query",
       "join_out_of_core_per_query")
SF1_ROWS = 6_001_215


def _args(rows, trace=0, seed=2**31 + 5, seconds=0.1):
    return types.SimpleNamespace(workload=CELL, seed=seed, seconds=seconds,
                                 trace=trace, rows=rows)


def scaled_cell(rows, batch_rows):
    """The cell with its batch capacity at ``batch_rows`` and the
    broadcast threshold (500,000 rows at SF1's 6,001,215) scaled to
    ``rows``: the plan's shape is then the cell's own."""
    cell = run.load_cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.config["tables"]["lineitem"]["row_group_rows"] = batch_rows
    cell.config["session_conf"].update({
        "spark.rapids.sql.batchSizeRows": str(batch_rows),
        "spark.rapids.sql.reader.batchSizeRows": str(batch_rows),
        "spark.rapids.sql.join.broadcastRowThreshold":
        str(500_000 * rows // SF1_ROWS)})
    return cell


def test_the_cell_is_data_and_lists_its_metrics():
    cell = run.load_cell(CELL)
    assert cell.chips == 1 and list(cell.queries) == ["q21_lineitem"]
    assert cell.config["reduced"] == {} and \
        cell.config["tables"] == run.load_cell(
            "q1_parquet_sf1").config["tables"]
    assert cell.config["session_conf"] == run.load_cell(
        "q1_parquet_sf1").config["session_conf"]
    assert {m["name"] for m in cell.end_to_end} == {"rows_per_s", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW) <= set(names)
    assert {"exchange_ms_per_query", "range_sort_ms_per_query",
            "agg_final_ms_per_query", "discarded_launches_per_query",
            "fused_host_ms_per_batch"} <= set(names)
    assert "agg_out_of_core_groups_per_query" not in names
    for other in ("q6_parquet_sf10", "q6_parquet_sf1", "q1_parquet_sf1",
                  "q18_inner_parquet_sf1"):
        assert set(NEW).isdisjoint(
            m["name"] for m in run.load_cell(other).per_layer)
    mod = cell.queries["q21_lineitem"]
    assert mod.ORDERED is True and mod.COLUMNS == (
        "l_orderkey", "l_suppkey", "l_commitdate", "l_receiptdate")
    from benchmark import cost
    assert cost.bytes_per_row(mod, cell.tables["lineitem"]) == 24


def _warm_up(capfd):
    return [json.loads(ln) for ln in capfd.readouterr().err.splitlines()
            if ln.startswith('{"phase": "warm_up"')][0]


def test_the_rehearsal_at_one_batch_is_correct(capfd):
    r = run.run(_args(65_536), rehearsal=True)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert r["compared"]["float_gap"]["value"] == 0.0
    assert set(r["metrics"]) == {"rows_per_s", "setup_s"}
    warm = _warm_up(capfd)
    assert warm["fallback_nodes"] == []
    assert sum("Join" in n for n in warm["plan"]) == 2, warm["plan"]


@pytest.mark.parametrize("rows,batch_rows", [
    (2 * 16_384 + 17, 16_384), (2 * 1_048_576 + 17, 1_048_576)],
    ids=["small_batches", "the_cells_batches"])
def test_the_rehearsal_at_several_batches_has_the_cells_plan(rows,
                                                             batch_rows,
                                                             capfd):
    """Two full batches and one of 17 rows, the threshold scaled to the
    rows: the semi-join is planned shuffled (two exchanges), the anti-join
    adaptive (its two exchanges are built at run time), the aggregate is
    across a third planned exchange."""
    r = run.run(_args(rows), rehearsal=True,
                cell=scaled_cell(rows, batch_rows))
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert r["compared"]["answers_wrong"]["value"] == 0
    warm = _warm_up(capfd)
    assert warm["fallback_nodes"] == []
    plan = warm["plan"]
    assert {"TpuAdaptiveJoin", "TpuShuffledHashJoin", "TpuRangeSort",
            "TpuHashAggregate", "TpuFusedSegment"} <= set(plan), plan
    assert plan.count("TpuShuffleExchange") == 3, plan
    assert plan.index("TpuAdaptiveJoin") < plan.index("TpuShuffledHashJoin")


def test_a_traced_rehearsal_reports_the_three_new_metrics():
    rows = 2 * 16_384 + 17
    r = run.run(_args(rows, trace=1), rehearsal=True,
                cell=scaled_cell(rows, 16_384))
    assert r["correct"] is True, r["compared"]
    got = {n: r["metrics"][n]["value"] for n in NEW}
    assert got["join_ms_per_query"] > 0
    # warmed up, every guess held and every reduce group in core; 0 and
    # not left out, since the program has the spans
    assert got["join_retries_per_query"] == 0
    assert got["join_out_of_core_per_query"] == 0
    assert {"exchange_ms_per_query", "range_sort_ms_per_query",
            "agg_final_ms_per_query", "discarded_launches_per_query",
            "launches_per_query", "device_idle_pct"} <= set(r["metrics"])
    assert r["breakdown"]["device_ops"]


# -- the reference against a nested loop ------------------------------------

@pytest.mark.parametrize("seed", [3, 2**31 + 4])
def test_the_reference_equals_a_nested_loop(tmp_path, seed):
    """2,000 rows, the spec's SQL read literally: for each late line, scan
    the table for another supplier's line of its order (EXISTS) and for
    another supplier's late line (NOT EXISTS)."""
    mod = run.load_module("queries", controls.QUERY)
    tmod = run.load_module("tables", mod.TABLE)
    # the scale factor of 60,000 rows: 100 suppliers, not 4
    files = datagen.write_table(str(tmp_path), tmod, mod.TABLE, 2_000, 2,
                                512, seed, 0.01)
    frame = datagen.read_frame(files, mod.COLUMNS)
    lines = list(zip(*(frame[c].tolist() for c in mod.COLUMNS)))
    numwait = {}
    for o1, s1, c1, r1 in lines:
        if not r1 > c1:
            continue
        exists = any(o2 == o1 and s2 != s1 for o2, s2, _, _ in lines)
        late_other = any(o3 == o1 and s3 != s1 and r3 > c3
                         for o3, s3, c3, r3 in lines)
        if exists and not late_other:
            numwait[s1] = numwait.get(s1, 0) + 1
    want = sorted(numwait.items(), key=lambda r: (-r[1], r[0]))
    assert len(want) > 20
    assert mod.reference(frame) == want


# -- the readers -------------------------------------------------------------

SPANS = [
    ("join.build", S + 1.0, S + 1.002), ("join.probe", S + 1.002, S + 1.052),
    ("join.decide", S + 2.0, S + 2.030), ("join.build", S + 6.0, S + 6.004),
    ("join.probe", S + 6.1, S + 6.114),
    ("join.retry", S + 1.01, S + 1.03), ("join.retry", S + 6.1, S + 6.11),
    ("join.retry", S + 6.11, S + 6.112),
    ("join.out_of_core", S + 3.0, S + 3.5),
    # before the slice: counted by none
    ("join.probe", S - 2.0, S - 1.0), ("join.retry", S - 2.0, S - 1.5),
    ("join.out_of_core", S - 3.0, S - 2.5), ("join.decide", S - 1.0, S - 0.5),
]
WANT = {"join_ms_per_query": (2 + 50 + 30 + 4 + 14) / 2,
        "join_retries_per_query": 3 / 2,
        "join_out_of_core_per_query": 1 / 2}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_hand_written_spans(name):
    assert reader(name).read(ctx(SPANS)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_without_its_spans_or_without_a_query(name, monkeypatch):
    """A count reads 0 where the program has the span and it never fired;
    every reader reads None where the program has no such span (the parent
    of the PR that added them) or the slice completed no query."""
    other = [("scan.wait", S + 0.0, S + 0.5)]
    want = None if name == "join_ms_per_query" else 0
    assert reader(name).read(ctx(other)) == want
    assert reader(name).read(ctx(SPANS, queries=0)) is None
    from spark_rapids_tpu.utils import tracing
    monkeypatch.setattr(tracing, "static_ranges", lambda: {"fused.batch": ""})
    assert reader(name).read(ctx(other)) is None


def test_only_spans_the_profiler_has_name_idle_gaps():
    assert reader("join_ms_per_query").SPANS == (
        "join.build", "join.probe", "join.decide")
    assert reader("join_out_of_core_per_query").SPANS == \
        ("join.out_of_core",)
    assert not hasattr(reader("join_retries_per_query"), "SPANS")


# -- the controls: each has to come out as not correct ----------------------

@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
@pytest.mark.parametrize("control", list(controls.CONTROLS))
def test_the_control_is_not_correct(tmp_path, control, seed):
    """At 32 row groups of 8,192 rows: 31 boundaries that cut an order,
    where the cell has five."""
    mod = run.load_module("queries", controls.QUERY)
    tmod = run.load_module("tables", mod.TABLE)
    group, rows = 8_192, 32 * 8_192
    files = datagen.write_table(str(tmp_path), tmod, mod.TABLE, rows, 2,
                                group, seed, rows / SF1_ROWS)
    frame = datagen.read_frame(files, mod.COLUMNS)
    config = run.load_cell(CELL).config
    r = controls.reading(control, mod, frame, group, config["limits"])
    assert r["correct"] is False and r["answers_wrong"] == 1, r
    assert r["float_gap"] == 0.0 and r["rows_that_differ"] > 0
    if control == "anti_join_without_its_condition":
        assert r["rows_got"] == 0 < r["rows_want"]
    # and the reference in the program's place is correct
    key = (controls.QUERY, ())
    ok = controls.compare.compare(
        [(key, mod.reference(frame))],
        {key: mod.reference(frame)}, config["limits"], 0, 0,
        {controls.QUERY})
    assert controls.compare.is_correct(ok)
