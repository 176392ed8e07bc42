"""``q18_inner_parquet_sf1`` rehearsed on the CPU (``run.run`` with the look
for a chip skipped): ``correct`` at one batch and at several, where the plan
is the fused one over an exchange; the three readers this cell brought, on
hand-written spans and in the line of a traced run; and the two controls
that stand where the float32 control cannot (``q18_control_readings.py``)
coming out as not ``correct``."""
import json
import types

import pytest

from benchmark import datagen, run
from benchmark.tests import q18_control_readings as controls
from benchmark.tests.test_span_readers import S, ctx, reader

CELL = "q18_inner_parquet_sf1"
NEW = ("agg_final_ms_per_query", "agg_out_of_core_groups_per_query",
       "discarded_launches_per_query")


def _args(rows, trace=0, seed=2**31 + 5, seconds=0.1):
    return types.SimpleNamespace(workload=CELL, seed=seed, seconds=seconds,
                                 trace=trace, rows=rows)


def test_the_cell_is_data_and_lists_its_metrics():
    cell = run.load_cell(CELL)
    assert cell.chips == 1 and list(cell.queries) == ["q18_inner"]
    assert cell.config["reduced"] == {} and \
        cell.config["tables"] == run.load_cell(
            "q1_parquet_sf1").config["tables"]
    assert {m["name"] for m in cell.end_to_end} == {"rows_per_s", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    # 19 when the cell came; later PRs add readers
    assert len(names) >= 19 and set(NEW) <= set(names)
    assert "range_sort_ms_per_query" not in names
    for other in ("q6_parquet_sf10", "q6_parquet_sf1", "q1_parquet_sf1"):
        assert set(NEW).isdisjoint(
            m["name"] for m in run.load_cell(other).per_layer)


@pytest.mark.parametrize("rows,nodes", [
    (65_536, {"TpuHashAggregate", "TpuFilter"}),
    (3 * 1_048_576 + 17, {"TpuFusedSegment", "TpuShuffleExchange"})])
def test_the_rehearsal_is_correct_and_the_plan_is_the_cells(rows, nodes,
                                                             capfd):
    r = run.run(_args(rows), rehearsal=True)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert r["compared"]["float_gap"]["value"] == 0.0
    assert set(r["metrics"]) == {"rows_per_s", "setup_s"}
    warm = [json.loads(ln) for ln in capfd.readouterr().err.splitlines()
            if ln.startswith('{"phase": "warm_up"')]
    assert nodes <= set(warm[0]["plan"]), warm[0]["plan"]
    assert warm[0]["fallback_nodes"] == []


def test_a_traced_rehearsal_reports_the_three_new_metrics():
    r = run.run(_args(3 * 1_048_576 + 17, trace=1), rehearsal=True)
    assert r["correct"] is True, r["compared"]
    got = {n: r["metrics"][n]["value"] for n in NEW}
    assert got["agg_final_ms_per_query"] > 0
    # warmed up: every reduce group in core, nothing discarded; 0 and not
    # left out, since the program has the spans
    assert got["agg_out_of_core_groups_per_query"] == 0
    assert got["discarded_launches_per_query"] == 0
    assert {"exchange_ms_per_query", "fused_host_ms_per_batch",
            "scan_open_ms_per_query", "scan_decode_s_per_query",
            "launches_per_query"} <= set(r["metrics"])


SPANS = [
    ("agg.final", S + 1.0, S + 1.004), ("agg.final", S + 1.1, S + 1.102),
    ("batch.shrink", S + 1.2, S + 1.260), ("agg.final", S + 6.0, S + 6.006),
    ("batch.shrink", S + 6.1, S + 6.140),
    ("agg.out_of_core", S + 2.0, S + 2.5), ("fused.discard", S + 0.5, S + 0.9),
    ("fused.discard", S + 0.9, S + 1.0), ("fused.discard", S + 5.0, S + 5.3),
    # before the slice: counted by none
    ("agg.final", S - 2.0, S - 1.0), ("agg.out_of_core", S - 2.0, S - 1.5),
    ("fused.discard", S - 3.0, S - 2.5), ("batch.shrink", S - 1.0, S - 0.5),
]
WANT = {"agg_final_ms_per_query": (4 + 2 + 60 + 6 + 40) / 2,
        "agg_out_of_core_groups_per_query": 1 / 2,
        "discarded_launches_per_query": 3 / 2}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_hand_written_spans(name):
    assert reader(name).read(ctx(SPANS)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_without_its_spans_or_without_a_query(name, monkeypatch):
    """A count reads 0 where the program has the span and it never fired;
    every reader reads None where the program has no such span (the parent
    of the PR that added them) or the slice completed no query."""
    other = [("scan.wait", S + 0.0, S + 0.5)]
    want = None if name == "agg_final_ms_per_query" else 0
    assert reader(name).read(ctx(other)) == want
    assert reader(name).read(ctx(SPANS, queries=0)) is None
    from spark_rapids_tpu.utils import tracing
    monkeypatch.setattr(tracing, "static_ranges", lambda: {"fused.batch": ""})
    assert reader(name).read(ctx(other)) is None


def test_only_spans_the_profiler_has_name_idle_gaps():
    assert reader("agg_final_ms_per_query").SPANS == ("agg.final",
                                                      "batch.shrink")
    assert reader("agg_out_of_core_groups_per_query").SPANS == \
        ("agg.out_of_core",)
    assert not hasattr(reader("discarded_launches_per_query"), "SPANS")


# -- the controls: each has to come out as not correct ----------------------

@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
@pytest.mark.parametrize("control", list(controls.CONTROLS))
def test_the_control_is_not_correct(tmp_path, control, seed):
    """At four row groups of 65,536 rows, with QUANTITY lowered to 250 so
    that each row group holds some tens of orders over it (over 300, one
    row group in two holds none at this size)."""
    mod = run.load_module("queries", controls.QUERY)
    tmod = run.load_module("tables", mod.TABLE)
    group, rows = 65_536, 4 * 65_536
    files = datagen.write_table(str(tmp_path), tmod, mod.TABLE, rows, 2,
                                group, seed, rows / 6_001_215)
    frame = datagen.read_frame(files, mod.COLUMNS)
    config = run.load_cell(CELL).config
    r = controls.reading(control, mod, frame, group, config["limits"],
                         quantity=250.0)
    assert r["correct"] is False and r["answers_wrong"] == 1, r
    assert r["float_gap"] == 0.0 and r["rows_got"] != r["rows_want"]
    # and the reference in the program's place is correct
    got = mod.reference(frame)
    key = (controls.QUERY, ())
    ok = controls.compare.compare([(key, got)],
                                  {key: mod.reference(frame)},
                                  config["limits"], 0, 0)
    assert controls.compare.is_correct(ok)


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
def test_the_float32_control_cannot_fail_this_cell(tmp_path, seed):
    """Sums of at most seven whole numbers under 51 are exact in float32:
    the reference in float32 equals the reference, ``float_gap`` 0."""
    mod = run.load_module("queries", controls.QUERY)
    tmod = run.load_module("tables", mod.TABLE)
    files = datagen.write_table(str(tmp_path), tmod, mod.TABLE, 262_144, 2,
                                65_536, seed, 262_144 / 6_001_215)
    want = mod.reference(datagen.read_frame(files, mod.COLUMNS), 0.0)
    got = mod.reference(datagen.read_frame(files, mod.COLUMNS, "float32"),
                        0.0)
    assert controls.compare.answer_gap(got, want) == (True, 0.0)
