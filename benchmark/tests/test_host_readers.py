"""The three readers of what the host does under the scan
(``scan_put_ms_per_batch``, ``scan_stage_ms_per_batch``,
``host_lock_wait_pct``) on hand-written spans: the arithmetic of each, that
the first two partition ``scan.upload``, ``None`` on a program that does not
name the span (the parent of the PR that added them), the 0.0 of a registered
span that never fired, and all three in the line of a traced run of the
engine on the CPU."""
import types

import pytest

from benchmark import run
from spark_rapids_tpu.utils import tracing

S = 100.0       # the slice starts here on the spans' clock
NAMES = ("scan_put_ms_per_batch", "scan_stage_ms_per_batch",
         "host_lock_wait_pct")


def ctx(spans, queries=2, interval=(S, S + 10.0)):
    return types.SimpleNamespace(
        spans=spans, slice_interval=interval,
        slice_queries=[object()] * queries)


def reader(name):
    return run.load_module("metrics", name)


SPANS = [
    # a batch of 30 ms with two planes put, one of 20 ms with one
    ("scan.upload", S + 1.000, S + 1.030),
    ("upload.put", S + 1.005, S + 1.010), ("upload.put", S + 1.012, S + 1.020),
    ("scan.upload", S + 2.000, S + 2.020),
    ("upload.put", S + 2.001, S + 2.004),
    # a batch the slice's start cuts: 10 ms of it and 4 ms of its put count
    ("scan.upload", S - 0.010, S + 0.010),
    ("upload.put", S - 0.002, S + 0.004),
    # a plane put outside any scan's upload (create_dataframe): not counted
    ("upload.put", S + 3.0, S + 3.5),
    # before the slice: counted by none
    ("scan.upload", S - 3.0, S - 2.0), ("upload.put", S - 2.5, S - 2.4),
    ("host.lock_wait", S - 2.0, S - 1.0),
    # two late ticks that overlap (a union), and one the slice's start cuts
    ("host.lock_wait", S + 1.00, S + 1.10), ("host.lock_wait", S + 1.05, S + 1.20),
    ("host.lock_wait", S - 1.0, S + 0.3),
    ("scan.wait", S + 0.0, S + 0.5), ("scan.decode", S + 0.0, S + 0.4),
]

WANT = {
    "scan_put_ms_per_batch": (5 + 8 + 3 + 4) / 3,
    "scan_stage_ms_per_batch": ((30 + 20 + 10) - (5 + 8 + 3 + 4)) / 3,
    "host_lock_wait_pct": 100 * (0.2 + 0.3) / 10,
}


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_hand_written_spans(name):
    assert reader(name).read(ctx(SPANS)) == pytest.approx(WANT[name])


def test_put_and_stage_partition_the_upload():
    put = reader("scan_put_ms_per_batch").read(ctx(SPANS))
    stage = reader("scan_stage_ms_per_batch").read(ctx(SPANS))
    assert put + stage == pytest.approx((30 + 20 + 10) / 3)


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_on_a_program_without_the_span(name,
                                                            monkeypatch):
    """The parent commit records ``scan.upload`` and names neither new span:
    the metric is left out of the line, and nothing raises."""
    old = {n: d for n, d in tracing.static_ranges().items()
           if n not in ("upload.put", "host.lock_wait")}
    monkeypatch.setattr(tracing, "static_ranges", lambda: old)
    spans = [s for s in SPANS if s[0] in old]
    assert reader(name).read(ctx(spans)) is None
    assert reader(name).read(ctx([])) is None


def test_a_registered_span_that_never_fired_reads_zero():
    quiet = [s for s in SPANS if s[0] not in ("host.lock_wait", "upload.put")]
    assert reader("host_lock_wait_pct").read(ctx(quiet)) == 0.0
    assert reader("host_lock_wait_pct").read(ctx([])) == 0.0
    # an upload that put nothing through put_plane is all staging
    assert reader("scan_put_ms_per_batch").read(ctx(quiet)) == 0.0
    assert reader("scan_stage_ms_per_batch").read(ctx(quiet)) == \
        pytest.approx(60 / 3)


@pytest.mark.parametrize("name", NAMES[:2])
def test_scan_readers_need_an_upload_in_the_slice(name):
    no_upload = [s for s in SPANS if s[0] != "scan.upload"]
    assert reader(name).read(ctx(no_upload)) is None


def test_lock_wait_share_stays_within_the_slice():
    whole = [("host.lock_wait", S - 5.0, S + 50.0)]
    assert reader("host_lock_wait_pct").read(ctx(whole)) == 100.0
    assert reader("host_lock_wait_pct").read(
        ctx(whole, interval=(S, S))) is None


def test_none_of_the_three_names_an_idle_gap():
    """``upload.put`` is nested in ``scan.upload`` and ``host.lock_wait`` is
    not in the profiler's trace: the ledger's ``breakdown`` keeps its names."""
    assert not [n for n in NAMES if getattr(reader(n), "SPANS", ())]


def test_a_traced_run_of_the_engine_reports_all_three():
    args = types.SimpleNamespace(workload="q6_parquet_sf1", seed=2**31 + 11,
                                 seconds=0.1, trace=1,
                                 rows=2 * 1_048_576 + 17)
    r = run.run(args, rehearsal=True)
    assert r["correct"] is True, r["compared"]
    assert set(NAMES) <= set(r["metrics"]), sorted(r["metrics"])
    value = {n: r["metrics"][n]["value"] for n in NAMES}
    assert value["scan_put_ms_per_batch"] > 0
    assert value["scan_stage_ms_per_batch"] > 0
    assert 0.0 <= value["host_lock_wait_pct"] <= 100.0
    # every cell reports them, under the scan's and the client's layer
    for cell in ("q6_parquet_sf10", "q6_parquet_sf1", "q1_parquet_sf1",
                 "q18_inner_parquet_sf1", "q21_lineitem_parquet_sf1"):
        layers = {m["name"]: m["layer"]
                  for m in run.load_cell(cell).per_layer}
        assert [layers.get(n) for n in NAMES] == ["scan", "scan", "client"]
