"""``tools/host_timeline.py``'s reduction of the span log and the sampler's
late ticks, on hand-written spans (the run it drives is a chip call's)."""
import pytest

from tools import host_timeline as ht

SPANS = [
    ("query.collect", 0.0, 1.0),
    # the reader decodes chunk 2 while the task uploads chunk 1
    ("scan.decode", 0.00, 0.05), ("scan.decode", 0.05, 0.11),
    ("scan.wait", 0.00, 0.05),
    ("scan.upload", 0.05, 0.08),
    ("upload.put", 0.06, 0.065), ("upload.put", 0.07, 0.075),
    ("fused.batch", 0.08, 0.081),
    ("scan.wait", 0.081, 0.11),
    ("scan.upload", 0.12, 0.14),
    ("upload.put", 0.125, 0.130),
    # a plane put outside any upload
    ("upload.put", 0.50, 0.60),
    # late while decode and upload were both open, and late while neither
    ("host.lock_wait", 0.055, 0.075), ("host.lock_wait", 0.30, 0.33),
]


def test_per_chunk_milliseconds():
    got = ht.per_chunk(ht.by_name(SPANS))
    assert got["chunks"] == 2 and got["puts_per_chunk"] == 2.0
    assert got["scan.decode_ms"] == pytest.approx(110 / 2)
    assert got["scan.wait_ms"] == pytest.approx((50 + 29) / 2)
    assert got["scan.upload_ms"] == pytest.approx((30 + 20) / 2)
    assert got["upload.put_ms"] == pytest.approx((5 + 5 + 5) / 2)


def test_a_cycle_is_upload_wait_dispatch_and_what_nobody_owns():
    got = ht.chunk_cycles(ht.by_name(SPANS))
    assert got["cycles"] == 1 and got["cycle_ms"] == pytest.approx(70)
    assert got["scan.upload_ms"] == pytest.approx(30)
    assert got["scan.wait_ms"] == pytest.approx(29)
    assert got["fused.batch_ms"] == pytest.approx(1)
    assert got["unowned_ms"] == pytest.approx(10)


def test_lock_wait_is_put_down_to_the_spans_open_with_it():
    rows = {tuple(r["open"]): r for r in ht.lock_wait_by_open_spans(SPANS)}
    both = rows[("scan.decode", "scan.upload")]
    assert both["open_s"] == pytest.approx(0.02)        # less the two puts
    assert both["lock_wait_s"] == pytest.approx(0.01)
    put = rows[("scan.decode", "scan.upload", "upload.put")]
    assert put["open_s"] == pytest.approx(0.01)
    assert put["lock_wait_s"] == pytest.approx(0.01)
    assert rows[()]["lock_wait_s"] == pytest.approx(0.03)
    assert sum(r["lock_wait_s"] for r in rows.values()) == pytest.approx(0.05)
    assert rows[("scan.decode", "scan.wait")]["lock_wait_s"] == 0


def test_late_ticks_are_grouped_by_thread_and_innermost_frames():
    ticks = [
        (1.0, 1.03, {"ThreadPoolExecutor-0_1": ["parquet.py:10:read", "a.py:1:f"],
                     "Thread-3 (one_client)": ["arrow.py:170:to_dev", "b.py:2:g"]}),
        (2.0, 2.05, {"ThreadPoolExecutor-0_2": ["parquet.py:10:read", "a.py:1:f"],
                     "Thread-4 (one_client)": ["threading.py:355:wait", "c.py:3:h"]}),
    ]
    rows = ht.holders(ticks)
    assert rows[0] == {"thread": "ThreadPoolExecutor-N_N",
                       "frames": ["parquet.py:10:read", "a.py:1:f"],
                       "ticks": 2, "late_s": pytest.approx(0.08)}
    assert {r["thread"] for r in rows[1:]} == {"Thread-N (one_client)"}
    report = ht.reduce(SPANS, ticks, queries=1)
    assert report["late_ticks"] == 2
    assert report["lock_wait"]["spans"] == 2
    assert report["lock_wait"]["longest_ms"] == pytest.approx(30)
    assert report["spans"]["scan.upload"]["count"] == 2
    assert "scan.decode + scan.upload" in ht.render(report)
