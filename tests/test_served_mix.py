"""The served path of ``served_mix_sf1``: a single-flight follower waits for
a leader that runs past the admission timeout instead of running the query
again, and a leader that fails hands over; the serving spans reach the span
log (``serving.submit`` / ``admission`` / ``run``, and ``serving.follow``)
and cost nothing there while it is off; the three readers the cell adds, on
hand-written spans and on a program without their span or counter; and the
cell rehearsed through ``benchmark/run.py`` on the CPU.

On the CPU backend: rows, counts and spans, never times."""
import threading
import time
import types

import pytest

from benchmark import run, traffic
from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.expressions import count
from spark_rapids_tpu.expressions.core import Alias
from spark_rapids_tpu.serving import QueryQueue
from spark_rapids_tpu.utils import tracing

SERVING_SPANS = ("serving.submit", "serving.admission", "serving.run",
                 "serving.follow")


@pytest.fixture
def span_log():
    tracing.span_log.clear()
    tracing.span_log.enabled = True
    try:
        yield tracing.span_log
    finally:
        tracing.span_log.enabled = False
        tracing.span_log.clear()


def _plan():
    s = TpuSession({})
    return s.create_dataframe({"k": [1, 1, 2]}, Schema.of(k=T.INT)) \
        .group_by("k").agg(Alias(count(), "n")).plan


def _gated(answer, fail_first=False):
    """A runner that holds each run until ``gate`` is set; ``runs`` counts
    the runs, ``started`` is set when the first one starts."""
    gate, started, runs = threading.Event(), threading.Event(), []

    def runner(plan, ctx):
        runs.append(time.monotonic())
        started.set()
        assert gate.wait(30)
        if fail_first and len(runs) == 1:
            raise RuntimeError("the leader failed")
        return answer
    return runner, gate, started, runs


def test_a_follower_waits_for_a_leader_slower_than_the_queue_timeout(
        span_log):
    runner, gate, started, runs = _gated([(1, 2), (2, 1)])
    q = QueryQueue(runner, conf={
        "spark.rapids.serving.queue.timeout": "0.3",
        "spark.rapids.serving.maxConcurrentQueries": "1"})
    plan = _plan()
    try:
        leader = q.submit_async(plan, tenant="dashboards")
        assert started.wait(10)
        follower = q.submit_async(plan, tenant="analysts")
        time.sleep(1.0)             # three admission timeouts go by
        assert not follower.done()
        gate.set()
        assert follower.result(timeout=30) == leader.result(timeout=30) \
            == [(1, 2), (2, 1)]
    finally:
        gate.set()
        q.close()
    assert len(runs) == 1, "the follower ran the query again"
    follows = [(s, e) for n, s, e in span_log.snapshot()
               if n == "serving.follow"]
    assert len(follows) == 1 and follows[0][1] - follows[0][0] > 0.9


def test_a_leader_that_fails_hands_over_to_its_follower():
    runner, gate, started, runs = _gated([(1, 2)], fail_first=True)
    q = QueryQueue(runner, conf={
        "spark.rapids.serving.queue.timeout": "5"})
    plan = _plan()
    try:
        leader = q.submit_async(plan)
        assert started.wait(10)
        follower = q.submit_async(plan)
        time.sleep(0.3)
        gate.set()
        with pytest.raises(RuntimeError, match="the leader failed"):
            leader.result(timeout=30)
        assert follower.result(timeout=30) == [(1, 2)]
    finally:
        gate.set()
        q.close()
    assert len(runs) == 2


def test_the_serving_spans_reach_the_span_log(span_log):
    runner, gate, started, runs = _gated([(7,)])
    gate.set()
    q = QueryQueue(runner, conf={})
    plan = _plan()
    try:
        q.submit(plan)              # a miss: admitted and run
        q.submit(plan)              # a hit: no admission, no run
    finally:
        q.close()
    names = [n for n, _, _ in span_log.snapshot() if n in SERVING_SPANS]
    assert sorted(names) == ["serving.admission", "serving.run",
                             "serving.submit", "serving.submit"]
    assert set(SERVING_SPANS) <= set(tracing.static_ranges())


def test_with_the_span_log_off_no_serving_span_is_kept():
    tracing.span_log.clear()
    runner, gate, started, runs = _gated([(7,)])
    gate.set()
    q = QueryQueue(runner, conf={})
    try:
        q.submit(_plan())
    finally:
        q.close()
    assert tracing.span_log.snapshot() == []


# -- the cell's readers ---------------------------------------------------------

S = 50.0        # the slice starts here on the spans' clock


def _ctx(spans, executed=2, interval=(S, S + 10.0)):
    return types.SimpleNamespace(spans=spans, slice_interval=interval,
                                 slice_queries=[object()] * executed)


SPANS = [
    ("serving.submit", S + 0.0, S + 2.0), ("serving.admission", S + 0.1,
                                           S + 0.4),
    ("serving.run", S + 0.4, S + 2.0),
    ("serving.submit", S + 0.5, S + 2.1), ("serving.follow", S + 0.5,
                                           S + 2.0),
    ("serving.submit", S + 3.0, S + 3.5), ("serving.admission", S + 3.0,
                                           S + 3.1),
    ("serving.run", S + 3.1, S + 3.5),
    ("serving.submit", S + 4.0, S + 4.001),       # a hit
    # before the slice: counted by none
    ("serving.admission", S - 2.0, S - 1.0), ("serving.follow", S - 2.0,
                                              S - 1.5),
]


def _reader(name):
    return run.load_module("metrics", name)


def test_the_readers_on_hand_written_spans():
    assert _reader("serving_admit_ms_per_query").read(_ctx(SPANS)) == \
        pytest.approx(1e3 * (0.3 + 0.1) / 2)
    assert _reader("follower_wait_ms_per_answer").read(_ctx(SPANS)) == \
        pytest.approx(1e3 * 1.5 / 4)
    no_follow = [s for s in SPANS if s[0] != "serving.follow"]
    assert _reader("follower_wait_ms_per_answer").read(
        _ctx(no_follow)) == 0.0


def test_the_readers_find_nothing_on_a_program_without_them(monkeypatch):
    """The parent writes no serving span to the log and names neither
    ``serving.follow`` nor the counter: each metric is left out of the
    line, and nothing raises."""
    from spark_rapids_tpu.plan.execs import base
    parent = [s for s in SPANS if not s[0].startswith("serving.")]
    assert _reader("serving_admit_ms_per_query").read(_ctx(parent)) is None
    assert _reader("follower_wait_ms_per_answer").read(_ctx(parent)) is None
    old = {n: d for n, d in tracing.static_ranges().items()
           if n != "serving.follow"}
    monkeypatch.setattr(tracing, "static_ranges", lambda: old)
    assert _reader("follower_wait_ms_per_answer").read(_ctx(SPANS)) is None
    monkeypatch.delattr(base, "program_keys_since")
    assert _reader("programs_in_slice").read(_ctx(SPANS)) is None


def test_programs_in_slice_counts_the_keys_launched_in_it():
    from spark_rapids_tpu.plan.execs import base
    base.reset_launch_stats()
    t0 = time.perf_counter()
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    s.create_dataframe({"k": [1, 1, 2]}, Schema.of(k=T.INT)) \
        .group_by("k").agg(Alias(count(), "n")).collect()
    got = _reader("programs_in_slice").read(
        _ctx([], interval=(t0, time.perf_counter())))
    assert got == base.launch_stats()["program_keys"] >= 1
    assert _reader("programs_in_slice").read(
        _ctx([], interval=(time.perf_counter(), time.perf_counter() + 1))) == 0


# -- the cell -------------------------------------------------------------------

def test_q1_runs_its_validation_values_only():
    cell = run.load_cell("served_mix_sf1")
    mix = {m["query"]: m for m in cell.traffic["mix"]}
    assert "distinct" not in mix["q1"] and mix["q6"]["distinct"] == 64
    sets = traffic.draw_sets(cell.traffic, 2**31 + 47, cell.spaces)
    schedule = traffic.open_schedule(cell.traffic, 2**31 + 47, 45.0, sets)
    assert {traffic.sub_key(a.sub) for a in schedule
            if a.query == "q1"} == {()}
    assert len({traffic.sub_key(a.sub) for a in schedule
                if a.query == "q6"}) > 10
    conf = cell.config["session_conf"]
    assert conf["spark.rapids.serving.maxConcurrentQueries"] == "4"
    assert conf["spark.rapids.serving.queue.maxDepth"] == "32"
    assert conf["spark.rapids.serving.queue.timeout"] == "30"


def test_the_served_cell_rehearses_correct(capfd):
    """``served_mix_sf1`` end to end at a small table, traced: every answer
    its own set's, nothing refused, nothing compiled in the window, and the
    three metrics it adds in the line."""
    args = types.SimpleNamespace(workload="served_mix_sf1", seed=2**31 + 48,
                                 seconds=3.0, trace=1, rows=65_536)
    r = run.run(args, rehearsal=True)
    assert r["correct"] is True and r["failed"] == 0, r["compared"]
    m = r["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["serving_admit_ms_per_query"]["value"] >= 0
    assert m["follower_wait_ms_per_answer"]["value"] >= 0
    assert m["programs_in_slice"]["value"] >= 1
    warm = [ln for ln in capfd.readouterr().err.splitlines()
            if '"phase": "warm_up"' in ln and '"query": "q6"' in ln]
    # every q6 set after the first runs the first set's programs
    assert len(warm) > 1 and all('"compiles": 0' in ln for ln in warm[1:])
