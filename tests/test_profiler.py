"""Profiling-depth tests: the process's one sampler thread (reference:
asyncProfiler.scala:58 per-stage flamegraphs): the sampled flamegraph, the
``host.lock_wait`` span of a tick that got the interpreter lock late, the
late ticks with the frames of the thread that held it, and when the thread
exists at all."""
import json
import os
import threading
import time

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.utils import tracing

CONF = {"spark.rapids.sql.enabled": "true"}


def sampler_threads() -> int:
    return sum(t.name == tracing.SAMPLER_THREAD_NAME
               for t in threading.enumerate())


def wait_for_no_sampler(seconds: float = 0.05) -> int:
    """Sampler threads left ``seconds`` after the last thing that kept one
    was taken away (its loop ends within two intervals of 5 ms)."""
    time.sleep(seconds)
    return sampler_threads()


@pytest.fixture
def span_log_on():
    assert wait_for_no_sampler() == 0
    tracing.span_log.clear()
    tracing.sampler.clear()
    tracing.span_log.enabled = True
    try:
        yield
    finally:
        tracing.span_log.enabled = False
        assert wait_for_no_sampler() == 0


def keep_the_lock(seconds: float) -> float:
    """One C call that keeps the interpreter lock for at least ``seconds``
    (``sum`` over a list runs no bytecode); returns how long it took."""
    n = 1 << 18
    while True:
        data = list(range(n))
        t0 = time.perf_counter()
        sum(data)
        took = time.perf_counter() - t0
        if took >= seconds:
            return took
        n *= 2


def lock_waits():
    return [(t0, t1) for name, t0, t1 in tracing.span_log.snapshot()
            if name == "host.lock_wait"]


def test_stack_sampler_produces_collapsed_stacks():
    profile = tracing.sampler.hold()
    try:
        t0 = time.monotonic()
        x = 0
        while time.monotonic() - t0 < 0.15:
            x += sum(range(200))
    finally:
        tracing.sampler.release(profile)
    lines = profile.collapsed_stacks()
    assert lines and profile.samples >= 1, "no samples collected"
    # collapsed format: "frame;frame;... count"
    stack, count = lines[0].rsplit(" ", 1)
    assert int(count) >= 1 and ";" in stack
    assert any("test_profiler" in ln for ln in lines)
    assert wait_for_no_sampler() == 0   # nothing holds it any more


def test_a_c_call_that_keeps_the_lock_is_a_lock_wait_and_a_late_tick(
        span_log_on):
    tracing.sampler.ensure_running()
    time.sleep(0.02)                    # the sampler is in its loop
    t0 = time.perf_counter()
    took = keep_the_lock(0.05)
    t1 = time.perf_counter()
    time.sleep(0.02)                    # the late tick has run
    waits = [(a, b) for a, b in lock_waits() if b > t0 and a < t1]
    assert waits and max(b - a for a, b in waits) >= 0.020, (took, waits)
    late = [tick for tick in tracing.late_ticks() if t0 < tick[1] < t1 + 0.02]
    assert late, (took, tracing.late_ticks())
    for due, ran, threads in late:
        assert ran - due > tracing.LATE_TICK_S
    # the thread that held the lock stands in the function that made the call
    held = [frames for _due, _ran, threads in late
            for name, frames in threads.items()
            if name == threading.current_thread().name]
    assert held and all(len(f) <= tracing.LATE_TICK_FRAMES for f in held)
    assert any("test_profiler.py" in frames[0] and "keep_the_lock" in frames[0]
               for frames in held), held
    assert any(any("test_a_c_call_that_keeps_the_lock" in fr for fr in frames)
               for frames in held), held


def test_a_thread_that_sleeps_is_no_lock_wait(span_log_on):
    tracing.sampler.ensure_running()
    time.sleep(0.15)                    # the lock is free all the while
    assert sampler_threads() == 1
    assert not tracing.late_ticks()
    assert all(b - a < tracing.LATE_TICK_S for a, b in lock_waits())


def test_late_ticks_are_a_bounded_ring_of_the_newest():
    s = tracing.StackSampler()
    for i in range(tracing.LATE_TICKS_KEPT + 10):
        s._read_frames(threading.get_ident(), (float(i), i + 0.5))
    ticks = s.late_ticks()
    assert len(ticks) == tracing.LATE_TICKS_KEPT
    assert ticks[-1][:2] == (tracing.LATE_TICKS_KEPT + 9.0,
                             tracing.LATE_TICKS_KEPT + 9.5)
    s.clear()
    assert s.late_ticks() == []


def small_frame(sess, fn=None):
    sch = Schema.of(k=T.INT, v=T.LONG)
    rng = np.random.RandomState(1)
    df = sess.create_dataframe(
        {"k": rng.randint(0, 5, 5000).tolist(),
         "v": rng.randint(-9, 9, 5000).tolist()}, schema=sch)
    return df if fn is None else df.map_in_pandas(fn, sch)


def test_no_sampler_thread_without_the_span_log_or_a_profile():
    """What a timed run of the benchmark is: the program as it was before
    the sampler, with no thread of it before, during or after."""
    assert not tracing.span_log.enabled and wait_for_no_sampler() == 0
    seen = []

    def during(pdf):
        seen.append(sampler_threads())
        return pdf

    rows = small_frame(TpuSession(dict(CONF)), during).collect()
    assert len(rows) == 5000 and seen and set(seen) == {0}
    assert sampler_threads() == 0


def test_one_sampler_thread_for_two_concurrent_collects(span_log_on):
    both = threading.Barrier(2, timeout=60)
    seen, errors = [], []

    def during(pdf):
        both.wait()                     # two collect() calls are under way
        seen.append(sampler_threads())
        return pdf

    sess = TpuSession(dict(CONF))

    def client():
        try:
            assert len(small_frame(sess, during).collect()) == 5000
        except Exception as e:          # noqa: BLE001 — read below
            errors.append(e)

    clients = [threading.Thread(target=client) for _ in range(2)]
    for t in clients:
        t.start()
    for t in clients:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors, errors
    assert len(seen) >= 2 and set(seen) == {1}
    # a later collect() finds it running and starts no second one
    assert len(small_frame(sess).collect()) == 5000
    assert sampler_threads() == 1
    tracing.span_log.enabled = False
    assert wait_for_no_sampler(0.05) == 0


def test_query_profiler_end_to_end(tmp_path):
    """Conf-gated per-collect profiling: artifacts land in profile.dir."""
    s = TpuSession({**CONF, "spark.rapids.profile.enabled": "true",
                    "spark.rapids.profile.dir": str(tmp_path)})
    from spark_rapids_tpu.expressions import col, sum_
    held = []

    def during(pdf):
        held.append((sampler_threads(), keep_the_lock(0.05)))
        return pdf

    rows = (small_frame(s, during).group_by("k")
            .agg(sum_(col("v")).alias("sv")).collect())
    assert len(rows) == 5
    assert held and held[0][0] == 1     # the profile holds the one sampler
    assert wait_for_no_sampler() == 0   # and lets it go with the query
    names = sorted(os.listdir(tmp_path))
    flames = [f for f in names if f.endswith("_flame.txt")]
    ticks = [f for f in names if f.endswith("_late_ticks.json")]
    assert flames and ticks and not [f for f in names if "bubble" in f]
    assert "test_profiler" in open(os.path.join(tmp_path, flames[0])).read()
    rep = json.load(open(os.path.join(tmp_path, ticks[0])))
    assert rep["wall_ms"] > 0 and rep["samples"] >= 1
    # the UDF's C call kept the lock: a late tick names where it stood
    assert rep["late_ticks"], rep
    assert all(t["late_ms"] > 20 for t in rep["late_ticks"])
    assert any("keep_the_lock" in frame
               for t in rep["late_ticks"]
               for frames in t["threads"].values() for frame in frames)
