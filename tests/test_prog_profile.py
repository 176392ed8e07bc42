"""Launches by program name (plan/execs/base ``launch_stats()["by_program"]``).

Every ``shared_jit`` program is jitted under a name, ``<kind>_<8 hex digits
of a digest of its cache key>``, so the one string names it in the launch
counts and in the profiler's trace; the trace then says what each program
cost on the device, with nothing blocking a dispatch.  The names must (1)
count every launch under the program that ran, (2) need no arming, (3)
appear in a trace, and (4) say what the program does.
"""
import glob
import os

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.expressions import count, sum_
from spark_rapids_tpu.plan.execs.base import (
    launch_stats,
    program_name,
    reset_launch_stats,
)

SCHEMA = Schema.of(k=T.INT, v=T.DOUBLE)


def _batch(n=4096, seed=3):
    rng = np.random.RandomState(seed)
    return ColumnarBatch.from_pydict(
        {"k": (1 + rng.randint(0, 17, n)).tolist(),
         "v": np.round(rng.uniform(-5, 5, n), 3).tolist()}, SCHEMA)


def _query(s):
    df = s.create_dataframe([_batch()], num_partitions=2)
    return (df.group_by("k").agg(sum_("v").alias("sv"),
                                 count().alias("n"))
            .order_by("k"))


def test_launches_are_counted_by_program_name():
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    q = _query(s)
    q.collect()                      # warm: compile once
    reset_launch_stats()
    assert q.collect()
    stats = launch_stats()
    by = stats["by_program"]
    assert by and sum(by.values()) == stats["launches"]
    assert len(by) == stats["programs"]
    # the aggregate's and the sort's programs are attributable by kind
    assert any(n.startswith(("agg_", "fused_agg")) for n in by), list(by)
    assert any(n.startswith("sort_") for n in by), list(by)
    for name in by:
        kind, _, digest = name.rpartition("_")
        assert kind and len(digest) == 8 and int(digest, 16) >= 0, name


def test_counting_needs_no_arming_and_resets():
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    q = _query(s)
    q.collect()
    reset_launch_stats()
    assert launch_stats() == {"launches": 0, "programs": 0,
                              "program_keys": 0, "by_program": {},
                              "discarded": {}}
    q.collect()
    once = launch_stats()
    q.collect()
    twice = launch_stats()
    assert once["launches"] >= 1
    assert twice["by_program"] == {k: 2 * v
                                   for k, v in once["by_program"].items()}
    # a snapshot, not the live table
    once["by_program"].clear()
    assert launch_stats()["by_program"]


def test_program_names_are_a_digest_of_the_key():
    a = program_name("agg_partial", "agg|schema|exprs|partial|0")
    assert a == program_name("agg_partial", "agg|schema|exprs|partial|0")
    assert a != program_name("agg_partial", "agg|schema|exprs|partial|8")
    assert a.startswith("agg_partial_") and len(a) == len("agg_partial_") + 8


def test_names_appear_in_a_trace_recorded_on_the_cpu_backend(tmp_path):
    """What ``launch_stats`` calls a program is what the profiler calls its
    module (``jit_<name>``): on the CPU backend the operation events carry
    it in their ``hlo_module`` stat."""
    import jax.profiler
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    q = _query(s)
    q.collect()
    reset_launch_stats()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        q.collect()
    finally:
        jax.profiler.stop_trace()
    launched = set(launch_stats()["by_program"])
    pb, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                 "*.xplane.pb"))
    modules = set()
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for ev in line.events:
                modules.update(str(v) for k, v in ev.stats
                               if k == "hlo_module")
    assert modules, "the trace names no module"
    assert not any(m.startswith("jit_fn") for m in modules), modules
    assert {f"jit_{n}" for n in launched} <= modules, (launched, modules)


def test_run_suites_still_names_this_file():
    from tools.run_suites import SUITES
    here = "tests/" + os.path.basename(__file__)
    assert here in SUITES["profile"][0]
    assert here in SUITES["observability"][0]


def _fill_jit_cache(monkeypatch, n):
    import collections

    from spark_rapids_tpu.plan.execs import base
    monkeypatch.setattr(base, "_JIT_CACHE", collections.OrderedDict())
    for i in range(n):
        base.shared_jit(f"mappings-bound-{i}", lambda: (lambda x: x + 1),
                        kind="probe")
    return base


def test_the_program_cache_sheds_its_older_half_near_the_mappings_limit(
        monkeypatch):
    """A CPU executable is JIT code in anonymous mappings and the kernel
    caps a process's mappings: past half of vm.max_map_count a miss drops
    the least recently used half of the cache, the new program stays, and
    under the budget nothing goes but by the LRU bound."""
    base = _fill_jit_cache(monkeypatch, 8)
    assert len(base._JIT_CACHE) == 8          # this process: far under
    base.shared_jit("mappings-bound-0", lambda: None, kind="probe")  # a hit
    monkeypatch.setattr(base, "_mappings_in_use",
                        lambda: base._mappings_budget() + 1)
    base.shared_jit("mappings-bound-new", lambda: (lambda x: x), kind="probe")
    kept = [k.split("|")[0] for k in base._JIT_CACHE]
    assert kept == ["mappings-bound-5", "mappings-bound-6", "mappings-bound-7",
                    "mappings-bound-0", "mappings-bound-new"]


def test_the_mappings_budget_is_half_of_what_the_kernel_allows():
    from spark_rapids_tpu.plan.execs import base
    with open("/proc/sys/vm/max_map_count") as f:
        assert base._mappings_budget() == int(f.read()) // 2
    with open("/proc/self/maps") as f:
        here = len(f.readlines())
    assert 0 < base._mappings_in_use() <= here + 64
