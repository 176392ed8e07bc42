"""The open loop: the schedule an open traffic file gives, the substitution
parameters of the query files, the served runner rehearsed on the CPU with a
stall and a refusal in it, and the watchdog that writes a stalled query's
stacks."""
import io
import json
import threading
import time
import types

import pytest

from benchmark import compare, datagen, run, traffic
from benchmark.queries import q1, q6
from benchmark.tables import lineitem
from benchmark.tables.lineitem import days

OPEN = {"loop": "open", "rate_qps": 4.0, "burst": 1,
        "tenants": [{"name": "dash", "weight": 3, "priority": 0},
                    {"name": "adhoc", "weight": 1, "priority": 1}],
        "mix": [{"query": "q6", "weight": 4, "distinct": 64, "zipf": 1.1},
                {"query": "q1", "weight": 1, "distinct": 61, "zipf": 1.1}]}
SPACES = {"q6": traffic.substitution_space(q6.SUBSTITUTIONS),
          "q1": traffic.substitution_space(q1.SUBSTITUTIONS)}


def _schedule(spec, seed, seconds):
    return traffic.open_schedule(spec, seed, seconds,
                                 traffic.draw_sets(spec, seed, SPACES))


def _write(tmp_path, spec) -> str:
    path = tmp_path / "traffic.json"
    path.write_text(json.dumps(spec))
    return str(path)


# -- the schedule ------------------------------------------------------------

def test_the_spec_ranges_hold_80_and_61_sets():
    assert len(SPACES["q6"]) == 80 and len(SPACES["q1"]) == 61
    assert {"year": 1994, "discount": 6, "quantity": 24} in SPACES["q6"]
    assert len({traffic.sub_key(s) for s in SPACES["q6"]}) == 80


def test_the_open_schedule_is_deterministic_in_the_seed():
    seed = 2**31 + 40
    a, b = _schedule(OPEN, seed, 45.0), _schedule(OPEN, seed, 45.0)
    assert a == b and len(a) == 180
    assert _schedule(OPEN, seed + 1, 45.0) != a
    assert [x.due for x in a] == sorted(x.due for x in a)
    assert all(0.0 <= x.due < 45.0 for x in a)
    # every seed gets the same work in another order
    other = _schedule(OPEN, 7, 45.0)
    for field in ("query", "tenant", "priority"):
        assert sorted(getattr(x, field) for x in a) == \
            sorted(getattr(x, field) for x in other)
    assert [x.query for x in a].count("q6") == 144
    assert [x.tenant for x in a].count("dash") == 135
    assert {x.priority for x in a if x.tenant == "adhoc"} == {1}


def test_its_mean_rate_holds_over_10000_arrivals():
    spec = dict(OPEN, rate_qps=50.0)
    arrivals = _schedule(spec, 2**31 + 41, 200.0)
    assert len(arrivals) == 10_000
    gaps = [b.due - a.due for a, b in zip(arrivals, arrivals[1:])]
    mean = sum(gaps) / len(gaps)
    assert abs(mean * 50.0 - 1.0) < 0.05
    # exponential gaps: as wide as they are long
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    assert abs(var ** 0.5 / mean - 1.0) < 0.05


def test_a_burst_shares_its_due_time():
    spec = dict(OPEN, rate_qps=8.0, burst=8)
    arrivals = _schedule(spec, 3, 10.0)
    assert len(arrivals) == 80
    groups = [arrivals[i:i + 8] for i in range(0, 80, 8)]
    assert all(len({a.due for a in g}) == 1 for g in groups)
    assert len({g[0].due for g in groups}) == 10


def test_the_zipf_ranks_follow_their_exponent():
    spec = dict(OPEN, rate_qps=100.0, tenants=OPEN["tenants"][:1],
                mix=[{"query": "q6", "weight": 1, "distinct": 64,
                      "zipf": 1.1}])
    seed = 2**31 + 42
    sets = traffic.draw_sets(spec, seed, SPACES)["q6"]
    assert len(sets) == 64
    arrivals = traffic.open_schedule(spec, seed, 100.0, {"q6": sets})
    rank = {traffic.sub_key(s): r for r, s in enumerate(sets, 1)}
    counts = [0] * 65
    for a in arrivals:
        counts[rank[traffic.sub_key(a.sub)]] += 1
    h = sum(r ** -1.1 for r in range(1, 65))
    for r in (1, 2, 4, 8, 16):
        assert abs(counts[r] - 10_000 * r ** -1.1 / h) <= 1, r
    assert counts[1] / counts[2] == pytest.approx(2 ** 1.1, rel=0.01)
    # uniform without an exponent
    flat = dict(spec, mix=[dict(spec["mix"][0], zipf=0)])
    arrivals = traffic.open_schedule(flat, seed, 100.0, {"q6": sets})
    per = [sum(a.sub == s for a in arrivals) for s in sets]
    assert max(per) - min(per) <= 1


def test_without_distinct_an_arrival_runs_the_validation_values():
    spec = dict(OPEN, mix=[{"query": "q6", "weight": 1}])
    assert {traffic.sub_key(a.sub) for a in _schedule(spec, 5, 5.0)} == {()}


@pytest.mark.parametrize("change,why", [
    ({"loop": "poisson"}, "not one this generator knows"),
    ({"mix": [dict(OPEN["mix"][0], distinct=81)]}, "its ranges allow 80"),
    ({"mix": [dict(OPEN["mix"][1], distinct=62)]}, "its ranges allow 61"),
    ({"rate_qps": 0}, "rate_qps"),
    ({"burst": 0}, "burst"),
    ({"tenants": []}, "tenants"),
    ({"clients": 1}, "unknown keys"),
])
def test_a_file_outside_the_rules_is_refused(tmp_path, change, why):
    counts = {q: len(s) for q, s in SPACES.items()}
    path = _write(tmp_path, dict(OPEN, **change))
    with pytest.raises(ValueError, match=why):
        traffic.load(path, counts.get)
    # and the file as it was loads
    assert traffic.load(_write(tmp_path, OPEN), counts.get)["loop"] == "open"


def _stream_before(spec, seed, client):
    """``traffic.client_stream`` as the harness had it before the open
    loop."""
    import numpy as np
    cycle = [m["query"] for m in spec["mix"] for _ in range(int(m["weight"]))]
    rng = np.random.default_rng([int(seed), 7, int(client)])
    while True:
        for i in rng.permutation(len(cycle)):
            yield cycle[i]


@pytest.mark.parametrize("name", ["q6_closed_1", "q1_closed_1",
                                  "q18_inner_closed_1",
                                  "q21_lineitem_closed_1"])
def test_the_closed_traffic_files_stream_as_before(name):
    with open(run.traffic_path(name)) as f:
        raw = json.load(f)
    spec = traffic.load(run.traffic_path(name))
    assert spec == raw and spec["loop"] == "closed"
    two = dict(spec, mix=spec["mix"] + [{"query": "other", "weight": 3}])
    for s in (spec, two):
        for seed in (5, 2**31 + 43):
            new, old = traffic.client_stream(s, seed, 0), \
                _stream_before(s, seed, 0)
            assert [next(new) for _ in range(40)] == \
                [next(old) for _ in range(40)]


# -- the substitution parameters --------------------------------------------

def _q6_before(df):
    """``queries/q6.py``'s ``build`` as it was before its parameters."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expressions import Cast, col, lit, sum_
    dec = T.DecimalType(12, 2)
    price = Cast(col("l_extendedprice"), T.DOUBLE)
    disc = Cast(col("l_discount"), T.DOUBLE)
    return (df.filter(
                (col("l_shipdate") >= lit(days(1994, 1, 1), T.DATE))
                & (col("l_shipdate") < lit(days(1995, 1, 1), T.DATE))
                & (col("l_discount") >= lit(5, dec))
                & (col("l_discount") <= lit(7, dec))
                & (col("l_quantity") < lit(2400, dec)))
            .agg((sum_(price * disc)).alias("revenue")))


def _q1_before(df):
    """``queries/q1.py``'s ``build`` as it was before its parameter."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expressions import Cast, avg, col, count, lit, sum_
    qty = Cast(col("l_quantity"), T.DOUBLE)
    price = Cast(col("l_extendedprice"), T.DOUBLE)
    disc = Cast(col("l_discount"), T.DOUBLE)
    tax = Cast(col("l_tax"), T.DOUBLE)
    disc_price = price * (lit(1.0) - disc)
    charge = disc_price * (lit(1.0) + tax)
    return (df.filter(col("l_shipdate") <= lit(days(1998, 9, 2), T.DATE))
            .group_by("l_returnflag", "l_linestatus")
            .agg(sum_(qty).alias("sum_qty"),
                 sum_(price).alias("sum_base_price"),
                 sum_(disc_price).alias("sum_disc_price"),
                 sum_(charge).alias("sum_charge"),
                 avg(qty).alias("avg_qty"),
                 avg(price).alias("avg_price"),
                 avg(disc).alias("avg_disc"),
                 count().alias("count_order"))
            .order_by("l_returnflag", "l_linestatus"))


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """A small LINEITEM, two files of one row group each, its session and
    its frame for the references."""
    from spark_rapids_tpu.api.session import TpuSession
    rows = 65_536
    files = datagen.write_table(str(tmp_path_factory.mktemp("li")), lineitem,
                                "lineitem", rows, 2, rows // 2, 2**31 + 44,
                                rows / 6_001_215)
    cols = sorted(set(q6.COLUMNS) | set(q1.COLUMNS))
    return types.SimpleNamespace(
        files=files, frame=datagen.read_frame(files, cols),
        session=TpuSession({"spark.rapids.sql.enabled": "true"}))


def _plan_text(df) -> str:
    """The physical plan node for node, with every expression."""
    return df.physical_plan().tree_string()


def _fingerprint(df) -> str:
    """The logical plan's canonical hash, as the result cache keys it."""
    from spark_rapids_tpu.serving.cache import plan_fingerprint
    return plan_fingerprint(df.plan, {})[0]


@pytest.mark.parametrize("mod,before", [(q6, _q6_before), (q1, _q1_before)])
def test_the_default_plan_is_the_one_before(table, mod, before):
    df = table.session.read_parquet(*table.files)
    assert _plan_text(mod.build(df)) == _plan_text(before(df))
    assert "lit(" in _plan_text(before(df))
    assert _fingerprint(mod.build(df)) == _fingerprint(before(df))
    # and a substitution set is another plan, and another key of the cache
    other = {"year": 1995} if mod is q6 else {"delta": 61}
    assert _plan_text(mod.build(df, **other)) != _plan_text(before(df))
    assert _fingerprint(mod.build(df, **other)) != _fingerprint(before(df))


@pytest.mark.parametrize("mod,sub", [
    (q6, {}), (q6, {"year": 1993, "discount": 2, "quantity": 25}),
    (q6, {"year": 1997, "discount": 9, "quantity": 24}),
    (q1, {"delta": 60}), (q1, {"delta": 120}), (q1, {"delta": 97})])
def test_each_substitution_set_agrees_with_its_reference(table, mod, sub):
    got = mod.build(table.session.read_parquet(*table.files), **sub).collect()
    want = mod.reference(table.frame, **sub)
    assert compare.answer_gap(got, want, getattr(mod, "ORDERED", False)) \
        [0] is True
    assert compare.answer_gap(got, want)[1] < 1e-12
    assert want != [(None,)]
    if sub:     # another answer than the validation values'
        assert want != mod.reference(table.frame)


# -- the served runner, rehearsed ---------------------------------------------

def _open_cell(monkeypatch, tmp_path, traffic_spec, session_conf):
    """A cell of an open traffic file over the SF1 configuration with
    another ``session_conf``, from a temporary ``BENCHMARK.json``."""
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    cfg = next(c for c in bench["configs"]
               if c["name"] == "tpch_sf1_lineitem_parquet")
    config = run.load_json(run.os.path.join(run.ROOT, cfg["file"]))
    config["session_conf"] = dict(config["session_conf"], **session_conf)
    (tmp_path / "config.json").write_text(json.dumps(config))
    bench["configs"].append(dict(cfg, name="served",
                                 file=str(tmp_path / "config.json")))
    bench["workloads"].append({"name": "served_rehearsal", "config": "served",
                               "traffic": "open_rehearsal", "chips": 1,
                               "why": "a rehearsal"})
    path = _write(tmp_path, traffic_spec)
    real = run.traffic_path
    monkeypatch.setattr(run, "traffic_path", lambda name: (
        path if name == "open_rehearsal" else real(name)))
    monkeypatch.setattr(run, "load_cell",
                        lambda name: run.Cell(bench, name))


def _args(seconds, trace=0, seed=2**31 + 45):
    return types.SimpleNamespace(workload="served_rehearsal", seed=seed,
                                 seconds=seconds, trace=trace, rows=65_536)


def test_an_open_rehearsal_counts_a_stall_from_the_due_time(
        monkeypatch, tmp_path, capfd):
    """One slot and no room to wait: the first query stalls for a second;
    the arrivals of its substitution set due meanwhile wait for it and show
    the stall in their latency, those of the other set are refused."""
    from spark_rapids_tpu.serving import admission
    _open_cell(monkeypatch, tmp_path, {
        "loop": "open", "rate_qps": 10.0,
        "tenants": [{"name": "t", "weight": 1, "priority": 0}],
        "mix": [{"query": "q6", "weight": 1, "distinct": 2}]},
        {"spark.rapids.serving.maxConcurrentQueries": "1",
         "spark.rapids.serving.queue.maxDepth": "0"})
    stall, real_call = [], admission.LocalSessionRunner.__call__

    def stalls_once(self, plan, ctx):
        if not stall:
            stall.append(time.perf_counter())
            time.sleep(1.0)
            stall.append(time.perf_counter())
        return real_call(self, plan, ctx)
    monkeypatch.setattr(admission.LocalSessionRunner, "__call__",
                        stalls_once)
    seen, real_loop = {}, run.open_loop

    def spy(served, schedule, seconds):
        seen["window"] = out = real_loop(served, schedule, seconds)
        return out
    monkeypatch.setattr(run, "open_loop", spy)

    r = run.run(_args(2.5), rehearsal=True)
    window, t_start, t_end = seen["window"]
    assert len(window) == 25 and r["attempted"] == 25
    refused = [q for q in window if q.refused]
    assert refused and {q.refused for q in refused} == {"queue_full"}
    # a refusal is in failed, and is no missing answer
    assert r["failed"] == len(refused)
    assert r["compared"]["answers_missing"]["value"] == 0
    assert r["correct"] is True, r["compared"]
    # coordinated omission: an arrival due during the stall counts the
    # stall from its due time, not from when it got through
    during = [q for q in window if stall[0] < q.t0 < stall[1] - 0.2
              and q.refused is None]
    assert during
    for q in during:
        assert q.t1 >= stall[1] and q.t1 - q.t0 >= stall[1] - q.t0
        assert q.fired - q.t0 < 0.1      # fired on time all the same
    assert max(q.t1 - q.t0 for q in during) > 0.5
    assert r["metrics"]["query_p95_ms"]["value"] > 500 \
        if "query_p95_ms" in r["metrics"] else True
    line = next(json.loads(ln) for ln in capfd.readouterr().err.splitlines()
                if ln.startswith('{"phase": "window"'))
    assert line["arrivals"] == 25 and line["refused"] == {
        "queue_full": len(refused)}
    assert line["answered"] == 25 - len(refused)
    assert line["cache_hits"] + line["executed"] == line["answered"]
    assert line["by_tenant"]["t"]["refused"] == line["refused"]
    assert line["compiles_in_window"] == 0
    assert set(line["lateness_s"]) == {"p50", "p99", "max"}


def test_an_open_rehearsal_traced_with_two_queries(monkeypatch, tmp_path):
    _open_cell(monkeypatch, tmp_path, dict(
        OPEN, rate_qps=3.0, mix=[dict(OPEN["mix"][0], distinct=4),
                                 dict(OPEN["mix"][1], distinct=2)]), {})
    seen, real_slice = {}, run.traced_slice

    def spy(loop, trace_dir):
        seen["slice"] = out = real_slice(loop, trace_dir)
        return out
    monkeypatch.setattr(run, "traced_slice", spy)
    r = run.run(_args(2.0, trace=1), rehearsal=True)
    assert r["correct"] is True and r["failed"] == 0, r["compared"]
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "breakdown", "compared"]
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < r["device"]["busy_s"] < r["device"]["window_s"]
    # the per-query readers count the arrivals that ran, not the cache's
    ran = [q for q in seen["slice"]["queries"] if q.service_s is not None]
    assert 0 < len(ran) < len(seen["slice"]["queries"])
    assert r["metrics"]["launches_per_query"]["value"] == pytest.approx(
        seen["slice"]["launches"] / len(ran))


def test_an_error_in_the_served_path_is_a_missing_answer(monkeypatch,
                                                         tmp_path):
    from spark_rapids_tpu.serving import admission
    _open_cell(monkeypatch, tmp_path, dict(
        OPEN, mix=[{"query": "q6", "weight": 1}]), {})

    def boom(self, plan, ctx):
        raise RuntimeError("planted")
    monkeypatch.setattr(admission.LocalSessionRunner, "__call__", boom)
    r = run.run(_args(1.0), rehearsal=True)
    assert r["correct"] is False and r["failed"] == r["attempted"] == 4
    assert r["compared"]["answers_missing"]["value"] == 4


# -- the watchdog ------------------------------------------------------------

def test_the_watchdog_writes_every_stack_once_and_the_process_lives():
    out = io.StringIO()
    dog = run.Watchdog(out=out, tick_s=0.02)
    parked = threading.Event()

    def a_stuck_query():
        with dog.watch("query q6", 0.1):
            parked.wait(0.6)

    other = threading.Thread(target=parked.wait, args=(5.0,))
    other.start()
    try:
        a_stuck_query()
        with dog.watch("query q1", 5.0):        # under its limit
            time.sleep(0.1)
    finally:
        parked.set()
        other.join(5.0)
        dog.close()
    text = out.getvalue()
    assert dog.dumps == 1 and text.count("run.py: stall:") == 1
    assert "query q6 has run" in text and "query q1" not in text
    assert "a_stuck_query" in text          # the stalled thread's own stack
    assert text.count("-- thread ") >= 3    # and every other thread's
    assert not dog._thread.is_alive() and not other.is_alive()


def test_the_stall_limit_has_a_floor():
    assert run.stall_limit(0.308) == run.STALL_FLOOR_S == 10.0
    assert run.stall_limit(8.33) == pytest.approx(24.99)


def test_a_run_arms_no_faulthandler_and_dumps_once_a_query(monkeypatch,
                                                           capfd):
    import faulthandler

    def armed(*a, **kw):
        raise AssertionError("faulthandler armed")
    monkeypatch.setattr(faulthandler, "dump_traceback_later", armed)
    # every query after the first is over its limit
    monkeypatch.setattr(run, "STALL_FLOOR_S", 0.0)
    monkeypatch.setattr(run, "STALL_FACTOR", 0.01)
    monkeypatch.setattr(run, "WATCH_TICK_S", 0.001)
    r = run.run(types.SimpleNamespace(
        workload="q6_parquet_sf1", seed=2**31 + 46, seconds=1.0, trace=0,
        rows=65_536), rehearsal=True)
    assert r["correct"] is True
    err = capfd.readouterr().err
    line = next(json.loads(ln) for ln in err.splitlines()
                if ln.startswith('{"phase": "window"'))
    assert 0 < line["stall_dumps"] <= line["queries"]
    assert err.count("run.py: stall:") == line["stall_dumps"]


def test_a_warm_up_that_compiles_is_not_watched(monkeypatch, tmp_path,
                                                capfd):
    """Each substitution set is a program of its own, so an open cell's
    warm-up compiles again and again: only the loops are watched."""
    _open_cell(monkeypatch, tmp_path, dict(
        OPEN, mix=[dict(OPEN["mix"][0], distinct=3, zipf=0)]), {})
    monkeypatch.setattr(run, "STALL_FLOOR_S", 0.0)
    monkeypatch.setattr(run, "STALL_FACTOR", 0.01)
    monkeypatch.setattr(run, "WATCH_TICK_S", 0.001)
    r = run.run(_args(1.0), rehearsal=True)
    assert r["correct"] is True
    err = capfd.readouterr().err
    warm = err.rindex('{"phase": "warm_up"')
    assert err.count('{"phase": "warm_up"') == 3
    assert "run.py: stall:" not in err[:warm]
