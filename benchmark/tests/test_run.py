"""The rest of a run with the look for a chip skipped (``run.run`` on the
CPU at small sizes): the result's keys, the references against the engine
at one batch and at several (where the plan is the fused one), and
``correct`` coming out false for each fault the cells can have and for the
control (the reference in the program's place, in float32)."""
import json
import types

import pytest

from benchmark import compare, datagen, run

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _args(workload, rows, trace=0, seed=2**31 + 5, seconds=0.5):
    return types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace, rows=rows)


@pytest.mark.parametrize("workload", ["q6_parquet_sf1", "q1_parquet_sf1"])
def test_one_batch_equals_the_reference_and_the_line_has_the_keys(workload):
    r = run.run(_args(workload, 65_536), rehearsal=True)
    assert list(r) == RESULT_KEYS + ["compared"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    want = {m["name"] for m in run.load_cell(workload).end_to_end}
    assert set(r["metrics"]) == want
    assert all(set(m) == {"value", "unit"} for m in r["metrics"].values())
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    json.dumps(r)


def test_traced_run_reports_per_layer_metrics_and_a_breakdown():
    r = run.run(_args("q6_parquet_sf1", 65_536, trace=1), rehearsal=True)
    assert list(r) == RESULT_KEYS + ["breakdown", "compared"]
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert 0 < r["device"]["busy_s"] < r["device"]["window_s"]
    # on the CPU: no peak for the roofline, no memory statistics; the
    # readers that later PRs add come on top
    assert {"queries_completed", "scan_wait_pct", "scan_upload_pct",
            "launches_per_query", "compiles_in_window", "oom_retries",
            "device_idle_pct"} <= set(r["metrics"])
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in r["breakdown"].values())


@pytest.mark.parametrize("workload,nodes", [
    ("q6_parquet_sf1", {"TpuFusedSegment"}),
    ("q1_parquet_sf1", {"TpuFusedSegment", "TpuShuffleExchange"})])
def test_several_batches_fused_plan_equals_the_reference(workload, nodes,
                                                         capfd):
    r = run.run(_args(workload, 3 * 1_048_576 + 17, seconds=0.1),
                rehearsal=True)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["float_gap"]["value"] < 1e-12
    warm = [json.loads(ln) for ln in capfd.readouterr().err.splitlines()
            if ln.startswith('{"phase": "warm_up"')]
    assert nodes <= set(warm[0]["plan"]), warm[0]["plan"]


def test_a_new_cell_is_one_entry_of_data(monkeypatch):
    """``q1_parquet_sf10`` (the SF10 configuration under the q1 traffic) as
    a later PR would add it: an entry under ``workloads``, no code."""
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    bench["workloads"].append({
        "name": "q1_parquet_sf10", "config": "tpch_sf10_lineitem_parquet",
        "traffic": "q1_closed_1", "chips": 1, "why": "long aggregates"})
    monkeypatch.setattr(run, "load_cell", lambda name: run.Cell(bench, name))
    r = run.run(_args("q1_parquet_sf10", 65_536), rehearsal=True)
    assert r["correct"] is True
    # query_p95_ms lists its cells, and the new cell is not among them
    assert set(r["metrics"]) == {"rows_per_s", "setup_s"}


# -- faults: each has to come out as not correct ----------------------------

def _with_fault(monkeypatch, fault):
    real = run.Client.run

    def broken(self, qname, sub=None):
        q = real(self, qname, sub)
        return fault(self, q) or q
    monkeypatch.setattr(run.Client, "run", broken)


def test_a_float_altered_where_it_is_produced(monkeypatch):
    def fault(self, q):
        row = list(q.answer[0])
        i = next(i for i, v in enumerate(row) if isinstance(v, float))
        row[i] *= 1.0 + 1e-6
        q.answer = [tuple(row)] + q.answer[1:]
    _with_fault(monkeypatch, fault)
    r = run.run(_args("q1_parquet_sf1", 65_536), rehearsal=True)
    assert r["correct"] is False
    assert r["compared"]["float_gap"]["value"] > 1e-7
    assert r["compared"]["answers_wrong"]["value"] == 0


def test_a_count_altered_where_it_is_produced(monkeypatch):
    def fault(self, q):
        row = list(q.answer[0])
        row[-1] += 1
        q.answer = [tuple(row)] + q.answer[1:]
    _with_fault(monkeypatch, fault)
    r = run.run(_args("q1_parquet_sf1", 65_536), rehearsal=True)
    assert r["correct"] is False
    assert r["compared"]["answers_wrong"]["value"] == r["attempted"]


def test_half_of_the_input_left_out(monkeypatch):
    real = run.Client.__init__

    def init(self, cell, files, rows, **kw):
        real(self, cell, {t: ps[:1] for t, ps in files.items()}, rows, **kw)
    monkeypatch.setattr(run.Client, "__init__", init)
    # two row groups, so two files: the second is left out
    r = run.run(_args("q6_parquet_sf1", 1_048_576 + 65_536, seconds=0.1),
                rehearsal=True)
    assert r["correct"] is False
    assert r["compared"]["float_gap"]["value"] > 0.01


def test_a_query_that_raises_is_missing_not_skipped(monkeypatch):
    def fault(self, q):
        q.answer, q.error = None, "Boom: planted"
    _with_fault(monkeypatch, fault)
    r = run.run(_args("q6_parquet_sf1", 65_536), rehearsal=True)
    assert r["correct"] is False and r["failed"] == r["attempted"] > 0
    assert r["compared"]["answers_missing"]["value"] == r["failed"]


@pytest.mark.parametrize("workload", ["q6_parquet_sf1", "q1_parquet_sf1"])
@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
def test_the_control_in_float32_is_not_correct(monkeypatch, workload, seed):
    """The reference, computed in float32, put in the program's place."""
    def control(self, qname, sub=None):
        mod = self.cell.queries[qname]
        q = self.query(qname, sub or {}, run.time.perf_counter())
        q.answer = mod.reference(datagen.read_frame(
            self.files[mod.TABLE], mod.COLUMNS, "float32"), **(sub or {}))
        q.t1 = run.time.perf_counter()
        q.service_s = q.t1 - q.t0
        return q
    monkeypatch.setattr(run.Client, "run", control)
    r = run.run(_args(workload, 262_144, seed=seed, seconds=0.05),
                rehearsal=True)
    assert r["compared"]["answers_wrong"]["value"] == 0
    assert r["compared"]["float_gap"]["value"] > \
        3 * r["compared"]["float_gap"]["limit"]
    assert r["correct"] is False


def test_two_clients_each_in_a_closed_loop_and_the_mix_keeps_its_weights():
    spec = {"loop": "closed", "clients": 2,
            "mix": [{"query": "a", "weight": 3}, {"query": "b", "weight": 1}]}

    class Fake:
        def run(self, qname):
            t = run.time.perf_counter()
            return run.Query(name=qname, error=None, t0=t,
                             t1=run.time.perf_counter())
    done, t0, t1 = run.closed_loop(Fake(), spec, 2**31 + 1,
                                   lambda elapsed, n: n >= 8)
    assert len(done) == 16 and t0 <= done[0].t1 <= done[-1].t1 == t1
    assert sorted(q.name for q in done) == ["a"] * 12 + ["b"] * 4
    first = [n for n, _ in zip(run.traffic.client_stream(spec, 5, 0),
                               range(8))]
    again = [n for n, _ in zip(run.traffic.client_stream(spec, 5, 0),
                               range(8))]
    other = [n for n, _ in zip(run.traffic.client_stream(spec, 6, 1),
                               range(40))]
    assert first == again and other.count("b") == 10


def test_rows_out_of_order_fail_a_query_with_an_order_by(monkeypatch):
    def fault(self, q):
        q.answer = q.answer[::-1]
    _with_fault(monkeypatch, fault)
    r = run.run(_args("q1_parquet_sf1", 65_536), rehearsal=True)
    assert r["correct"] is False
    assert r["compared"]["answers_wrong"]["value"] == r["attempted"]


def test_compare_rules():
    want = [(1, 2.0, 7), (2, 4.0, 9)]
    assert compare.answer_gap([(2, 4.0, 9), (1, 2.0, 7)], want) == (True, 0.0)
    assert compare.answer_gap([(1, 2.0, 7)], want)[0] is False
    assert compare.answer_gap(None, want)[0] is False
    assert compare.answer_gap([(2, 4.0, 9), (1, 2.0, 7)], want,
                              ordered=True)[0] is False
    assert compare.answer_gap(list(want), want, ordered=True) == (True, 0.0)
    assert compare.answer_gap([(1, float("nan"), 7), (2, 4.0, 9)],
                              want)[1] == float("inf")
    c = compare.compare([(("q", ()), want)], {("q", ()): want},
                        {"float_gap": 1e-9},
                        fallback_nodes=1, missing=0)
    assert compare.is_correct(c) is False
