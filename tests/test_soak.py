"""Chaos soak: elastic durable shuffle at 6+ ranks under kill/revive,
a chaos-delayed straggler, speculation, pipelining and the stall
watchdog — all at once (ISSUE 10 satellite; ROADMAP item 4 soak).

Slow-marked: tier-1 skips it by budget; ``python tools/run_suites.py
soak`` runs it (the suite carries a marker override).

The scenario (seeded/event-gated, no wall-clock randomness):

  * 6 protocol-level executors with REAL shuffle nodes, replication=2,
    speculation + pipelining ON, watchdog armed at a generous threshold;
  * rank 5's executor is KILLED mid-query after its map commit
    replicated; a fresh executor REVIVES (joins mid-session) and adopts
    the re-dispatched rank;
  * rank 4 is a seeded chaos-delayed straggler (cluster.task.delay),
    giving the speculation path live traffic in the same run.

Counters must prove the recovery was a replica RE-FETCH plus one rank
re-dispatch — never a whole-query re-execution — and that NOTHING
stalled: ``blocks_refetched_replica > 0``, ``scoped_resubmits == 0``,
``watchdog_stalls == 0`` with the watchdog genuinely armed.
"""
import threading
import time

import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.shuffle.net import (
    TcpShuffleTransport, connection_pool, set_network_retry)
from spark_rapids_tpu.shuffle.stats import (
    reset_shuffle_counters, shuffle_counters)
from spark_rapids_tpu.testing.chaos import CHAOS
from spark_rapids_tpu.utils.watchdog import WATCHDOG

from test_cancel import _ProtoExecutor

SCHEMA = Schema.of(k=T.INT, v=T.LONG)
N = 240
WORLD = 6


@pytest.fixture(autouse=True)
def _clean():
    CHAOS.clear()
    reset_shuffle_counters()
    set_network_retry(2, 0.01, 0.05)
    WATCHDOG.configure(15.0, cancel_on_stall=False)
    yield
    CHAOS.clear()
    WATCHDOG.configure(0.0, False)
    WATCHDOG.reset()
    set_network_retry(4, 0.05, 2.0)
    connection_pool().close_all()


def _share(rank: int, world: int):
    return [i for i in range(N) if (i // 10) % world == rank]


def _pbatch(vals):
    return ColumnarBatch.from_pydict(
        {"k": [v % 3 for v in vals], "v": list(vals)}, SCHEMA)


def _transport(node, task):
    node.heartbeat()
    return TcpShuffleTransport(
        node, 2, SCHEMA, shuffle_id=(task["query_id"] << 16) | 0,
        participants=task["participants"],
        attempt=task.get("attempt", 0), logical_id=task.get("as"),
        replication=2, completeness_timeout_s=60)


def _write_share(t, task):
    vals = _share(task["rank"], task["world"])
    t.write([(0, _pbatch([v for v in vals if v < N // 2])),
             (1, _pbatch([v for v in vals if v >= N // 2]))])


def _reduce_rows(t, task):
    out = []
    for p in range(2):
        if p % task["world"] != task["rank"]:
            continue
        vals = []
        for b in t.read(p):
            vals.extend(int(v) for v in b.to_pydict()["v"])
        out.append((p, [[v] for v in sorted(vals)]))
    return out


@pytest.mark.slow
def test_soak_kill_revive_delay_under_replication_and_speculation():
    from spark_rapids_tpu.cluster.driver import TpuClusterDriver
    driver = TpuClusterDriver(
        conf={"spark.rapids.shuffle.replication.factor": "2",
              "spark.rapids.cluster.speculation.enabled": "true",
              "spark.rapids.cluster.speculation.minTasks": "2",
              "spark.rapids.cluster.speculation.multiplier": "3.0"},
        heartbeat_timeout_s=0.7)
    died = threading.Event()
    workers = []
    revived = []

    def behavior(ex, task):
        # the seeded straggler: rank 4's primary attempt serves the
        # injected delay (a speculation/redispatch copy must not)
        if task["rank"] == 4 and task.get("attempt", 0) == 0:
            CHAOS.delay("cluster.task.delay")
        t = _transport(ex.node, task)
        _write_share(t, task)
        if task["rank"] == 5 and task.get("attempt", 0) == 0 \
                and ex.name == "w5":
            # durable FIRST, then die: the whole point is that loss
            # after the commit costs a re-fetch, not a re-execution
            assert ex.node.wait_replicated((task["query_id"] << 16) | 0,
                                           15)
            died.set()
            return "die"
        if task["rank"] in (0, 1):
            # the reduce owners wait out the death + registry aging so
            # their reads exercise the replica failover path
            died.wait(30)
            time.sleep(1.0)
        return _reduce_rows(t, task)

    try:
        for i in range(WORLD):
            workers.append(_ProtoExecutor(driver, f"w{i}", behavior))
        driver.wait_for_executors(WORLD, timeout_s=30)
        CHAOS.install("cluster.task.delay", count=1, seconds=1.2,
                      seed=11)

        # REVIVE: once the kill lands, a fresh executor joins
        # mid-session and becomes the natural re-dispatch target
        def revive():
            died.wait(60)
            revived.append(_ProtoExecutor(driver, "w6", behavior))
        rt = threading.Thread(target=revive, daemon=True)
        rt.start()

        rows = driver.submit({"soak": True}, timeout_s=120,
                             max_retries=2)
        assert [list(r) for r in rows] == [[v] for v in range(N)]
        assert died.is_set()
        c = shuffle_counters()
        assert c["blocks_replicated"] > 0
        assert c["blocks_refetched_replica"] > 0, \
            "loss must be served by replica re-fetch"
        assert c["scoped_resubmits"] == 0, \
            "durable loss must not re-execute the whole query"
        # the dead rank recovered through a SINGLE-rank second attempt —
        # a post-loss re-dispatch or a straggler speculation copy,
        # whichever won the detection race — never a query resubmit
        assert c["rank_redispatches"] + c["speculative_launches"] >= 1
        assert c["executors_joined"] >= 1      # the revive joined live
        # fired_count, not delayed_seconds: a speculation copy of the
        # delayed rank can win first-result-wins while the primary is
        # STILL inside the injected sleep (delayed_seconds records only
        # after the sleep completes)
        assert CHAOS.fired_count("cluster.task.delay") >= 1
        # the watchdog was ARMED the whole run and saw nothing stall
        assert c["watchdog_stalls"] == 0
        assert c["queries_cancelled"] == 0
    finally:
        rt.join(timeout=5)
        for w in workers + revived:
            w.close()
        driver.close()
