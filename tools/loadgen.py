"""Open-loop load generator for the serving tier.

Coordinated omission is the classic closed-loop lie: a generator that
waits for each completion before submitting the next query slows down
exactly when the system does, so the measured latency distribution
misses the requests that WOULD have queued.  This generator is
open-loop: the whole Poisson arrival schedule is drawn up front from a
seeded RNG, and every arrival fires at its scheduled time on its own
thread regardless of how many submissions are still in flight.  Under
overload the in-flight count grows and the serving tier's protections
(admission queueing, shedding, rate limits, breakers — serving/) must
answer; the per-arrival outcomes record what they answered.

Used by the chaos soak (tests/test_load_soak.py) and by
``bench.py --load`` (BENCH_load_*.json artifacts); also runnable
stand-alone against a self-built mini cluster:

    python tools/loadgen.py --rate 20 --duration 5

Stand-alone it forces JAX_PLATFORMS=cpu unless the variable is already
set: its latencies are then CPU-backend numbers, not device numbers.
"""
from __future__ import annotations

import random
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

#: outcome taxonomy — every arrival lands in exactly one bucket
OUTCOMES = ("ok", "shed", "ratelimited", "breaker", "queue_full",
            "timeout", "cancelled", "error")


def _classify(exc: BaseException) -> str:
    """Map one submission failure onto the outcome taxonomy (typed
    AdmissionRejected reasons pass through verbatim)."""
    from spark_rapids_tpu.serving.admission import AdmissionRejected
    from spark_rapids_tpu.utils.cancel import QueryCancelled
    if isinstance(exc, AdmissionRejected):
        reason = getattr(exc, "reason", "")
        return reason if reason in OUTCOMES else "queue_full"
    if isinstance(exc, QueryCancelled):
        return "cancelled"
    if isinstance(exc, TimeoutError):
        return "timeout"
    return "error"


def poisson_schedule(rate_qps: float, duration_s: float, seed: int,
                     mix: Sequence[Tuple[str, int]]
                     ) -> List[Tuple[float, str, int]]:
    """The arrival plan, drawn entirely up front (open loop): sorted
    ``(t_offset, tenant, priority)`` with exponential inter-arrival
    gaps at ``rate_qps`` and the tenant/priority mix sampled uniformly.
    Deterministic in ``seed``."""
    rng = random.Random(seed)
    out: List[Tuple[float, str, int]] = []
    t = rng.expovariate(rate_qps)
    while t < duration_s:
        tenant, priority = mix[rng.randrange(len(mix))]
        out.append((t, tenant, priority))
        t += rng.expovariate(rate_qps)
    return out


def _percentiles(xs: List[float]) -> Dict[str, float]:
    if not xs:
        return {"p50": 0.0, "p90": 0.0, "p99": 0.0}
    xs = sorted(xs)

    def pick(q):
        return round(xs[min(int(len(xs) * q), len(xs) - 1)], 4)
    return {"p50": pick(0.50), "p90": pick(0.90), "p99": pick(0.99)}


def run_load(submit: Callable[[int, str, int], object],
             rate_qps: float, duration_s: float, seed: int = 0,
             mix: Optional[Sequence[Tuple[str, int]]] = None,
             drain_timeout_s: float = 60.0,
             on_arrival: Optional[Callable[[int], None]] = None) -> dict:
    """Fire the schedule and collect outcomes.

    ``submit(i, tenant, priority)`` runs one submission to completion
    (raising on rejection/failure); it is called from a fresh thread
    per arrival — open loop, no coordination with completions.
    ``on_arrival(i)`` (optional) runs on the pacing thread right
    before arrival ``i`` fires: the chaos soak uses it to kill/revive
    an executor at a known point in the schedule.

    Returns the summary dict (schedule size, offered/achieved rates,
    outcome counts, ok-latency percentiles, per-tenant outcomes, raw
    per-arrival records)."""
    mix = list(mix or [("tenant0", 0), ("tenant1", 2)])
    schedule = poisson_schedule(rate_qps, duration_s, seed, mix)
    records: List[dict] = []
    lock = threading.Lock()
    threads: List[threading.Thread] = []

    def _one(i: int, tenant: str, priority: int, t_sched: float) -> None:
        t0 = time.monotonic()
        outcome = "ok"
        try:
            submit(i, tenant, priority)
        except BaseException as e:  # noqa: BLE001 — taxonomy, not policy
            outcome = _classify(e)
        with lock:
            records.append({"i": i, "t_s": round(t_sched, 4),
                            "tenant": tenant, "priority": priority,
                            "outcome": outcome,
                            "latency_s": round(time.monotonic() - t0, 4)})

    t_start = time.monotonic()
    for i, (at, tenant, priority) in enumerate(schedule):
        delay = at - (time.monotonic() - t_start)
        if delay > 0:
            time.sleep(delay)
        if on_arrival is not None:
            on_arrival(i)
        # tpu-lint: allow-ambient-propagation(each arrival simulates an independent external client; inheriting the pacing thread's ambients is exactly what a fresh client has)
        th = threading.Thread(target=_one,
                              args=(i, tenant, priority, at),
                              daemon=True, name=f"loadgen-{i}")
        th.start()
        threads.append(th)
    deadline = time.monotonic() + drain_timeout_s
    for th in threads:
        th.join(timeout=max(deadline - time.monotonic(), 0.1))
    wall_s = time.monotonic() - t_start

    with lock:
        recs = list(records)
    counts = {o: 0 for o in OUTCOMES}
    for r in recs:
        counts[r["outcome"]] += 1
    ok_lat = [r["latency_s"] for r in recs if r["outcome"] == "ok"]
    per_tenant: Dict[str, Dict[str, int]] = {}
    for r in recs:
        per_tenant.setdefault(r["tenant"],
                              {o: 0 for o in OUTCOMES}
                              )[r["outcome"]] += 1
    return {
        "arrivals": len(schedule),
        "completed": len(recs),
        "unfinished": len(schedule) - len(recs),
        "offered_qps": round(len(schedule) / duration_s, 3),
        "achieved_qps": round(counts["ok"] / wall_s, 3) if wall_s else 0.0,
        "wall_s": round(wall_s, 3),
        "outcomes": counts,
        "ok_latency_s": _percentiles(ok_lat),
        "per_tenant": per_tenant,
        "records": recs,
    }


def _main() -> None:
    """Stand-alone demo: open-loop load against an in-process serving
    queue (LocalSessionRunner over generated lineitem rows), overload
    protections armed.  Prints the summary JSON."""
    import argparse
    import json
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="Runs on the CPU backend unless JAX_PLATFORMS is set: the "
               "latencies it prints are not device numbers.")
    parser.add_argument("--rate", type=float, default=10.0,
                        help="offered arrival rate (queries/second)")
    parser.add_argument("--duration", type=float, default=5.0,
                        help="schedule length (seconds)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rows", type=int, default=1 << 14)
    args = parser.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from spark_rapids_tpu.serving import LocalSessionRunner, QueryQueue
    from spark_rapids_tpu.testing import tpch
    runner = LocalSessionRunner({})
    batches = list(tpch.gen_lineitem(args.rows, batch_rows=args.rows))
    q = QueryQueue(runner, conf={
        "spark.rapids.serving.maxConcurrent": "2",
        "spark.rapids.serving.overload.enabled": "true",
        "spark.rapids.serving.overload.sloP99Seconds": "0.5",
    })

    def submit(i, tenant, priority):
        df = runner.session.create_dataframe(list(batches),
                                             num_partitions=2)
        return q.submit(tpch.q6(df).plan, tenant=tenant,
                        priority=priority, timeout_s=30.0)

    out = run_load(submit, args.rate, args.duration, seed=args.seed)
    out.pop("records")
    print(json.dumps(out, indent=2))
    q.close()


if __name__ == "__main__":
    _main()
