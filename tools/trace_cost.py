"""What one ``trace_range`` costs on this host, in nanoseconds.

    python tools/trace_cost.py [--spans 100000] [--out FILE]

Two readings over a loop of empty spans: every sink off (what a timed run
pays), then ``span_log`` on under ``jax.profiler`` with an ambient
``QueryTrace`` (what a traced run pays).  Then the sampler thread
(``tracing.StackSampler``, which runs only while the span log is on or a
profile is held): a loop of plain bytecode timed with it off and with it
asking for the interpreter lock every 5 ms, and what it wrote meanwhile.
Host numbers: they say nothing of the device, and are comparable only
between runs on one machine.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def ns_per_span(n: int) -> float:
    from spark_rapids_tpu.utils.tracing import trace_range
    t0 = time.perf_counter_ns()
    for _ in range(n):
        with trace_range("fused.batch"):
            pass
    return (time.perf_counter_ns() - t0) / n


def busy_ns(n: int) -> float:
    """A loop of plain bytecode: what a thread that computes in Python pays
    for the sampler's ticks, each of which takes the lock from it."""
    t0 = time.perf_counter_ns()
    x = 0
    for i in range(n):
        x += i & 7
    return time.perf_counter_ns() - t0


def sampler_cost(n: int) -> dict:
    from spark_rapids_tpu.utils import tracing
    busy_ns(n // 10)
    off = min(busy_ns(n) for _ in range(3))
    tracing.span_log.clear()
    tracing.span_log.enabled = True
    tracing.sampler.ensure_running()
    try:
        on = min(busy_ns(n) for _ in range(3))
    finally:
        tracing.span_log.enabled = False
    waits = [t1 - t0 for name, t0, t1 in tracing.span_log.snapshot()
             if name == "host.lock_wait"]
    tracing.span_log.clear()
    return {"busy_loop_ms_sampler_off": off / 1e6,
            "busy_loop_ms_sampler_on": on / 1e6,
            "sampler_slowdown_pct": 100.0 * (on - off) / off,
            "lock_wait_spans": len(waits),
            "lock_wait_longest_ms": 1e3 * max(waits, default=0.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spans", type=int, default=100_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax.profiler

    from spark_rapids_tpu.utils import obs, tracing
    ns_per_span(1000)                       # imports, first-call set-up
    result = {"spans": args.spans, "sinks_off_ns": ns_per_span(args.spans)}
    trace = obs.QueryTrace("trace_cost", max_spans=args.spans)
    with tempfile.TemporaryDirectory() as d:
        tracing.span_log.enabled = True
        jax.profiler.start_trace(d)
        try:
            with obs.trace_scope(trace):
                result["sinks_on_ns"] = ns_per_span(args.spans)
        finally:
            jax.profiler.stop_trace()
            tracing.span_log.enabled = False
            tracing.span_log.clear()
    result.update(sampler_cost(100 * args.spans))
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
