"""Targeted suite runner: the practical verify loop for this container.

The 870s tier-1 slice covers ~10% of the test suite on this machine
(ROADMAP container notes), so builders verify touched areas with
targeted per-suite runs.  This tool records those suites ONCE — files,
per-suite timeout — and runs any subset serially with a summary table,
so "run the shuffle and cluster suites" stops being a hand-maintained
shell history.

Run:
    python tools/run_suites.py                  # every suite
    python tools/run_suites.py shuffle cluster  # a subset
    python tools/run_suites.py --list
    python tools/run_suites.py --timeout-scale 2.0   # slow container

Every suite runs with JAX_PLATFORMS=cpu forced: these are correctness
runs on XLA's CPU backend, and the seconds in the summary table are suite
wall-clock there, never device numbers.

Exit code: number of failing suites (0 = all green).
"""
from __future__ import annotations

import argparse
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: suite -> (test files, timeout seconds[, marker override]).  Timeouts
#: are ~2x observed wall on this container's CPU backend (memory: ~5x
#: slower than the r5-era machines); --timeout-scale adjusts them
#: wholesale.  A suite with a marker override ignores -m (the pipeline
#: suite runs its slow-marked tests, which tier-1 skips by budget).
SUITES = {
    "shuffle": (["tests/test_net_shuffle.py", "tests/test_range_shuffle.py",
                 "tests/test_chaos.py", "tests/test_elastic.py"], 600),
    "query": (["tests/test_queries.py", "tests/test_tpch.py",
               "tests/test_tpcds.py"], 900),
    "cluster": (["tests/test_cluster.py", "tests/test_distributed.py",
                 "tests/test_ici_exchange.py"], 900),
    "fused": (["tests/test_fused.py", "tests/test_spmd_stage.py"], 600),
    "ooc": (["tests/test_out_of_core.py",
             "tests/test_out_of_core_joins_full.py",
             "tests/test_memory.py"], 900),
    "gauntlet": (["tests/test_tpcds_gauntlet.py"], 1200),
    "serving": (["tests/test_serving.py", "tests/test_agg_tail.py",
                 "tests/test_cancel.py"], 900),
    # cancellation alone (the serving suite's slowest cohabitant): a
    # focused target for the sanitizer's ambient/teardown contracts
    "cancel": (["tests/test_cancel.py"], 600),
    "pipeline": (["tests/test_fused_shuffle.py", "tests/test_fused.py",
                  "tests/test_aqe_coalesce.py"], 1200, ""),
    # slow-marked chaos soaks (kill/revive/delay at 6+ ranks under
    # replication + speculation + watchdog, plus the open-loop load
    # soak with autoscaler + overload protections armed): marker
    # override runs what tier-1 skips by budget
    "soak": (["tests/test_soak.py", "tests/test_load_soak.py"], 1200, ""),
    # closed-loop elasticity + overload protection (ISSUE 19): policy
    # units, shed/ratelimit/breaker, drain handshake, tier-1 mini-soak
    "elasticity": (["tests/test_autoscaler.py", "tests/test_overload.py",
                    "tests/test_load_soak.py"], 600),
    # launches by program name (launch_stats()["by_program"], the names
    # the device trace shows) + the CACHE_ONLY range-view store
    "profile": (["tests/test_prog_profile.py",
                 "tests/test_range_views.py"], 900),
    # observability: the query-scoped plane (trace context + counter
    # attribution, cross-process span round-trip, EXPLAIN ANALYZE,
    # Perfetto export, latency histograms — utils/obs.py) AND the
    # continuous resource plane (sampler ring, heartbeat piggyback,
    # Prometheus scrape, flight-recorder post-mortems — utils/telemetry)
    "observability": (["tests/test_obs.py",
                       "tests/test_prog_profile.py",
                       "tests/test_telemetry.py"], 900),
    "lint": (["tests/test_lint.py", "tests/test_ambient.py",
              "tests/test_lint_interproc.py",
              "tests/test_sanitizer.py"], 300),
}

#: suites that run with the runtime contract sanitizer armed
#: (SPARK_RAPIDS_TPU_SANITIZE=1, utils/sanitizer.py) unless
#: --no-sanitize: the shuffle/serving/cancel paths are where the pin/
#: lock/ambient contracts the sanitizer witnesses actually concentrate.
SANITIZE_SUITES = {"shuffle", "serving", "cancel", "soak", "elasticity"}

#: extra commands run (and required green) after a suite's pytest pass.
#: The lint suite also runs the CLI with --timing so the per-rule wall
#: clock shows up in every `run_suites.py lint` report — the flow rules
#: (pin-balance etc.) must stay affordable in tier-1.
POST_CMDS = {
    "lint": [[sys.executable, "-m", "tools.tpulint", "--timing"]],
}

def _parse_tail(tail: str):
    """(passed, failed, skipped) from pytest's summary line, best
    effort — a crashed run reports (0, 0, 0) and the exit code rules."""
    for line in reversed(tail.splitlines()):
        if " passed" in line or " failed" in line or " error" in line:
            passed = failed = skipped = 0
            m = re.search(r"(\d+) passed", line)
            passed = int(m.group(1)) if m else 0
            m = re.search(r"(\d+) failed", line)
            failed = int(m.group(1)) if m else 0
            m = re.search(r"(\d+) skipped", line)
            skipped = int(m.group(1)) if m else 0
            m = re.search(r"(\d+) error", line)
            failed += int(m.group(1)) if m else 0
            return passed, failed, skipped
    return 0, 0, 0


def run_suite(name: str, files, timeout_s: float, extra_args,
              sanitize: bool = False):
    cmd = [sys.executable, "-m", "pytest", "-q",
           "-p", "no:cacheprovider", *files, *extra_args]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if sanitize:
        env["SPARK_RAPIDS_TPU_SANITIZE"] = "1"
    t0 = time.monotonic()
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT,
                              timeout=timeout_s)
        out = proc.stdout.decode("utf-8", "replace")
        rc = proc.returncode
        timed_out = False
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"").decode("utf-8", "replace")
        rc, timed_out = -1, True
    wall = time.monotonic() - t0
    passed, failed, skipped = _parse_tail(out[-4000:])
    status = ("TIMEOUT" if timed_out
              else "PASS" if rc == 0
              else "FAIL")
    return {"suite": name, "status": status, "passed": passed,
            "failed": failed, "skipped": skipped, "wall_s": wall,
            "rc": rc, "tail": out[-2500:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog="Forces JAX_PLATFORMS=cpu for every suite: the seconds it "
               "prints are CPU-backend test wall-clock, not device numbers.")
    ap.add_argument("suites", nargs="*",
                    help=f"subset to run (default all): {sorted(SUITES)}")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--timeout-scale", type=float, default=1.0,
                    help="multiply every suite timeout (slow containers)")
    ap.add_argument("--verbose", action="store_true",
                    help="print each suite's output tail even on PASS")
    ap.add_argument("-m", dest="marker", default="not slow",
                    help="pytest -m expression (default: 'not slow')")
    ap.add_argument("--no-sanitize", action="store_true",
                    help="do not arm the runtime contract sanitizer for "
                         f"the {sorted(SANITIZE_SUITES)} suites")
    args = ap.parse_args(argv)
    if args.list:
        for name, spec in SUITES.items():
            files, tmo = spec[0], spec[1]
            print(f"{name:10s} {tmo:5d}s  {' '.join(files)}")
        return 0
    names = args.suites or list(SUITES)
    unknown = [n for n in names if n not in SUITES]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; known: {sorted(SUITES)}")
    results = []
    for name in names:
        spec = SUITES[name]
        files, tmo = spec[0], spec[1]
        marker = spec[2] if len(spec) > 2 else args.marker
        extra = ["-m", marker] if marker else []
        missing = [f for f in files
                   if not os.path.exists(os.path.join(REPO, f))]
        if missing:
            # a renamed test file must FAIL the suite loudly — silently
            # narrowing it (or worse, handing pytest zero file args and
            # collecting the whole repo) would report the wrong thing
            # under this suite's name
            print(f"== {name} ==\n   -> FAIL (missing files: {missing})",
                  flush=True)
            results.append({"suite": name, "status": "FAIL", "passed": 0,
                            "failed": 0, "skipped": 0, "wall_s": 0.0,
                            "rc": 2, "tail": f"missing files: {missing}"})
            continue
        sanitize = name in SANITIZE_SUITES and not args.no_sanitize
        print(f"== {name} ({len(files)} files, "
              f"timeout {int(tmo * args.timeout_scale)}s"
              f"{', sanitized' if sanitize else ''}) ==", flush=True)
        r = run_suite(name, files, tmo * args.timeout_scale, extra,
                      sanitize=sanitize)
        for cmd in POST_CMDS.get(name, ()):
            try:
                post = subprocess.run(cmd, cwd=REPO,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT,
                                      timeout=tmo * args.timeout_scale)
                print(post.stdout.decode("utf-8", "replace"), flush=True)
                if post.returncode != 0 and r["status"] == "PASS":
                    r["status"], r["rc"] = "FAIL", post.returncode
            except (OSError, subprocess.TimeoutExpired) as e:
                print(f"post command {cmd} failed: {e}", flush=True)
                if r["status"] == "PASS":
                    r["status"], r["rc"] = "FAIL", 2
        results.append(r)
        if r["status"] != "PASS" or args.verbose:
            print(r["tail"])
        print(f"   -> {r['status']} ({r['passed']} passed, "
              f"{r['failed']} failed, {r['skipped']} skipped, "
              f"{r['wall_s']:.0f}s)", flush=True)
    print("\n| suite | status | passed | failed | skipped | wall |")
    print("|-------|--------|--------|--------|---------|------|")
    for r in results:
        print(f"| {r['suite']} | {r['status']} | {r['passed']} "
              f"| {r['failed']} | {r['skipped']} | {r['wall_s']:.0f}s |")
    bad = [r for r in results if r["status"] != "PASS"]
    if bad:
        print(f"\n{len(bad)} suite(s) not green: "
              f"{[r['suite'] for r in bad]}")
    return len(bad)


if __name__ == "__main__":
    sys.exit(main())
