"""Closed-loop elasticity (cluster/autoscaler.py; ISSUE 19).

Deterministic policy units over an injected clock — every decision and
reason string pinned verbatim (hysteresis, cooldowns, flap
suppression, pending-capacity accounting, bounds) — plus the windowed
admission-wait p99 reconstruction from ring bucket-count deltas, the
chaos join sites (``cluster.join.delay`` must NOT trigger a redundant
second scale-out; ``cluster.join.fail`` retries under the named
``cluster.join`` RetryBudget), the single live-capacity definition
shared by ``HeartbeatRegistry.rank_rings()`` and the autoscaler
(satellite 3), and the real-driver drain handshake: ``request_drain``
makes the executor's poll loop leave gracefully — re-replicate, then
deregister — with ``scoped_resubmits`` untouched."""
import threading
import time

import pytest

from spark_rapids_tpu.cluster.autoscaler import (
    _BOUNDS, Autoscaler, AutoscalePolicy, attach_autoscaler,
    thread_launcher, windowed_admission_p99)
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.shuffle.net import HeartbeatRegistry
from spark_rapids_tpu.shuffle.stats import (
    reset_shuffle_counters, shuffle_counters)
from spark_rapids_tpu.testing.chaos import CHAOS
from spark_rapids_tpu.utils.telemetry import TELEMETRY


@pytest.fixture(autouse=True)
def _clean():
    CHAOS.clear()
    reset_shuffle_counters()
    TELEMETRY.reset_events()
    yield
    CHAOS.clear()


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _wait_for(cond, timeout_s=10.0, interval_s=0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval_s)
    raise AssertionError("condition not met within timeout")


_KNOBS = {
    "minExecutors": "1", "maxExecutors": "4", "queueDepthHigh": "5",
    "admissionWaitP99High": "1.0", "arenaPressureHigh": "0.9",
    "scaleOutStep": "2", "upCooldownSeconds": "30",
    "downCooldownSeconds": "60", "idleSeconds": "10",
    "flapSeconds": "20", "joinTimeoutSeconds": "60", "joinRetries": "2",
}


def _conf(**over):
    knobs = dict(_KNOBS)
    knobs.update({k: str(v) for k, v in over.items()})
    return RapidsConf({f"spark.rapids.autoscale.{k}": v
                       for k, v in knobs.items()})


def _policy(clk, **over):
    return AutoscalePolicy(_conf(**over), clock=clk)


# -- policy units: exact decisions against synthetic signals -------------------

def test_policy_scale_out_pending_cooldown_bounds():
    clk = FakeClock()
    p = _policy(clk)
    d = p.decide(9, 0.0, 0.0, available=1, draining=0, pending=0)
    assert (d.action, d.count, d.reason) == \
        ("scale_out", 2, "queue_depth 9 >= 5")
    # pending-capacity accounting (satellite 2): the rank answering
    # this pressure is still joining — NO second scale-out
    d = p.decide(9, 0.0, 0.0, available=1, draining=0, pending=2)
    assert (d.action, d.reason) == ("hold", "pending join in flight")
    d = p.decide(9, 0.0, 0.0, available=3, draining=0, pending=0)
    assert (d.action, d.reason) == ("hold", "up-cooldown")
    clk.t += 31.0
    d = p.decide(9, 0.0, 0.0, available=3, draining=0, pending=0)
    assert (d.action, d.count) == ("scale_out", 1)   # step capped by max
    clk.t += 31.0
    d = p.decide(9, 0.0, 0.0, available=4, draining=0, pending=0)
    assert d.action == "hold"
    assert d.reason.startswith("at maxExecutors=4")


def test_policy_pressure_reasons_compose():
    d = _policy(FakeClock()).decide(0, 2.0, 0.95, available=1,
                                    draining=0, pending=0)
    assert d.action == "scale_out"
    assert d.reason == ("admission-wait p99 2.000s > 1.000s; "
                        "arena pressure 0.95 > 0.90")


def test_policy_scale_in_hysteresis_and_cooldown():
    clk = FakeClock()
    p = _policy(clk)
    assert p.decide(0, 0.0, 0.0, 3, 0, 0).reason == "steady"
    clk.t += 9.9
    assert p.decide(0, 0.0, 0.0, 3, 0, 0).reason == "steady"
    clk.t += 0.1                        # idleSeconds reached
    d = p.decide(0, 0.0, 0.0, 3, 0, 0)
    assert (d.action, d.count, d.reason) == \
        ("scale_in", 1, "idle 10.0s >= 10.0s")
    # one graceful drain at a time: the next eligible idle tick is
    # inside downCooldownSeconds
    assert p.decide(0, 0.0, 0.0, 2, 0, 0).reason == "down-cooldown"
    clk.t += 61.0
    assert p.decide(0, 0.0, 0.0, 2, 0, 0).action == "scale_in"


def test_policy_scale_in_blocked_by_min_pending_draining():
    clk = FakeClock()
    p = _policy(clk)
    p.decide(0, 0.0, 0.0, 3, 0, 0)      # idle streak starts
    clk.t += 100.0
    # at minExecutors: hold forever
    assert p.decide(0, 0.0, 0.0, 1, 0, 0).reason == "steady"
    # a drain already in flight, or a join in flight: no new drain
    assert p.decide(0, 0.0, 0.0, 3, 1, 0).reason == "steady"
    assert p.decide(0, 0.0, 0.0, 3, 0, 1).reason == "steady"


def test_policy_flap_suppression_both_directions():
    clk = FakeClock()
    p = _policy(clk, idleSeconds="1", flapSeconds="100",
                upCooldownSeconds="0", downCooldownSeconds="0")
    assert p.decide(9, 0.0, 0.0, 1, 0, 0).action == "scale_out"
    clk.t += 1.0
    assert p.decide(0, 0.0, 0.0, 2, 0, 0).reason == "steady"
    clk.t += 2.0                        # idle long enough, but...
    d = p.decide(0, 0.0, 0.0, 2, 0, 0)
    assert (d.action, d.reason) == \
        ("hold", "flap-suppressed (recent scale-out)")
    p2 = _policy(clk, idleSeconds="1", flapSeconds="100",
                 upCooldownSeconds="0", downCooldownSeconds="0")
    p2.decide(0, 0.0, 0.0, 3, 0, 0)
    clk.t += 2.0
    assert p2.decide(0, 0.0, 0.0, 3, 0, 0).action == "scale_in"
    clk.t += 1.0
    d = p2.decide(9, 0.0, 0.0, 2, 0, 0)
    assert (d.action, d.reason) == \
        ("hold", "flap-suppressed (recent scale-in)")


def test_policy_idle_streak_resets_on_any_queue_depth():
    """Scale-in hysteresis means a sustained streak of ZERO pressure:
    sub-threshold queue depth is still work, and it restarts the
    clock."""
    clk = FakeClock()
    p = _policy(clk)
    p.decide(0, 0.0, 0.0, 3, 0, 0)
    clk.t += 5.0
    p.decide(1, 0.0, 0.0, 3, 0, 0)      # depth 1 < high 5: no pressure,
    clk.t += 6.0                        # but the idle streak resets
    assert p.decide(0, 0.0, 0.0, 3, 0, 0).reason == "steady"
    clk.t += 9.9
    assert p.decide(0, 0.0, 0.0, 3, 0, 0).reason == "steady"
    clk.t += 0.2
    assert p.decide(0, 0.0, 0.0, 3, 0, 0).action == "scale_in"


# -- windowed admission-wait p99 from ring deltas ------------------------------

def _sample(counts, max_s=0.0):
    return {"histograms": {"admission_wait_s": {"counts": list(counts),
                                                "max_s": max_s}}}


def test_windowed_p99_from_bucket_deltas():
    n = len(_BOUNDS) + 1
    zero = [0] * n
    newest = list(zero)
    newest[10] = 100
    p99 = windowed_admission_p99([_sample(zero), _sample(newest, 5.0)])
    assert p99 == pytest.approx(_BOUNDS[10])


def test_windowed_p99_ignores_cumulative_history():
    """The whole point of diffing: one bad epoch long ago must not pin
    the p99 high forever (a cumulative p99 never comes back down, and
    an autoscaler keyed on it would never scale back in)."""
    n = len(_BOUNDS) + 1
    history = [0] * n
    history[20] = 1000                  # old slow epoch, pre-window
    newest = list(history)
    newest[3] += 50                     # the window's actual waits
    p99 = windowed_admission_p99([_sample(history),
                                  _sample(newest, 9.0)])
    assert p99 == pytest.approx(_BOUNDS[3])


def test_windowed_p99_edge_cases():
    n = len(_BOUNDS) + 1
    zero = [0] * n
    assert windowed_admission_p99([]) == 0.0
    assert windowed_admission_p99([_sample(zero)]) == 0.0
    assert windowed_admission_p99(
        [_sample(zero), {"gauges": {}}]) == 0.0
    assert windowed_admission_p99(
        [_sample(zero), _sample(zero)]) == 0.0      # no admissions
    overflow = list(zero)
    overflow[n - 1] = 5                 # beyond the last bound
    assert windowed_admission_p99(
        [_sample(zero), _sample(overflow, 7.5)]) == pytest.approx(7.5)


# -- the daemon: tick() against a fake registry + chaos join sites -------------

class FakeRegistry:
    def __init__(self, available=()):
        self.available = list(available)
        self.draining_ranks = []

    def peers(self, workers_only=False):
        return {e: ("h", 0)
                for e in self.available + self.draining_ranks}

    def live_capacity(self):
        return {"available": sorted(self.available),
                "draining": sorted(self.draining_ranks)}


def _pressure_sig():
    return {"queue_depth": 9, "wait_p99_s": 0.0, "arena_pressure": 0.0}


def test_slow_join_no_redundant_scale_out():
    """Chaos ``cluster.join.delay``: while the launched rank is slowly
    joining, pending-capacity accounting holds further scale-outs even
    with every cooldown at zero (satellite 2)."""
    CHAOS.install("cluster.join.delay", count=-1, seconds=0.25)
    clk = FakeClock()
    reg = FakeRegistry(["seed-0"])
    sig = _pressure_sig()
    launched, ev = [], threading.Event()

    def launcher(eid):
        launched.append(eid)
        ev.set()

    a = Autoscaler(reg, launcher, lambda e: True,
                   conf=_conf(upCooldownSeconds="0", flapSeconds="0",
                              scaleOutStep="1"),
                   clock=clk, signals=lambda: dict(sig))
    try:
        assert a.tick().action == "scale_out"
        for _ in range(3):              # sustained pressure, join slow
            d = a.tick()
            assert (d.action, d.reason) == \
                ("hold", "pending join in flight")
        assert ev.wait(5.0), "launcher never ran"
        assert CHAOS.fired_count("cluster.join.delay") >= 1
        events = [e for e in TELEMETRY.events()
                  if e["kind"] == "autoscale"
                  and e.get("action") == "scale_out"]
        assert len(events) == 1, "slow join triggered a redundant launch"
        reg.available.extend(launched)  # the join finally lands
        sig["queue_depth"] = 0          # and the pressure is answered
        assert a.tick().reason == "steady"
        assert a.pending() == []
    finally:
        a.stop()


def test_failed_join_retries_under_budget():
    """Chaos ``cluster.join.fail`` firing twice: the launch succeeds on
    the third attempt under the named ``cluster.join`` RetryBudget."""
    base = CHAOS.fired_count("cluster.join.fail")
    CHAOS.install("cluster.join.fail", count=2)
    reg = FakeRegistry(["seed-0"])
    launched, ev = [], threading.Event()

    def launcher(eid):
        launched.append(eid)
        ev.set()

    a = Autoscaler(reg, launcher, lambda e: True,
                   conf=_conf(upCooldownSeconds="0", joinRetries="5",
                              scaleOutStep="1"),
                   clock=FakeClock(), signals=_pressure_sig)
    try:
        assert a.tick().action == "scale_out"
        assert ev.wait(5.0), "launch never succeeded after retries"
        assert launched == ["autoscale-1"]
        assert CHAOS.fired_count("cluster.join.fail") == base + 2
    finally:
        a.stop()


def test_join_budget_exhaustion_forgets_pending():
    """A join that keeps failing exhausts its budget: the pending slot
    is forgotten (so the policy may scale out again), a ``join_failed``
    event lands, and the launcher is never reached."""
    CHAOS.install("cluster.join.fail", count=-1)
    reg = FakeRegistry(["seed-0"])
    launched = []
    a = Autoscaler(reg, launched.append, lambda e: True,
                   conf=_conf(upCooldownSeconds="0", flapSeconds="0",
                              joinRetries="1", scaleOutStep="1"),
                   clock=FakeClock(), signals=_pressure_sig)
    try:
        assert a.tick().action == "scale_out"
        _wait_for(lambda: any(
            e.get("action") == "join_failed"
            for e in TELEMETRY.events() if e["kind"] == "autoscale"))
        _wait_for(lambda: a.pending() == [])
        assert launched == []
        assert a.tick().action == "scale_out"   # free to try again
    finally:
        a.stop()


def test_scale_in_prefers_autoscaled_ranks_and_counts():
    reg = FakeRegistry(["autoscale-1", "seed-0", "seed-1"])
    drained = []

    def drainer(eid):
        drained.append(eid)
        reg.available.remove(eid)
        reg.draining_ranks.append(eid)
        return True

    clk = FakeClock()
    sig = {"queue_depth": 0, "wait_p99_s": 0.0, "arena_pressure": 0.0}
    a = Autoscaler(reg, lambda e: None, drainer,
                   conf=_conf(idleSeconds="1", downCooldownSeconds="0",
                              flapSeconds="0"),
                   clock=clk, signals=lambda: dict(sig))
    assert a.tick().action == "hold"    # idle streak starts
    clk.t += 2.0
    assert a.tick().action == "scale_in"
    assert drained == ["autoscale-1"]   # unwind scale-out first
    assert shuffle_counters()["autoscale_down"] == 1
    clk.t += 2.0
    # the drain is still in flight: one graceful drain at a time
    assert a.tick().reason == "steady"


def test_drain_refused_does_not_count():
    reg = FakeRegistry(["seed-0", "seed-1"])
    clk = FakeClock()
    a = Autoscaler(reg, lambda e: None, lambda e: False,
                   conf=_conf(idleSeconds="1", downCooldownSeconds="0",
                              flapSeconds="0"),
                   clock=clk,
                   signals=lambda: {"queue_depth": 0, "wait_p99_s": 0.0,
                                    "arena_pressure": 0.0})
    a.tick()
    clk.t += 2.0
    assert a.tick().action == "scale_in"
    assert shuffle_counters()["autoscale_down"] == 0
    assert any(e.get("action") == "drain_refused"
               for e in TELEMETRY.events() if e["kind"] == "autoscale")


def test_attach_autoscaler_off_builds_nothing():
    """Knobs-off pin: without spark.rapids.autoscale.enabled the wiring
    helper returns None before touching the driver at all."""
    assert attach_autoscaler(None, conf={}) is None


# -- satellite 3: ONE definition of live capacity ------------------------------

def test_registry_live_capacity_and_rank_rings_agree():
    reg = HeartbeatRegistry(timeout_s=5.0)
    reg.register("a", "h", 1)
    reg.register("b", "h", 2)
    reg.heartbeat("a", telemetry={"t_s": 1.0})
    reg.heartbeat("b", telemetry={"t_s": 1.0})
    assert reg.live_capacity() == {"available": ["a", "b"],
                                   "draining": []}
    assert sorted(reg.rank_rings()) == ["a", "b"]
    assert reg.begin_drain("b")
    # drained out of BOTH views at once (shared predicate), but still a
    # live fetch target until it leaves
    assert reg.live_capacity() == {"available": ["a"],
                                   "draining": ["b"]}
    assert sorted(reg.rank_rings()) == ["a"]
    assert "b" in reg.peers()
    assert not reg.begin_drain("nope")
    reg.leave("b")
    assert reg.draining() == []


def test_registry_drain_mark_cleared_on_rejoin_and_exclude():
    reg = HeartbeatRegistry(timeout_s=5.0)
    reg.register("c", "h", 3)
    reg.begin_drain("c")
    reg.register("c", "h", 3)           # a genuine rejoin starts fresh
    assert reg.draining() == []
    assert reg.live_capacity()["available"] == ["c"]
    reg.begin_drain("c")
    reg.exclude("c")                    # loss mid-drain: record cleared
    assert reg.draining() == []


def test_registry_staleness_shares_the_predicate():
    reg = HeartbeatRegistry(timeout_s=0.05)
    reg.register("x", "h", 1)
    reg.heartbeat("x", telemetry={"t_s": 1.0})
    time.sleep(0.12)
    assert reg.live_capacity()["available"] == []
    assert reg.rank_rings() == {}


# -- real-driver drain handshake + the full loop -------------------------------

@pytest.fixture
def no_stale_shuffle_node():
    """``executor_main`` installs its shuffle node process-wide; a test
    that runs it on a thread of its own takes the node out again, or the
    next MULTIPROCESS exchange of this process asks a closed driver for
    its shuffle id."""
    yield
    from spark_rapids_tpu.shuffle.transport import (
        set_process_shuffle_executor)
    set_process_shuffle_executor(None)


def _spawn_executor(driver, eid, stop):
    from spark_rapids_tpu.cluster.executor import executor_main
    t = threading.Thread(
        target=executor_main, args=(driver.rpc_addr,),
        kwargs={"executor_id": eid, "stop_check": stop.is_set,
                "poll_s": 0.02},
        daemon=True, name=f"exec-{eid}")
    t.start()
    return t


def test_request_drain_graceful_handshake(no_stale_shuffle_node):
    """``request_drain`` → the executor's next get_task poll carries
    ``drain: true`` → it leaves gracefully (re-replicates, deregisters,
    thread EXITS) — and a scale-in never costs a scoped resubmit."""
    from spark_rapids_tpu.cluster.driver import TpuClusterDriver
    driver = TpuClusterDriver(heartbeat_timeout_s=10.0)
    stop = threading.Event()
    ths = []
    try:
        ths = [_spawn_executor(driver, f"seed-{i}", stop)
               for i in range(2)]
        _wait_for(lambda: len(
            driver.shuffle.registry.peers(workers_only=True)) == 2)
        assert driver.request_drain("seed-1")
        assert driver.shuffle.registry.live_capacity()["available"] \
            == ["seed-0"]
        _wait_for(lambda: "seed-1" not in driver.shuffle.registry.peers())
        ths[1].join(timeout=5.0)
        assert not ths[1].is_alive()
        assert shuffle_counters()["scoped_resubmits"] == 0
        assert not driver.request_drain("seed-1")   # already gone
    finally:
        stop.set()
        driver.close()
        for t in ths:
            t.join(timeout=5.0)


def test_autoscaler_full_loop_scale_out_join_idle_drain(no_stale_shuffle_node):
    """The tentpole end to end over a REAL driver: pressure scales out
    a real executor rank (it registers), sustained idle drains it
    gracefully, counters and flight-recorder events tell the story, and
    ``scoped_resubmits`` stays 0 throughout."""
    from spark_rapids_tpu.cluster.driver import TpuClusterDriver
    driver = TpuClusterDriver(heartbeat_timeout_s=10.0)
    stop = threading.Event()
    sig = {"queue_depth": 9, "wait_p99_s": 0.0, "arena_pressure": 0.0}
    a = None
    ths = []
    try:
        ths = [_spawn_executor(driver, "seed-0", stop)]
        _wait_for(lambda: len(
            driver.shuffle.registry.peers(workers_only=True)) == 1)
        a = Autoscaler(
            driver.shuffle.registry,
            thread_launcher(driver, stop_event=stop, poll_s=0.02),
            driver.request_drain,
            conf=_conf(maxExecutors="2", upCooldownSeconds="0",
                       downCooldownSeconds="0", idleSeconds="0.1",
                       flapSeconds="0", scaleOutStep="1"),
            signals=lambda: dict(sig))
        assert a.tick().action == "scale_out"
        _wait_for(lambda: "autoscale-1"
                  in driver.shuffle.registry.peers())
        sig["queue_depth"] = 0          # load gone: idle streak starts
        a.tick()
        time.sleep(0.15)
        d = a.tick()
        assert d.action == "scale_in"
        _wait_for(lambda: "autoscale-1"
                  not in driver.shuffle.registry.peers())
        c = shuffle_counters()
        assert c["autoscale_up"] == 1 and c["autoscale_down"] == 1
        assert c["scoped_resubmits"] == 0
        actions = [e.get("action") for e in TELEMETRY.events()
                   if e["kind"] == "autoscale"]
        assert actions.count("scale_out") == 1
        assert actions.count("scale_in") == 1
    finally:
        stop.set()
        if a is not None:
            a.stop()
        driver.close()
        for t in ths:
            t.join(timeout=5.0)
