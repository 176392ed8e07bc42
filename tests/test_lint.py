"""Tier-1 gate for tpu-lint (tools/tpulint): the four invariant checkers
run against the live tree, each checker is proven to fire on a synthetic
violation fixture, and the real defects fixed while building the linter
are pinned as regression fixtures (their PRE-FIX shapes must fire; the
fixed files must be clean rather than baselined).

Reference analog: the TypeChecks / ApiValidation / retry-suite tooling
the reference uses instead of review for its hardest invariants.
"""
import json
import os
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.tpulint import core as lint_core
from tools.tpulint import (ambient_spawn, counter_discipline, drift,
                           host_sync, locks, pin_balance,
                           retry_discipline, swallow, waits)


def _src(path: str, text: str) -> lint_core.SourceFile:
    import ast
    text = textwrap.dedent(text)
    lines = text.splitlines()
    allows, problems = lint_core._parse_allows(lines)
    s = lint_core.SourceFile(path=path, text=text, lines=lines,
                             tree=ast.parse(text), allows=allows)
    s.suppression_problems = problems
    return s


def _unsuppressed(rule_violations, src):
    return [v for v in rule_violations if not src.allowed(v.rule, v.line)]


# -- the repo gate -----------------------------------------------------------

def test_repo_is_lint_clean():
    """New violations in the AST rules fail tier-1 (drift rules run in
    their own tests below so a doc drift reports as exactly one failure)."""
    violations = lint_core.run_all(REPO, with_drift=False)
    baseline = lint_core.load_baseline()
    fresh, _stale = lint_core.apply_baseline(violations, baseline)
    assert not fresh, "new tpu-lint violations:\n" + "\n".join(
        v.render() for v in fresh)


def test_baseline_entries_are_reviewed():
    baseline = lint_core.load_baseline()
    bad = [e["fingerprint"] for e in baseline.values()
           if not e.get("reason")
           or e["reason"] == lint_core.PLACEHOLDER_REASON]
    assert not bad, f"baseline entries without a reviewed reason: {bad}"


def test_baseline_has_no_stale_entries():
    violations = lint_core.run_all(REPO, with_drift=False)
    _fresh, stale = lint_core.apply_baseline(violations,
                                             lint_core.load_baseline())
    assert not stale, f"stale baseline entries: {stale}"


# -- drift rules against the live tree (satellite: api_check coverage) -------

def test_supported_ops_and_configs_not_drifted():
    assert drift._check_generated_docs(REPO) == []


def test_every_override_has_a_typesig_row():
    assert drift._check_typesig_rows() == []


def test_api_surface_matches_snapshot():
    """tools/api_check.py against the committed api_surface.json."""
    assert drift._check_api_surface(REPO) == []


def test_drift_fires_on_unregistered_expr():
    from spark_rapids_tpu.planner import overrides as O

    class _FakeExpr:   # deliberately absent from typesig
        pass

    O._SUPPORTED_EXPRS.add(_FakeExpr)
    try:
        vs = drift._check_typesig_rows()
    finally:
        O._SUPPORTED_EXPRS.discard(_FakeExpr)
    assert any("_FakeExpr" in v.message for v in vs)


def test_api_check_detects_removal_and_signature_change():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "api_check_under_test", os.path.join(REPO, "tools", "api_check.py"))
    ac = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ac)
    recorded = {"functions": ["a", "b"],
                "DataFrame": {"select": "(cols)"}}
    live = {"functions": ["a"],
            "DataFrame": {"select": "(cols, how)"}}
    problems = ac.diff_surface(recorded, live)
    assert "functions: b removed" in problems
    assert any("select signature changed" in p for p in problems)


# -- synthetic fixture per AST rule (each checker must FIRE) -----------------

def test_retry_checker_fires_on_unprotected_materializer():
    src = _src("spark_rapids_tpu/plan/execs/_fixture.py", """
        def execute_partition(batches, schema):
            merged = coalesce_to_one(batches)
            return merged
    """)
    vs = retry_discipline.check([src])
    assert any("coalesce_to_one" in v.message for v in vs)


def test_retry_checker_fires_on_unspillable_closure():
    src = _src("spark_rapids_tpu/plan/execs/_fixture.py", """
        def execute_partition(batches, run):
            merged = coalesce_to_one(batches)
            return with_retry_no_split(lambda: run(merged))
    """)
    vs = retry_discipline.check([src])
    assert any("closes over unspillable local 'merged'" in v.message
               for v in vs)


def test_retry_checker_accepts_protected_idiom():
    """The repo idiom: materializer inside the retry lambda, and inside a
    helper referenced only from retry lambdas."""
    src = _src("spark_rapids_tpu/plan/execs/_fixture.py", """
        class Exec:
            def _run(self, batches):
                return coalesce_to_one(batches)

            def execute_partition(self, batches):
                return with_retry_no_split(lambda: self._run(batches))
    """)
    assert retry_discipline.check([src]) == []


def test_host_sync_checker_fires_on_each_form():
    src = _src("spark_rapids_tpu/kernels/_fixture.py", """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def hot_path(col, batch):
            n = int(jnp.max(col.data))
            x = jax.device_get(col.data)
            col.data.block_until_ready()
            buf = np.asarray(col.offsets)
            out = []
            for c in batch.columns:
                out.append(c.to_numpy(4))
            return n, x, buf, out
    """)
    msgs = [v.message for v in host_sync.check([src])]
    assert any("hidden scalar sync" in m for m in msgs)
    assert any("device_get" in m for m in msgs)
    assert any("block_until_ready" in m for m in msgs)
    assert any("downloads it synchronously" in m for m in msgs)
    assert any("inside a loop" in m for m in msgs)


def test_lock_checker_fires_on_blocking_and_order():
    src = _src("spark_rapids_tpu/shuffle/_fixture.py", """
        import threading
        import time

        _a = threading.Lock()
        _b = threading.Lock()

        def sleep_under_lock():
            with _a:
                time.sleep(1)

        def order_ab():
            with _a:
                with _b:
                    pass

        def order_ba():
            with _b:
                with _a:
                    pass
    """)
    msgs = [v.message for v in locks.check([src])]
    assert any("sleep" in m and "while holding" in m for m in msgs)
    assert any("inconsistent lock order" in m for m in msgs)


def test_lock_checker_fires_on_callback_under_lock():
    src = _src("spark_rapids_tpu/shuffle/_fixture.py", """
        import threading

        class Conn:
            def __init__(self):
                self._lock = threading.Lock()

            def roundtrip(self, send):
                with self._lock:
                    return send()
    """)
    vs = locks.check([src])
    assert any("callback parameter 'send'" in v.message for v in vs)


def test_lock_checker_fires_on_self_deadlock():
    src = _src("spark_rapids_tpu/io/_fixture.py", """
        import threading

        _a = threading.Lock()

        def recurse():
            with _a:
                with _a:
                    pass
    """)
    vs = locks.check([src])
    assert any("self-deadlock" in v.message for v in vs)


# -- suppression mechanics ---------------------------------------------------

def test_swallow_fires_on_silent_broad_except():
    src = _src("spark_rapids_tpu/cluster/_fixture.py", """
        def poll(peer):
            try:
                peer.heartbeat()
            except Exception:
                pass
            try:
                peer.cleanup()
            except (ValueError, BaseException):
                continue
    """)
    msgs = [v.message for v in swallow.check([src])]
    assert len(msgs) == 2
    assert all("silently swallowed" in m for m in msgs)


def test_swallow_fires_on_bare_except():
    src = _src("spark_rapids_tpu/cluster/_fixture.py", """
        def f(x):
            try:
                return x.close()
            except:
                return None
    """)
    msgs = [v.message for v in swallow.check([src])]
    assert len(msgs) == 1 and "bare `except:`" in msgs[0]


def test_swallow_accepts_logged_handled_narrow_and_raising():
    src = _src("spark_rapids_tpu/cluster/_fixture.py", """
        import logging
        log = logging.getLogger(__name__)

        def f(x, state):
            try:
                x.run()
            except Exception as e:
                log.warning("run failed: %s", e)     # logged
            try:
                x.run()
            except Exception as e:
                state["error"] = e                   # handled (stored)
                return None
            try:
                x.run()
            except OSError:
                pass                                 # narrow catch
            try:
                x.run()
            except BaseException:
                raise                                # re-raised
            except:
                log.exception("boom")                # bare but logged
    """)
    assert swallow.check([src]) == []


def test_swallow_suppression_with_reason():
    src = _src("spark_rapids_tpu/cluster/_fixture.py", """
        def f(x):
            try:
                x.close()
            # tpu-lint: allow-swallow(teardown of a possibly-dead handle)
            except Exception:
                pass
    """)
    assert _unsuppressed(swallow.check([src]), src) == []


def test_heartbeat_swallow_was_fixed():
    """Regression pin: the executor liveness beat's old shape — a tight
    ``except Exception: pass`` loop, silent at full rate against a dead
    driver — is exactly what the swallow rule flags.  The current
    executor_main paces failures (HeartbeatPacer: backoff + one log per
    streak transition + streak gauge) and stays lint-clean (the repo
    gate above proves it)."""
    src = _src("spark_rapids_tpu/cluster/_fixture.py", """
        def _beat(stop, client, executor_id):
            while not stop.is_set():
                try:
                    client.heartbeat(executor_id)
                except Exception:
                    pass
                stop.wait(2.0)
    """)
    vs = swallow.check([src])
    assert len(vs) == 1 and vs[0].scope == "_beat"


def test_unbounded_wait_fires_on_each_form():
    """The unbounded-wait rule flags every no-timeout blocking form the
    cancellation/watchdog layer cannot see (ISSUE 10 satellite): raw
    Condition/Event wait(), Future.result(), queue-ish get()."""
    src = _src("spark_rapids_tpu/shuffle/_fixture.py", """
        def f(cv, ev, fut, q):
            with cv:
                cv.wait()
            ev.wait()
            fut.result()
            q.get()
            fut.result(timeout=None)
    """)
    msgs = [v.message for v in waits.check([src])]
    assert len(msgs) == 5, msgs
    assert sum("`.wait()`" in m for m in msgs) == 2
    assert sum("`.result()`" in m for m in msgs) == 2
    assert sum("queue `.get()`" in m for m in msgs) == 1


def test_unbounded_wait_accepts_bounded_and_nonqueue_forms():
    src = _src("spark_rapids_tpu/shuffle/_fixture.py", """
        from spark_rapids_tpu.utils.cancel import cancellable_wait

        def f(cv, ev, fut, q, task_metrics, conf):
            with cv:
                cv.wait(0.25)                      # bounded slice
            ev.wait(timeout=2.0)
            fut.result(timeout=30)
            q.get(timeout=0.1)
            task_metrics.get()                     # accessor, not a queue
            conf.get("key")                        # dict-style get
            cancellable_wait(ev, site="x")         # the blessed form
    """)
    assert waits.check([src]) == []


def test_unbounded_wait_pre_fix_semaphore_shape_fires():
    """Regression pin: PrioritySemaphore.acquire's old no-deadline
    branch — a bare ``self._cv.wait()`` a cancelled query could never
    escape (the PR 9 deadlock class) — is exactly what this rule flags.
    The live semaphore now waits in bounded slices with ambient-token
    checks and watchdog registration (the repo gate proves it clean)."""
    src = _src("spark_rapids_tpu/memory/_fixture.py", """
        class Sem:
            def acquire(self, deadline=None):
                with self._cv:
                    while not self._head():
                        if deadline is not None:
                            self._cv.wait(deadline)
                        else:
                            self._cv.wait()
    """)
    vs = waits.check([src])
    assert len(vs) == 1 and vs[0].scope == "Sem.acquire"


def test_unbounded_wait_suppression_and_exempt_module():
    src = _src("spark_rapids_tpu/io/_fixture.py", """
        def f(throttle):
            # tpu-lint: allow-unbounded-wait(drains via a blessed cancellable_wait internally)
            throttle.wait()
    """)
    assert _unsuppressed(waits.check([src]), src) == []
    # utils/cancel.py IS the blessed implementation: exempt wholesale
    exempt = _src("spark_rapids_tpu/utils/cancel.py", """
        def f(cv):
            with cv:
                cv.wait()
    """)
    assert waits.check([exempt]) == []


def test_suppression_requires_a_reason():
    src = _src("spark_rapids_tpu/kernels/_fixture.py", """
        import jax

        def f(x):
            # tpu-lint: allow-host-sync()
            return jax.device_get(x)
    """)
    assert any(p[1].startswith("allow-host-sync")
               for p in src.suppression_problems)
    # and the reasonless comment does NOT suppress
    vs = _unsuppressed(host_sync.check([src]), src)
    assert vs


def test_suppression_with_reason_suppresses():
    src = _src("spark_rapids_tpu/kernels/_fixture.py", """
        import jax

        def f(x):
            # tpu-lint: allow-host-sync(documented single batched sync)
            return jax.device_get(x)
    """)
    assert _unsuppressed(host_sync.check([src]), src) == []


def test_baseline_roundtrip(tmp_path):
    path = str(tmp_path / "baseline.json")
    entries = {"host-sync|a.py|f|m": {
        "fingerprint": "host-sync|a.py|f|m", "rule": "host-sync",
        "file": "a.py", "scope": "f", "message": "m",
        "reason": "reviewed: historical"}}
    lint_core.save_baseline(entries, path)
    loaded = lint_core.load_baseline(path)
    assert loaded == entries
    v = lint_core.Violation("host-sync", "a.py", 3, "f", "m")
    fresh, stale = lint_core.apply_baseline([v], loaded)
    assert fresh == [] and stale == []
    fresh, stale = lint_core.apply_baseline([], loaded)
    assert stale == ["host-sync|a.py|f|m"]


# -- regression pins: real defects found by the linter were FIXED ------------
# Each fixture is the PRE-FIX shape of real repo code; the checker must
# fire on it, and the fixed file must be clean WITHOUT a baseline entry.

def test_filecache_io_under_lock_was_fixed():
    pre_fix = _src("spark_rapids_tpu/io/filecache.py", """
        import os
        import threading

        _lock = threading.Lock()
        _metrics = {"hits": 0, "misses": 0}

        def cached_path(entry):
            with _lock:
                if os.path.exists(entry):
                    _metrics["hits"] += 1
                    os.utime(entry)
                    return entry
                _metrics["misses"] += 1
            return None
    """)
    assert any("filesystem IO" in v.message for v in locks.check([pre_fix]))
    real = lint_core.load_source(REPO, "spark_rapids_tpu/io/filecache.py")
    assert _unsuppressed(locks.check([real]), real) == []


def test_pooled_connection_socket_io_under_lock_was_fixed():
    pre_fix = _src("spark_rapids_tpu/shuffle/net.py", """
        import socket
        import threading

        class PooledConnection:
            def __init__(self, addr):
                self._lock = threading.Lock()
                self._sock = None

            def _connect(self):
                self._sock = socket.create_connection(self.addr)
                return self._sock

            def _roundtrip(self, send, recv):
                with self._lock:
                    sock = self._sock or self._connect()
                    send(sock)
                    return recv(sock)
    """)
    msgs = [v.message for v in locks.check([pre_fix])]
    assert any("socket connect" in m for m in msgs)
    assert any("callback parameter" in m for m in msgs)
    real = lint_core.load_source(REPO, "spark_rapids_tpu/shuffle/net.py")
    assert _unsuppressed(locks.check([real]), real) == []


def test_per_column_download_loop_was_fixed():
    pre_fix = _src("spark_rapids_tpu/expressions/_fixture.py", """
        def from_batch(batch):
            cols = []
            for col in batch.columns:
                vals, valid = col.to_numpy(3)
                cols.append((vals, valid))
            return cols
    """)
    assert any("inside a loop" in v.message
               for v in host_sync.check([pre_fix]))
    real = lint_core.load_source(REPO,
                                 "spark_rapids_tpu/expressions/core.py")
    assert _unsuppressed(host_sync.check([real]), real) == []


def test_shuffle_merge_runs_under_retry():
    """net.py read_iter / transport.py read were fixed to wrap their
    merge_batches in with_retry_no_split; keep them that way."""
    for rel in ("spark_rapids_tpu/shuffle/net.py",
                "spark_rapids_tpu/shuffle/transport.py"):
        src = lint_core.load_source(REPO, rel)
        vs = _unsuppressed(retry_discipline.check([src]), src)
        assert vs == [], f"{rel}:\n" + "\n".join(v.render() for v in vs)


def test_retry_over_spillable_is_pin_balanced():
    """Each retry attempt re-materializes (pin +1) AND unpins before it
    ends: after an injected OOM + retry the handles are back to pins=0
    and still spillable.  Naively materializing inside a retry body leaks
    one pin per extra attempt, permanently unspilling the handles."""
    import jax.numpy as jnp

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.memory.arena import TpuRetryOOM
    from spark_rapids_tpu.memory.spill import make_spillable
    from spark_rapids_tpu.plan.execs.coalesce import retry_over_spillable

    def mkbatch(lo):
        col = DeviceColumn(data=jnp.arange(lo, lo + 4, dtype=jnp.int64),
                           validity=jnp.ones(4, bool), dtype=T.LONG)
        return ColumnarBatch((col,), jnp.int32(4),
                             Schema(("n",), (T.LONG,)))

    handles = [make_spillable(mkbatch(0)), make_spillable(mkbatch(4))]
    for h in handles:
        h.unpin()   # make_spillable hands the batch back pinned-or-not;
                    # normalize to the spillable resting state
    base_pins = [h._pins for h in handles]
    attempts = [0]

    def body(merged):
        attempts[0] += 1
        if attempts[0] == 1:
            raise TpuRetryOOM("injected mid-attempt")
        return merged

    out = retry_over_spillable(handles, body)
    assert attempts[0] == 2
    assert int(out.num_rows) == 8
    assert [h._pins for h in handles] == base_pins, "pin leak on retry"
    # still spillable and re-materializable after the retried attempt
    assert handles[0].spill_to_host() > 0
    again = retry_over_spillable(handles, lambda m: m)
    assert int(again.num_rows) == 8
    for h in handles:
        h.close()


def test_retry_checker_fires_on_bare_materialize_in_fused_program():
    """Sub-rule (c): a fused reduce program materializing a spillable
    piece outside the pin-balanced wrappers is flagged."""
    src = _src("spark_rapids_tpu/plan/fused.py", """
        def _execute_fused(self, pieces, fn):
            mats = [p.materialize_pinned() for p in pieces]
            return fn(mats)
    """)
    vs = retry_discipline.check([src])
    assert any("pin-balanced wrapper" in v.message for v in vs)


def test_retry_checker_accepts_pin_balanced_piece_idiom():
    """The blessed idiom: materialization flows through
    retry_over_stream_pieces / retry_over_spillable arguments."""
    src = _src("spark_rapids_tpu/plan/fused.py", """
        def _execute_fused(self, pieces, fn):
            return retry_over_stream_pieces(
                [pieces], lambda mats: fn(tuple(mats[0])))

        def _other(self, handles, body):
            return retry_over_spillable(
                handles, lambda m: body(m.materialize()))
    """)
    assert [v for v in retry_discipline.check([src])
            if "pin-balanced" in v.message] == []


def test_fused_py_pin_rule_is_clean_or_reasoned():
    """The real plan/fused.py passes sub-rule (c) (held-pin contracts
    carry inline reasons)."""
    src = lint_core.load_source(REPO, "spark_rapids_tpu/plan/fused.py")
    vs = _unsuppressed(retry_discipline.check([src]), src)
    assert vs == [], "\n".join(v.render() for v in vs)


def test_retry_over_stream_pieces_is_pin_balanced():
    """Piece-list twin of the retry_over_spillable contract: an injected
    mid-attempt OOM leaves every piece unpinned and spillable."""
    import jax.numpy as jnp

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.memory.arena import TpuRetryOOM
    from spark_rapids_tpu.memory.spill import make_spillable
    from spark_rapids_tpu.plan.execs.coalesce import (
        retry_over_stream_pieces)
    from spark_rapids_tpu.shuffle.transport import StreamPiece

    def mkbatch(lo):
        col = DeviceColumn(data=jnp.arange(lo, lo + 4, dtype=jnp.int64),
                           validity=jnp.ones(4, bool), dtype=T.LONG)
        return ColumnarBatch((col,), jnp.int32(4),
                             Schema(("n",), (T.LONG,)))

    handles = [make_spillable(mkbatch(0)), make_spillable(mkbatch(4))]
    for h in handles:
        h.unpin()
    pieces = [StreamPiece.of_range_view(h, 0, 4, h.size_bytes)
              for h in handles]
    base_pins = [h._pins for h in handles]
    attempts = [0]

    def body(mats):
        attempts[0] += 1
        assert len(mats) == 1 and len(mats[0]) == 2
        if attempts[0] == 1:
            raise TpuRetryOOM("injected mid-attempt")
        return sum(int(m.count) for m in mats[0])

    assert retry_over_stream_pieces([pieces], body) == 8
    assert attempts[0] == 2
    assert [h._pins for h in handles] == base_pins, "pin leak on retry"
    assert handles[0].spill_to_host() > 0   # still spillable
    for h in handles:
        h.close()


# -- functional check of the lock fix (handoff semantics) --------------------

def test_pooled_connection_close_does_not_wait_for_inflight():
    """close() must return while a round-trip is blocked in IO (the old
    lock-across-IO design deadlocked this for the socket timeout)."""
    import threading
    import time as _time

    from spark_rapids_tpu.shuffle.net import PooledConnection

    conn = PooledConnection(("127.0.0.1", 1))
    started = threading.Event()
    release = threading.Event()

    def slow_send(sock):
        started.set()
        release.wait(5.0)

    def fake_recv(sock):
        return None

    class _FakeSock:
        def close(self):
            pass

    def run():
        sock = conn._checkout()
        try:
            slow_send(_FakeSock())
        finally:
            conn._checkin(_FakeSock())

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(5.0)
    t0 = _time.monotonic()
    conn.close()                      # must not block on the in-flight IO
    assert _time.monotonic() - t0 < 1.0
    release.set()
    t.join(5.0)
    assert not t.is_alive()
    # the in-flight socket was checked in after close() latched: dropped
    assert conn._sock is None


# -- the flow engine: CFG construction on a golden mini-module ---------------
# The exception-edge model is the part reviews kept getting wrong by
# hand (ISSUE 12): pin these shapes — try/finally, with, early return
# THROUGH a finally, loop break — as graph facts.

def _golden_cfg(src_text, name):
    import ast as _ast

    from tools.tpulint.cfg import build_module_info
    info = build_module_info(_ast.parse(textwrap.dedent(src_text)))
    return info.functions[name].cfg


def _node_containing(cfg, needle):
    import ast as _ast
    hits = []
    for n in cfg.stmt_nodes():
        try:
            if needle in _ast.unparse(n.stmt):
                hits.append(n)
        except Exception:  # noqa: BLE001 — synthetic nodes
            pass
    assert hits, f"no CFG node contains {needle!r}"
    return hits[0]


def test_cfg_try_finally_exception_edge_routes_through_finally():
    cfg = _golden_cfg("""
        def f(h):
            h.acquire()
            try:
                work(h)
            finally:
                h.release()
            after(h)
    """, "f")
    work = _node_containing(cfg, "work(h)")
    release = _node_containing(cfg, "h.release()")
    # work can leave exceptionally...
    assert any(e.kind == "exc" for e in cfg.successors(work.idx))
    # ...and every exceptional continuation reaches the finally body,
    # which in turn reaches BOTH the raise exit (propagation) and the
    # fallthrough (normal completion)
    reach_work = cfg.reachable_from(work.idx)
    assert release.idx in reach_work
    reach_rel = cfg.reachable_from(release.idx)
    assert cfg.raise_exit in reach_rel
    assert _node_containing(cfg, "after(h)").idx in reach_rel
    # the acquire is OUTSIDE the try: its exception edge must NOT pass
    # through the release
    acq = _node_containing(cfg, "h.acquire()")
    exc_targets = [e.dst for e in cfg.successors(acq.idx)
                   if e.kind == "exc"]
    assert exc_targets == [cfg.raise_exit]


def test_cfg_early_return_tunnels_through_finally():
    cfg = _golden_cfg("""
        def f(h):
            try:
                return mk(h)
            finally:
                h.release()
    """, "f")
    ret = _node_containing(cfg, "return mk(h)")
    release = _node_containing(cfg, "h.release()")
    # the return does NOT go straight to the exit...
    assert cfg.exit not in [e.dst for e in cfg.successors(ret.idx)]
    # ...but the exit is reachable from it, via the finally body
    assert release.idx in cfg.reachable_from(ret.idx)
    assert cfg.exit in cfg.reachable_from(release.idx)


def test_cfg_with_body_has_exception_edge():
    cfg = _golden_cfg("""
        def f(path):
            with open(path) as fh:
                parse(fh)
            return done()
    """, "f")
    ctx = _node_containing(cfg, "open(path)")
    body = _node_containing(cfg, "parse(fh)")
    for n in (ctx, body):
        assert [e.dst for e in cfg.successors(n.idx)
                if e.kind == "exc"] == [cfg.raise_exit]


def test_cfg_loop_break_and_back_edges():
    cfg = _golden_cfg("""
        def f(xs):
            for x in xs:
                if bad(x):
                    break
                use(x)
            return tally()
    """, "f")
    brk = _node_containing(cfg, "break")
    use = _node_containing(cfg, "use(x)")
    ret = _node_containing(cfg, "return tally()")
    # break jumps past the loop: the return is reachable without a
    # back edge
    assert ret.idx in cfg.reachable_from(brk.idx, skip_kinds=("back",))
    # the body's fallthrough loops back (a back edge exists somewhere
    # downstream of use)
    assert any(e.kind == "back"
               for n in cfg.nodes for e in cfg.successors(n.idx))
    assert ret.idx in cfg.reachable_from(use.idx)


def test_cfg_catch_all_handler_consumes_the_exception():
    """`except BaseException` leaves no unmatched-handler path — the
    imprecision that would otherwise fabricate leak reports from every
    try/except unwind."""
    cfg = _golden_cfg("""
        def f(h):
            try:
                return work(h)
            except BaseException:
                h.unwind()
                raise
    """, "f")
    work = _node_containing(cfg, "work(h)")
    unwind = _node_containing(cfg, "h.unwind()")
    # every exceptional path out of work passes through the handler
    exc_dsts = [e.dst for e in cfg.successors(work.idx)
                if e.kind == "exc"]
    assert exc_dsts and all(
        unwind.idx in ({d} | cfg.reachable_from(d)) for d in exc_dsts)


# -- fixture corpus: the three HISTORICAL pre-fix bug shapes -----------------
# Each is the shape of real repo code BEFORE its fix (PR 9/11); the
# flow engine must catch all three (ISSUE 12 acceptance).

def test_pin_balance_catches_pr11_unmatched_unpin_on_raise():
    """PR 11: CacheOnlyTransport's read path unpinned in a finally that
    also ran when materialize_pinned ITSELF raised — the unmatched unpin
    stole a concurrent consumer's pin, so spill could free data
    mid-use."""
    src = _src("spark_rapids_tpu/shuffle/transport.py", """
        class CacheOnlyTransport:
            def read(self, partition):
                out = []
                for piece in self._pieces[partition]:
                    try:
                        mat = piece.materialize_pinned()
                        out.append(slice_view(mat))
                    finally:
                        piece.unpin()
                return out
    """)
    vs = pin_balance.check([src])
    assert any("never acquired" in v.message for v in vs), \
        "\n".join(v.render() for v in vs)


def test_pin_balance_catches_pr11_failed_fallback_gather_leak():
    """PR 11's second shape: the fallback gather after a successful
    acquire could raise, leaving the backing pinned with no owner."""
    src = _src("spark_rapids_tpu/shuffle/transport.py", """
        class StreamPiece:
            def materialize_batch_pinned(self):
                mat = self.materialize_pinned()
                return with_retry_no_split(lambda: slice_view(mat))
    """)
    vs = pin_balance.check([src])
    assert any("exception path" in v.message for v in vs), \
        "\n".join(v.render() for v in vs)


def test_ambient_rule_catches_pr9_bare_thread_producer():
    """PR 9: the pipelined producer ran on a bare Thread, acquired the
    device semaphore at default priority with no cover and deadlocked
    once every slot was held by blocked consumers."""
    src = _src("spark_rapids_tpu/shuffle/pipeline.py", """
        import threading

        from spark_rapids_tpu.memory.semaphore import tpu_semaphore
        from spark_rapids_tpu.memory.tenant import TENANTS

        def pipelined(source, pipe):
            def produce():
                with TENANTS.scope(None), tpu_semaphore().held():
                    for item in source:
                        pipe.put(item)
            t = threading.Thread(target=produce, daemon=True)
            t.start()
    """)
    vs = ambient_spawn.check([src])
    assert any("spawn_with_ambients" in v.message for v in vs), \
        "\n".join(v.render() for v in vs)


def test_counter_rule_catches_pr11_increment_inside_retry():
    """PR 11: range_view_materializes counted inside a body retried by
    with_retry_no_split — every OOM retry double-counted it."""
    src = _src("spark_rapids_tpu/shuffle/transport.py", """
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS

        def materialize_view_batch(piece):
            def attempt():
                SHUFFLE_COUNTERS.add(range_view_materializes=1)
                return slice_view(piece.materialize_pinned())
            return with_retry_no_split(attempt)
    """)
    vs = counter_discipline.check([src])
    assert any("once per ATTEMPT" in v.message for v in vs), \
        "\n".join(v.render() for v in vs)


# -- the blessed/fixed shapes analyze clean ----------------------------------

def test_pin_balance_accepts_acquire_before_try():
    src = _src("spark_rapids_tpu/shuffle/transport.py", """
        def materialize_view_batch(piece):
            def attempt():
                mat = piece.materialize_pinned()
                try:
                    return slice_view(mat)
                finally:
                    piece.unpin()
            return with_retry_no_split(attempt)
    """)
    assert pin_balance.check([src]) == []


def test_pin_balance_accepts_pinned_ledger_unwind():
    src = _src("spark_rapids_tpu/plan/execs/_fixture.py", """
        def merge_bucket(q, merge):
            batches = []
            pinned = []
            try:
                for h in q:
                    batches.append(h.materialize())
                    pinned.append(h)
                return merge(batches)
            finally:
                for h in pinned:
                    h.unpin()
    """)
    assert pin_balance.check([src]) == []


def test_pin_balance_accepts_guarded_release():
    """Path-condition-lite: the release guard correlates with the
    acquire having run, so the join does not fabricate an unmatched
    unpin."""
    src = _src("spark_rapids_tpu/plan/execs/_fixture.py", """
        def run_once(h, body):
            mat = None
            try:
                mat = h.materialize()
                return body(mat)
            finally:
                if mat is not None:
                    h.unpin()
    """)
    assert pin_balance.check([src]) == []


def test_pin_balance_accepts_transfer_api_and_except_unwind():
    src = _src("spark_rapids_tpu/shuffle/transport.py", """
        class StreamPiece:
            def materialize_pinned(self):
                batch = self._handle.materialize()
                try:
                    return self.as_view(batch)
                except BaseException:
                    self._handle.unpin()
                    raise
    """)
    assert pin_balance.check([src]) == []


def test_ambient_rule_accepts_blessed_spawn_and_infra_thread():
    src = _src("spark_rapids_tpu/shuffle/pipeline.py", """
        import threading

        from spark_rapids_tpu.memory.tenant import TENANTS
        from spark_rapids_tpu.utils.ambient import spawn_with_ambients

        def pipelined(source, pipe):
            def produce():
                with TENANTS.scope(None):
                    for item in source:
                        pipe.put(item)
            spawn_with_ambients(produce, name="producer")

        def sampler():
            def tick():
                return 42
            threading.Thread(target=tick, daemon=True).start()
    """)
    assert ambient_spawn.check([src]) == []


def test_ambient_rule_flags_pool_by_provenance():
    """A pool recognized by ThreadPoolExecutor provenance, not name."""
    src = _src("spark_rapids_tpu/io/_fixture.py", """
        from concurrent.futures import ThreadPoolExecutor

        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS

        _workers = ThreadPoolExecutor(2)

        def kick():
            def job():
                SHUFFLE_COUNTERS.add(blocks_fetched=1)
            _workers.submit(job)
    """)
    vs = ambient_spawn.check([src])
    assert any("pool submit" in v.message for v in vs)


def test_counter_rule_accepts_attempt_idempotent_increment():
    """An increment with nothing fallible after it runs exactly once —
    on the attempt that succeeds."""
    src = _src("spark_rapids_tpu/shuffle/transport.py", """
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS

        def materialize_view_batch(piece):
            def attempt():
                out = slice_view(piece.materialize_pinned())
                SHUFFLE_COUNTERS.add(range_view_materializes=1)
                return out
            return with_retry_no_split(attempt)
    """)
    assert counter_discipline.check([src]) == []


def test_counter_rule_accepts_increment_outside_retry():
    src = _src("spark_rapids_tpu/shuffle/transport.py", """
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS

        def materialize_view_batch(piece):
            SHUFFLE_COUNTERS.add(range_view_materializes=1)
            return with_retry_no_split(lambda: slice_view(piece))
    """)
    assert counter_discipline.check([src]) == []


def test_counter_rule_flags_raw_shuffle_counters_mutation():
    """PR 13: add/set_max tee each delta into the per-query counter
    scope (utils/obs.py); raw attribute mutation bypasses the tee and
    silently loses per-query attribution."""
    src = _src("spark_rapids_tpu/shuffle/transport.py", """
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS

        def fast_path():
            SHUFFLE_COUNTERS.merges += 1
            SHUFFLE_COUNTERS.blocks_fetched = 7
            setattr(SHUFFLE_COUNTERS, "bytes_fetched", 0)
    """)
    vs = counter_discipline.check([src])
    assert len([v for v in vs if "scoped tee" in v.message]) == 3, \
        "\n".join(v.render() for v in vs)


def test_counter_rule_raw_mutation_allowed_only_in_stats_module():
    """shuffle/stats.py itself owns the blessed entry points (add and
    set_max mutate fields under the lock by construction)."""
    src = _src("spark_rapids_tpu/shuffle/stats.py", """
        def reset(self):
            SHUFFLE_COUNTERS.merges = 0
    """)
    assert counter_discipline.check([src]) == []


def test_counter_rule_blessed_add_is_clean():
    src = _src("spark_rapids_tpu/shuffle/transport.py", """
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS

        def fast_path():
            SHUFFLE_COUNTERS.add(merges=1)
            SHUFFLE_COUNTERS.set_max(heartbeat_failure_streak=3)
    """)
    assert counter_discipline.check([src]) == []


# -- regression pins: the pin leaks the new rule found were FIXED ------------

def test_window_exception_path_pin_leak_was_fixed():
    """Pre-fix shape of window.py's two-pass loops: a retry-exhausted
    OOM between materialize and unpin left the batch pinned (and
    therefore unspillable) for the rest of the query."""
    pre_fix = _src("spark_rapids_tpu/plan/execs/window.py", """
        def two_pass(handles, run):
            for h in handles:
                b = h.materialize()
                out = run(b)
                h.unpin()
                h.close()
    """)
    assert any("exception path" in v.message
               for v in pin_balance.check([pre_fix]))
    for rel in ("spark_rapids_tpu/plan/execs/window.py",
                "spark_rapids_tpu/plan/execs/aggregate.py",
                "spark_rapids_tpu/plan/execs/join.py",
                "spark_rapids_tpu/shuffle/transport.py"):
        real = lint_core.load_source(REPO, rel)
        vs = _unsuppressed(pin_balance.check([real]), real)
        assert vs == [], f"{rel}:\n" + "\n".join(v.render() for v in vs)


def test_spawn_sites_are_migrated_or_reasoned():
    """Every engine-reaching spawn site goes through utils/ambient.py
    (or carries a reasoned suppression) — the PR 9/10 class stays a
    lint error."""
    for rel in ("spark_rapids_tpu/shuffle/pipeline.py",
                "spark_rapids_tpu/shuffle/net.py",
                "spark_rapids_tpu/cluster/executor.py",
                "spark_rapids_tpu/io/async_writer.py",
                "spark_rapids_tpu/io/reader_pool.py",
                "spark_rapids_tpu/serving/admission.py"):
        real = lint_core.load_source(REPO, rel)
        vs = _unsuppressed(ambient_spawn.check([real]), real)
        assert vs == [], f"{rel}:\n" + "\n".join(v.render() for v in vs)


# -- machine-readable output (--format sarif / github) -----------------------

def test_sarif_output_matches_schema_shape():
    from tools.tpulint.formats import to_sarif
    vs = [lint_core.Violation("pin-balance", "a/b.py", 12, "C.m", "msg"),
          lint_core.Violation("drift", "docs/x.md", 1, "<rules>", "m2")]
    log = to_sarif(vs)
    # the SARIF 2.1.0 shape CI ingesters require
    assert log["version"] == "2.1.0"
    assert log["$schema"].endswith("sarif-schema-2.1.0.json")
    (run,) = log["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "tpu-lint"
    rule_ids = [r["id"] for r in driver["rules"]]
    assert set(lint_core.ALL_RULES) <= set(rule_ids)
    assert all("shortDescription" in r and "text" in r["shortDescription"]
               for r in driver["rules"])
    assert len(run["results"]) == 2
    for res, v in zip(run["results"], vs):
        assert res["ruleId"] == v.rule
        assert rule_ids[res["ruleIndex"]] == v.rule
        assert res["level"] == "error"
        assert v.message in res["message"]["text"]
        (loc,) = res["locations"]
        phys = loc["physicalLocation"]
        assert phys["artifactLocation"]["uri"] == v.file
        assert phys["region"]["startLine"] == max(v.line, 1)
        assert res["partialFingerprints"]["tpulint/v1"] == v.fingerprint
    # and it round-trips through json
    json.loads(json.dumps(log))


def test_github_annotation_format():
    from tools.tpulint.formats import render_github
    v = lint_core.Violation("swallow", "x/y.py", 7, "f",
                            "multi%line\nmessage")
    (line,) = render_github([v]).splitlines()
    assert line.startswith("::error file=x/y.py,line=7,"
                           "title=tpu-lint swallow::")
    assert "\n" not in line and "%0A" in line and "%25" in line


# -- runner plumbing: timing, file subsets, doc coverage ---------------------

def test_run_all_timed_reports_every_ast_rule():
    violations, timings = lint_core.run_all_timed(
        REPO, with_drift=False,
        files=["spark_rapids_tpu/shuffle/pipeline.py"])
    expected = set(lint_core.ALL_RULES) - {"drift"}
    assert expected <= set(timings)
    assert all(t >= 0 for t in timings.values())
    # the subset run sees only the named file
    assert all(v.file == "spark_rapids_tpu/shuffle/pipeline.py"
               for v in violations)


def test_changed_files_is_well_formed():
    from tools.tpulint.__main__ import changed_files
    files = changed_files()
    assert isinstance(files, list)
    assert all(f.startswith("spark_rapids_tpu/") and f.endswith(".py")
               for f in files)


def test_lint_doc_covers_every_registered_rule():
    assert drift._check_lint_doc(REPO) == []


def test_lint_doc_drift_fires_on_undocumented_rule():
    old = lint_core.ALL_RULES
    lint_core.ALL_RULES = old + ("made-up-rule",)
    try:
        vs = drift._check_lint_doc(REPO)
    finally:
        lint_core.ALL_RULES = old
    assert any("made-up-rule" in v.message for v in vs)


def test_dataflow_backward_solver_release_reachability():
    """The backward solver: 'does a release lie on every path from
    here to an exit?' — YES downstream of the try (both continuations
    pass the finally), MAYBE at the acquire (its own exception edge
    bypasses the finally)."""
    from tools.tpulint.dataflow import NO, YES, MAYBE, solve_backward, \
        tri_join
    cfg = _golden_cfg("""
        def f(h):
            h.acquire()
            try:
                work(h)
            finally:
                h.release()
    """, "f")
    release = _node_containing(cfg, "h.release()")
    work = _node_containing(cfg, "work(h)")
    acq = _node_containing(cfg, "h.acquire()")

    def transfer(node, out_state):
        return YES if node.idx == release.idx else out_state

    out = solve_backward(cfg, NO, transfer, tri_join)
    assert out[work.idx] == YES
    assert out[acq.idx] == MAYBE


def test_pin_balance_ledger_does_not_mask_unrelated_leak():
    """A pinned-ledger unwind clears only ITS OWN receivers: an
    unrelated acquire's exception-path leak in the same function must
    still be flagged."""
    src = _src("spark_rapids_tpu/plan/execs/_fixture.py", """
        def merge_bucket(g, q, merge):
            extra = g.materialize()
            pinned = []
            try:
                batches = []
                for h in q:
                    batches.append(h.materialize())
                    pinned.append(h)
                return merge(batches, extra)
            finally:
                for h in pinned:
                    h.unpin()
    """)
    vs = pin_balance.check([src])
    assert any("g.materialize()" in v.message for v in vs), \
        "\n".join(v.render() for v in vs)


def test_pin_balance_catches_single_expression_acquire_then_raise():
    """The one-statement spelling of the failed-fallback-gather leak:
    the acquire succeeds and the consuming call raises in the same
    expression."""
    src = _src("spark_rapids_tpu/shuffle/transport.py", """
        def materialize_view(h):
            return slice_view(h.materialize())
    """)
    vs = pin_balance.check([src])
    assert any("exception path" in v.message for v in vs), \
        "\n".join(v.render() for v in vs)


def test_changed_mode_refuses_baseline_update():
    from tools.tpulint.__main__ import main as lint_main
    with pytest.raises(SystemExit):
        lint_main(["--changed", "--update-baseline"])


# -- knob-wiring and counter-registry drift -----------------------------------

def test_knob_wiring_drift_fires_both_directions():
    """Dead registered key and unregistered read key both fire; a key
    wired through its accessor property stays silent."""
    cfg = _src("spark_rapids_tpu/config.py", """
        DEAD = conf("spark.rapids.test.deadKnob").doc("d").int_conf(1)
        LIVE = conf("spark.rapids.test.liveKnob").doc("d").int_conf(2)
        DIRECT = conf("spark.rapids.test.directKnob").doc("d").int_conf(3)

        class RapidsConf:
            @property
            def live_knob(self):
                return self.get(LIVE)
    """)
    user = _src("spark_rapids_tpu/user.py", """
        from spark_rapids_tpu import config as C

        def f(conf):
            n = conf.live_knob
            d = conf.get(C.DIRECT)
            raw = conf.raw("spark.rapids.test.notRegistered")
            return n, d, raw
    """)
    vs = drift._check_knob_wiring(REPO, [cfg, user])
    msgs = [v.message for v in vs]
    assert any("spark.rapids.test.deadKnob" in m and "never read" in m
               for m in msgs), msgs
    assert any("spark.rapids.test.notRegistered" in m
               and "not registered" in m for m in msgs), msgs
    assert not any("liveKnob" in m or "directKnob" in m for m in msgs), msgs
    # the unregistered-read finding points at the offending file
    (unreg,) = [v for v in vs if "notRegistered" in v.message]
    assert unreg.file == "spark_rapids_tpu/user.py"


def test_knob_wiring_clean_on_real_tree():
    """Every registered spark.rapids.* key is read somewhere and every
    read key is registered (the check that found reader.batchSizeRows,
    batchSizeBytes, multiThreaded.reader.threads dead and
    serving.query.tenant unregistered, all since fixed)."""
    vs = drift._check_knob_wiring(REPO, None)
    assert vs == [], "\n".join(v.render() for v in vs)


def test_unused_counter_drift_fires_and_real_tree_clean():
    stats = _src("spark_rapids_tpu/shuffle/stats.py", """
        _FIELDS = (
            "used_counter",
            "splat_counter",
            "ghost_counter",
        )
    """)
    user = _src("spark_rapids_tpu/shuffle/net.py", """
        def g():
            SHUFFLE_COUNTERS.add(used_counter=1)
            SHUFFLE_COUNTERS.set_max(**{"splat_counter": 2})
    """)
    vs = drift._check_unused_counters(REPO, [stats, user])
    assert len(vs) == 1 and "ghost_counter" in vs[0].message, \
        "\n".join(v.render() for v in vs)
    assert vs[0].file == "spark_rapids_tpu/shuffle/stats.py"
    assert drift._check_unused_counters(REPO, None) == []


def test_sarif_fingerprints_stable_across_runs():
    """Re-rendering the same violations must byte-match — CI dedupe
    keys on partialFingerprints, so any instability (dict order, ids)
    would resurface every finding as new on every push."""
    from tools.tpulint.formats import render_sarif
    vs = [lint_core.Violation("pin-balance", "a/b.py", 12, "C.m", "msg"),
          lint_core.Violation("drift", "docs/x.md", 1, "<rules>", "m2")]
    again = [lint_core.Violation("pin-balance", "a/b.py", 12, "C.m", "msg"),
             lint_core.Violation("drift", "docs/x.md", 1, "<rules>", "m2")]
    assert render_sarif(vs) == render_sarif(again)
    # empty log is still schema-shaped (the --changed no-files path)
    log = json.loads(render_sarif([]))
    assert log["version"] == "2.1.0" and log["runs"][0]["results"] == []
