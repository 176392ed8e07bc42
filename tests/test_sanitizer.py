"""Runtime contract sanitizer (utils/sanitizer.py): the dynamic twin of
tpulint's static rules.

Three SEEDED failures prove each contract fires with a useful name (pin
leak, lock inversion, dropped ambient), the transfer-guard/compile-budget
pair catches injected regressions, a real query runs green under the
sanitizer, and the slow-marked micro-bench pins the OFF-path cost of the
hook seams to within noise on a 64MB reduce-fetch merge.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.memory.spill import make_spillable
from spark_rapids_tpu.memory.tenant import TENANTS
from spark_rapids_tpu.utils import sanitizer as san
from spark_rapids_tpu.utils.sanitizer import SanitizerError

SCHEMA = Schema.of(a=T.LONG)


def _batch(n: int = 64) -> ColumnarBatch:
    return ColumnarBatch.from_pydict({"a": list(range(n))}, SCHEMA)


@pytest.fixture
def san_on(monkeypatch):
    """Sanitizer armed for the test, fully disarmed after (the env
    override is cleared so the teardown disable actually sticks even
    when the suite runs under SPARK_RAPIDS_TPU_SANITIZE=1)."""
    monkeypatch.delenv("SPARK_RAPIDS_TPU_SANITIZE", raising=False)
    monkeypatch.delenv("SPARK_RAPIDS_TPU_SANITIZE_COMPILE_BUDGET",
                       raising=False)
    san.configure_sanitizer(True)
    san.reset_sanitizer_state()
    try:
        yield san
    finally:
        san.configure_sanitizer(False)
        san.reset_sanitizer_state()


# -- end to end ---------------------------------------------------------------


def test_query_runs_green_under_sanitizer(san_on):
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.expressions import col, sum_
    from spark_rapids_tpu.expressions.core import Alias
    s = TpuSession({"spark.rapids.sql.enabled": "true",
                    "spark.rapids.sanitizer.enabled": "true"})
    assert san.sanitizer_enabled()
    schema = Schema.of(a=T.LONG, b=T.LONG)
    df = s.create_dataframe({"a": list(range(300)),
                             "b": [i % 3 for i in range(300)]}, schema)
    rows = sorted(df.group_by("b").agg(Alias(sum_(col("a")), "s"))
                  .collect())
    expect = sorted((k, sum(i for i in range(300) if i % 3 == k))
                    for k in range(3))
    assert rows == [tuple(r) for r in expect], rows
    assert san.outstanding_pins() == []


def test_sanitizer_off_leaves_every_seam_cold(monkeypatch):
    monkeypatch.delenv("SPARK_RAPIDS_TPU_SANITIZE", raising=False)
    san.configure_sanitizer(False)
    from spark_rapids_tpu.memory import spill as _spill
    from spark_rapids_tpu.plan.execs import base as _base
    from spark_rapids_tpu.utils import ambient as _ambient
    assert _spill._PIN_HOOK is None
    assert _base._COMPILE_HOOK is None
    assert _ambient._AMBIENT_HOOK is None
    assert threading.Lock is san._REAL_LOCK
    assert threading.RLock is san._REAL_RLOCK


# -- seeded failure 1: pin leak -----------------------------------------------


def test_seeded_pin_leak_named_at_query_teardown(san_on):
    h = None
    try:
        with pytest.raises(SanitizerError) as ei:
            with san.query_scope("seeded-leak"):
                h = make_spillable(_batch())
                h.materialize()        # pinned, deliberately never unpinned
        msg = str(ei.value)
        assert "pin leak" in msg and "seeded-leak" in msg
        assert "SpillableBatchHandle" in msg
        # the ledger names the ACQUIRING stack: this file must be on it
        assert "test_sanitizer" in msg and "materialize" in msg
    finally:
        if h is not None:
            h.unpin()
            h.close()
    assert san.outstanding_pins() == []


def test_balanced_pins_pass_query_teardown(san_on):
    with san.query_scope("balanced"):
        h = make_spillable(_batch())
        with h.borrowed():
            pass
        h.close()


def test_tenant_ledger_residue_named_at_query_teardown(san_on):
    h = None
    try:
        with pytest.raises(SanitizerError, match="tenant-ledger residue"):
            with san.query_scope("seeded-residue"):
                with TENANTS.scope("sanit-residue-tenant"):
                    h = make_spillable(_batch())   # charged, never closed
    finally:
        if h is not None:
            h.close()


# -- seeded failure 2: lock inversion -----------------------------------------


def test_seeded_lock_inversion_raises_with_both_sites(san_on):
    a = san._WitnessLock(threading.Lock(), "fixture/mod.A._lock", False)
    b = san._WitnessLock(threading.Lock(), "fixture/mod.B._lock", False)
    with a:
        with b:
            pass
    with pytest.raises(SanitizerError) as ei:
        with b:
            with a:
                pass
    msg = str(ei.value)
    assert "inversion" in msg
    assert "fixture/mod.A._lock" in msg and "fixture/mod.B._lock" in msg
    assert "fixture" in msg and "lock-order" in msg
    # the inverted acquire released its lock on the way out
    assert not a.locked() and not b.locked()


def test_package_locks_get_witnessed_with_static_naming(san_on):
    """A lock born in package code while the sanitizer is armed is
    wrapped, and its derived id uses the static table's naming
    (tools/tpulint/locks.py _LockTable) so witnessed edges are
    comparable against the static graph."""
    h = make_spillable(_batch())
    try:
        assert isinstance(h._lock, san._WitnessLock), type(h._lock)
        assert h._lock.lock_id == "memory/spill.SpillableBatchHandle._lock"
    finally:
        h.close()


def test_witnessed_edge_missing_from_static_graph_is_fixture_candidate(
        san_on):
    outer = san._WitnessLock(threading.Lock(),
                             "fixture/ghost.Outer._lock", False)
    inner = san._WitnessLock(threading.Lock(),
                             "fixture/ghost.Inner._lock", False)
    with outer:
        with inner:
            pass
    rep = san.lock_order_report()
    assert rep["static"] is not None and rep["static"] > 0
    assert any(o == "fixture/ghost.Outer._lock"
               and i == "fixture/ghost.Inner._lock"
               for o, i, _site in rep["unexpected"]), rep


# -- seeded failure 3: dropped ambient ----------------------------------------


def test_seeded_dropped_ambient_fails_at_spawn_target_entry(
        san_on, monkeypatch):
    """A blessed spawn whose scope re-establishment DROPS the tenant
    must fail at target entry, before the worker runs a single line
    under the wrong attribution."""
    from spark_rapids_tpu.utils.ambient import Ambients, \
        submit_with_ambients

    @contextmanager
    def broken_scope(self):      # everything EXCEPT the tenant
        from spark_rapids_tpu.memory.semaphore import task_priority
        from spark_rapids_tpu.utils.cancel import cancel_scope
        from spark_rapids_tpu.utils.obs import trace_scope
        with task_priority(self.priority), cancel_scope(self.token), \
                trace_scope(self.trace):
            yield self

    monkeypatch.setattr(Ambients, "scope", broken_scope)
    ran = []
    with TENANTS.scope("sanit-amb-tenant"):
        with ThreadPoolExecutor(max_workers=1) as pool:
            fut = submit_with_ambients(pool, lambda: ran.append(1))
            err = fut.exception(timeout=30)
    assert isinstance(err, SanitizerError), err
    assert "ambient integrity" in str(err)
    assert "tenant" in str(err) and "sanit-amb-tenant" in str(err)
    assert ran == []             # the target never ran


def test_intact_ambients_pass_the_spawn_entry_check(san_on):
    from spark_rapids_tpu.utils.ambient import submit_with_ambients
    with TENANTS.scope("sanit-amb-ok"):
        with ThreadPoolExecutor(max_workers=1) as pool:
            fut = submit_with_ambients(pool, TENANTS.current)
            assert fut.result(timeout=30) == "sanit-amb-ok"


# -- transfer guard + compile budget ------------------------------------------


def test_hot_section_catches_injected_host_sync(san_on):
    import jax.numpy as jnp
    x = jnp.arange(8)
    with pytest.raises(SanitizerError) as ei:
        with san.hot_section("seeded-sync"):
            float(x[0])          # implicit transfer: the injected regression
    msg = str(ei.value)
    assert "hot section" in msg and "seeded-sync" in msg
    # explicit movement stays allowed inside a hot section
    with san.hot_section("explicit-ok"):
        jnp.asarray(np.arange(4))


def test_hot_path_scalar_commits_are_explicit(san_on):
    """Pin the defect class the guard found over the real suites: row
    counts committed as bare python scalars (an implicit h2d per
    batch).  Batch construction and the host_scalar idiom must stay
    legal inside a hot section; the bare-scalar form must not."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.batch import host_scalar
    with san.hot_section("explicit-commits"):
        _batch()                      # from_pydict: host_scalar num_rows
        host_scalar(7)                # the fix idiom itself
    with pytest.raises(SanitizerError):
        with san.hot_section("bare-scalar"):
            jnp.asarray(7, jnp.int32)   # the old implicit form

    # blessed_sync: runtime twin of `# tpu-lint: allow-host-sync(...)`
    x = jnp.arange(4)
    with san.hot_section("blessed"):
        with san.blessed_sync("documented one-scalar sync"):
            assert float(x[1]) == 1.0


def test_hot_section_is_transparent_when_off(monkeypatch):
    monkeypatch.delenv("SPARK_RAPIDS_TPU_SANITIZE", raising=False)
    san.configure_sanitizer(False)
    import jax.numpy as jnp
    with san.hot_section("off"):
        assert float(jnp.arange(3)[1]) == 1.0


def test_compile_budget_catches_injected_recompile(san_on):
    from spark_rapids_tpu.plan.execs.base import shared_jit
    stamp = time.monotonic_ns()     # keys must MISS the cross-test cache
    with san.compile_budget_scope(1):
        shared_jit(f"sanit-{stamp}-0", lambda: (lambda x: x + 1),
                   kind="sanit")
        with pytest.raises(SanitizerError) as ei:
            shared_jit(f"sanit-{stamp}-1", lambda: (lambda x: x + 2),
                       kind="sanit")
    assert "compile budget" in str(ei.value)
    assert f"sanit-{stamp}-1" in str(ei.value)
    # outside the scope the process-wide budget (0 = unlimited) rules
    shared_jit(f"sanit-{stamp}-2", lambda: (lambda x: x + 3),
               kind="sanit")


# -- off-path overhead --------------------------------------------------------


@pytest.mark.slow
def test_off_path_within_noise_on_64mb_reduce_fetch(monkeypatch):
    """The hook seams cost one global load + None test each; prove the
    OFF path is within 1% of even a no-op-hook-armed run on a 64MB
    reduce-fetch merge plus a pin/unpin borrow loop (interleaved A/B,
    median of per-pair ratios so common-mode drift cancels)."""
    monkeypatch.delenv("SPARK_RAPIDS_TPU_SANITIZE", raising=False)
    san.configure_sanitizer(False)
    import spark_rapids_tpu.shuffle.serializer as S
    from spark_rapids_tpu.memory import spill as _spill
    rows = 1 << 17                              # 1MB of int64 per block
    block = S.serialize_batch(
        ColumnarBatch.from_pydict({"a": np.arange(rows)}, SCHEMA))
    blocks = [block] * 64                       # 64MB reduce fetch

    def run_once() -> float:
        import gc
        gc.collect()            # GC pauses, not seam cost, set the noise floor
        t0 = time.perf_counter()
        merged = S.merge_batches(blocks, SCHEMA)
        h = make_spillable(merged)
        for _ in range(32):
            with h.borrowed():                  # pin seam x2 per loop
                pass
        h.close()
        return time.perf_counter() - t0

    def run_armed() -> float:
        _spill.set_pin_hook(lambda h, d: None)  # B: no-op hook armed
        try:
            return run_once()
        finally:
            _spill.set_pin_hook(None)

    def trimmed_mean(xs) -> float:
        xs = sorted(xs)
        k = max(1, len(xs) // 5)                # drop top/bottom 20%
        xs = xs[k:-k]
        return sum(xs) / len(xs)

    run_once()                                  # warm compile/caches
    a1, a2, b_times = [], [], []
    for i in range(18):
        # rotate the order so drift/GC bias cancels instead of landing
        # on whichever side always runs first; the split A series is
        # the same-code noise CONTROL the bound calibrates against
        runs = [(a1, run_once), (b_times, run_armed), (a2, run_once)]
        for acc, fn in runs[i % 3:] + runs[:i % 3]:
            acc.append(fn())
    # seam cost from above: the ARMED run does strictly more work than
    # the shipped OFF path, so if armed-vs-off is within noise the
    # OFF-path None-check seams certainly are
    cost = trimmed_mean(b_times) / trimmed_mean(a1 + a2) - 1.0
    control = abs(trimmed_mean(a1) / trimmed_mean(a2) - 1.0)
    # within noise: the A/B gap must not exceed what the SAME code
    # shows against itself (plus the 1% floor the contract names)
    assert cost <= max(0.01, 2.0 * control), (cost, control, a1, a2, b_times)
