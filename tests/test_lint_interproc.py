"""Fixtures for the interprocedural tpulint tier (tools/tpulint/
callgraph.py + summaries.py + interproc.py).

Three layers of pinning:

* summary-engine goldens — the per-function effect summaries (pins,
  releases, counters, locks, engine reach) computed for small closed
  fixture worlds, including the mutual-recursion fixpoint;
* pass fixtures — each interprocedural pass must FIRE on the defect
  shape the intraprocedural rules are blind to, and stay silent where
  the intra rule already reports (no double findings);
* the historical review-round shapes (PR 11 unmatched-unpin through a
  batch materializer, PR 9 bare-thread producer, wrapper pin-transfer)
  re-pinned as *interprocedural* fixtures: the defect is split across
  call/module boundaries so only the summary tier can see it.

Fixture worlds include a fake ``spark_rapids_tpu/__init__.py`` so the
whole-program augmentation treats them as closed worlds (never mixed
with the on-disk tree).
"""
import ast
import os
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.tpulint import core as lint_core
from tools.tpulint import interproc, locks, summaries


def _src(path: str, text: str) -> lint_core.SourceFile:
    text = textwrap.dedent(text)
    lines = text.splitlines()
    allows, problems = lint_core._parse_allows(lines)
    s = lint_core.SourceFile(path=path, text=text, lines=lines,
                             tree=ast.parse(text), allows=allows)
    s.suppression_problems = problems
    return s


def _world(*files):
    """A closed fixture program: (path, text) pairs plus the package
    __init__ marker that pins the world closed."""
    srcs = [_src("spark_rapids_tpu/__init__.py", "")]
    srcs.extend(_src(p, t) for p, t in files)
    return srcs


def _engine(*files):
    return summaries.build_engine(_world(*files))


def _summary(eng, path, qual):
    return eng.summaries[f"{path}:{qual}"]


# -- summary-engine goldens --------------------------------------------------

WRAPPER_WORLD = ("spark_rapids_tpu/shuffle/fx_helpers.py", """
    def fetch_block(store, key):
        buf = store.materialize(key)
        return buf

    def fetch_via_wrapper(store, key):
        return fetch_block(store, key)

    def fetch_twice_removed(store, key):
        return fetch_via_wrapper(store, key)
""")


def test_returns_pinned_through_wrapper_chain():
    eng = _engine(WRAPPER_WORLD)
    p = "spark_rapids_tpu/shuffle/fx_helpers.py"
    direct = _summary(eng, p, "fetch_block")
    assert direct.returns_pinned
    assert "store.materialize()" in direct.pin_path
    once = _summary(eng, p, "fetch_via_wrapper")
    assert once.returns_pinned
    assert once.pin_path.startswith("fetch_block()")
    twice = _summary(eng, p, "fetch_twice_removed")
    assert twice.returns_pinned
    assert twice.pin_path.startswith("fetch_via_wrapper()")
    assert "fetch_block()" in twice.pin_path


def test_conditional_producer_is_returns_pinned():
    """A wrapper that produces a pinned handle on only ONE branch still
    summarizes as returns-pinned — the caller owns whatever comes back."""
    eng = _engine(("spark_rapids_tpu/shuffle/fx_cond.py", """
        def maybe_fetch(store, key, want):
            if want:
                return store.materialize(key)
            return None
    """))
    s = _summary(eng, "spark_rapids_tpu/shuffle/fx_cond.py",
                 "maybe_fetch")
    assert s.returns_pinned


def test_releases_arg_direct_elementwise_and_through_wrapper():
    eng = _engine(("spark_rapids_tpu/shuffle/fx_release.py", """
        def drop_one(buf):
            buf.unpin()

        def drop_all(bufs):
            for b in bufs:
                b.unpin()

        def drop_via_wrapper(handle):
            drop_one(handle)

        def conditional_drop(buf, ok):
            if ok:
                buf.unpin()
    """))
    p = "spark_rapids_tpu/shuffle/fx_release.py"
    assert 0 in _summary(eng, p, "drop_one").releases_params
    assert 0 in _summary(eng, p, "drop_all").releases_params
    assert "element-wise" in \
        _summary(eng, p, "drop_all").releases_params[0]
    wrapped = _summary(eng, p, "drop_via_wrapper")
    assert 0 in wrapped.releases_params
    assert wrapped.releases_params[0].startswith("drop_one()")
    # any-path semantics, deliberately: a conditional release still
    # transfers ownership from the caller's point of view (the caller
    # cannot safely unpin after the call), so it counts as releasing
    assert 0 in _summary(eng, p, "conditional_drop").releases_params


MUTUAL_WORLD = [
    ("spark_rapids_tpu/utils/fx_walker.py", """
        from spark_rapids_tpu.shuffle import net

        def ping(n):
            if n:
                return pong(n - 1)
            return net.fetch(n)

        def pong(n):
            if n:
                return ping(n - 1)
            return 0
    """),
]


def test_mutual_recursion_engine_fixpoint_converges():
    eng = _engine(*MUTUAL_WORLD)
    p = "spark_rapids_tpu/utils/fx_walker.py"
    ping, pong = _summary(eng, p, "ping"), _summary(eng, p, "pong")
    assert ping.engine is not None and "net" in ping.engine
    # pong only reaches engine code through ping: fixpoint must carry it
    assert pong.engine is not None and "ping()" in pong.engine


def test_mutual_recursion_counters_conservatively_not_tail():
    eng = _engine(("spark_rapids_tpu/shuffle/fx_recount.py", """
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS

        def even(n):
            SHUFFLE_COUNTERS.add(bytes_sent=n)
            if n:
                return odd(n - 1)
            return 0

        def odd(n):
            if n:
                return even(n - 1)
            return 1
    """))
    p = "spark_rapids_tpu/shuffle/fx_recount.py"
    for qual in ("even", "odd"):
        s = _summary(eng, p, qual)
        assert "bytes_sent" in s.counters
        assert not s.counters_tail


def test_summary_annotation_replaces_computed_summary():
    eng = _engine(("spark_rapids_tpu/shuffle/fx_ann.py", """
        # tpu-lint: summary(returns-pinned, releases-arg 1)
        def exotic_dispatch(registry, handle):
            return registry.lookup(handle)

        # tpu-lint: summary(pure)
        def actually_acquires(store, key):
            return store.materialize(key)
    """))
    p = "spark_rapids_tpu/shuffle/fx_ann.py"
    ann = _summary(eng, p, "exotic_dispatch")
    assert ann.annotated and ann.returns_pinned
    assert 1 in ann.releases_params
    assert "summary annotation" in ann.pin_path
    # `pure` is a contract: it REPLACES what the body would compute
    pure = _summary(eng, p, "actually_acquires")
    assert pure.annotated and not pure.returns_pinned
    assert not eng.annotation_problems


def test_malformed_annotation_clause_is_reported():
    world = _world(("spark_rapids_tpu/shuffle/fx_badann.py", """
        # tpu-lint: summary(returns-pined)
        def typo(store, key):
            return store.materialize(key)
    """))
    vs = interproc.check_pins(world)
    bad = [v for v in vs if v.rule == "bad-suppression"]
    assert bad and "returns-pined" in bad[0].message


# -- pin-balance: leaks only a summary can see -------------------------------

def test_wrapper_pin_transfer_discard_fires():
    """The wrapper pin-transfer review shape, split across modules: the
    caller discards a handle produced two calls away."""
    world = _world(
        WRAPPER_WORLD,
        ("spark_rapids_tpu/shuffle/fx_consumer.py", """
            from spark_rapids_tpu.shuffle.fx_helpers import \\
                fetch_via_wrapper

            def consume(store, key):
                fetch_via_wrapper(store, key)
                return True
        """))
    vs = [v for v in interproc.check_pins(world)
          if v.rule == "pin-balance"]
    assert len(vs) == 1
    v = vs[0]
    assert v.file == "spark_rapids_tpu/shuffle/fx_consumer.py"
    assert v.scope == "consume"
    assert "discarded" in v.message
    assert "interprocedural path" in v.message
    assert "fetch_block()" in v.message


def test_pr11_batch_materializer_leak_fires_interprocedurally():
    """PR 11's unmatched-unpin: the pinned BATCH comes out of a helper
    wrapping materialize_batch_pinned; the caller binds it and forgets
    every element."""
    world = _world(("spark_rapids_tpu/shuffle/fx_batch.py", """
        def fetch_batch(transport, keys):
            return transport.materialize_batch_pinned(keys)

        def reduce_side(transport, keys):
            pieces = fetch_batch(transport, keys)
            total = 0
            for k in keys:
                total += k
            return total
    """))
    vs = [v for v in interproc.check_pins(world)
          if v.rule == "pin-balance"]
    assert len(vs) == 1
    assert vs[0].scope == "reduce_side"
    assert "never unpinned" in vs[0].message


def test_pin_released_or_escaping_results_are_silent():
    world = _world(
        WRAPPER_WORLD,
        ("spark_rapids_tpu/shuffle/fx_clean.py", """
            from spark_rapids_tpu.shuffle.fx_helpers import \\
                fetch_via_wrapper

            def releases(store, key):
                buf = fetch_via_wrapper(store, key)
                buf.unpin()

            def escapes(store, key):
                return fetch_via_wrapper(store, key)

            def hands_off(store, key, sink):
                buf = fetch_via_wrapper(store, key)
                sink.push(buf, key)
        """))
    assert [v for v in interproc.check_pins(world)
            if v.rule == "pin-balance"] == []


def test_pin_passed_to_releasing_helper_is_silent():
    """Ownership transfer through releases-arg — including the any-path
    conditional releaser, which still owns the handle after the call."""
    world = _world(("spark_rapids_tpu/shuffle/fx_transfer.py", """
        def fetch(store, key):
            return store.materialize(key)

        def drop(buf):
            buf.unpin()

        def conditional_drop(buf, ok):
            if ok:
                buf.unpin()

        def ok_direct(store, key):
            buf = fetch(store, key)
            drop(buf)

        def ok_conditional(store, key):
            buf = fetch(store, key)
            conditional_drop(buf, True)
    """))
    assert [v for v in interproc.check_pins(world)
            if v.rule == "pin-balance"] == []


def test_annotated_returns_pinned_fires_at_caller():
    world = _world(("spark_rapids_tpu/shuffle/fx_annfire.py", """
        # tpu-lint: summary(returns-pinned)
        def dynamic_fetch(store, key):
            return getattr(store, "materialize")(key)

        def leaky(store, key):
            dynamic_fetch(store, key)
    """))
    vs = [v for v in interproc.check_pins(world)
          if v.rule == "pin-balance"]
    assert len(vs) == 1
    assert "summary annotation" in vs[0].message


# -- ambient-propagation: reach only a summary can see -----------------------

def test_pr9_bare_thread_producer_fires_across_modules():
    """PR 9's bare-thread producer, made interprocedural: the target is
    IMPORTED, and only reaches engine code through mutual recursion in
    its own module — invisible to the one-module rule."""
    world = _world(
        MUTUAL_WORLD[0],
        ("spark_rapids_tpu/io/fx_spawner.py", """
            import threading
            from spark_rapids_tpu.utils.fx_walker import pong

            def start():
                t = threading.Thread(target=pong)
                t.start()
                return t
        """))
    vs = [v for v in interproc.check_ambients(world)
          if v.rule == "ambient-propagation"]
    assert len(vs) == 1
    v = vs[0]
    assert v.file == "spark_rapids_tpu/io/fx_spawner.py"
    assert "threading.Thread" in v.message
    assert "pong" in v.message
    assert "spawn_with_ambients" in v.message


def test_pool_submitted_closure_ambient_loss_fires():
    """The reader_pool shape: a pool submit whose imported target only
    reaches engine code through a same-module helper."""
    world = _world(
        ("spark_rapids_tpu/serving/fx_worker.py", """
            def run_task(item):
                return _locate(item)

            def _locate(item):
                from spark_rapids_tpu.memory import pools
                return pools.reserve(item)
        """),
        ("spark_rapids_tpu/serving/fx_dispatch.py", """
            from concurrent.futures import ThreadPoolExecutor
            from spark_rapids_tpu.serving.fx_worker import run_task

            _POOL = ThreadPoolExecutor(max_workers=2)

            def dispatch(items):
                for item in items:
                    _POOL.submit(run_task, item)
        """))
    vs = [v for v in interproc.check_ambients(world)
          if v.rule == "ambient-propagation"]
    assert len(vs) == 1
    v = vs[0]
    assert v.file == "spark_rapids_tpu/serving/fx_dispatch.py"
    assert "pool submit" in v.message
    assert "run_task" in v.message


def test_ambient_interproc_defers_to_intra_rule():
    """A same-module engine-reaching target is the INTRA rule's finding;
    the interprocedural pass must not double-report it."""
    world = _world(("spark_rapids_tpu/io/fx_local.py", """
        import threading
        from spark_rapids_tpu.shuffle import net

        def producer():
            return net.fetch(0)

        def start():
            threading.Thread(target=producer).start()
    """))
    from tools.tpulint import ambient_spawn
    intra = [v for v in ambient_spawn.check(world)
             if v.rule == "ambient-propagation"]
    assert len(intra) == 1          # the one-module rule owns this
    assert interproc.check_ambients(world) == []


def test_ambient_silent_for_infra_only_target():
    world = _world(
        ("spark_rapids_tpu/utils/fx_infra.py", """
            def tick(n):
                return n + 1
        """),
        ("spark_rapids_tpu/io/fx_timer.py", """
            import threading
            from spark_rapids_tpu.utils.fx_infra import tick

            def start():
                threading.Thread(target=tick).start()
        """))
    assert interproc.check_ambients(world) == []


# -- counter-discipline: mutation through helpers ----------------------------

RETRY_WORLD = ("spark_rapids_tpu/shuffle/fx_retrycnt.py", """
    from spark_rapids_tpu.memory.retry import with_retry
    from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS

    def _bump(n):
        SHUFFLE_COUNTERS.add(bytes_sent=n)

    def _transform(batch):
        return [b * 2 for b in batch]

    def _attempt(batch):
        _bump(1)
        return _transform(batch)

    def run(batch):
        return with_retry(lambda: _attempt(batch))
""")


def test_counter_mutation_through_helper_in_retry_body_fires():
    world = _world(RETRY_WORLD)
    from tools.tpulint import counter_discipline
    # the increment is NOT lexical in the retry body: intra is blind
    assert [v for v in counter_discipline.check(world)
            if v.rule == "counter-discipline"] == []
    vs = [v for v in interproc.check_counters(world)
          if v.rule == "counter-discipline"]
    assert vs, "helper counter mutation inside retry body must fire"
    assert any("bytes_sent" in v.message for v in vs)
    assert any("retry" in v.message for v in vs)


def test_tail_positioned_helper_counter_is_silent():
    world = _world(("spark_rapids_tpu/shuffle/fx_tailcnt.py", """
        from spark_rapids_tpu.memory.retry import with_retry
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS

        def _bump(n):
            SHUFFLE_COUNTERS.add(bytes_sent=n)

        def _transform(batch):
            return [b * 2 for b in batch]

        def _attempt(batch):
            out = _transform(batch)
            _bump(1)
            return out

        def run(batch):
            return with_retry(lambda: _attempt(batch))
    """))
    assert interproc.check_counters(world) == []


# -- lock-order: inversions assembled across call boundaries -----------------

ABBA_WORLD = [
    ("spark_rapids_tpu/shuffle/fx_lk_a.py", """
        import threading
        import spark_rapids_tpu.shuffle.fx_lk_b as lk_b

        _lock_a = threading.Lock()

        def take_a():
            with _lock_a:
                return 1

        def outer_ab():
            with _lock_a:
                return lk_b.take_b()
    """),
    ("spark_rapids_tpu/shuffle/fx_lk_b.py", """
        import threading
        import spark_rapids_tpu.shuffle.fx_lk_a as lk_a

        _lock_b = threading.Lock()

        def take_b():
            with _lock_b:
                return 2

        def outer_ba():
            with _lock_b:
                return lk_a.take_a()
    """),
]


def test_cross_module_abba_inversion_fires():
    world = _world(*ABBA_WORLD)
    # each direction is a single with + a CALL: the one-level rule has
    # no edge at all, so it stays silent …
    assert [v for v in locks.check(world)
            if "inconsistent lock order" in v.message] == []
    # … and the summary tier sees both directions
    vs = [v for v in interproc.check_locks(world)
          if v.rule == "lock-order"]
    assert len(vs) == 1
    v = vs[0]
    assert "visible only interprocedurally" in v.message
    assert "shuffle/fx_lk_a._lock_a" in v.message
    assert "shuffle/fx_lk_b._lock_b" in v.message


def test_lock_pass_defers_to_intra_abba():
    world = _world(("spark_rapids_tpu/shuffle/fx_lk_intra.py", """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def ab():
            with _a:
                with _b:
                    return 1

        def ba():
            with _b:
                with _a:
                    return 2
    """))
    intra = [v for v in locks.check(world)
             if "inconsistent lock order" in v.message]
    assert len(intra) == 1          # locks.py owns the lexical shape
    assert interproc.check_locks(world) == []


def test_static_lock_graph_covers_summary_edges():
    world = _world(*ABBA_WORLD)
    graph = interproc.static_lock_graph(sources=world)
    assert ("shuffle/fx_lk_a._lock_a",
            "shuffle/fx_lk_b._lock_b") in graph
    assert ("shuffle/fx_lk_b._lock_b",
            "shuffle/fx_lk_a._lock_a") in graph


# -- whole-program augmentation ----------------------------------------------

def test_fixture_worlds_stay_closed():
    """A source set that doesn't byte-match the on-disk tree must never
    be augmented with real package files."""
    world = [_src("spark_rapids_tpu/shuffle/net.py", "x = 1\n")]
    assert interproc._whole_program(world) is world


def test_on_disk_subset_is_augmented():
    rel = "spark_rapids_tpu/shuffle/net.py"
    src = lint_core.load_source(REPO, rel)
    full = interproc._whole_program([src])
    assert len(full) > 100
    assert {s.path for s in full} >= {rel,
                                      "spark_rapids_tpu/memory/spill.py"}


# -- runtime budget (satellite: the tier must stay usable) -------------------

def test_lint_runtime_budgets():
    """The tier stays usable (ISSUE 18: full run 30 s, --changed 5 s), held
    in a form that does not depend on who shares the cores: seconds of
    process CPU time stretch twofold beside five busy xdist workers (the
    --changed run read 3.4 s alone and 6.99 s in the driver's run), so the
    two-file run is held to a share of the full run timed here beside it
    (0.41 to 0.46 of it, alone and beside six busy workers).  The full run
    keeps its 30 s: it reads 8 to 10 s here, so a loaded machine has
    threefold room."""
    import time

    t0 = time.process_time()
    _vs, full_t = lint_core.run_all_timed(REPO, with_drift=False)
    full_cpu = time.process_time() - t0
    assert full_cpu <= 30.0, (full_cpu, full_t)
    changed = ["spark_rapids_tpu/shuffle/net.py",
               "spark_rapids_tpu/memory/spill.py"]
    t0 = time.process_time()
    _vs, chg_t = lint_core.run_all_timed(REPO, with_drift=False,
                                         files=changed)
    chg_cpu = time.process_time() - t0
    assert chg_cpu <= 0.6 * full_cpu, (chg_cpu, full_cpu, chg_t)


# -- lock-order: transitive blocking-under-lock ------------------------------

BLOCKING_WORLD = [
    ("spark_rapids_tpu/shuffle/fx_blk_help.py", """
        import jax

        def device_sum(x):
            return jax.device_get(x)
    """),
    ("spark_rapids_tpu/shuffle/fx_blk_hold.py", """
        import threading
        from spark_rapids_tpu.shuffle.fx_blk_help import device_sum

        _lock = threading.Lock()

        def totals(x):
            with _lock:
                return device_sum(x)
    """),
]


def test_transitive_blocking_under_lock_fires():
    """A device sync two modules away, reached while holding a lock:
    locks.py (one-level, same-module) is blind; the summary tier
    reports it at the call site with the interprocedural path."""
    world = _world(*BLOCKING_WORLD)
    assert [v for v in locks.check(world)
            if "while holding" in v.message] == []
    vs = [v for v in interproc.check_locks(world)
          if "can block" in v.message]
    assert len(vs) == 1, "\n".join(v.render() for v in vs)
    v = vs[0]
    assert v.file == "spark_rapids_tpu/shuffle/fx_blk_hold.py"
    assert v.scope == "totals"
    assert "device_sum" in v.message
    assert "device sync" in v.message
    assert "shuffle/fx_blk_hold._lock" in v.message


def test_blessed_wait_exempt_from_blocking_under_lock():
    """cancellable_wait IS a blocking call by summary, but it is the
    blessed bounded wait — calling it under a lock must not fire."""
    world = _world(
        ("spark_rapids_tpu/utils/fx_cancel.py", """
            import time

            def cancellable_wait(cv, timeout):
                time.sleep(timeout)
        """),
        ("spark_rapids_tpu/shuffle/fx_blk_wait.py", """
            import threading
            from spark_rapids_tpu.utils.fx_cancel import cancellable_wait

            _lock = threading.Lock()

            def waits(cv):
                with _lock:
                    cancellable_wait(cv, 0.1)
        """))
    assert [v for v in interproc.check_locks(world)
            if "can block" in v.message] == []


def test_one_level_blocking_defers_to_intra():
    """Same-module bare call to a directly-blocking helper: locks.py's
    fn_blocking map owns that shape; the summary tier stays silent."""
    world = _world(("spark_rapids_tpu/shuffle/fx_blk_intra.py", """
        import threading
        import time

        _lock = threading.Lock()

        def _slow():
            time.sleep(1)

        def f():
            with _lock:
                _slow()
    """))
    intra = [v for v in locks.check(world)
             if "while holding" in v.message]
    assert len(intra) == 1, "\n".join(v.render() for v in intra)
    assert [v for v in interproc.check_locks(world)
            if "can block" in v.message] == []


def test_blocking_under_throttle_semaphore_silent():
    """Semaphores are throttles, not critical sections: blocking while
    holding one is the design, not a defect."""
    world = _world(*BLOCKING_WORLD[:1], (
        "spark_rapids_tpu/shuffle/fx_blk_sem.py", """
            import threading
            from spark_rapids_tpu.shuffle.fx_blk_help import device_sum

            _gate = threading.BoundedSemaphore(4)

            def totals(x):
                with _gate:
                    return device_sum(x)
        """))
    assert [v for v in interproc.check_locks(world)
            if "can block" in v.message] == []


MATERIALIZE_WORLD = BLOCKING_WORLD[:1] + [(
    "spark_rapids_tpu/shuffle/fx_blk_once.py", """
        import os
        import threading
        from spark_rapids_tpu.plan.execs.base import MaterializeLock
        from spark_rapids_tpu.shuffle.fx_blk_help import device_sum

        _stats = threading.Lock()

        class Once:
            def __init__(self):
                self._lock = MaterializeLock()
                self._sum = None

            def total(self, x):
                with self._lock:
                    if self._sum is None:
                        os.makedirs("/tmp/x")
                        self._sum = device_sum(x)
                    with _stats:
                        return self._sum

            def backwards(self):
                with _stats:
                    with self._lock:
                        return self._sum
    """)]


@pytest.mark.parametrize("tier", ["intra", "interproc"])
def test_blocking_under_materialize_lock_silent_order_still_checked(tier):
    """plan/execs/base.py's MaterializeLock is held across a child's whole
    execution by design (its waiters hold no device permit), so blocking
    under it needs no waiver; its place in the lock order is checked like
    any lock's."""
    world = _world(*MATERIALIZE_WORLD)
    vs = (locks.check(world) if tier == "intra"
          else interproc.check_locks(world))
    assert [v for v in vs if "while holding" in v.message] == []
    if tier == "intra":
        assert [v for v in vs if "inconsistent lock order" in v.message]


def test_try_lock_then_blocking_acquire_is_not_a_self_deadlock():
    """MaterializeLock.__enter__'s shape: the blocking acquire runs only
    when the try-lock failed; and an explicit acquire() holds to the end
    of its own function, not into the next one's callback call."""
    world = _world((
        "spark_rapids_tpu/shuffle/fx_trylock.py", """
            import threading

            class Gate:
                def __init__(self):
                    self._lock = threading.Lock()

                def __enter__(self):
                    if not self._lock.acquire(blocking=False):
                        self._lock.acquire()

            def later(make_fn):
                return make_fn()
        """))
    assert locks.check(world) == []
