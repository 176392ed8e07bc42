"""Out-of-process Python UDF workers — the GPU-aware PySpark worker.

Reference: python/rapids/{daemon.py,worker.py} — the plugin patches
PySpark's daemon so Python workers initialize with a bounded share of
GPU memory (spark.rapids.python.memory.gpu.allocFraction, gated by
spark.rapids.python.concurrentPythonWorkers) before running pandas UDFs.
The TPU analog keeps the same three properties:

  * ISOLATION: the UDF runs in a separate long-lived worker process, so
    a crashing/leaking UDF (segfault, C-extension abort, runaway RSS)
    fails its task instead of the engine;
  * MEMORY BOUND: each worker applies an address-space rlimit before
    touching user code (the allocFraction analog for host memory —
    Python never holds TPU HBM here, batches cross as Arrow IPC);
  * REUSE: workers are daemons serving many tasks (daemon.py's fork
    server role); the pool is a process-wide singleton per config.

Functions ship via cloudpickle (lambdas included), data as Arrow IPC
streams over pipes.  Workers force JAX_PLATFORMS=cpu at spawn so a UDF
worker never grabs the chip the engine owns.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import threading
from typing import Optional


def _send(conn, *parts: bytes) -> None:
    for p in parts:
        conn.send_bytes(p)


def _worker_main(conn, mem_limit_bytes: int) -> None:
    """Worker loop: (fn_pickle, arrow ipc) -> (status, arrow ipc/error)."""
    try:
        if mem_limit_bytes > 0:
            import resource
            resource.setrlimit(resource.RLIMIT_AS,
                               (mem_limit_bytes, mem_limit_bytes))
    # tpu-lint: allow-swallow(rlimit is best-effort hardening; platforms without RLIMIT_AS still run UDFs)
    except Exception:
        pass
    import io
    import pickle
    import traceback

    import pyarrow as pa
    while True:
        try:
            fn_bytes = conn.recv_bytes()
            data = conn.recv_bytes()
        except EOFError:
            return
        try:
            try:
                import cloudpickle
                fn = cloudpickle.loads(fn_bytes)
            except ImportError:
                fn = pickle.loads(fn_bytes)
            with pa.ipc.open_stream(pa.BufferReader(data)) as r:
                table = r.read_all()
            result = fn(table)
            sink = io.BytesIO()
            with pa.ipc.new_stream(sink, result.schema) as w:
                w.write_table(result)
            conn.send_bytes(b"ok")
            conn.send_bytes(sink.getvalue())
        except BaseException:
            try:
                conn.send_bytes(b"err")
                conn.send_bytes(traceback.format_exc().encode("utf-8"))
            except Exception:
                return


#: spawn mutates process-global state (env var + __main__.__file__);
#: concurrent respawns from two task threads must serialize on it
_spawn_lock = threading.Lock()


class _Worker:
    def __init__(self, mem_limit_bytes: int):
        import sys
        ctx = mp.get_context("spawn")
        self.conn, child = ctx.Pipe()
        # 1. a chip belongs to one process: the parent holds it, so a
        #    worker whose UDF imports jax must get the CPU backend — a
        #    second process reaching for the chip fails or hangs
        #    (pinned by tests/test_python_exec.py);
        # 2. suppress re-execution of the parent's __main__ in the child
        #    (spawn's init_main_from_path): functions ship by VALUE via
        #    cloudpickle, so the child never needs the user's script —
        #    and parents launched from stdin/REPL have no re-runnable
        #    path at all ('<stdin>' would crash the worker at start)
        with _spawn_lock:
            saved_env = os.environ.get("JAX_PLATFORMS")
            os.environ["JAX_PLATFORMS"] = "cpu"
            main = sys.modules.get("__main__")
            had_file = main is not None and hasattr(main, "__file__")
            saved_file = getattr(main, "__file__", None) if had_file \
                else None
            try:
                if had_file:
                    main.__file__ = None
                self.proc = ctx.Process(target=_worker_main,
                                        args=(child, mem_limit_bytes),
                                        daemon=True)
                self.proc.start()
            finally:
                if had_file:
                    main.__file__ = saved_file
                if saved_env is None:
                    os.environ.pop("JAX_PLATFORMS", None)
                else:
                    os.environ["JAX_PLATFORMS"] = saved_env
        child.close()

    def close(self) -> None:
        try:
            self.conn.close()
        # tpu-lint: allow-swallow(teardown of a possibly-dead pipe; the terminate below is the real cleanup)
        except Exception:
            pass
        if self.proc.is_alive():
            self.proc.terminate()
        self.proc.join(timeout=5)


class PythonWorkerPool:
    """Fixed-size pool of reusable UDF workers (daemon.py role)."""

    _instances = {}
    _ilock = threading.Lock()

    def __init__(self, size: int, mem_limit_bytes: int = 0):
        self.size = max(1, int(size))
        self.mem_limit_bytes = int(mem_limit_bytes)
        self._lock = threading.Lock()
        self._free = [ _Worker(self.mem_limit_bytes)
                       for _ in range(self.size) ]
        self._cv = threading.Condition(self._lock)

    @classmethod
    def shared(cls, size: int, mem_limit_bytes: int = 0
               ) -> "PythonWorkerPool":
        key = (int(size), int(mem_limit_bytes))
        with cls._ilock:
            pool = cls._instances.get(key)
            if pool is None:
                pool = cls(size, mem_limit_bytes)
                cls._instances[key] = pool
            return pool

    def _borrow(self) -> _Worker:
        from spark_rapids_tpu.utils.cancel import cancellable_wait
        with self._cv:
            cancellable_wait(self._cv,
                             predicate=lambda: bool(self._free),
                             site="python.worker.borrow")
            w = self._free.pop()
        if w is None:
            # lazy revival of a slot whose worker died/desynced: spawn
            # OUTSIDE the condition lock (other borrows stay unblocked),
            # and never during exception unwinding.  A failed spawn must
            # return the token — losing it would shrink the pool until
            # every caller blocks forever.
            try:
                w = _Worker(self.mem_limit_bytes)
            except BaseException:
                self._give_back(None)
                raise
        return w

    def _give_back(self, w: Optional[_Worker]) -> None:
        """None = the slot's worker was retired; _borrow revives it."""
        with self._cv:
            self._free.append(w)
            self._cv.notify()

    def run(self, fn, arrow_table):
        """Apply fn to one Arrow table in a worker; returns the result
        table.  A dead worker (hard crash / rlimit kill) retires its
        slot — revived lazily on the next borrow — and the task gets a
        RuntimeError instead of a dead engine."""
        import io

        import cloudpickle
        import pyarrow as pa
        # serialize BEFORE borrowing: an unpicklable UDF must fail
        # without touching (or retiring) any worker
        fn_bytes = cloudpickle.dumps(fn)
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, arrow_table.schema) as wtr:
            wtr.write_table(arrow_table)
        w = self._borrow()
        try:
            try:
                _send(w.conn, fn_bytes, sink.getvalue())
                status = w.conn.recv_bytes()
                payload = w.conn.recv_bytes()
            except (EOFError, BrokenPipeError, ConnectionResetError,
                    OSError):
                code = None
                if not w.proc.is_alive():
                    w.proc.join(timeout=1)
                    code = w.proc.exitcode
                w.close()
                w = None                      # retire the slot
                raise RuntimeError(
                    f"python worker died (exit code {code}) while running "
                    f"{getattr(fn, '__name__', 'fn')} — the engine "
                    "survives; rerun or raise "
                    "spark.rapids.python.memory.maxBytes")
            except BaseException:
                # interrupted mid-protocol (KeyboardInterrupt while
                # blocked, MemoryError on a huge payload): the pipe may
                # hold a half-read reply — NEVER return a desynced worker
                # to the pool, its stale reply would become the NEXT
                # task's result.  Retire the slot.
                w.close()
                w = None
                raise
            if status == b"err":
                raise RuntimeError(
                    "python worker UDF failed:\n"
                    + payload.decode("utf-8", "replace"))
            with pa.ipc.open_stream(pa.BufferReader(payload)) as r:
                return r.read_all()
        finally:
            self._give_back(w)

    def close(self) -> None:
        with self._cv:
            for w in self._free:
                if w is not None:
                    w.close()
            self._free = []
