"""Per-query profiling depth: a sampled flamegraph and the late ticks.

Reference analog: per-stage flame graphs — asyncProfiler.scala:58 embeds
async-profiler and emits one flamegraph per stage epoch
(docs/additional-functionality/per-stage-flamegraph.md).

TPU lowering: the process's one sampler thread (``tracing.StackSampler``:
``sys._current_frames`` at a fixed cadence) aggregates every thread's stack
into collapsed-stack lines that flamegraph.pl / speedscope ingest directly,
and keeps the ticks that got the interpreter lock more than 20 ms late with
the frames of the thread that held it.  Query-scoped and conf-gated:

    spark.rapids.profile.enabled     -> both artifacts per collect()
    spark.rapids.profile.dir         -> where they land

How long the device sat idle is not guessed from the host's clock here: the
benchmark's ``device_idle_pct`` reads it from the profiler's trace.
"""
from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Dict

from spark_rapids_tpu.utils import tracing


class QueryProfiler:
    """Conf-gated per-collect() profiler.

    Artifacts: <dir>/query<N>_flame.txt (collapsed stacks) and
    <dir>/query<N>_late_ticks.json (the sampler's ticks over 20 ms late
    that fell inside this collect(), each with every other thread's
    innermost frames)."""

    _seq = 0
    _lock = threading.Lock()

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.profile = None
        self._t0 = 0.0

    def __enter__(self) -> "QueryProfiler":
        os.makedirs(self.out_dir, exist_ok=True)
        with QueryProfiler._lock:
            if QueryProfiler._seq == 0:
                # resume numbering past artifacts from earlier processes
                # sharing this dir (a fresh process would clobber query1_*)
                mx = 0
                for n in os.listdir(self.out_dir):
                    m = re.match(r"query(\d+)_", n)
                    if m:
                        mx = max(mx, int(m.group(1)))
                QueryProfiler._seq = mx
        self._t0 = time.perf_counter()
        self.profile = tracing.sampler.hold()
        return self

    def finish(self) -> Dict[str, object]:
        tracing.sampler.release(self.profile)
        with QueryProfiler._lock:
            QueryProfiler._seq += 1
            n = QueryProfiler._seq
        flame = os.path.join(self.out_dir, f"query{n}_flame.txt")
        self.profile.write(flame)
        late = [{"due": due, "ran": ran, "late_ms": 1e3 * (ran - due),
                 "threads": threads}
                for due, ran, threads in tracing.late_ticks()
                if ran >= self._t0]
        lpath = os.path.join(self.out_dir, f"query{n}_late_ticks.json")
        with open(lpath, "w") as f:
            json.dump({"wall_ms": 1e3 * (time.perf_counter() - self._t0),
                       "samples": self.profile.samples,
                       "late_ticks": late}, f, indent=1)
        return {"flamegraph": flame, "samples": self.profile.samples,
                "late_ticks": lpath}

    def __exit__(self, *exc) -> None:
        tracing.sampler.release(self.profile)   # idempotent
