"""Rehearsal (c): what will chip_smoke.py's cold start cost on the chip?

Runs chip_smoke's one-chip phases HERE on the CPU at the real batch
capacity while recording every ``jax.jit`` program the engine dispatches
(function + argument shapes), then compiles each one for the DESCRIBED
``v5e:2x2`` chip, one device (on-chip-measurement guide §2.3) and writes,
per program: compiled or refused, seconds, sort count, and
``memory_analysis()`` bytes.  The seconds are compile seconds of this host,
not device time; their sum is the smoke's cold start, and it does not
shrink with --rows (the capacity, not the row count, keys a program).

    python tools/rehearse_chip_compile.py --rows 16777216 --out /root/scratch/rc.jsonl
    ... --queries q3 --shard 0/3      # split the compiles over processes

Code that asks ``jax.default_backend()`` would take its CPU branch during
such a compile and hit the f64 bitcast the chip refuses; this script steers
it to "tpu" for the recording run and the compiles alike.  Only one process
at a time may load libtpu: run shards one after another, or, for a scratch
run of your own, under ALLOW_MULTIPLE_LIBTPU_LOAD=1.  PR 22's readings are
in CHANGES.md and PERF.md §5.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import zlib

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Recorder:
    """Stands in for a jitted function: remembers each distinct top-level
    call signature, then calls through."""

    records: dict = {}

    def __init__(self, jitted, fn, jit_kwargs):
        self.jitted, self.fn = jitted, fn
        self.__wrapped__ = fn
        nums = jit_kwargs.get("static_argnums", ())
        self.static_nums = {nums} if isinstance(nums, int) else set(nums)
        names = jit_kwargs.get("static_argnames", ())
        self.static_names = {names} if isinstance(names, str) else set(names)

    def __call__(self, *args, **kwargs):
        leaves = jax.tree.leaves((args, kwargs))
        if not any(isinstance(x, jax.core.Tracer) for x in leaves):
            sig = (id(self), str(jax.tree.structure((args, kwargs))),
                   tuple((getattr(x, "shape", None),
                          str(getattr(x, "dtype", repr(x)[:40])))
                         for x in leaves))
            rec = self.records.setdefault(
                sig, {"rec": self, "args": args, "kwargs": kwargs,
                      "calls": 0})
            rec["calls"] += 1
        return self.jitted(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.jitted, name)


def _install_recorder():
    real_jit = jax.jit

    def recording_jit(fun=None, /, **kw):
        if fun is None:
            return lambda f: recording_jit(f, **kw)
        return _Recorder(real_jit(fun, **kw), fun, kw)

    jax.jit = recording_jit
    jax.default_backend = lambda: "tpu"


def _abstract(x, sharding):
    if isinstance(x, (jax.Array, np.ndarray, np.generic, int, float, bool)):
        aval = jax.typeof(x)
        return jax.ShapeDtypeStruct(
            aval.shape, aval.dtype, sharding=sharding,
            weak_type=getattr(aval, "weak_type", False))
    return x


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 24)
    ap.add_argument("--queries", default=None,
                    help="passed to chip_smoke.py (default: its default)")
    ap.add_argument("--out", required=True, help="JSON lines, one a program")
    ap.add_argument("--shard", default="0/1",
                    help="K/N: compile only the K-th of N shares")
    args = ap.parse_args()
    shard, n_shards = map(int, args.shard.split("/"))

    jax.config.update("jax_enable_compilation_cache", False)
    _install_recorder()
    sys.path.insert(0, REPO)
    import chip_smoke
    sys.argv = ["chip_smoke.py", "--rows", str(args.rows)] + (
        ["--queries", args.queries] if args.queries else [])
    try:
        chip_smoke.main()
    except SystemExit as e:     # the rehearsal's own non-zero exit
        print("chip_smoke:", e, flush=True)

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    total = 0.0
    with open(args.out, "w") as out:
        for i, (sig, r) in enumerate(_Recorder.records.items()):
            rec = r["rec"]
            name = getattr(rec.fn, "__qualname__", repr(rec.fn))
            stable = re.sub(r"0x[0-9a-f]+", "", name) + repr(sig[2])
            if zlib.crc32(stable.encode()) % n_shards != shard:
                continue
            a = tuple(x if j in rec.static_nums else
                      jax.tree.map(lambda v: _abstract(v, one_chip), x)
                      for j, x in enumerate(r["args"]))
            k = {n: (v if n in rec.static_names else
                     jax.tree.map(lambda w: _abstract(w, one_chip), v))
                 for n, v in r["kwargs"].items()}
            shapes = sorted({(tuple(x.shape), str(x.dtype))
                             for x in jax.tree.leaves((a, k))
                             if hasattr(x, "shape")},
                            key=lambda s: -int(np.prod(s[0])))[:3]
            row = {"i": i, "program": name[:120], "calls": r["calls"],
                   "largest_args": shapes}
            t0 = time.time()
            try:
                lowered = rec.jitted.lower(*a, **k)
                row["sorts"] = lowered.as_text().count("stablehlo.sort")
                mem = lowered.compile().memory_analysis()
                row.update(status="compiled",
                           argument_bytes=mem.argument_size_in_bytes,
                           output_bytes=mem.output_size_in_bytes,
                           temp_bytes=mem.temp_size_in_bytes)
            except Exception as e:  # noqa: BLE001 — a refusal IS the finding
                row.update(status="refused",
                           error=f"{type(e).__name__}: {str(e)[:600]}")
            row["seconds"] = round(time.time() - t0, 2)
            total += row["seconds"]
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(json.dumps(row), flush=True)
        out.write(json.dumps({"compile_seconds_sum": round(total, 1),
                              "programs_recorded":
                              len(_Recorder.records)}) + "\n")


if __name__ == "__main__":
    main()
