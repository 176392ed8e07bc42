"""spark-rapids-tpu: a TPU-native accelerated SQL engine with the
capabilities of the RAPIDS Accelerator for Apache Spark.

Top half (planner, spill/retry memory model, shuffle SPI, differential test
oracle) reproduces the reference architecture (see SURVEY.md); bottom half is
TPU-first: Arrow-layout columns in HBM as JAX arrays, kernels as XLA/Pallas
programs with static capacities + dynamic row counts, ICI collectives for the
distributed exchange.
"""

__version__ = "0.1.0"

import jax as _jax

# The engine requires x64 mode: Spark LongType/DoubleType are 64-bit and JAX
# otherwise silently downcasts int64->int32 / float64->float32 at upload.
# (On real TPU hardware f64 is emulated as float32 pairs — a documented
# precision divergence for DoubleType, mirroring the reference's
# variableFloatAgg-style caveats; integral types emulate exactly.)
_jax.config.update("jax_enable_x64", True)

# Serialize XLA compilation AND persistent-cache executable serialization:
# jaxlib 0.9's CPU backend segfaults under concurrent compile load (faulting
# stacks observed in backend_compile_and_load and, with the persistent
# cache enabled, in compilation_cache.put_executable_and_time).  Wrapping
# _compile_and_write_cache covers both as one unit.  Execution stays fully
# parallel — only compile+cache-write takes the lock, and compiles are
# cached afterwards.  Private-API patch, pinned to the baked-in jax version
# of this image.
import threading as _threading

import jax._src.compiler as _jax_compiler

if not getattr(_jax_compiler, "_srtpu_compile_lock_installed", False):
    # RLock: _compile_and_write_cache calls backend_compile_and_load
    # internally, and both are wrapped.
    _compile_lock = _threading.RLock()

    def _serialize(name):
        orig = getattr(_jax_compiler, name)

        def wrapped(*args, _orig=orig, **kwargs):
            with _compile_lock:
                # tpu-lint: allow-lock-order(serializing XLA compiles IS this lock's purpose; the jaxlib CPU backend crashes on concurrent compile)
                return _orig(*args, **kwargs)

        setattr(_jax_compiler, name, wrapped)

    for _name in ("backend_compile_and_load", "_compile_and_write_cache"):
        _serialize(_name)
    _jax_compiler._srtpu_compile_lock_installed = True

# Persistent XLA compilation cache.  Sort-bearing programs take minutes
# each to compile for the TPU (CHANGES.md, PR 22), so a chip run is only
# usable warm.  The directory is part of the cache key's environment and
# must not move: JAX_COMPILATION_CACHE_DIR places it from outside (JAX
# reads that variable itself — nothing is set here then); otherwise it is
# <checkout>/.jax_cache.  JAX's defaults persist only programs that took
# a second or more to build.  The CPU test suite turns the cache off
# (tests/conftest.py): jaxlib 0.9's cache WRITE has crashed natively there
# under the engine's thread pool.
import os as _os

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))
from spark_rapids_tpu import types  # noqa: F401
from spark_rapids_tpu.config import RapidsConf  # noqa: F401
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema  # noqa: F401
from spark_rapids_tpu.columnar.column import DeviceColumn  # noqa: F401
