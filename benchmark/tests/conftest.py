"""Tests of the benchmark itself.  Run by hand from the root of the repo:

    python -m pytest benchmark/tests -q -p no:cacheprovider

They run on the CPU (``JAX_PLATFORMS=cpu``) at small sizes: they say that
the harness's arithmetic and control flow are right, never how fast
anything is.  Tier-1 (``pytest tests/``) does not collect them.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# jaxlib 0.9's persistent-cache write has crashed natively on the CPU
# under the engine's thread pool (tests/conftest.py turns it off too)
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
