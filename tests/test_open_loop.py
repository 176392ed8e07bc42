"""The open loop's schedule, substitution sets, served rehearsals and
watchdog, from the benchmark's own test file so that tier-1 holds them too."""
from benchmark.tests.test_open_loop import *  # noqa: F401,F403
