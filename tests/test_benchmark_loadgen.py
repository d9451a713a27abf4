"""The gate's view of ``benchmark/tests/test_loadgen.py`` (the driver collects ``tests/`` only)."""

from benchmark.tests.test_loadgen import *  # noqa: F401,F403
