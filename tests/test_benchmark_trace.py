"""The gate's view of ``benchmark/tests/test_trace.py`` (the driver collects ``tests/`` only)."""

from benchmark.tests.test_trace import *  # noqa: F401,F403
