"""The gate's view of ``benchmark/tests/test_run.py`` (the driver collects ``tests/`` only)."""

from benchmark.tests.test_run import *  # noqa: F401,F403
