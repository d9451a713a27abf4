"""The gate's view of ``benchmark/tests/test_recheck_x4.py`` (the driver collects ``tests/`` only)."""

from benchmark.tests.test_recheck_x4 import *  # noqa: F401,F403
