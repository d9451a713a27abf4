"""The gate's view of ``benchmark/tests/test_recheck_v2.py`` (the driver collects ``tests/`` only)."""

from benchmark.tests.test_recheck_v2 import *  # noqa: F401,F403
