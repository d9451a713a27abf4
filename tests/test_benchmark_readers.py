"""The gate's view of ``benchmark/tests/test_readers.py`` (the driver collects ``tests/`` only)."""

from benchmark.tests.test_readers import *  # noqa: F401,F403
