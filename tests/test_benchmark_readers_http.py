"""The gate's view of ``benchmark/tests/test_readers_http.py`` (the driver collects ``tests/`` only)."""

from benchmark.tests.test_readers_http import *  # noqa: F401,F403
