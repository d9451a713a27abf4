"""The gate's view of ``benchmark/tests/test_library.py`` (the driver collects ``tests/`` only)."""

from benchmark.tests.test_library import *  # noqa: F401,F403
