"""The gate's view of ``benchmark/tests/test_session.py`` (the driver collects ``tests/`` only)."""

from benchmark.tests.test_session import *  # noqa: F401,F403
