"""The gate's view of ``benchmark/tests/test_manifest.py`` (the driver collects ``tests/`` only)."""

from benchmark.tests.test_manifest import *  # noqa: F401,F403
