"""What this process runs on, and where it keeps its compiled programs.

Two facts every entry point that reaches the device needs, each read
from JAX in exactly one place:

- :func:`device_info` — platform, device kind and count as JAX reports
  them. Records, ``/v1/info`` and the chip smoke name the device from
  here, never from a ``--hasher`` flag: ``hasher="tpu"`` selects the
  batched strategy and runs on whatever JAX resolved, CPU included.
- :func:`enable_compile_cache` — JAX's persistent compilation cache.
  A cold compile of the hash plane costs tens of seconds per geometry;
  the cache directory is part of the cache key, so it has to be the
  same path in every process that should share compiles.

One process holds a chip. A parent that has called ``jax.devices()``
owns every chip it can see, and a child that needs one then fails or
hangs — launchers of device workers therefore stay off JAX themselves
and hand each child its chip through :func:`worker_env`.
"""

from __future__ import annotations

import os
import pathlib

# the checkout root (parent of the package); `.jax_cache/` is git-ignored
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and
    no directory is set in code; otherwise the cache goes to one fixed
    path inside the checkout. A process pinned to the CPU platform gets
    none (``None``): host compiles are cheap, and XLA:CPU's loader warns
    about machine features on every hit. Call before the first compile;
    it does not initialize the backend."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    if jax.config.jax_platforms == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend, as JAX
    reports it. Initializes the backend (and so takes the chip)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def hasher_device(hasher: str) -> dict:
    """The device a ``--hasher`` choice runs on. ``"cpu"`` is hashlib on
    the host: it never imports JAX, so it takes no chip from the process
    that holds one. Anything else runs where JAX resolved."""
    if hasher == "cpu":
        return {"platform": "cpu", "kind": "hashlib", "count": 0}
    return device_info()


def worker_env(base: dict, hasher: str, index: int) -> dict:
    """Environment for worker ``index`` of a launcher that starts several
    hashing processes on one host; set before the child imports JAX.

    A ``"cpu"`` worker hashes with hashlib and is pinned to the CPU
    platform, so whatever it imports stays off the chips. A device worker
    is shown exactly one chip — its own — as a one-chip topology (the
    recipe four concurrent processes each took one chip with on a
    v5litepod-4 host under libtpu 0.0.34) and is pinned to the TPU
    platform alone: the chip host exports ``JAX_PLATFORMS=tpu,cpu``,
    under which a worker whose chip is missing or busy would hash on
    XLA:CPU without a word. Pinned, its backend init is fatal, so a
    launcher of more workers than chips fails on the worker's exit code
    instead of measuring the host."""
    env = dict(base)
    if hasher == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    else:
        env["JAX_PLATFORMS"] = "tpu"
        env["TPU_VISIBLE_CHIPS"] = str(index)
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env
