"""XLA environment bootstrap helpers.

The virtual host-device count used for CPU-mesh testing and the driver's
multichip dry-run is carried in ``XLA_FLAGS`` and is only read once, when
the CPU backend initializes; these helpers centralize the mutation so the
test conftest and ``__graft_entry__`` can't drift apart. Importing this
module imports no jax.
"""

from __future__ import annotations

import os
import re

_FLAG = "xla_force_host_platform_device_count"

# The persistent compile cache's default home: fixed inside the checkout
# (gitignored), because the path is part of the cache key — a directory
# that moves between runs never hits.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def ensure_host_device_count(n: int) -> None:
    """Ensure ``XLA_FLAGS`` requests at least ``n`` virtual CPU devices.

    Appends the flag when absent; raises an existing smaller value to
    ``n`` (never lowers a larger one). Takes effect only if the CPU
    backend has not yet initialized in this process.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"--{_FLAG}=(\d+)", flags)
    if m is None:
        flags = f"{flags} --{_FLAG}={n}".strip()
    elif int(m.group(1)) < n:
        flags = flags[: m.start(1)] + str(n) + flags[m.end(1):]
    else:
        return
    os.environ["XLA_FLAGS"] = flags


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first
    compile; returns the directory in use.

    A ``JAX_COMPILATION_CACHE_DIR`` from the environment is used as is
    (JAX reads it itself) and nothing else is set. Otherwise the cache
    lives at the fixed ``DEFAULT_COMPILE_CACHE``. Entry points call
    this (chip_smoke.py, ``serve``, tools/perf_smoke.py);
    importing the package does not, so the tests stay uncached.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE
