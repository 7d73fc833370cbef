"""Process start-up seam: which JAX platform this process runs on, and where
its compiled programs are cached.

``force_platform`` pins a process to one platform before the first backend
exists. Its main use is the virtual CPU mesh: the test suite, the static
gates and ``dryrun_multichip`` ask for N virtual host devices so sharded
programs run without N chips. A process that should use the chip calls
nothing — JAX picks the accelerator by default and fails at start-up when
there is none.

``enable_compile_cache`` is the one place the persistent compilation cache
directory is decided (``chip_smoke.py``, ``bench.py``, ``__graft_entry__.py``).
"""

from __future__ import annotations

import logging
import os
import re
from pathlib import Path

LOG = logging.getLogger(__name__)

_COUNT_FLAG = "xla_force_host_platform_device_count"

#: The cache directory when the environment names none. Resolved from this
#: file — never from ``~``, a temp name, a pid or a clock — so every process
#: of one checkout finds what the previous one compiled.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def force_platform(platform: str, n_host_devices: int | None = None) -> bool:
    """Point the live jax config at ``platform`` before any backend exists.

    ``n_host_devices`` (CPU only) requests that many virtual host devices via
    ``XLA_FLAGS``; the flag is read lazily at first backend initialization, so
    setting it post-import still works. Returns True when the config update
    succeeded; on failure (a backend is already live) a warning is logged and
    the caller should verify ``jax.devices()[0].platform`` before trusting the
    process.
    """
    os.environ["JAX_PLATFORMS"] = platform
    if n_host_devices is not None and platform == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if _COUNT_FLAG in flags:
            # Replace a conflicting count rather than silently keeping it
            # (e.g. inherited --...count=8 when the caller asked for 16).
            flags = re.sub(rf"--{_COUNT_FLAG}=\d+", f"--{_COUNT_FLAG}={n_host_devices}", flags)
            os.environ["XLA_FLAGS"] = flags
        else:
            os.environ["XLA_FLAGS"] = f"{flags} --{_COUNT_FLAG}={n_host_devices}".strip()
    import jax

    try:
        jax.config.update("jax_platforms", platform)
        return True
    except Exception as exc:  # pragma: no cover  # noqa: BLE001 — backend
        # init failures vary by runtime (RuntimeError, plugin errors); all
        # mean "platform not forced", reported to the caller as False.
        LOG.warning("could not force jax platform %r: %s", platform, exc)
        return False


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
    function touches nothing: whoever placed the cache from outside keeps
    control of it. Otherwise the cache goes to ``<repo>/.jax_cache``. Errors
    propagate — a cache that silently failed to set up reads as "every run
    compiles cold" with no trace of why.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    DEFAULT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
