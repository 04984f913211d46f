"""Process-level JAX backend set-up shared by the CLI, the tools and the
tests: the virtual CPU mesh and the persistent compilation cache.
"""

from __future__ import annotations

import logging
import os
import re
from typing import Optional

_LOGGER = logging.getLogger("msrflute_tpu")

#: the in-checkout cache directory, resolved from this file's location
#: (``<checkout>/msrflute_tpu/utils/backend.py``): a cache that moves
#: never hits, so the path must not depend on the working directory, a
#: temp name, a pid or the time
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def force_cpu_backend(n_devices: Optional[int] = None) -> None:
    """Configure this process for a (virtual) CPU mesh before first backend
    init: force ``jax_platforms=cpu`` (env var AND config — jax may have
    been imported already), and optionally request ``n_devices`` virtual
    host devices.

    Must run before anything triggers jax backend initialization; after
    that, XLA_FLAGS changes are ignored.
    """
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        # replace any pre-existing count unless it already suffices —
        # a smaller ambient value would bring up too few devices
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m and int(m.group(1)) >= n_devices:
            pass
        else:
            if m:
                flags = flags.replace(m.group(0), "")
            os.environ["XLA_FLAGS"] = (
                flags.strip() +
                f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def device_report() -> dict:
    """``{"platform", "kind", "count"}`` as jax reports them — what every
    record of a run names, so no reader has to guess the platform from
    the environment.  Initializes the backend."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def enable_compilation_cache() -> Optional[str]:
    """Turn on jax's persistent XLA compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is
    placed from outside: jax reads the variable itself and nothing is set
    in code.  Otherwise the cache lives at
    :data:`DEFAULT_COMPILATION_CACHE_DIR`.  Best-effort: an unwritable
    path must not abort a training run — it only forfeits the warm start —
    but it is logged; returns None then."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    try:
        os.makedirs(DEFAULT_COMPILATION_CACHE_DIR, exist_ok=True)
    except OSError as exc:
        _LOGGER.warning("persistent compilation cache disabled: cannot "
                        "create %s (%s)", DEFAULT_COMPILATION_CACHE_DIR, exc)
        return None
    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILATION_CACHE_DIR)
    return DEFAULT_COMPILATION_CACHE_DIR
