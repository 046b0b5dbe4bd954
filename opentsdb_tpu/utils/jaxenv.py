"""Where JAX runs and where it keeps compiled programs — decided once.

Two facts every entry point (the daemon, the scripts) must
settle before its first JAX call, kept here so they are settled the
same way everywhere:

- **The compile cache directory comes from outside.** Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets no directory in code. Otherwise the cache is
  ``<checkout>/.jax_cache`` — a fixed path, because the path is part of
  the cache key: a directory named after a pid, a time or a temp name
  never hits.
- **The platform the daemon serves from is checked, not assumed.**
  ``--backend tpu`` means "jitted kernels on ``jax.devices()[0]``"; if
  that is not a TPU and nobody asked for the CPU by name, the daemon
  refuses to boot rather than serve from the wrong device unnoticed.
"""

from __future__ import annotations

import logging
import os

LOG = logging.getLogger(__name__)

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# The one place that names JAX's cache-directory option.
_CACHE_OPTION = "jax_compilation_cache_dir"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that path. Call before the first jit."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(_CACHE_OPTION,
                          os.path.join(_CHECKOUT, ".jax_cache"))
    # The default threshold (1 s) never stores the small kernels, and
    # the query path is mostly small kernels.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return compile_cache_dir()


def compile_cache_dir() -> str | None:
    """The directory this process's JAX persists compiled programs to
    (None = it persists nothing)."""
    import jax

    return getattr(jax.config, _CACHE_OPTION)


def device_info() -> dict:
    """The devices JAX resolved, as /healthz reports them."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_memory(device=None) -> dict | None:
    """What a device says of its memory: ``bytes_limit``,
    ``bytes_in_use`` and ``peak_bytes_in_use`` of its
    ``memory_stats()``, or None where it states no limit (a CPU backend
    states none: its arrays are the host's to page). ``device`` None is
    this process's first device (under a mesh plane ``jax.devices()[0]``
    may be another process's, which cannot be asked)."""
    import jax

    st = (device or jax.local_devices()[0]).memory_stats()
    if not st or not st.get("bytes_limit"):
        return None
    return {name: int(st.get(name, 0))
            for name in ("bytes_limit", "bytes_in_use",
                         "peak_bytes_in_use")}


def require_serving_device(backend: str) -> dict:
    """Resolve the devices once at daemon boot, log them, and refuse to
    serve ``--backend tpu`` from anything but a TPU unless the CPU was
    asked for by name: ``--backend cpu`` or ``JAX_PLATFORMS=cpu`` (how
    tier-1 and the virtual mesh ask). Returns :func:`device_info`."""
    info = device_info()
    LOG.info("jax devices: platform=%s kind=%s count=%d",
             info["platform"], info["kind"], info["count"])
    cpu_by_name = (backend == "cpu"
                   or os.environ.get("JAX_PLATFORMS") == "cpu")
    if info["platform"] != "tpu" and not cpu_by_name:
        raise SystemExit(
            f"tsd: --backend tpu but jax resolved platform "
            f"{info['platform']!r} ({info['kind']}); refusing to serve "
            f"from it unnoticed. Ask for the CPU by name with "
            f"--backend cpu or JAX_PLATFORMS=cpu.")
    return info
