"""Where a process runs, and where it keeps compiled programs.

Two facts every entry point and artifact states the same way:

- :func:`device_info` — the device as JAX reports it (``platform``,
  ``kind``, ``count``). Artifacts, ``/health`` and start-up logs carry
  exactly this block, so a CPU run can never be read as a chip run.
- :func:`enable_compile_cache` — JAX's persistent compilation cache.
  ``JAX_COMPILATION_CACHE_DIR`` places it from outside (JAX reads the
  variable itself, so nothing is set in code); otherwise it lives at
  one fixed path inside the checkout. The path never holds a pid, a
  timestamp or a temp dir: a cache that moves never hits.

jax is imported lazily: importing this module touches no device.
"""

import os
from pathlib import Path
from typing import Optional

#: the fixed in-checkout cache (gitignored)
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def device_info(devices=None) -> dict:
    """``{"platform", "kind", "count"}`` of ``devices`` (default: all
    of ``jax.devices()``) — e.g. the devices one replica's cache lives
    on."""
    import jax

    devices = sorted(devices, key=lambda d: d.id) if devices else jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def device_bytes_in_use() -> list:
    """``bytes_in_use`` of every local device, in device order (None
    where the backend reports no memory stats, i.e. the CPU)."""
    import jax

    return [
        (d.memory_stats() or {}).get("bytes_in_use")
        for d in jax.local_devices()
    ]


def select_platform(platform: Optional[str]) -> dict:
    """Apply an explicit ``--platform`` and initialise the backend NOW,
    so an entry point whose chip fails to come up dies at start-up with
    JAX's own error instead of quietly computing on the CPU. Without an
    explicit platform only an accelerator is accepted. Returns
    :func:`device_info`."""
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    info = device_info()
    if not platform and info["platform"] == "cpu":
        raise SystemExit(
            "no accelerator: jax.devices() found only the CPU "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}); "
            "the CPU is used only when asked for by name "
            "(--platform cpu, where the entry point has it)"
        )
    return info


def compile_cache_dir(explicit: Optional[str] = None) -> str:
    """The directory in effect: ``explicit`` (a ``--compile-cache``
    flag), else ``JAX_COMPILATION_CACHE_DIR``, else the fixed
    in-checkout path."""
    return (
        explicit
        or os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or str(DEFAULT_COMPILE_CACHE)
    )


def enable_compile_cache(explicit: Optional[str] = None) -> str:
    """Turn the persistent cache on and return its directory. With
    ``JAX_COMPILATION_CACHE_DIR`` set and no ``explicit`` override the
    directory is left to JAX."""
    import jax

    if explicit or not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", compile_cache_dir(explicit)
        )
    # small programs too: a boot compiles dozens of sub-second variants
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return compile_cache_dir(explicit)
