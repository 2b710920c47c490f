"""Backend start-up rules shared by every entry point: which device the
process runs on, and where JAX's persistent compilation cache lives.

JAX falls back to the CPU without failing when an installed accelerator
runtime cannot get its device (the libtpu error is only logged). A script
whose numbers are meant for the chip must therefore look at the platform
it got: :func:`require_accelerator` does, and ``JAX_PLATFORMS=cpu`` is the
one way to ask for the CPU on purpose.
"""

from __future__ import annotations

import os
import sys

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
MIN_COMPILE_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"


def cpu_requested() -> bool:
    """Whether the operator asked for the CPU backend (``JAX_PLATFORMS``
    names ``cpu`` first)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    return platforms.split(",")[0].strip().lower() == "cpu"


def require_accelerator(script_name: str):
    """The first JAX device, or exit 3 when it is a CPU the operator did
    not ask for — a chip the process failed to get must not be benchmarked
    as if it were one."""
    import jax

    device = jax.devices()[0]
    if device.platform == "cpu" and not cpu_requested():
        sys.stderr.write(
            f"{script_name}: JAX found no accelerator (first device is "
            f"{device.platform}:{device.device_kind}) and JAX_PLATFORMS does "
            "not ask for the CPU; set JAX_PLATFORMS=cpu to run there on "
            "purpose\n"
        )
        sys.exit(3)
    return device


def enable_persistent_compile_cache() -> str:
    """The ONE place that decides where JAX's persistent compilation cache
    lives; every entry point calls it before its first compile.

    - ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing is
      set here, so the operator's placement always holds.
    - otherwise: ``<checkout>/.jax_compilation_cache`` — a fixed path, so
      a second run of any entry point finds the first run's programs.
    - ``GORDO_COMPILE_CACHE=off``: the cacheless mode (returns ""); the
      operator's environment variable is left alone.

    Unless the CPU was asked for, every compiled program is kept, however
    quick its compile (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``, if
    the operator set it, stands).
    """
    import jax

    if os.environ.get("GORDO_COMPILE_CACHE") == "off":
        jax.config.update("jax_compilation_cache_dir", None)
        return ""
    if not cpu_requested() and MIN_COMPILE_ENV not in os.environ:
        # JAX keeps only programs that took over a second to compile. On
        # the chip most of this system's programs are small ones under
        # that bar (249 of 254 in chip_smoke.py's first v5e run, PR 21),
        # and recompiling them was 18 s of a 71 s warm run; loading one
        # costs milliseconds. On the CPU compiles are cheap: JAX's
        # default stands.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(CACHE_DIR_ENV):
        return os.environ[CACHE_DIR_ENV]
    cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
        ".jax_compilation_cache",
    )
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
