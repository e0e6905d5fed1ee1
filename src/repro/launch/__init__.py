"""Launchers: mesh construction, multi-pod dry-run, train/finetune/serve."""

import os
from pathlib import Path

#: Default persistent compile-cache directory, ``<checkout>/.jax_cache``:
#: a fixed path, so a later run of the same checkout finds what an earlier
#: one compiled.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache(devices_per_program: int = 1) -> str | None:
    """Set up JAX's persistent compilation cache before the first compile;
    return its directory, or None when it is off.

    ``devices_per_program`` is how many devices one of the caller's
    programs spans (the model axis of a session mesh, the whole mesh of a
    pjit trainer; 1 for single-device and data-axis programs). Above 1 the
    cache is off: a cached program spanning a model-axis device pair,
    loaded in a later process on a four-chip TPU v5e host, halted the chip
    once ("Invalid logical z: enhanced-barrier"; PERF.md §7). Otherwise a
    ``JAX_COMPILATION_CACHE_DIR`` set in the environment is left alone (JAX
    reads it itself), and without one the cache lives at ``CACHE_DIR``.
    The entry points call this; the tests never do."""
    import jax

    if devices_per_program > 1:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
