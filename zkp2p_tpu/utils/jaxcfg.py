"""JAX runtime configuration helpers (shared by CLI / bench / tests).

The limb-arithmetic graphs are wide and XLA compiles them slowly; the
persistent compilation cache turns that into a once-per-directory cost —
on every entry path, not just pytest (tests/conftest.py does the same).

Where the cache lives is decided from OUTSIDE the program: when
JAX_COMPILATION_CACHE_DIR is set, JAX itself reads it and this module
sets no directory in code; when it is not, the cache is
`<checkout>/.jax_cache`.  The path is part of what makes a later process
find the entries again, so it is never derived from the host, a pid, a
temp name or the time.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def cache_dir() -> str:
    """The persistent compile-cache directory this process uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(_CHECKOUT, ".jax_cache")


def enable_cache(min_compile_s: float = 1.0) -> None:
    # ZKP2P_NO_CACHE=1 is a global off-switch (every caller, including
    # in-process CLI drives inside the test suite): long full-suite runs
    # have segfaulted inside the persistent-cache WRITE path
    # (executable.serialize() under put_executable_and_time) — the
    # slow-suite run trades cache reuse for stability.
    if os.environ.get("ZKP2P_NO_CACHE") == "1":
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    # min_compile_s: the default 1.0 keeps trivial executables out of the
    # cache; the warm-cache command and the tpu-shard smoke pass 0.0 so
    # the toy-circuit compiles (sub-second on the virtual mesh) round-trip
    # and the >=10x warm-start assertion has entries to hit.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", float(min_compile_s))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # A Pallas kernel rides its executable as serialized Mosaic bytecode,
    # MLIR locations included, and those bytes are hashed into the cache
    # key.  With full Python tracebacks in the locations the key depends
    # on the call stack under which a (cached) kernel jaxpr was FIRST
    # traced, so a second process that reaches the prover by another
    # road misses on every kernel-bearing executable (seen on the chip:
    # 80.7 s of fresh compiles against 90.9 s cold, PERF.md PR 21).
    jax.config.update("jax_include_full_tracebacks_in_locations", False)


def on_tpu() -> bool:
    """True when the first JAX device is a TPU.  Every backend gate in
    the tree funnels through here — the ONE audit record covers them
    all (lazy import: tools import this module before jax/numpy are safe
    to load)."""
    import jax

    from .audit import record_arm

    v = jax.devices()[0].platform == "tpu"
    record_arm("on_tpu", "tpu" if v else "host")
    return v
