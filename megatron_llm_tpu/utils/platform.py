"""Process-level JAX set-up shared by the entry points: CPU pinning for
hermetic runs (tests/conftest.py, __graft_entry__.py, the CPU arms of the
bench programs) and the persistent compilation cache.
"""

from __future__ import annotations

import os
from typing import Optional

# the in-code default: one fixed directory inside the checkout (the path is
# part of how a cache is found again, so it is never built from a temporary
# name, a pid or the time); listed in .gitignore
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def pin_cpu_platform(n_devices: int | None = None) -> None:
    """Force jax onto the host CPU backend; optionally request `n_devices`
    virtual CPU devices. Must run before any jax backend is initialized."""
    if n_devices is not None:
        # Append unconditionally: the later flag wins within XLA_FLAGS, so a
        # preset count from some other harness is overridden, not kept.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_devices}"
        )
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    # If a backend was already initialized the pin is a silent no-op and the
    # "hermetic CPU" run would target the accelerator — fail loudly instead.
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "pin_cpu_platform called after a non-CPU jax backend was "
            f"initialized ({jax.default_backend()}); pin before any jax use")


def enable_compilation_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache; every entry point calls
    this before its first compile.  Returns the directory in use.  The
    compile log (observability/compiles.py) starts here, whichever way
    the cache goes, so the weights' build, an engine's constructor and
    whatever else follows are in it.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and nothing is
    set in code.  Unset: :data:`REPO_CACHE_DIR` — except on a CPU backend,
    where no cache is kept (XLA:CPU AOT entries carry machine-feature lists
    that mis-load across toolchain updates)."""
    import jax

    from megatron_llm_tpu.observability import compiles

    compiles.install()
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
