"""Test configuration.

x64 is enabled for solver accuracy tests (the paper's CPU baselines are
f64).  XLA_FLAGS / device count are NOT touched here — smoke tests must see
the real single CPU device; multi-device tests spawn subprocesses.
"""
import jax
import pytest

jax.config.update("jax_enable_x64", True)


@pytest.fixture(autouse=True)
def _x64_on():
    """Each test starts with x64 on: a file run earlier in the same worker
    (the benchmark harness's tests run ``bench/run.py`` in-process, which
    turns x64 off) must not leave these tests in float32."""
    if not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)


# Every live XLA:CPU executable holds ~50 anonymous memory mappings (LLVM
# ORC JIT code/data sections).  The full suite compiles enough distinct
# programs in one process to cross the kernel's vm.max_map_count (65530 by
# default), at which point mmap fails inside the JIT and the NEXT compile
# segfaults.  Dropping the compile caches releases the mappings (measured:
# 16k -> 0.5k), so bound the count here: check after each test, clear well
# below the kernel limit.  Costs nothing until triggered; when triggered the
# affected programs simply recompile on next use.
_MAPS_LIMIT = 30_000


@pytest.fixture(autouse=True)
def _bound_jit_map_count():
    yield
    try:
        with open("/proc/self/maps") as f:
            n_maps = sum(1 for _ in f)
    except OSError:          # non-Linux: no /proc, no known map-count limit
        return
    if n_maps > _MAPS_LIMIT:
        jax.clear_caches()
