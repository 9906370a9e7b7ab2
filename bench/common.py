"""Helpers shared by the harness, the loops and the families.

Nothing here imports the program under test.
"""
from __future__ import annotations

import importlib.util
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_module(path: str, name: str):
    """Import one of the benchmark's files by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def bench_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, found by the name BENCHMARK.json gives."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    return load_module(path, f"bench_{kind}_{name.replace('.', '_')}")


def prng_key(seed: int, stream: int = 0):
    """A JAX key from any non-negative seed (x64 is off, so a seed above
    2**31 is folded in as its high part) and a stream number."""
    import jax
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed % (1 << 31))
    key = jax.random.fold_in(key, seed >> 31)
    return jax.random.fold_in(key, stream)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
