"""The 27-point stencil and SymGS roofline readers of the HPCG cell, on
the small recorded trace with synthetic kernel ops laid into its window.

Run by path, on the CPU: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from common import bench_module, load_module  # noqa: E402
from peaks import peak  # noqa: E402

trace = load_module(os.path.join(BENCH, "trace.py"), "bench_trace_hpcg")
NG = 256
READERS = ["stencil27_roofline.solve", "symgs27_roofline.solve"]


@pytest.fixture(scope="module")
def tr():
    with open(os.path.join(HERE, "data", "trace_poisson_v5e.json")) as fh:
        return json.load(fh)


def _ctx(tr, ops=(), grid=(NG, NG), traced=True):
    events = {"devices": {k: v + [list(e) for e in ops]
                          for k, v in tr["devices"].items()},
              "host": tr["host"]}
    return {"trace": trace.reduce(events) if traced else None,
            "counters": {"solves": 1}, "setup": {}, "grid": grid,
            "peak": lambda: peak("TPU v5 lite"),
            "kernel_time": lambda pat: trace.kernel_time(events, pat)}


def _ops(tr, names_ns):
    """Ops named ``name`` lasting ``ns`` each, one after another in the
    middle of the recorded window."""
    w0, w1 = trace.window(tr)
    t = w0 + 0.25 * (w1 - w0)
    out = []
    for name, ns in names_ns:
        out.append([f"%{name} = f32[{NG},{NG},{NG}] custom-call(...)", t, ns])
        t += ns + 1e3
    return out


def test_stencil27_roofline_over_levels(tr):
    mod = bench_module("metrics", "stencil27_roofline.solve")
    ops = _ops(tr, [("stencil27_l0.1", 3.0e6), ("stencil27_l0.7", 3.0e6),
                    ("stencil27_l2.4", 0.5e6),
                    ("stencil27_t.2", 9.0e6),          # a VJP launch: not read
                    ("fusion.3", 9.0e6)])
    got = mod.read(_ctx(tr, ops))
    least = 29 * 4 * (2 * NG ** 3 + (NG // 4) ** 3) / 819e9
    assert got == pytest.approx(100.0 * least / 6.5e-3)
    assert 0.0 < got <= 100.0


def test_symgs27_roofline_counts_half_sweeps(tr):
    mod = bench_module("metrics", "symgs27_roofline.solve")
    # one symmetric step at level 0 (four launches) and one at level 3
    ops = _ops(tr, [(f"symgs27_l0.{i}", 5.0e6) for i in range(4)]
               + [(f"symgs27_l3.{i}", 0.2e6) for i in range(4, 8)])
    got = mod.read(_ctx(tr, ops))
    least = 4 * 15 * 4 * (NG ** 3 + (NG // 8) ** 3) / 819e9
    assert got == pytest.approx(100.0 * least / 20.8e-3)
    assert 0.0 < got <= 100.0


@pytest.mark.parametrize("metric", READERS)
@pytest.mark.parametrize("case", ["no_ops", "no_grid", "no_trace"])
def test_readers_read_nothing(tr, metric, case):
    """Where the program has no such kernel (the parent of the 3-D path,
    the 2-D cells), where the cell has no grid, and in a CPU rehearsal."""
    ops = [] if case == "no_ops" else _ops(
        tr, [("stencil27_l0.1", 1e6), ("symgs27_l0.2", 1e6)])
    ctx = _ctx(tr, ops, grid=None if case == "no_grid" else (NG, NG),
               traced=case != "no_trace")
    assert bench_module("metrics", metric).read(ctx) is None


def test_entries_name_their_layer_and_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    layer = {m["name"]: m for m in spec["per_layer"]}
    assert layer["stencil27_roofline.solve"]["layer"] == "SpMV kernel"
    assert layer["symgs27_roofline.solve"]["layer"] == \
        "kernels and preconditioner"
    for name in READERS:
        assert layer[name]["workloads"] == ["hpcg_256.solve"]
        assert layer[name]["moves"] == "solve_ms"
        assert layer[name]["unit"] == "%"
