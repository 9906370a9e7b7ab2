"""The readers of the program's own spans and counters, and the fused-step
roofline, on synthetic solve records and on the small recorded trace.

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
sys.path.insert(0, os.path.join(ROOT, "src"))

from common import bench_module, load_module  # noqa: E402
from peaks import peak  # noqa: E402

trace = load_module(os.path.join(BENCH, "trace.py"), "bench_trace_spans")
COUNTERS = {"lowerings_per_solve.solve": "jax_lowerings",
            "jaxpr_traces_per_solve.solve": "jax_traces"}
PROGRAM = sorted(COUNTERS) + ["host_ms_per_solve.solve"]


@pytest.fixture(scope="module")
def tr():
    with open(os.path.join(HERE, "data", "trace_poisson_v5e.json")) as fh:
        return json.load(fh)


def _record(i, name="sla.solve", seconds=2.0, wait=0.5, traced=False):
    return {"name": name, "id": i, "start_ns": 0, "end_ns": int(seconds * 1e9),
            "seconds": seconds,
            "incl_s": {name: seconds, "solve.wait": wait},
            "self_s": {name: seconds - wait, "solve.wait": wait},
            "counters": {"jax_lowerings": i % 2, "jax_traces": 100 + i},
            "lowered": ["jit(while)"] * (i % 2), "traced": traced}


def _ctx(tr, solves, grid=None, extra_ops=()):
    events = {"devices": {k: v + [list(e) for e in extra_ops]
                          for k, v in tr["devices"].items()},
              "host": tr["host"]}
    return {"trace": trace.reduce(events), "counters": {"solves": solves},
            "setup": {}, "grid": grid,
            "peak": lambda: peak("TPU v5 lite"),
            "kernel_time": lambda pat: trace.kernel_time(events, pat)}


@pytest.fixture
def records(monkeypatch):
    from repro import sla
    recs = []
    monkeypatch.setattr(sla, "solve_records", lambda n=None: list(recs))
    return recs


@pytest.mark.parametrize("metric", PROGRAM)
def test_program_readers_take_the_window_solves(tr, records, metric):
    # a set-up root (get_plan) and three warm-up solves, then the window's 4
    records.append(_record(0, name="plan.get", seconds=9.0, wait=0.0))
    records.extend(_record(i, seconds=5.0, wait=0.1) for i in (1, 2, 3))
    window = [_record(i, seconds=1.0 + 0.5 * i, wait=0.25) for i in range(4, 8)]
    records.extend(window)
    value = bench_module("metrics", metric).read(_ctx(tr, solves=4))
    if metric in COUNTERS:
        key = COUNTERS[metric]
        want = sum(r["counters"][key] for r in window) / 4
    else:
        want = 1e3 * sum(r["seconds"] - 0.25 for r in window) / 4
    assert value == pytest.approx(want)


@pytest.mark.parametrize("metric", PROGRAM)
@pytest.mark.parametrize("case", ["fewer_records", "traced", "no_trace",
                                  "no_solves", "other_roots"])
def test_program_readers_read_nothing(tr, records, metric, case):
    solves = 3
    if case == "fewer_records":             # count mismatch: a record short
        records.extend(_record(i) for i in range(2))
    elif case == "traced":
        records.extend(_record(i, traced=(i == 2)) for i in range(3))
    elif case == "other_roots":             # the --control runs: no solves
        records.extend(_record(i, name="plan.get") for i in range(5))
    else:
        records.extend(_record(i) for i in range(3))
    ctx = _ctx(tr, solves=0 if case == "no_solves" else solves)
    if case == "no_trace":
        ctx["trace"] = None
    assert bench_module("metrics", metric).read(ctx) is None


@pytest.mark.parametrize("metric", PROGRAM)
def test_program_readers_on_a_program_without_records(tr, monkeypatch,
                                                      metric):
    """A program without the recorder: ``repro.sla`` has no
    ``solve_records``."""
    from repro import sla
    monkeypatch.delattr(sla, "solve_records", raising=False)
    assert bench_module("metrics", metric).read(_ctx(tr, solves=3)) is None


def test_fused_step_roofline_on_the_recorded_trace(tr):
    mod = bench_module("metrics", "fused_step_roofline.solve")
    # recorded before the kernels were named: their ops were ``body.N``
    assert mod.read(_ctx(tr, solves=1, grid=(4096, 4096))) is None
    w0, w1 = trace.window(tr)
    t = w0 + 0.25 * (w1 - w0)
    ops = [["%fused_cg_halfstep.7 = (f32[16384,8,128]) custom-call(...)",
            t, 2.0e6],
           ["%fused_cg_halfstep.7 = (f32[16384,8,128]) custom-call(...)",
            t + 3.0e6, 2.0e6],
           ["%fused_cg_update.3 = (f32[16384,8,128]) custom-call(...)",
            t + 6.0e6, 1.0e6],
           ["%fusion.1 = f32[8] fusion(%fused_cg_halfstep.7)", t + 8e6, 1e6]]
    n = 4096 * 4096
    got = mod.read(_ctx(tr, solves=1, grid=(4096, 4096), extra_ops=ops))
    least = (2 * 6 + 8) * n * 4 / 819e9
    assert got == pytest.approx(100.0 * least / 5.0e-3)
    assert 0.0 < got <= 100.0
    assert mod.read(_ctx(tr, solves=1, grid=None, extra_ops=ops)) is None


def test_new_metrics_are_entries_with_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    layer = {m["name"]: m for m in spec["per_layer"]}
    for name in PROGRAM:
        assert layer[name]["layer"] == "plan engine"
        assert layer[name]["workloads"] == ["poisson_vc_16m.solve",
                                            "rgg_dimacs10.amg_cg"]
    assert layer["fused_step_roofline.solve"]["workloads"] == [
        "poisson_vc_16m.solve"]
