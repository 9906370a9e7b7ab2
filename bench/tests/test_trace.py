"""The trace reduction, checked on a small recorded trace.

``data/trace_poisson_v5e.json`` is a 40 ms slice of a traced window of the
``poisson_vc_16m.solve`` cell on a TPU v5e, in the form ``trace.load``
gives (HLO texts cut to 160 characters), with a ``bench.window`` span laid
over the slice.  Run by path: ``python -m pytest bench/tests``.
"""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from common import load_module  # noqa: E402

trace = load_module(os.path.join(os.path.dirname(HERE), "trace.py"),
                    "bench_trace_under_test")


@pytest.fixture(scope="module")
def tr():
    with open(os.path.join(HERE, "data", "trace_poisson_v5e.json")) as fh:
        return json.load(fh)


def _brute_busy(events, w0, w1, step=10.0):
    """Busy seconds by marking a 10 ns grid: independent of the union."""
    grid = np.zeros(int((w1 - w0) / step) + 1, bool)
    for _, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            grid[int((a - w0) / step):int((b - w0) / step)] = True
    return grid.sum() * step * 1e-9


def test_busy_and_idle_share(tr):
    r = trace.reduce(tr)
    w0, w1 = trace.window(tr)
    brute = _brute_busy(tr["devices"]["/device:TPU:0"], w0, w1)
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert r["busy_s"] == pytest.approx(brute, abs=5e-6)
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert 0.0 < r["idle_share"] < 1.0
    assert r["devices"] == 1


def test_breakdown_ordering(tr):
    r = trace.reduce(tr, top=1000)
    for key in ("device_ops", "idle_gaps"):
        secs = [v for _, v in r[key]]
        assert secs == sorted(secs, reverse=True)
        assert all(v > 0 for v in secs)
    assert len(trace.reduce(tr)["device_ops"]) <= 10
    # every idle nanosecond of the window is in exactly one labelled gap
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], abs=1e-9)
    # leaf ops only: they add up to no more than the busy time
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] + 1e-9
    assert all(lab.startswith("bench.") for lab, _ in r["idle_gaps"])


def test_stencil_events_match_by_op_name(tr):
    events = tr["devices"]["/device:TPU:0"]
    named = [e for e in events if trace.op_name(e[0]).startswith("stencil5_pallas")]
    mentioned = [e for e in events if "stencil5_pallas" in e[0]]
    assert len(named) == 1
    secs, calls = trace.kernel_time(tr, "stencil5_pallas")
    assert calls == 1
    assert secs == pytest.approx(named[0][2] * 1e-9)
    # an op that only takes the kernel's output as an operand is not it
    assert len(mentioned) >= len(named)


def test_op_name_and_label():
    hlo = ("%fusion.226 = f32[3359700]{0:T(1024)} fusion(f32[262144]{0:T(1024)}"
           " %get-tuple-element.1024), kind=kCustom")
    assert trace.op_name(hlo) == "fusion.226"
    assert trace.op_label(hlo) == "fusion.226 f32[3359700]"


def test_nested_events_and_gap_labels():
    """A while op enclosing two body ops: busy is the union, the per-op
    time counts the body ops only, and gaps take the innermost host span."""
    ms = 1e6
    tr = {"devices": {"/device:TPU:0": [
        ["%while.1 = (f32[]) while(...)", 0 * ms, 10 * ms],
        ["%a.1 = f32[8] add(...)", 1 * ms, 3 * ms],
        ["%b.2 = f32[8] multiply(...)", 5 * ms, 4 * ms],
        ["%c.3 = f32[8] copy(...)", 14 * ms, 2 * ms]]},
        "host": [["bench.window", 0.0, 20 * ms],
                 ["bench.solve", 0.0, 20 * ms],
                 ["PjitFunction(add)", 10.5 * ms, 3 * ms],
                 ["bench.rhs", 16.5 * ms, 3 * ms]]}
    r = trace.reduce(tr)
    assert r["busy_s"] == pytest.approx(12e-3)
    assert dict(r["device_ops"]) == pytest.approx(
        {"a.1 f32[8]": 3e-3, "b.2 f32[8]": 4e-3, "c.3 f32[8]": 2e-3})
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.solve > PjitFunction(add)": 4e-3, "bench.rhs": 4e-3})
