"""The span recorder: nesting, self times, JAX compile counters, the
PLAN_STATS deltas of a record, the bounded ring, per-thread stacks, traced
roots, and the record of a warm eager MG-CG solve."""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sla
from repro.core import spans
from repro.core.dispatch import PLAN_STATS, get_plan, reset_plan_stats
from repro.core.spans import span
from repro.data.poisson import poisson2d, poisson2d_vc


def _last():
    return sla.solve_records(1)[0]


def _walk(tree):
    """Run ``tree`` (a nested {name: children} dict) as nested spans."""
    for name, kids in tree.items():
        with span(name):
            time.sleep(0.001)
            _walk(kids)


@pytest.mark.parametrize("tree", [
    {"root": {}},
    {"root": {"a": {"b": {}, "c": {}}, "d": {}}},
    {"root": {"a": {"a": {"a": {}}}}},            # a name nested in itself
])
def test_spans_nest_and_self_times_sum_to_the_root(tree):
    _walk(tree)
    rec = _last()
    assert rec["name"] == "root"
    assert rec["seconds"] == pytest.approx(
        (rec["end_ns"] - rec["start_ns"]) * 1e-9)
    assert rec["incl_s"]["root"] == pytest.approx(rec["seconds"])
    assert all(v >= 0 for v in rec["self_s"].values())
    assert sum(rec["self_s"].values()) == pytest.approx(rec["seconds"],
                                                        abs=1e-8)

    def names(t):
        return set(t) | {n for kids in t.values() for n in names(kids)}
    assert set(rec["incl_s"]) == names(tree)
    for name in rec["incl_s"]:
        assert rec["self_s"][name] <= rec["incl_s"][name] + 1e-12


def test_a_fresh_jit_lowers_once_inside_a_span():
    scale = float(np.random.default_rng().integers(2, 10**6))
    f = jax.jit(lambda x: x * scale + 1.0)
    x = jnp.arange(8.0)
    with span("first"):
        f(x).block_until_ready()
    first = _last()
    with span("second"):
        f(x).block_until_ready()
    second = _last()
    assert first["counters"]["jax_lowerings"] == 1
    assert first["counters"]["jax_compiles"] == 1
    assert any("lambda" in name for name in first["lowered"])
    assert second["counters"].get("jax_lowerings", 0) == 0
    assert second["lowered"] == []


def test_plan_stats_increments_are_the_record_deltas():
    A = poisson2d(24)
    before = dict(PLAN_STATS)
    with span("root"):
        get_plan(A, backend="jnp", method="cg", precond="amg")
        get_plan(A, backend="jnp", method="cg", precond="amg")
    rec = _last()
    delta = {k: PLAN_STATS[k] - before[k] for k in PLAN_STATS
             if PLAN_STATS[k] != before[k]}
    assert rec["counters"] == delta
    assert {"cache_miss": 1, "cache_hit": 1, "analyze": 1,
            "coarsen": 1}.items() <= delta.items()
    assert {"plan.get", "plan.analyze", "analyze.kernel_plan",
            "analyze.precond", "amg.coarsen"} <= set(rec["incl_s"])


@pytest.mark.parametrize("n", [None, 0, 5, spans.RING + 100])
def test_the_ring_is_bounded(n):
    reset_plan_stats()
    assert sla.solve_records() == []
    for _ in range(spans.RING + 10):
        with span("r"):
            pass
    recs = sla.solve_records(n)
    want = {None: spans.RING, 0: 0, 5: 5}.get(n, spans.RING)
    assert len(recs) == want
    if want:
        ids = [r["id"] for r in recs]
        assert ids == list(range(ids[0], ids[0] + want))    # oldest first
        assert ids[-1] == sla.solve_records()[-1]["id"]      # the newest
    reset_plan_stats()
    assert sla.solve_records() == []


def test_two_threads_keep_separate_stacks():
    barrier = threading.Barrier(2)

    def work(tag):
        with span(f"root.{tag}"):
            barrier.wait()
            with span(f"child.{tag}"):
                barrier.wait()
            barrier.wait()

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = {r["name"]: r for r in sla.solve_records(2)}
    assert set(recs) == {"root.a", "root.b"}
    for tag in "ab":
        assert set(recs[f"root.{tag}"]["incl_s"]) == {f"root.{tag}",
                                                      f"child.{tag}"}


def _plain(obj):
    if isinstance(obj, dict):
        return all(isinstance(k, str) and _plain(v) for k, v in obj.items())
    if isinstance(obj, list):
        return all(_plain(v) for v in obj)
    return isinstance(obj, (bool, int, float, str))


def test_solve_under_jit_gives_a_traced_record_without_tracers():
    A = poisson2d(16)
    f = jax.jit(lambda b: sla.solve(A, b, backend="jnp", method="cg",
                                    precond="jacobi"))
    x = f(jnp.ones(A.shape[0]))
    rec = [r for r in sla.solve_records() if r["name"] == "sla.solve"][-1]
    assert rec["traced"] is True
    assert _plain(rec)
    json.dumps(rec)
    assert np.all(np.isfinite(np.asarray(x)))
    with span("eager"):
        pass
    assert _last()["traced"] is False


def test_warm_stencil_mg_cg_solve_leaves_one_record():
    ng = 32
    kappa = jnp.asarray(np.exp(0.3 * np.random.default_rng(0).normal(
        size=(ng, ng))))
    A = poisson2d_vc(kappa, use_stencil_kernel=True)
    b = jnp.ones(A.shape[0])
    kw = dict(precond="mg", tol=1e-8)
    reset_plan_stats()
    sla.solve_with_info(A, b, **kw)                 # analyze, set up, compile
    cold = sla.solve_records()[-1]
    # the stage spans open while the solve program is traced: cold solve only
    assert {"plan.analyze", "plan.setup", "plan.solve", "precond.make_apply",
            "krylov.cg"} <= set(cold["incl_s"])
    assert cold["counters"]["solve_program_build"] == 1
    reset_plan_stats()
    res = sla.solve_with_info(A, b, **kw)
    recs = sla.solve_records()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["name"] == "sla.solve" and rec["traced"] is False
    assert {"plan.get", "plan.setup", "plan.solve",
            "solve.wait"} <= set(rec["incl_s"])
    assert not {"plan.analyze", "precond.make_apply",
                "krylov.cg"} & set(rec["incl_s"])
    assert rec["counters"]["cache_hit"] == 1
    assert rec["counters"]["setup_reuse"] == 1
    assert rec["counters"]["solve_program_call"] == 1
    assert rec["counters"].get("solve_program_build", 0) == 0
    assert rec["counters"].get("jax_lowerings", 0) == 0
    assert 0.0 <= rec["incl_s"]["solve.wait"] < rec["seconds"]
    assert bool(res.converged)
