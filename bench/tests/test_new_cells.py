"""The correctness comparison of the ``hpcg_256.solve`` and
``poisson_vc_16m.grad_step`` cells fails what it must fail: the plain
reference in bfloat16 in the program's place (``--control``; at the
rehearsal size the gradient's control is compared with the program's
reading, the limit being set for the full size), and a gradient or answer
altered where the program produces it.

Tiny sizes on the CPU; run by path: ``python -m pytest bench/tests``.
"""
import argparse
import json
import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from common import load_module  # noqa: E402

def run_inprocess(cell, seed=23):
    harness = load_module(os.path.join(BENCH, "run.py"), "bench_run_new_cells")
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.5, trace=0,
                              rehearse=True, control=False)
    return harness.run(args)


def run_cli(cell, *extra):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "4000000037", "--seconds", "0.5", "--trace", "0",
         "--rehearse", *extra], capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_hpcg_control_reads_not_correct():
    assert run_cli("hpcg_256.solve", "--control")["correct"] is False


def test_grad_step_control_reads_far_above_the_program():
    """At the rehearsal's 32² grid the bfloat16 control's gradient error
    (≈ 0.1) stays under the limit set for 4096², where the control reads
    12 or more; here it must still read thousands of times the program's."""
    sound = run_cli("poisson_vc_16m.grad_step")["check"]["grad_err"]["value"]
    control = run_cli("poisson_vc_16m.grad_step",
                      "--control")["check"]["grad_err"]["value"]
    assert control > 1e3 * sound


def test_hpcg_altered_answer_reads_not_correct(monkeypatch):
    from repro import sla
    orig = sla.solve_with_info

    def broken(A, b, **kw):
        res = orig(A, b, **kw)
        x = res.x.at[res.x.shape[0] // 3].add(0.01 * abs(res.x).max())
        return res._replace(x=x)

    monkeypatch.setattr(sla, "solve_with_info", broken)
    assert run_inprocess("hpcg_256.solve")["correct"] is False


@pytest.mark.parametrize("fault", ["gradient_zero", "gradient_altered"])
def test_grad_step_faults_read_not_correct(fault, monkeypatch):
    import jax
    orig = jax.value_and_grad

    def broken(fn, *a, **kw):
        vg = orig(fn, *a, **kw)

        def wrapped(*args):
            loss, g = vg(*args)
            if fault == "gradient_zero":
                return loss, g * 0.0
            return loss, g.at[g.shape[0] // 3, 5].add(abs(g).max())
        return wrapped

    monkeypatch.setattr(jax, "value_and_grad", broken)
    assert run_inprocess("poisson_vc_16m.grad_step")["correct"] is False
