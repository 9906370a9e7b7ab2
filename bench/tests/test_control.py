"""The comparison that decides ``correct`` fails what it must fail.

- The control: the plain reference in bfloat16 put in the program's place
  (``--control``) reads not correct in every cell.
- Faults planted under the timed path, the rest of the run driven as the
  harness drives it (its look for a chip skipped by ``rehearse``): a solve
  that returns its state unchanged, and an answer altered where it is
  produced.  A cell has no batch to halve and one chip, so those faults
  do not arise.

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

SOLVE_CELLS = ["poisson_vc_16m.solve", "rgg_dimacs10.amg_cg"]


def run_inprocess(cell, seed=21):
    harness = load_module(os.path.join(BENCH, "run.py"), "bench_run_under_test")
    args = argparse.Namespace(
        workload=cell, seed=seed, seconds=0.5, trace=0, rehearse=True,
        control=False)
    return harness.run(args)


@pytest.mark.parametrize("cell", SOLVE_CELLS)
def test_control_reads_not_correct(cell):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "31", "--seconds", "0.5", "--trace", "0", "--rehearse",
         "--control"], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False


def test_sound_run_in_process_is_correct():
    assert run_inprocess("poisson_vc_16m.solve")["correct"] is True


@pytest.mark.parametrize("cell", SOLVE_CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_solve_faults(cell, fault, monkeypatch):
    import jax.numpy as jnp
    from repro import sla
    orig = sla.solve_with_info

    def broken(A, b, **kw):
        res = orig(A, b, **kw)
        if fault == "state_unchanged":       # the solve returns x0 = 0
            return res._replace(x=jnp.zeros_like(res.x))
        x = res.x.at[res.x.shape[0] // 3].add(0.05 * jnp.max(jnp.abs(res.x)))
        return res._replace(x=x)

    monkeypatch.setattr(sla, "solve_with_info", broken)
    assert run_inprocess(cell)["correct"] is False
