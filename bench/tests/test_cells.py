"""Every cell rehearsed on the CPU at a tiny size through the same files,
the harness's refusals, and a cell defined by new files and an entry only.

Run by path, on the CPU: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
RUN = os.path.join(BENCH, "run.py")

with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    SPEC = json.load(fh)
CELLS = [w["name"] for w in SPEC["workloads"]]


def run(*args, root=ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(root, "bench", "run.py"),
                        *args], capture_output=True, text=True, env=env,
                       timeout=timeout, cwd=root)
    out = p.stdout.strip().splitlines()
    return p.returncode, (json.loads(out[-1]) if out and p.returncode == 0
                          else None), p.stderr


def expected_e2e(cell):
    return {m["name"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell):
    rc, out, err = run("--workload", cell, "--seed", "3000000007",
                       "--seconds", "1", "--trace", "0", "--rehearse")
    assert rc == 0, err[-3000:]
    assert out["correct"] is True, err[-3000:]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == expected_e2e(cell) | {"setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    assert list(out)[-1] == "check"
    assert err.rstrip().splitlines()[-1].startswith("check ")


def test_traced_rehearsal_reports_per_layer_metrics():
    rc, out, err = run("--workload", "rgg_dimacs10.amg_cg", "--seed", "5",
                       "--seconds", "1", "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    # a CPU has no device plane: only the counters and host-clock metrics
    assert set(out["metrics"]) == {"krylov_iters.solve", "analyze_s"}


def test_refuses_without_a_tpu():
    rc, out, err = run("--workload", CELLS[0], "--seed", "1", "--seconds",
                       "1", "--trace", "0")
    assert rc != 0 and out is None
    assert "no TPU" in err


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, out, err = run("--workload", CELLS[0], "--seed", "1", "--seconds",
                       "1", "--trace", "0", "--rehearse", root=str(tmp_path))
    assert rc != 0 and out is None


def test_new_cell_from_new_files_only(tmp_path):
    """A cell that no code knows of: a new traffic file, a new limits file
    and a new BENCHMARK.json entry, with every existing file untouched."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    traffic = {"kind": "solve",
               "solver": {"method": "cg", "precond": "jacobi", "tol": 1e-5,
                          "maxiter": 4000},
               "rhs": {"dist": "normal"}, "check_samples": 2}
    (tmp_path / "bench" / "traffic" / "jacobi_cg.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "limits" / "poisson_vc_16m.jacobi_cg.json"
     ).write_text(json.dumps({"fwd_err": 2e-3}))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "poisson_vc_16m.jacobi_cg",
                              "config": "poisson_vc_16m",
                              "traffic": "jacobi_cg", "chips": 1,
                              "why": "jacobi-CG on the same grid"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "poisson_vc_16m.solve" in m.get("workloads", []):
            m["workloads"].append("poisson_vc_16m.jacobi_cg")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, out, err = run("--workload", "poisson_vc_16m.jacobi_cg", "--seed",
                       "11", "--seconds", "1", "--trace", "0", "--rehearse",
                       root=str(tmp_path))
    assert rc == 0, err[-3000:]
    assert out["correct"] is True
    assert set(out["metrics"]) == {"solve_ms", "setup_s"}
