"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only table3,fig2,...]
                                            [--json BENCH.json]

Prints ``name,us_per_call,derived`` CSV rows (the harness contract).
``--json`` additionally writes the rows as structured JSON (the CI
bench-smoke artifact).  A suite that raises still lets the others run, but
the process exits nonzero so CI goes red on any benchmark failure.
"""
import argparse
import json
import sys
import traceback

import jax

from repro.launch.compile_cache import enable_compile_cache

jax.config.update("jax_enable_x64", True)   # paper CPU baselines are f64


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slower)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized subset of each suite (minutes, not tens)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: "
                         "table3,table4,fig2,table5,fig3,spmv,serve")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows + failure count as JSON")
    args = ap.parse_args()
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    suites = []
    if only is None or "table3" in only:
        from . import table3_single_device
        suites.append(("table3", lambda: table3_single_device.run(
            args.full, smoke=args.smoke)))
    if only is None or "table4" in only:
        from . import table4_distributed
        suites.append(("table4", lambda: table4_distributed.run(
            args.full, smoke=args.smoke)))
    if only is None or "spmv" in only:
        from . import spmv
        suites.append(("spmv", lambda: spmv.run(args.full, smoke=args.smoke)))
    if only is None or "serve" in only:
        from . import serve
        suites.append(("serve", lambda: serve.run(args.full,
                                                  smoke=args.smoke)))
    if only is None or "fig2" in only:
        from . import fig2_adjoint_vs_naive
        suites.append(("fig2", fig2_adjoint_vs_naive.run))
    if only is None or "table5" in only:
        from . import table5_gradcheck
        suites.append(("table5", lambda: table5_gradcheck.run(
            args.full, smoke=args.smoke)))
    if only is None or "fig3" in only:
        from . import fig3_inverse
        steps = 1500 if args.full else 300
        suites.append(("fig3", lambda: fig3_inverse.run(steps=steps)))

    rows, errors = [], []
    for name, fn in suites:
        try:
            for row in fn():
                rows.append(row)
                print(row, flush=True)
        except Exception as e:  # report, keep the remaining suites running
            errors.append(f"{name}: {type(e).__name__}: {e}")
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)

    if args.json:
        def parse(row: str) -> dict:
            name, us, derived = row.split(",", 2)
            return {"name": name, "us_per_call": float(us), "derived": derived}

        with open(args.json, "w") as f:
            json.dump({"rows": [parse(r) for r in rows],
                       "failures": len(errors), "errors": errors}, f, indent=1)

    # a failed suite MUST surface as a nonzero exit code — the CI bench job
    # gates on it (a swallowed traceback used to leave the job green)
    sys.exit(1 if errors else 0)


if __name__ == "__main__":
    main()
