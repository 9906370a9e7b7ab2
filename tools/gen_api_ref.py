"""Generate ``docs/api.md`` from the :mod:`repro.sla` docstrings.

    PYTHONPATH=src python tools/gen_api_ref.py            # rewrite docs/api.md
    PYTHONPATH=src python tools/gen_api_ref.py --check    # exit 1 on drift

Stdlib only (``inspect``) — no doc toolchain.  The rendered
file is CHECKED IN: the docs CI job runs without JAX installed, so it
verifies links in the committed ``docs/api.md`` rather than regenerating
it.  Re-run this script whenever the ``repro.sla`` surface or a public
docstring changes; ``--check`` makes drift visible locally.
"""
from __future__ import annotations

import argparse
import inspect
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "src"))

HEADER = """\
# `repro.sla` API reference

<!-- GENERATED FILE — do not edit by hand.
     Rebuild with: PYTHONPATH=src python tools/gen_api_ref.py -->
"""


def _doc(obj) -> str:
    doc = inspect.getdoc(obj)
    return doc.strip() if doc else "*(no docstring)*"


def _signature(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"


def _render_class(name: str, cls: type) -> list:
    lines = [f"### `{name}`", "", _doc(cls), ""]
    if hasattr(cls, "_fields"):          # NamedTuple: fields are the API
        lines += ["Fields: " + ", ".join(f"`{f}`" for f in cls._fields), ""]
        return lines
    methods = []
    for mname, m in sorted(vars(cls).items()):
        if mname.startswith("_") or not callable(m):
            continue
        if not inspect.getdoc(m):
            continue
        methods.append((mname, m))
    for mname, m in methods:
        first = _doc(m).split("\n\n")[0].replace("\n", " ")
        lines += [f"- **`.{mname}{_signature(m)}`** — {first}"]
    if methods:
        lines.append("")
    return lines


def _render_function(name: str, fn) -> list:
    return [f"### `{name}{_signature(fn)}`", "", _doc(fn), ""]


def render() -> str:
    import repro.sla as sla

    out = [HEADER]
    # the module docstring is the narrative front page
    out += [inspect.getdoc(sla).strip(), "", "---", ""]

    groups = [
        ("Tensors and plans",
         ["SparseTensor", "DSparseTensor", "SolverPlan", "get_plan"]),
        ("Solving",
         ["solve", "solve_with_info", "SolveResult", "SolverConfig",
          "register_backend"]),
        ("Nonlinear and eigen",
         ["nonlinear_solve", "SparseNewton", "eigsh"]),
        ("Options",
         ["Options", "set_options", "options", "get_options"]),
        ("Serving",
         ["serve", "SolveServer"]),
        ("Introspection",
         ["PLAN_STATS", "reset_plan_stats", "solve_records"]),
    ]
    grouped = {n for _, names in groups for n in names}
    missing = sorted(set(sla.__all__) - grouped)
    if missing:                      # new public names must pick a section
        raise SystemExit(f"gen_api_ref: ungrouped public names: {missing}")

    for title, names in groups:
        out += [f"## {title}", ""]
        for name in names:
            obj = getattr(sla, name)
            if inspect.isclass(obj):
                out += _render_class(name, obj)
            elif callable(obj):
                out += _render_function(name, obj)
            else:                    # plain objects (PLAN_STATS dict)
                desc = {
                    "PLAN_STATS": "Process-wide plan-lifecycle counters "
                    "(`analyze`, `setup`, `setup_reuse`, `factorize`, "
                    "`cache_hit`, `cache_miss`, `evictions`, ...) — read "
                    "them to verify amortization, reset with "
                    "`reset_plan_stats()`.",
                }.get(name, "*(module-level object)*")
                out += [f"### `{name}`", "", desc, ""]
    return "\n".join(out).rstrip() + "\n"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if docs/api.md is stale instead of writing")
    ap.add_argument("--out", default=str(REPO / "docs" / "api.md"))
    args = ap.parse_args()

    text = render()
    out = Path(args.out)
    if args.check:
        current = out.read_text(encoding="utf-8") if out.exists() else ""
        if current != text:
            print(f"{out} is stale — re-run: "
                  "PYTHONPATH=src python tools/gen_api_ref.py",
                  file=sys.stderr)
            return 1
        print(f"OK: {out} is up to date")
        return 0
    out.write_text(text, encoding="utf-8")
    print(f"wrote {out} ({len(text.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
