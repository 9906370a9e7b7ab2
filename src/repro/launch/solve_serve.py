"""Solve-as-a-service: a request-batching driver over the plan engine.

Production traffic means thousands of concurrent solves on a handful of
sparsity patterns — exactly the amortization the plan engine was built for.
This driver turns a stream of independent ``(A, b)`` requests into grouped,
vmapped dispatches:

1. **group** incoming requests by plan key — shared pattern (the tensors'
   plan-cache identity) + resolved :class:`SolverConfig`, so every request
   in a group runs the same traced program;
2. **pad** each group's stacked values/rhs to the next power-of-two batch
   size (bounded jit recompiles: at most log2(max_batch) shapes per group);
3. **dispatch** ONE jitted, vmapped ``plan.solve`` per group — one analyze
   per pattern (``PLAN_STATS["analyze"]``), one vmapped setup per batch
   (``setup_batch``), one XLA program for the whole group.

The CLI runs the smoke workload and prints the serving report::

    PYTHONPATH=src python -m repro.launch.solve_serve --smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dispatch as _dispatch
from ..core.dispatch import PLAN_STATS, SolverConfig, make_config
from ..core.solvers import SolveInfo, SolveResult, as_solve_result
from ..core.spans import spanned
from ..core.sparse import SparseTensor


@dataclasses.dataclass
class SolveRequest:
    """One serving request: a values-carrying tensor, a right-hand side, and
    per-request solver options (``backend``/``method``/``precond``/``tol``/
    ``atol``/``maxiter``).  Requests sharing a pattern (``with_values`` views
    of one tensor) and options land in the same dispatch group."""
    A: SparseTensor
    b: jax.Array
    options: dict = dataclasses.field(default_factory=dict)


def _pow2(k: int) -> int:
    return 1 << max(k - 1, 0).bit_length()


class SolveServer:
    """Groups, pads, and dispatches solve requests as vmapped batches.

    Stateless between batches except for caches: the jit cache (one traced
    program per (plan, config); padded pow2 shapes bound recompiles) and the
    plan caches living on the request tensors themselves.  ``stats`` tracks
    dispatch counts and batch-group occupancy (real requests over padded
    slots — the padding overhead the pow2 policy trades for trace reuse).
    """

    def __init__(self, max_batch: int = 64):
        self.max_batch = max_batch
        self._jits: Dict[tuple, callable] = {}
        self.stats = {"dispatches": 0, "requests": 0, "padded_slots": 0}

    @property
    def occupancy(self) -> float:
        """Real requests / padded batch slots across all dispatches so far."""
        slots = self.stats["padded_slots"]
        return self.stats["requests"] / slots if slots else 1.0

    def _plan_for(self, req: SolveRequest):
        cfg = make_config(req.A, **req.options)
        plan = _dispatch.get_plan(req.A, cfg)
        return plan, cfg

    def _dispatch_fn(self, plan, cfg: SolverConfig):
        key = (id(plan), cfg)
        fn = self._jits.get(key)
        if fn is None:
            def batched(vals, bs, plan=plan, cfg=cfg):
                return plan.solve(plan.matrix(vals), bs, cfg=cfg)
            fn = jax.jit(batched)
            self._jits[key] = fn
        return fn

    @spanned("sla.serve_batch")
    def submit_batch(self, requests: List[SolveRequest]) -> List[SolveResult]:
        """Solve a wave of requests; results come back in request order.

        Groups by (pattern identity, resolved config), pads each group's
        stacked values/rhs to a power of two by repeating the first lane,
        and runs one vmapped ``plan.solve`` per group.  Per-request
        diagnostics are sliced back out of the stacked :class:`SolveInfo`.
        """
        groups: Dict[tuple, dict] = {}
        for idx, req in enumerate(requests):
            plan, cfg = self._plan_for(req)
            key = (id(getattr(req.A, "_plans", None)), cfg)
            g = groups.setdefault(key, {"plan": plan, "cfg": cfg,
                                        "members": []})
            g["members"].append((idx, req))

        results: List[Optional[SolveResult]] = [None] * len(requests)
        for g in groups.values():
            plan, cfg, members = g["plan"], g["cfg"], g["members"]
            for start in range(0, len(members), self.max_batch):
                chunk = members[start:start + self.max_batch]
                k = len(chunk)
                pad = _pow2(k)
                vals = jnp.stack(
                    [r.A.val for _, r in chunk]
                    + [chunk[0][1].A.val] * (pad - k))
                bs = jnp.stack(
                    [r.b for _, r in chunk] + [chunk[0][1].b] * (pad - k))
                xs, infos = self._dispatch_fn(plan, cfg)(vals, bs)
                self.stats["dispatches"] += 1
                self.stats["requests"] += k
                self.stats["padded_slots"] += pad
                for lane, (idx, _) in enumerate(chunk):
                    info = SolveInfo(infos.iters[lane], infos.resnorm[lane],
                                     infos.converged[lane])
                    results[idx] = as_solve_result(xs[lane], info)
        return results


# ---------------------------------------------------------------------------
# smoke workload + serving report (what benchmarks/serve.py gates on)
# ---------------------------------------------------------------------------

def _workload(n_requests: int, grid: int, n_patterns: int, seed: int,
              options: dict) -> List[SolveRequest]:
    """Shared-pattern request stream: ``n_patterns`` Poisson grids, each
    request a scaled-values view (same pattern, different values) with a
    random rhs — the traffic shape the plan engine amortizes."""
    from ..data.poisson import poisson2d
    rng = np.random.default_rng(seed)
    bases = [poisson2d(grid + i) for i in range(n_patterns)]
    reqs = []
    for i in range(n_requests):
        A0 = bases[i % n_patterns]
        scale = float(rng.uniform(0.7, 1.4))   # similar conditioning: vmap
        Ai = A0.with_values(A0.val * scale)    # lanes stay near-lockstep
        bi = jnp.asarray(rng.normal(size=A0.shape[0]), A0.val.dtype)
        reqs.append(SolveRequest(Ai, bi, dict(options)))
    return reqs


def serve(n_requests: int = 64, grid: int = 20, n_patterns: int = 1,
          max_batch: int = 32, seed: int = 0, check: bool = True,
          **solve_options) -> dict:
    """Run the serving smoke workload; return the metrics report.

    Times two drivers over the SAME request stream and jitted programs:
    the batched server (grouped + padded + vmapped dispatch) and the
    one-at-a-time loop (one jitted single solve per request).  Reports
    p50/p99 request latency, solves/sec for both, their ratio, batch-group
    occupancy, and the analyze count — the acceptance gate is
    ``speedup ≥ 2`` with ``analyze == n_patterns`` across the whole run.

    ``check=True`` additionally verifies every batched solution against the
    sequential one (parity, not just speed).
    """
    solve_options.setdefault("backend", "jnp")
    solve_options.setdefault("method", "cg")
    solve_options.setdefault("precond", "jacobi")
    solve_options.setdefault("tol", 1e-8)

    _dispatch.reset_plan_stats()
    requests = _workload(n_requests, grid, n_patterns, seed, solve_options)
    server = SolveServer(max_batch=max_batch)

    # sequential driver: one jitted single-rhs solve per request, plan and
    # trace reused — this is the fair baseline (no re-analyze, no re-compile)
    seq_fns = {}
    for req in requests:
        plan, cfg = server._plan_for(req)
        key = (id(req.A._plans), cfg)
        if key not in seq_fns:
            def single(v, bb, plan=plan, cfg=cfg):
                return plan.solve(plan.matrix(v), bb, cfg=cfg)
            seq_fns[key] = (jax.jit(single), plan, cfg)

    # warmup: compile every traced program outside the timed windows
    _ = server.submit_batch(requests)
    seq_results = []
    for req in requests:
        plan, cfg = server._plan_for(req)
        fn = seq_fns[(id(req.A._plans), cfg)][0]
        seq_results.append(fn(req.A.val, req.b))
    jax.block_until_ready([r[0] for r in seq_results])

    # timed: batched server, stream consumed in max_batch waves
    lat_batched = []
    t0 = time.perf_counter()
    out_batched = []
    for start in range(0, len(requests), max_batch):
        wave = requests[start:start + max_batch]
        res = server.submit_batch(wave)
        jax.block_until_ready([r.x for r in res])
        done = time.perf_counter() - t0
        lat_batched.extend([done] * len(wave))
        out_batched.extend(res)
    t_batched = time.perf_counter() - t0

    # timed: sequential loop
    lat_seq = []
    t0 = time.perf_counter()
    out_seq = []
    for req in requests:
        fn = seq_fns[(id(req.A._plans),
                      server._plan_for(req)[1])][0]
        x, info = fn(req.A.val, req.b)
        jax.block_until_ready(x)
        lat_seq.append(time.perf_counter() - t0)
        out_seq.append((x, info))
    t_seq = time.perf_counter() - t0

    if check:
        for res, (x_ref, _) in zip(out_batched, out_seq):
            np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_ref),
                                       rtol=1e-6, atol=1e-8)

    n = len(requests)
    report = {
        "n_requests": n,
        "n_patterns": n_patterns,
        "grid": grid,
        "max_batch": max_batch,
        "batched": {
            "total_s": t_batched,
            "solves_per_sec": n / t_batched,
            "p50_ms": float(np.percentile(lat_batched, 50) * 1e3),
            "p99_ms": float(np.percentile(lat_batched, 99) * 1e3),
        },
        "sequential": {
            "total_s": t_seq,
            "solves_per_sec": n / t_seq,
            "p50_ms": float(np.percentile(lat_seq, 50) * 1e3),
            "p99_ms": float(np.percentile(lat_seq, 99) * 1e3),
        },
        "speedup": t_seq / t_batched,
        "occupancy": server.occupancy,
        "plan_stats": dict(PLAN_STATS),
        "converged": bool(all(r.reason == "converged" for r in out_batched)),
    }
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--grid", type=int, default=32)
    ap.add_argument("--patterns", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    kw = dict(n_requests=args.requests, grid=args.grid,
              n_patterns=args.patterns, max_batch=args.max_batch,
              seed=args.seed)
    if args.smoke:
        kw.update(n_requests=64, grid=20, n_patterns=1)
    rep = serve(**kw)
    b, s = rep["batched"], rep["sequential"]
    print(f"requests={rep['n_requests']} patterns={rep['n_patterns']} "
          f"grid={rep['grid']} max_batch={rep['max_batch']}")
    print(f"batched    : {b['solves_per_sec']:8.1f} solves/s  "
          f"p50={b['p50_ms']:.2f} ms  p99={b['p99_ms']:.2f} ms")
    print(f"sequential : {s['solves_per_sec']:8.1f} solves/s  "
          f"p50={s['p50_ms']:.2f} ms  p99={s['p99_ms']:.2f} ms")
    print(f"speedup={rep['speedup']:.2f}x  occupancy={rep['occupancy']:.2f}  "
          f"analyze={rep['plan_stats']['analyze']} "
          f"(converged={rep['converged']})")
    return rep


if __name__ == "__main__":
    main()
