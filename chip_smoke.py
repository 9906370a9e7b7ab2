#!/usr/bin/env python3
"""Chip smoke test: drive the plan engine's solve paths once on a TPU.

    python chip_smoke.py              # phases 1-5 on one chip
    python chip_smoke.py --chips 4    # the 4-device mesh phase only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny sizes, any device

Everything runs in this one process, in float32 with x64 off, through the
public entry points (``repro.sla``, ``SparseTensor.solve``,
``DSparseTensor.solve``, ``SolveServer``).  Data is generated from ``--seed``.

Phases (each prints one line before the final JSON line):

1. stencil  — variable-coefficient 2-D Poisson at ng = 4096 (16.8M DOF),
   stencil Pallas kernel + fused CG steps, geometric-MG preconditioner;
2. gradient — d(loss)/d(kappa) through phase 1's solve on the same plan
   (one analyze for forward and backward), plus an ng = 64 check against
   dense float32 autodiff;
3. direct   — 2-D Poisson at ng = 316 (1e5 DOF) factored by the supernodal
   panel kernels, and slogdet on the same factors against the closed form;
4. graph    — AMG-preconditioned CG on a random geometric graph Laplacian
   (2^18 nodes), recording the SpMV kernel the plan chose and why;
5. serve    — SolveServer answers 48 requests over 2 patterns, each checked
   against the sequential solve;
6. mesh     — with ``--chips 4`` only: DSparseTensor 2-D Poisson at
   ng = 2048 on a 4-device mesh (CG + jacobi, manufactured solution, with
   a values gradient), compared with the same system on one device.

A phase fails the run (non-zero exit, no JSON line) when it raises, when a
solve does not converge, when the backward error
||b - Ax||_inf / (||A||_inf ||x||_inf + ||b||_inf) exceeds BERR_BOUND, or when
the plan did not choose the compiled Pallas path the phase exists to run.
Each phase line ends with its last solve's record (``sla.solve_records``):
``host_ms``, the host time outside the wait for the device; ``wait_ms``, the
``solve.wait`` span; ``lowerings``, the programs JAX lowered in that solve.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
BERR_BOUND = 1e-4


class SmokeFailure(Exception):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(name: str, **fields) -> None:
    parts = [f"{k}={v}" for k, v in fields.items()]
    print(f"[{name}] " + " ".join(parts), flush=True)


def progress(msg: str) -> None:
    """A step inside a phase, on stderr: what a cut run got through."""
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-device mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes; accept any platform (CPU rehearsal)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax
    jax.config.update("jax_enable_x64", False)
    # f32 solves need f32 products: TPU matmuls default to one bf16 pass
    jax.config.update("jax_default_matmul_precision", "highest")
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    report("device", platform=dev.platform, kind=repr(dev.device_kind),
           count=len(devices), compile_cache=cache_dir)

    smoke = Smoke(jax, args.seed, args.rehearse)
    try:
        if args.chips == 4:
            smoke.mesh()
        else:
            smoke.stencil()
            smoke.gradient()
            smoke.direct()
            smoke.graph()
            smoke.serve()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


class Smoke:
    """The phases; ``rehearse`` shrinks every size and skips the checks that
    only hold where the kernels compile."""

    def __init__(self, jax, seed: int, rehearse: bool):
        import jax.numpy as jnp
        import numpy as np
        from repro import sla
        from repro.core import dispatch
        from repro.core.sparse import backward_error
        self.jax, self.jnp, self.np = jax, jnp, np
        self.sla, self.dispatch = sla, dispatch
        self.backward_error = backward_error
        self.seed = seed
        self.rehearse = rehearse
        self.stencil_A = None

    # -- helpers ------------------------------------------------------------
    def berr(self, val, row, col, n, x, b) -> float:
        """The library's normwise backward error, through the plain
        segment-sum product (independent of the kernel the solve used)."""
        return float(self.backward_error(val, row, col, n, x, b))

    def check_solve(self, name, res, berr) -> None:
        require(bool(self.np.all(self.np.asarray(res.converged))),
                f"{name}: solve did not converge ({res.reason})")
        require(math.isfinite(berr) and berr <= BERR_BOUND,
                f"{name}: backward error {berr:.3e} > {BERR_BOUND:.0e}")

    def last_solve(self) -> dict:
        """Fields of the newest eager solve record: host ms outside the
        wait for the device, the wait's ms and the programs lowered."""
        recs = [r for r in self.sla.solve_records() if not r["traced"] and
                r["name"] in ("sla.solve", "sla.serve_batch", "plan.solve")]
        if not recs:
            return {}
        r = recs[-1]
        wait = r["incl_s"].get("solve.wait", 0.0)
        return dict(host_ms=f"{(r['seconds'] - wait) * 1e3:.1f}",
                    wait_ms=f"{wait * 1e3:.1f}",
                    lowerings=r["counters"].get("jax_lowerings", 0))

    def kernel_plan(self, A, **kw):
        plan = self.sla.get_plan(A, **kw)
        return plan, plan.artifacts.get("kernel")

    def need_pallas(self, name, ok: bool, what: str) -> None:
        if not self.rehearse:
            require(ok, f"{name}: plan did not choose {what}")

    # -- phases ---------------------------------------------------------------
    def stencil(self):
        jax, jnp, sla = self.jax, self.jnp, self.sla
        from repro.data.poisson import poisson2d_vc
        ng = 128 if self.rehearse else 4096
        key = jax.random.PRNGKey(self.seed)
        kappa = jnp.exp(0.3 * jax.random.normal(key, (ng, ng), jnp.float32))
        A = poisson2d_vc(kappa, use_stencil_kernel=True)
        n = ng * ng
        b = jnp.ones((n,), jnp.float32)
        sla.reset_plan_stats()
        kw = dict(precond="mg", tol=1e-6, maxiter=500)
        res = sla.solve_with_info(A, b, **kw)
        plan, kp = self.kernel_plan(A, **kw)
        fused = self.dispatch._fuse_enabled(kp)
        be = self.berr(A.val, A.row, A.col, n, res.x, b)
        report("stencil", dof=n, nnz=A.nnz, backend=plan.cfg.backend,
               kernel=kp.choice, interpret=kp.interpret, fused_step=fused,
               iters=int(res.iterations), converged=bool(res.converged),
               berr=f"{be:.3e}", **self.last_solve())
        self.check_solve("stencil", res, be)
        self.need_pallas("stencil", kp.choice == "stencil"
                         and kp.interpret is False, "the compiled stencil kernel")
        self.need_pallas("stencil", fused, "the fused CG steps")
        self.stencil_A, self.stencil_kw, self.stencil_b = A, kw, b

    def gradient(self):
        jax, jnp, np, sla = self.jax, self.jnp, self.np, self.sla
        from repro.data.poisson import poisson2d_vc, vc_coefficients
        A, kw, b = self.stencil_A, self.stencil_kw, self.stencil_b
        ng = int(round(math.sqrt(A.shape[0])))
        key = jax.random.PRNGKey(self.seed)
        kappa = jnp.exp(0.3 * jax.random.normal(key, (ng, ng), jnp.float32))

        def loss(k):
            return jnp.mean(A.with_values(vc_coefficients(k)).solve(b, **kw))

        g = jax.grad(loss)(kappa)
        finite = bool(jnp.all(jnp.isfinite(g)))
        analyze = sla.PLAN_STATS["analyze"]

        # dense float32 autodiff reference at ng = 64, same loss
        ns = 32 if self.rehearse else 64
        ks = jnp.exp(0.3 * jax.random.normal(jax.random.PRNGKey(self.seed + 1),
                                             (ns, ns), jnp.float32))
        As = poisson2d_vc(ks, use_stencil_kernel=True)
        bs = jnp.ones((ns * ns,), jnp.float32)
        kws = dict(precond="mg", tol=1e-7, maxiter=500)

        def loss_sparse(k):
            return jnp.mean(As.with_values(vc_coefficients(k)).solve(bs, **kws))

        def loss_dense(k):
            Ad = jnp.zeros((ns * ns, ns * ns), jnp.float32).at[
                As.row, As.col].add(vc_coefficients(k))
            return jnp.mean(jnp.linalg.solve(Ad, bs))

        gs = jax.grad(loss_sparse)(ks)
        gd = jax.grad(loss_dense)(ks)
        rel = float(jnp.linalg.norm(gs - gd) / jnp.linalg.norm(gd))
        report("gradient", dof=ng * ng, grad_finite=finite,
               grad_norm=f"{float(jnp.linalg.norm(g)):.4e}",
               analyze=analyze, dense_check_ng=ns,
               dense_rel_err=f"{rel:.3e}", **self.last_solve())
        require(finite, "gradient: non-finite entries")
        require(analyze == 1, f"gradient: analyze ran {analyze} times, not 1")
        require(rel <= 1e-3, f"gradient: rel err vs dense {rel:.3e} > 1e-3")

    def direct(self):
        jnp, np, sla = self.jnp, self.np, self.sla
        from repro.core import direct as _direct
        from repro.data.poisson import poisson2d
        ng = 40 if self.rehearse else 316
        A = poisson2d(ng, dtype=np.float32)
        n = ng * ng
        b = jnp.asarray(np.random.default_rng(self.seed).uniform(
            0.5, 1.5, n).astype(np.float32))
        sla.reset_plan_stats()
        res = sla.solve_with_info(A, b, backend="direct")
        plan = sla.get_plan(A, backend="direct")
        snode = plan.artifacts["direct"].snode is not None
        pallas = _direct._sn_use_pallas()
        be = self.berr(A.val, A.row, A.col, n, res.x, b)
        sign, logdet = A.slogdet()
        # closed form: eigenvalues 4 - 2cos(j pi h) - 2cos(k pi h), h = 1/(ng+1)
        c = 2.0 * np.cos(np.arange(1, ng + 1) * np.pi / (ng + 1))
        exact = float(np.sum(np.log(4.0 - c[:, None] - c[None, :])))
        ld_rel = abs(float(logdet) - exact) / abs(exact)
        factorize = sla.PLAN_STATS["factorize"]
        report("direct", dof=n, nnz=A.nnz, backend=plan.cfg.backend,
               method=plan.cfg.method, supernodal=snode, panel_kernels=pallas,
               iters=int(res.iterations), converged=bool(res.converged),
               berr=f"{be:.3e}", slogdet_sign=float(sign),
               slogdet_rel_err=f"{ld_rel:.3e}", factorize=factorize,
               **self.last_solve())
        self.check_solve("direct", res, be)
        require(float(sign) == 1.0 and ld_rel <= 1e-4,
                f"direct: slogdet ({float(sign)}, rel err {ld_rel:.3e})")
        require(factorize == 1, f"direct: {factorize} factorizations, not 1")
        require(snode, "direct: plan has no supernodal schedule")
        self.need_pallas("direct", pallas, "the supernodal panel kernels")

    def graph(self):
        jnp, np, sla = self.jnp, self.np, self.sla
        from repro.data.graphs import graph_laplacian
        n = 4096 if self.rehearse else 1 << 18
        A = graph_laplacian(n, seed=self.seed, dtype=np.float32)
        b = jnp.asarray(np.random.default_rng(self.seed).normal(
            size=n).astype(np.float32))
        kw = dict(backend="jnp", method="cg", precond="amg", tol=1e-6,
                  maxiter=1000)
        res = sla.solve_with_info(A, b, **kw)
        plan, kp = self.kernel_plan(A, **kw)
        fused = self.dispatch._fuse_enabled(kp)
        be = self.berr(A.val, A.row, A.col, n, res.x, b)
        report("graph", dof=n, nnz=A.nnz, backend=plan.cfg.backend,
               kernel=kp.choice, kernel_reason=repr(kp.reason),
               interpret=kp.interpret, fused_step=fused,
               iters=int(res.iterations), converged=bool(res.converged),
               berr=f"{be:.3e}",
               **self.last_solve())
        self.check_solve("graph", res, be)
        self.need_pallas("graph", fused, "the fused CG steps")

    def serve(self):
        jax, jnp, np, sla = self.jax, self.jnp, self.np, self.sla
        from repro.data.poisson import poisson2d
        from repro.launch.solve_serve import SolveRequest
        # block-ELL is adopted at fill >= 1/64: 2-D Poisson up to 64^2
        grids = (16, 24) if self.rehearse else (48, 64)
        n_req, max_batch = 48, 16
        rng = np.random.default_rng(self.seed)
        bases = [poisson2d(g, dtype=np.float32) for g in grids]
        opts = dict(backend="jnp", method="cg", precond="jacobi", tol=1e-6)
        reqs = []
        for i in range(n_req):
            A0 = bases[i % len(bases)]
            Ai = A0.with_values(A0.val * np.float32(rng.uniform(0.7, 1.4)))
            bi = jnp.asarray(rng.normal(size=A0.shape[0]).astype(np.float32))
            reqs.append(SolveRequest(Ai, bi, dict(opts)))
        sla.reset_plan_stats()
        server = sla.SolveServer(max_batch=max_batch)
        out = server.submit_batch(reqs)
        worst_par = worst_be = 0.0
        seq = {}
        for req, r in zip(reqs, out):
            plan = self.dispatch.get_plan(req.A, **opts)
            if id(plan) not in seq:
                seq[id(plan)] = jax.jit(
                    lambda v, bb, plan=plan: plan.solve(plan.matrix(v), bb))
            x_ref, info = seq[id(plan)](req.A.val, req.b)
            require(bool(r.converged) and bool(info.converged),
                    "serve: a request did not converge")
            worst_par = max(worst_par, float(
                jnp.max(jnp.abs(r.x - x_ref)) / jnp.max(jnp.abs(x_ref))))
            worst_be = max(worst_be, self.berr(
                req.A.val, req.A.row, req.A.col, req.A.shape[0], r.x, req.b))
        plans = {id(self.dispatch.get_plan(A, **opts)): A for A in bases}
        kps = [self.kernel_plan(A, **opts)[1] for A in plans.values()]
        kinds = sorted({kp.choice for kp in kps})
        fused = all(self.dispatch._fuse_enabled(kp) for kp in kps)
        report("serve", requests=n_req, patterns=len(grids),
               dof="/".join(str(g * g) for g in grids),
               dispatches=server.stats["dispatches"],
               analyze=sla.PLAN_STATS["analyze"], kernel=",".join(kinds),
               interpret=any(kp.interpret for kp in kps), fused_step=fused,
               parity_rel=f"{worst_par:.3e}", berr=f"{worst_be:.3e}",
               **self.last_solve())
        require(worst_par <= 1e-4, f"serve: batched vs sequential {worst_par:.3e}")
        require(worst_be <= BERR_BOUND, f"serve: backward error {worst_be:.3e}")
        self.need_pallas("serve", kinds == ["bell"] and not any(
            kp.interpret for kp in kps), "the compiled block-ELL SpMV")
        self.need_pallas("serve", fused, "the fused CG steps")

    def mesh(self):
        jax, jnp, np = self.jax, self.jnp, self.np
        from repro.core.distributed import DSparseTensor
        from repro.data.poisson import poisson2d
        ng = 64 if self.rehearse else 2048
        A = poisson2d(ng, dtype=np.float32)
        n = ng * ng
        val, row, col = np.asarray(A.val), np.asarray(A.row), np.asarray(A.col)
        # manufactured solution: b = A x_true with a rough x_true.  With
        # b = 1 the solution is smooth (||x|| ~ 0.07 ng^2) and two f32 CG
        # runs that differ only in reduction order agree to no better than
        # ~cond·eps (1e-3 at 1024^2 on the CPU), whatever the tolerance.
        rng = np.random.default_rng(self.seed)
        x_true = rng.uniform(-1.0, 1.0, n)
        b = np.bincount(row, weights=val * x_true[col], minlength=n
                        ).astype(np.float32)
        b_d = jnp.asarray(b)
        w = jnp.asarray(rng.uniform(-1.0, 1.0, n).astype(np.float32))
        # 332 iterations on the CPU rehearsal; a stalled solve fails fast
        kw = dict(precond="jacobi", tol=1e-6, maxiter=2000)
        out = {}
        for p in (4, 1):
            mesh = jax.make_mesh((p,), ("data",), devices=jax.devices()[:p])
            D = DSparseTensor.from_global(val, row, col, (n, n), mesh,
                                          symmetric=True)
            bs = D.stack_vector(b)
            devs = len(D.lval.sharding.device_set)
            require(devs == p and len(bs.sharding.device_set) == p,
                    f"mesh: values on {devs} devices, not {p}")
            width = int(D.plan(**kw).artifacts["ell"].src.shape[1])
            x, info = D.solve_with_info(bs, **kw)
            xg = D.gather_global(x)
            progress(f"mesh: {p}-device solve done, {int(info.iters)} "
                     f"iterations")
            require(bool(info.converged), f"mesh: {p}-device solve did not "
                    f"converge in {int(info.iters)} iterations")
            be = self.berr(A.val, A.row, A.col, n, jnp.asarray(xg), b_d)
            out[p] = (xg, be, int(info.iters), devs, width)
            if p == 4:
                ws = D.stack_vector(w)
                g4 = np.asarray(jax.grad(lambda lv: jnp.vdot(
                    ws, D.with_values(lv).solve(bs, **kw)))(D.lval))
                progress("mesh: gradient done")
        x4, be4, it4, d4, width4 = out[4]
        x1, be1, it1, _, width1 = out[1]
        rel = float(np.max(np.abs(x4 - x1)) / np.max(np.abs(x1)))
        err = float(np.max(np.abs(x4 - x_true)))
        gfin = bool(np.all(np.isfinite(g4)))
        report("mesh", dof=n, nnz=int(val.size), devices=d4,
               ell_width=f"{width4}/{width1}", iters_4=it4, iters_1=it1,
               berr_4=f"{be4:.3e}", berr_1=f"{be1:.3e}", x_rel_4v1=f"{rel:.3e}",
               x_err_vs_true=f"{err:.3e}", grad_finite=gfin,
               **self.last_solve())
        require(be4 <= BERR_BOUND and be1 <= BERR_BOUND,
                f"mesh: backward errors {be4:.3e} / {be1:.3e}")
        require(rel <= 1e-4, f"mesh: 4-device vs 1-device {rel:.3e} > 1e-4")
        require(gfin, "mesh: non-finite values gradient")


if __name__ == "__main__":
    sys.exit(main())
