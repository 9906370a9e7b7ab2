"""Traffic kind ``grad_step``: one gradient step of a coefficient
inversion per step (the paper's §4.4 inversion).

A step is ``jax.jit(jax.value_and_grad(L))`` at an iterate κ_i, compiled
once in set-up as a JAX user writes it, with

    L(κ) = wᵀ x / n,    A(κ) x = b,    A(κ) = A.with_values(vc_coefficients(κ))

through the library's differentiable ``SparseTensor.solve``: each step
refreshes the values and the preconditioner, and runs one forward and one
adjoint solve.  The family's tensor ``A`` (its pattern arrays), b and w
enter the compiled step as arguments: arrays a jitted function closes over
are written into its program as constants.  κ_i = κ · exp(σ N(0, 1)) is
drawn on the device from the seed and the step's index, an optimizer's
iterate; b (``rhs``) and w ~ N(0, 1) are drawn once per run.  The step
waits for L and the gradient.

A sample of the steps, chosen from the seed by reservoir sampling, keeps
its gradient; after the window the gradient reference named by the
traffic (``grad_ref``, a module under ``bench/families``) recomputes each
from κ_i, b and w.

Traffic parameters: ``solver`` (keyword options of the solve), ``rhs``
(``{"dist": "uniform", "low", "high"}``), ``kappa_step_sigma``,
``grad_ref``, ``check_samples``.
"""
from __future__ import annotations

import functools
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from common import bench_module, log, prng_key

END_TO_END = "solve_ms"


@functools.partial(jax.jit, static_argnums=(1,))
def _uniform(key, n: int, low, high):
    return jax.random.uniform(key, (n,), jnp.float32, low, high)


@functools.partial(jax.jit, static_argnums=(1,))
def _normal(key, n: int):
    return jax.random.normal(key, (n,), jnp.float32)


@jax.jit
def _finite(loss, g):
    return jnp.isfinite(loss) & jnp.all(jnp.isfinite(g))


@jax.jit
def _iterate(key, kappa, sigma):
    return kappa * jnp.exp(sigma * jax.random.normal(key, kappa.shape,
                                                     jnp.float32))


class Loop:
    def __init__(self, system, traffic: dict, seed: int, ref, control: bool):
        self.sys = system
        self.kw = dict(traffic["solver"])
        rhs = traffic["rhs"]
        if rhs["dist"] != "uniform":
            raise ValueError(
                f"grad_step draws a uniform rhs, not {rhs['dist']!r}")
        self.b = _uniform(prng_key(seed, 3), system.n,
                          np.float32(rhs["low"]), np.float32(rhs["high"]))
        self.w = _normal(prng_key(seed, 4), system.n)
        self.kappa0 = system.kappa
        self.sigma = np.float32(traffic["kappa_step_sigma"])
        self.k = int(traffic["check_samples"])
        self.seed = seed
        self.grad_ref = bench_module("families", traffic["grad_ref"])
        self.control = control
        self.rng = random.Random(seed)
        self.failed = self.steps = 0
        self.samples = []                   # (index, g) reservoir

    def kappa(self, i: int):
        return _iterate(prng_key(self.seed, 2000 + i), self.kappa0, self.sigma)

    def _host(self, i: int):
        """κ_i, b and w on the host in float64, for the reference."""
        return (np.asarray(self.kappa(i), np.float64),
                np.asarray(self.b, np.float64), np.asarray(self.w, np.float64))

    def _grad(self, i: int):
        if self.control:                    # the reference in bfloat16
            g = self.grad_ref.gradient(*self._host(i), dtype="bfloat16")
            return np.float32(0.0), jnp.asarray(g, jnp.float32)
        loss, g = self.step_fn(self.kappa(i), self.sys.A, self.b, self.w)
        return loss.block_until_ready(), g.block_until_ready()

    def setup(self) -> dict:
        from repro import sla
        from repro.data.poisson import vc_coefficients
        kw, n = self.kw, self.sys.n

        def loss(kappa, A, b, w):
            x = A.with_values(vc_coefficients(kappa)).solve(b, **kw)
            return jnp.vdot(w, x) / n

        self.step_fn = jax.jit(jax.value_and_grad(loss))
        with jax.profiler.TraceAnnotation("bench.analyze"):
            t0 = time.perf_counter()
            sla.get_plan(self.sys.A, **kw)
            analyze_s = time.perf_counter() - t0
        for i in (-2, -1):                  # compile, then one warm step
            with jax.profiler.TraceAnnotation("bench.warmup"):
                _finite(*self._grad(i))
        return {"analyze_s": analyze_s}

    def step(self, i: int) -> None:
        with jax.profiler.TraceAnnotation("bench.grad_step"):
            loss, g = self._grad(i)
        self.steps += 1
        self.failed += not bool(_finite(loss, g))
        # reservoir sampling: a uniform sample of k of the steps so far
        if len(self.samples) < self.k:
            self.samples.append((i, g))
        else:
            j = self.rng.randrange(i + 1)
            if j < self.k:
                self.samples[j] = (i, g)

    def end_to_end(self, window_s: float, steps: int) -> dict:
        return {END_TO_END: window_s * 1e3 / steps}

    def counters(self) -> dict:
        return {"steps": self.steps}

    def release(self) -> None:
        """Take the sample to the host and drop the program's state."""
        self.samples = [(i, np.asarray(g, np.float64))
                        for i, g in self.samples]
        self.step_fn = None
        self.sys.release()

    def check(self, limits: dict) -> dict:
        """Worst relative gradient error max|g − g_ref| / max|g_ref| over
        the sample, each g_ref from the reference at the same κ_i, b, w;
        ``failed`` counts steps whose loss or gradient was not finite."""
        worst = 0.0
        for i, g in self.samples:
            gr = self.grad_ref.gradient(*self._host(i)).ravel()
            err = float(np.max(np.abs(g.ravel() - gr)) / np.max(np.abs(gr)))
            log(f"[check] step {i}: gradient error {err:.6e}")
            worst = max(worst, err) if np.isfinite(err) else float("inf")
        return {"grad_err": (worst, limits["grad_err"]),
                "failed": (float(self.failed), 0.0)}
