"""Traffic kind ``solve``: one operator, a fresh right-hand side per solve.

The window drives ``repro.sla.solve_with_info`` on a plan analyzed in
set-up.  Right-hand sides are drawn on the device from the seed and the
solve's index, so any one of them can be drawn again after the window.
A sample of the solves, chosen from the seed by reservoir sampling, is
kept and compared with the plain reference once the window has closed.

Traffic parameters: ``solver`` (keyword options of the solve), ``rhs``
(``{"dist": "uniform", "low", "high"}`` or ``{"dist": "normal"}``),
``check_samples`` (how many solves the reference checks).
"""
from __future__ import annotations

import functools
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from common import log, prng_key

END_TO_END = "solve_ms"


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, n: int, dist: str, low: float, high: float):
    if dist == "uniform":
        return jax.random.uniform(key, (n,), jnp.float32, low, high)
    if dist == "normal":
        return jax.random.normal(key, (n,), jnp.float32)
    raise ValueError(f"unknown rhs dist {dist!r}")


class Loop:
    def __init__(self, system, traffic: dict, seed: int, ref, control: bool):
        self.sys = system
        self.kw = dict(traffic["solver"])
        rhs = traffic["rhs"]
        self.dist = (rhs["dist"], float(rhs.get("low", 0.0)),
                     float(rhs.get("high", 1.0)))
        self.k = int(traffic["check_samples"])
        self.seed = seed
        self.ref = ref
        self.control = control
        self.rng = random.Random(seed)
        self.iters, self.failed = [], 0
        self.samples = []                   # (index, x) reservoir

    def rhs(self, i: int):
        return _draw(prng_key(self.seed, 1000 + i), self.sys.n, *self.dist)

    def _solve(self, b):
        if self.control:                    # the reference in bfloat16
            x = self.ref.solve(self.ref_data, np.asarray(b), "bfloat16")
            return jnp.asarray(x, jnp.float32), 0, True
        from repro import sla
        res = sla.solve_with_info(self.sys.A, b, **self.kw)
        return (res.x.block_until_ready(), int(res.iterations),
                bool(res.converged))

    def setup(self) -> dict:
        from repro import sla
        self.ref_data = self.sys.ref_data()
        with jax.profiler.TraceAnnotation("bench.analyze"):
            t0 = time.perf_counter()
            sla.get_plan(self.sys.A, **self.kw)
            analyze_s = time.perf_counter() - t0
        for i in (-2, -1):                  # compile, then one warm solve
            with jax.profiler.TraceAnnotation("bench.warmup"):
                x, it, ok = self._solve(self.rhs(i))
            log(f"[warmup] solve {i}: {it} iterations, converged={ok}")
        self.iters.clear()
        return {"analyze_s": analyze_s}

    def step(self, i: int) -> None:
        with jax.profiler.TraceAnnotation("bench.rhs"):
            b = self.rhs(i)
        with jax.profiler.TraceAnnotation("bench.solve"):
            x, it, ok = self._solve(b)
        self.iters.append(it)
        self.failed += not ok
        # reservoir sampling: a uniform sample of k of the solves so far
        if len(self.samples) < self.k:
            self.samples.append((i, x))
        else:
            j = self.rng.randrange(i + 1)
            if j < self.k:
                self.samples[j] = (i, x)

    def end_to_end(self, window_s: float, steps: int) -> dict:
        return {END_TO_END: window_s * 1e3 / steps}

    def counters(self) -> dict:
        return {"iterations": list(self.iters), "solves": len(self.iters)}

    def release(self) -> None:
        """Take the sample to the host and drop the program's state."""
        self.samples = [(i, np.asarray(x, np.float64)) for i, x in self.samples]
        self.sys.release()

    def check(self, limits: dict) -> dict:
        """Worst relative forward error max|x - x_ref| / max|x_ref| over the
        sample, each x_ref solved by the plain reference from the same
        right-hand side."""
        worst = 0.0
        for i, x in self.samples:
            b = np.asarray(self.rhs(i), np.float64)
            xr = self.ref.solve(self.ref_data, b)
            err = float(np.max(np.abs(x - xr)) / np.max(np.abs(xr)))
            log(f"[check] solve {i}: forward error {err:.6e}")
            worst = max(worst, err) if np.isfinite(err) else float("inf")
        return {"fwd_err": (worst, limits["fwd_err"]),
                "failed": (float(self.failed), 0.0)}
