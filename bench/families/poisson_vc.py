"""Program side of the variable-coefficient 2-D Poisson configurations.

The benchmark draws the conductivity field from the seed; the operator is
assembled by the program's own public constructor
(``repro.data.poisson.poisson2d_vc``), with the stencil-kernel layout when
the configuration asks for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from common import prng_key


@functools.partial(jax.jit, static_argnums=(1,))
def _lognormal(key, ng: int, sigma):
    return jnp.exp(sigma * jax.random.normal(key, (ng, ng), jnp.float32))


class System:
    def __init__(self, cfg: dict, seed: int, rehearse: bool):
        from repro.data.poisson import poisson2d_vc
        self.ng = int(cfg["rehearse"]["ng"] if rehearse else cfg["ng"])
        self.n = self.ng * self.ng
        self.stencil = bool(cfg.get("stencil_kernel", False))
        self.kappa = _lognormal(prng_key(seed, 1), self.ng,
                                np.float32(cfg["kappa_log_sigma"]))
        self.A = poisson2d_vc(self.kappa, use_stencil_kernel=self.stencil)

    def ref_data(self) -> dict:
        """What the plain reference is given: the benchmark's own data."""
        return {"kappa": np.asarray(self.kappa, np.float64)}

    def release(self) -> None:
        self.A = None
        self.kappa = None
