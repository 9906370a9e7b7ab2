"""Plain reference gradient for the variable-coefficient 2-D Poisson
configurations' ``grad_step`` traffic.

Imports nothing of the program; the solves are those of
``poisson_vc_ref`` (loaded by path), with its float64-refined accuracy.
For L(κ) = wᵀx / n with A(κ) x = b, A(κ) symmetric:

    x = A⁻¹ b,    λ = A⁻¹ (w / n),    dL/dκ_p = −λᵀ (∂A/∂κ_p) x.

With A = Σ_faces k_f (e_a − e_b)(e_a − e_b)ᵀ + Σ_p nb_p κ_p e_p e_pᵀ
(``poisson_vc_ref``'s definition; nb_p boundary faces of cell p) and the
harmonic face mean k_f = 2 κ_a κ_b / (κ_a + κ_b):

    dL/dκ_p = −[ Σ_{f ∋ p} (∂k_f/∂κ_p) (λ_a − λ_b)(x_a − x_b) + nb_p λ_p x_p ],
    ∂k_f/∂κ_a = 2 κ_b² / (κ_a + κ_b)².

In bfloat16 (the control) both solves run in bfloat16 and the gradient is
rounded through bfloat16.
"""
from __future__ import annotations

import os

import ml_dtypes
import numpy as np

from common import load_module

_vc = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "poisson_vc_ref.py"),
                  "bench_poisson_vc_ref_grad")


def _faces_term(k, x, lam, axis):
    """Each face's contributions to its two cells along ``axis``."""
    a = [slice(None)] * 2
    b = [slice(None)] * 2
    a[axis], b[axis] = slice(None, -1), slice(1, None)
    a, b = tuple(a), tuple(b)
    ka, kb = k[a], k[b]
    t = (lam[a] - lam[b]) * (x[a] - x[b])
    s = (ka + kb) ** 2
    g = np.zeros_like(k)
    g[a] += 2.0 * kb * kb / s * t
    g[b] += 2.0 * ka * ka / s * t
    return g


def gradient(kappa: np.ndarray, b: np.ndarray, w: np.ndarray,
             dtype: str = "float32") -> np.ndarray:
    """dL/dκ (ng, ng) in float64 for L(κ) = wᵀ A(κ)⁻¹ b / n."""
    k = np.asarray(kappa, np.float64)
    ng = k.shape[0]
    n = ng * ng
    data = {"kappa": k}
    x = _vc.solve(data, np.asarray(b, np.float64), dtype).reshape(ng, ng)
    lam = _vc.solve(data, np.asarray(w, np.float64) / n, dtype).reshape(ng, ng)
    nb = np.zeros_like(k)
    nb[0] += 1
    nb[-1] += 1
    nb[:, 0] += 1
    nb[:, -1] += 1
    g = -(_faces_term(k, x, lam, 0) + _faces_term(k, x, lam, 1)
          + nb * lam * x)
    if dtype == "bfloat16":
        g = g.astype(ml_dtypes.bfloat16).astype(np.float64)
    return g
