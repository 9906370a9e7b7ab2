"""Pure-jnp oracles for the Pallas kernels.

Each kernel in this package has its reference implementation here; tests
sweep shapes/dtypes and ``assert_allclose`` kernel-vs-ref (interpret mode on
CPU, compiled on TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def stencil5_ref(val5: jax.Array, x: jax.Array) -> jax.Array:
    """Variable-coefficient 5-point stencil apply.

    ``val5``: (5, nx, ny) signed coefficient planes ordered (C, N, S, W, E);
    ``x``: (nx, ny).  Out-of-domain coefficients are zero by construction, so
    clamped shifts never contribute.

        y[i,j] = C·x[i,j] + N·x[i-1,j] + S·x[i+1,j] + W·x[i,j-1] + E·x[i,j+1]
    """
    xn = jnp.pad(x, ((1, 0), (0, 0)))[:-1, :]   # x[i-1, j]
    xs = jnp.pad(x, ((0, 1), (0, 0)))[1:, :]    # x[i+1, j]
    xw = jnp.pad(x, ((0, 0), (1, 0)))[:, :-1]   # x[i, j-1]
    xe = jnp.pad(x, ((0, 0), (0, 1)))[:, 1:]    # x[i, j+1]
    return (val5[0] * x + val5[1] * xn + val5[2] * xs
            + val5[3] * xw + val5[4] * xe)


def fused_cg_update_ref(x, r, p, s, dinv, alpha):
    xn = x + alpha * p
    rn = r - alpha * s
    zn = dinv * rn
    return xn, rn, zn, jnp.sum(rn * zn), jnp.sum(rn * rn)


def fused_cg_direction_ref(z, w, p, s, beta):
    return z + beta * p, w + beta * s, jnp.sum(w * z)


def fused_cg_halfstep_ref(x, r, p, s, alpha):
    xn = x + alpha * p
    rn = r - alpha * s
    return xn, rn, jnp.sum(rn * rn)


def fused_cheb_step_ref(x, dk, rk, c1, c2):
    dn = c1 * dk + c2 * rk
    return x + dn, dn


def fused_dots2_ref(u, v):
    return jnp.sum(u * v), jnp.sum(u * u)


def fused_bicg_p_ref(r, p, v, dinv, beta, omega, restart):
    pn = jnp.where(restart != 0, r, r + beta * (p - omega * v))
    return pn, dinv * pn


def fused_bicg_s_ref(r, v, dinv, alpha):
    sn = r - alpha * v
    return sn, dinv * sn


def fused_bicg_tail_ref(x, s, t, phat, shat, rhat, alpha, omega):
    xn = x + alpha * phat + omega * shat
    rn = s - omega * t
    return xn, rn, jnp.sum(rhat * rn), jnp.sum(rn * rn)


def bell_matvec_ref(bell_vals: jax.Array, block_cols: jax.Array,
                    x_pad: jax.Array, n: int) -> jax.Array:
    """Block-ELL SpMV oracle.

    ``bell_vals``: (n_rb, k, bm, bn) dense blocks; ``block_cols``: (n_rb, k)
    column-block ids; ``x_pad``: (m_pad,).  Returns y (n,).
    """
    n_rb, k, bm, bn = bell_vals.shape
    xb = x_pad.reshape(-1, bn)                       # (n_cb, bn)
    gathered = xb[block_cols]                        # (n_rb, k, bn)
    y = jnp.einsum("rkab,rkb->ra", bell_vals, gathered)
    return y.reshape(n_rb * bm)[:n]


# ---------------------------------------------------------------------------
# Supernodal panel kernels (kernels/supernode.py)
#
# The single-lane *_body functions below are the single source of truth for
# the panel math: the oracles here vmap them over the bucket's lane axis, and
# the Pallas kernels call the very same bodies on their per-lane VMEM blocks,
# so kernel-vs-ref parity is structural rather than re-derived.
#
# Lane layout (one supernode of bucket shape (wb, rb), true size (w, r)):
#   P (wb+rb, wb): rows 0..wb-1 the dense diagonal block D (strict lower = L,
#       diagonal = pivots, strict upper = U), rows wb.. the sub-diagonal
#       L panel over the supernode's row structure R_s;
#   Q (wb, rb):    the U panel — rows of U over R_s.
# Entries gathered from pad slots hold scratch garbage, so every body first
# masks rows/columns beyond (w, r) to zero and plants a unit diagonal on pad
# pivots, making pad lanes exact no-ops (unlike the scalar path, where pads
# are element-wise no-ops by construction).
# ---------------------------------------------------------------------------


def _loop(n: int, body, init):
    """``fori_loop(0, n, body, init)`` as a scan with an int32 counter.

    ``fori_loop`` with static bounds counts in i64 under x64, which Mosaic
    cannot lower, and with traced bounds it is not reverse-differentiable."""
    def step(carry, _):
        t, x = carry
        return (t + 1, body(t, x)), None
    return jax.lax.scan(step, (np.int32(0), init), None, length=n)[0][1]


def _mask(keep, X):
    """X where ``keep``, else 0 — with a zero of X's dtype: a Python float
    would enter the kernel as an f64 constant under x64."""
    return jnp.where(keep, X, jnp.zeros_like(X))


def _iota(shape, dim):
    """2-D index grid (Mosaic lowers no 1-D iota)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _sum11(X):
    """Sum of a 2-D array kept as a (1, 1) array — one lane reduction then
    one sublane reduction, both of which Mosaic lowers."""
    return jnp.sum(jnp.sum(X, axis=1, keepdims=True), axis=0, keepdims=True)


def _col(X, cj, t):
    """Column ``t`` of X as an (m, 1) array: a masked lane reduction, exact
    (one term survives), in place of a dynamic slice."""
    return jnp.sum(_mask(cj == t, X), axis=1, keepdims=True)


def _row(X, ri, t):
    """Row ``t`` of X as a (1, n) array (masked sublane reduction)."""
    return jnp.sum(_mask(ri == t, X), axis=0, keepdims=True)


def _pick(v, idx, t):
    """Entry ``t`` of a (1, n) row or (m, 1) column as a (1, 1) array."""
    return _sum11(_mask(idx == t, v))


def _flag(bk, cr, t):
    """Pair-start flag of column ``t`` as a (1, 1) bool (False off range)."""
    return _sum11(((cr == t) & bk).astype(jnp.float32)) > 0


def sn_pair_det(a, b, c, e):
    """Clamped determinant of a static Bunch–Kaufman 2x2 pivot E=[[a,b],[c,e]].

    The floor is *locally* scaled (eps·max|E|² + tiny), computed identically
    at factor and solve time from the same raw stored entries, so both sides
    see the same (possibly clamped) determinant without persisting it."""
    det = a * e - b * c
    eps = jnp.finfo(det.dtype).eps
    scale = jnp.maximum(jnp.maximum(jnp.abs(a), jnp.abs(e)),
                        jnp.maximum(jnp.abs(b), jnp.abs(c)))
    floor = eps * scale * scale + jnp.finfo(det.dtype).tiny
    bad = jnp.abs(det) < floor
    detc = jnp.where(bad, jnp.where(det < 0, -floor, floor), det)
    return detc, bad


def sn_panel_mask(P, Q, w, r):
    """Zero pad rows/cols of a gathered (P, Q) lane; unit pad diagonal."""
    m, wb = P.shape
    rb = Q.shape[1]
    ri = _iota((m, wb), 0)
    cj = _iota((m, wb), 1)
    tw = cj < w
    row_ok = ((ri < wb) & (ri < w)) | ((ri >= wb) & (ri - wb < r))
    P = _mask(row_ok & tw, P)
    P = P + ((ri == cj) & (cj >= w)).astype(P.dtype)
    Q = _mask((_iota((wb, rb), 0) < w) & (_iota((wb, rb), 1) < r), Q)
    return P, Q


def sn_block_mask(D, w):
    """Zero pad rows/cols of a gathered diagonal block; unit pad diagonal."""
    wb = D.shape[0]
    ri = _iota((wb, wb), 0)
    cj = _iota((wb, wb), 1)
    D = _mask((ri < w) & (cj < w), D)
    return D + ((ri == cj) & (ri >= w)).astype(D.dtype)


def sn_panel_factor_body(P, Q, w, r, tau, bkm, *, pairs: bool, guard: bool):
    """Dense right-looking factorization of one supernode panel.

    Matches the scalar packed-scan semantics entry for entry: L columns are
    divided by their pivot, U rows (including the diagonal block's strict
    upper and the Q panel) stay raw, and clamped pivots persist into storage.
    With ``pairs``, columns flagged in ``bkm`` start a static 2x2 pivot: the
    pair is eliminated jointly through E⁻¹ and its four defining entries
    (a, e on the diagonal, b above, c below) are stored raw — they are private
    to the diagonal block, consumed only by block solves and slogdet.

    Written in 2-D masked form (no dynamic slice or 1-D vector) so that the
    Pallas kernel compiles it as it stands; ``bkm`` may be (wb,) or (1, wb).
    Returns (P, Q, nbad) where nbad, a (1, 1) array, counts clamped 1x1
    pivots / 2x2 dets.
    """
    m, wb = P.shape
    rb = Q.shape[1]
    P, Q = sn_panel_mask(P, Q, w, r)
    bk = jnp.reshape(bkm, (1, wb)) != 0
    ri, cj = _iota((m, wb), 0), _iota((m, wb), 1)
    rc = _iota((m, 1), 0)
    cr = _iota((1, wb), 1)
    qi = _iota((wb, rb), 0)
    one = jnp.ones((1, 1), P.dtype)
    zero = jnp.zeros((1, 1), P.dtype)
    false = zero > 0

    def step(t, carry):
        P, Q, nbad = carry
        start = _flag(bk, cr, t) if pairs else false
        second = _flag(bk, cr, t - 1) if pairs else false
        # -- 1x1 elimination (bypassed with a unit divisor on pair members,
        #    so the discarded branch stays finite under AD) --
        Pc = _col(P, cj, t)
        Pr = _row(P, ri, t)
        d = _pick(Pr, cr, t)
        if guard:
            bad1 = jnp.abs(d) < tau
            dc = jnp.where(bad1, jnp.where(d < 0, -tau, tau), d)
        else:
            bad1 = false
            dc = d
        deff = jnp.where(start | second, one, dc) if pairs else dc
        colL = _mask(rc > t, Pc / deff)
        urow = _mask(cr > t, Pr)
        P1 = P - colL * urow
        P1 = jnp.where(cj == t, jnp.where(ri > t, colL, P), P1)
        P1 = jnp.where((ri == t) & (cj == t), dc, P1)
        Qr = _row(Q, qi, t)
        Q1 = Q - colL[:wb] * Qr
        if not pairs:
            return P1, Q1, nbad + bad1.astype(P.dtype)
        # -- 2x2 elimination for the pair (t, t+1); t1 is clamped so the
        #    branch stays in-bounds when discarded at t = wb-1 --
        t1 = jnp.minimum(t + 1, wb - 1)
        Pc1, Pr1 = _col(P, cj, t1), _row(P, ri, t1)
        # E's entries from the rows and column already extracted
        a, b = d, _pick(Pr, cr, t1)
        c, e = _pick(Pc, rc, t1), _pick(Pr1, cr, t1)
        detc, bad2 = sn_pair_det(a, b, c, e)
        below2 = rc > t1
        u = _mask(below2, Pc)
        v = _mask(below2, Pc1)
        lu = (u * e - v * c) / detc
        lv = (v * a - u * b) / detc
        urow1 = _mask(cr > t1, Pr)
        urow2 = _mask(cr > t1, Pr1)
        P2 = P - lu * urow1 - lv * urow2
        P2 = jnp.where(cj == t, jnp.where(ri > t1, lu, P), P2)
        P2 = jnp.where(cj == t1, jnp.where(ri > t1, lv, P), P2)
        Q2 = Q - lu[:wb] * Qr - lv[:wb] * _row(Q, qi, t1)
        Pn = jnp.where(start, P2, jnp.where(second, P, P1))
        Qn = jnp.where(start, Q2, jnp.where(second, Q, Q1))
        nbad = nbad + jnp.where(
            start, bad2.astype(P.dtype),
            jnp.where(second, zero, bad1.astype(P.dtype)))
        return Pn, Qn, nbad

    return _loop(wb, step, (P, Q, zero))


def sn_trsv_body(D, y, w, bkm, *, mode: str, pairs: bool):
    """Dense triangular solve on one supernode diagonal block.

    Modes (all operate on the packed block: strict lower = unit-L, diagonal =
    pivots, strict upper = U):

    - ``"l"``:  unit-lower forward solve (L y = b);
    - ``"lt"``: unit-upper backward solve (Lᵀ x = y);
    - ``"u"``:  upper backward solve with pivot divides (U x = y);
    - ``"ut"``: lower forward solve with pivot divides (Uᵀ y = b).

    With ``pairs``, the stored subdiagonal c at a pair start is NOT an L entry
    (the pair's L block is the identity): the unit-triangular modes mask it,
    and the pivot modes solve the 2x2 system E / Eᵀ jointly.

    The right-hand side is held 2-D in the orientation the mode consumes: a
    (wb, 1) column where a column of D is subtracted ("l", "u"), a (1, wb)
    row where a row is ("lt", "ut").  ``y`` may come in any shape of wb
    elements; the result has ``y``'s shape.
    """
    wb = D.shape[0]
    D = sn_block_mask(D, w)
    ri, cj = _iota((wb, wb), 0), _iota((wb, wb), 1)
    colwise = mode in ("l", "u")
    shape = (wb, 1) if colwise else (1, wb)
    idx = _iota(shape, 0 if colwise else 1)
    x = _mask(idx < w, jnp.reshape(y, shape))
    bk = jnp.reshape(bkm, (1, wb)) != 0
    cr = _iota((1, wb), 1)
    one = jnp.ones((1, 1), D.dtype)
    false = one < 0

    def line(t):
        """Column t of D for column modes, row t for row modes."""
        return _col(D, cj, t) if colwise else _row(D, ri, t)

    def at(v, t):
        return _pick(v, idx, t)

    if pairs and mode in ("l", "lt"):
        # pair-start subdiagonal holds raw c — identity in the unit factor
        D = _mask(~((ri == cj + 1) & bk), D)
    if mode == "l":
        x = _loop(wb, lambda t, x: x - _mask(idx > t, line(t)) * at(x, t),
                  x)
        return jnp.reshape(x, y.shape)
    if mode == "lt":
        def lt_step(i, x):
            t = wb - 1 - i
            return x - _mask(idx < t, line(t)) * at(x, t)
        return jnp.reshape(_loop(wb, lt_step, x), y.shape)

    def step(i, x):
        t = (wb - 1 - i) if mode == "u" else i
        start = _flag(bk, cr, t) if pairs else false
        second = _flag(bk, cr, t - 1) if pairs else false
        ln_t = line(t)
        dtt = at(ln_t, t)
        dd = jnp.where(start | second, one, dtt) if pairs else dtt
        xt1 = at(x, t) / dd
        # "u": U column above t;  "ut": U row past t
        prop = _mask((idx < t) if mode == "u" else (idx > t), ln_t)
        x1 = jnp.where(idx == t, xt1, x - prop * xt1)
        if not pairs:
            return x1
        t1 = jnp.minimum(t + 1, wb - 1)
        ln_t1 = line(t1)
        # D[t, t1] and D[t1, t] sit in line t1 / line t by orientation
        a, e = dtt, at(ln_t1, t1)
        b, c = (at(ln_t1, t), at(ln_t, t1)) if colwise else \
            (at(ln_t, t1), at(ln_t1, t))
        detc, _ = sn_pair_det(a, b, c, e)
        rt, rt1 = at(x, t), at(x, t1)
        if mode == "u":           # E [xt, xtt] = [rt, rt1]
            xt = (e * rt - b * rt1) / detc
            xtt = (a * rt1 - c * rt) / detc
            keep = idx < t
        else:                     # Eᵀ [xt, xtt] = [rt, rt1]
            xt = (e * rt - c * rt1) / detc
            xtt = (a * rt1 - b * rt) / detc
            keep = idx > t1
        p1 = _mask(keep, ln_t)
        p2 = _mask(keep, ln_t1)
        x2 = jnp.where(idx == t1, xtt,
                       jnp.where(idx == t, xt, x - p1 * xt - p2 * xtt))
        return jnp.where(start, x2, jnp.where(second, x, x1))

    return jnp.reshape(_loop(wb, step, x), y.shape)


def sn_panel_factor_ref(P, Q, wvec, rvec, tau, bkm, *, pairs=False,
                        guard=True):
    """Batched oracle: vmap of :func:`sn_panel_factor_body` over lanes.

    Returns (P, Q, nbad_total)."""
    fn = jax.vmap(
        lambda p, q, w, r, m: sn_panel_factor_body(
            p, q, w, r, tau, m, pairs=pairs, guard=guard))
    P, Q, nbad = fn(P, Q, wvec, rvec, bkm)
    return P, Q, jnp.sum(nbad)


def sn_schur_ref(P, Q):
    """Batched Schur-complement GEMM: S[l] = Lpanel[l] @ Upanel[l].

    ``P`` (k, wb+rb, wb) masked/divided panels, ``Q`` (k, wb, rb) raw U rows;
    returns (k, rb, rb) updates to scatter-subtract into the trailing slots.
    """
    wb = Q.shape[1]
    return jnp.einsum("kiw,kwr->kir", P[:, wb:, :], Q)


def sn_trsv_ref(D, y, wvec, bkm, *, mode, pairs=False):
    """Batched oracle: vmap of :func:`sn_trsv_body` over lanes."""
    return jax.vmap(
        lambda d, yy, w, m: sn_trsv_body(d, yy, w, m, mode=mode,
                                         pairs=pairs))(D, y, wvec, bkm)
