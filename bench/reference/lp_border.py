"""The plain interior point of ``reference/lp.py``, with each Newton
system solved through the border of its rows instead of a dense normal
matrix, so it runs on relaxations of thousands of tasks.

The rows of :func:`bench.reference.lp.build` are task rows E, each
column in at most one of them, then a few border rows B (latency,
quanta, budget, each with its slack).  So the normal matrix
``[[E T E', E T B'], [B T E', B T B']]`` has a diagonal task block
``D_E``, and

    S dy_B = r_B - C' D_E^-1 r_E,   dy_E = D_E^-1 (r_E - C dy_B)

with ``C = E T B'`` and ``S = B T B' - C' D_E^-1 C``, of the border's
size.  That is Gaussian elimination of the task rows, in the same
precision as every other step; nothing else differs from
``reference/lp.py``'s ``solve_ipm``.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def _border_solver(e, bd, dtype):
    """``solve(theta, rhs)`` for ``(A diag(theta) A') dy = rhs`` with
    ``A = [e; bd]`` (sparse, in ``dtype``); raises LinAlgError where the
    system breaks down in that precision."""
    if (abs(e) > 0).sum(axis=0).max() > 1:
        raise ValueError("a column lies in two task rows: no diagonal block")
    e2 = e.multiply(e).tocsr()
    m_e = e.shape[0]

    def solve(theta, rhs):
        d_e = e2 @ theta
        if not (d_e > 0).all():
            raise np.linalg.LinAlgError("an empty task row")
        t = sp.diags(theta)
        c = (e @ t @ bd.T).toarray()                  # (tau, m_b)
        cd = c / d_e[:, None]
        s = (bd @ t @ bd.T).toarray() - c.T @ cd
        r_e, r_b = rhs[:m_e], rhs[m_e:]
        dy_b = np.linalg.solve(s, r_b - cd.T @ r_e).astype(dtype)
        dy_e = ((r_e - c @ dy_b) / d_e).astype(dtype)
        return np.concatenate([dy_e, dy_b])

    return solve


def solve_ipm(lp: dict, dtype=np.float64, max_iters: int = 200,
              tol: float = 1e-10):
    """(objective, x) by the Mehrotra predictor-corrector interior point
    of ``reference/lp.py``'s ``solve_ipm``, every step in ``dtype``, the
    Newton systems solved through the border; it stops at its last
    iterate if a system breaks down in that precision (no finite
    direction)."""
    lo = lp["lb"]
    keep = lp["ub"] > lo
    a_eq, a_ub = lp["a_eq"].tocsr(), lp["a_ub"].tocsr()
    b = np.concatenate([lp["b_eq"] - a_eq @ lo, lp["b_ub"] - a_ub @ lo])
    a_eq, a_ub = a_eq[:, keep], a_ub[:, keep]
    m_eq, m_ub = a_eq.shape[0], a_ub.shape[0]
    e = sp.hstack([a_eq, sp.csr_matrix((m_eq, m_ub))]).tocsr()
    bd = sp.hstack([a_ub, sp.eye(m_ub)]).tocsr()
    a = sp.vstack([e, bd]).tocsr()
    scale = abs(a).max(axis=1).toarray().ravel()
    a, b = sp.diags(1 / scale) @ a, b / scale
    c = np.concatenate([lp["c"][keep], np.zeros(m_ub)])
    u = np.concatenate([(lp["ub"] - lo)[keep], np.full(m_ub, np.inf)])
    a = a.astype(dtype).tocsr()
    b, c = b.astype(dtype), c.astype(dtype)
    solve_normal = _border_solver(a[:m_eq], a[m_eq:], dtype)
    one = dtype(1)
    bnd = np.isfinite(u)
    u = np.where(bnd, u, 0).astype(dtype)
    n = c.size
    x = np.ones(n, dtype)
    x[bnd] = u[bnd] / 2
    v = np.where(bnd, u - x, one).astype(dtype)
    z = np.ones(n, dtype)
    w = np.where(bnd, one, 0).astype(dtype)
    y = np.zeros(b.size, dtype)

    def step(val, dval):
        neg = dval < 0
        return min(one, float(np.min(-val[neg] / dval[neg]) if neg.any()
                              else 1.0))

    for _ in range(max_iters):
        rp = b - a @ x
        ru = np.where(bnd, u - x - v, 0)
        rd = c - a.T @ y - z + w
        gap = float(x @ z + v[bnd] @ w[bnd])
        if (np.linalg.norm(rp) / (1 + np.linalg.norm(b)) < tol
                and np.linalg.norm(rd) / (1 + np.linalg.norm(c)) < tol
                and gap / (1 + abs(float(c @ x))) < tol):
            break
        vinv = np.where(bnd, one / np.where(bnd, v, one), 0)
        theta = (one / (z / x + w * vinv)).astype(dtype)

        def solve(rxz, rvw):
            r = rd - rxz / x + (rvw - w * ru) * vinv
            dy = solve_normal(theta, (rp + a @ (theta * r)).astype(dtype))
            dx = theta * (a.T @ dy - r)
            dv = np.where(bnd, ru - dx, 0)
            dz = (rxz - z * dx) / x
            dw = np.where(bnd, (rvw - w * dv) * vinv, 0)
            if not all(np.isfinite(d).all() for d in (dx, dy, dz, dv, dw)):
                raise np.linalg.LinAlgError("no finite direction")
            return dx, dy, dz, dv, dw

        try:
            dx, dy, dz, dv, dw = solve(-x * z, np.where(bnd, -v * w, 0))
        except np.linalg.LinAlgError:
            break                    # the border system broke down: stop
        ap = min(step(x, dx), step(v[bnd], dv[bnd]) if bnd.any() else one)
        ad = min(step(z, dz), step(w[bnd], dw[bnd]) if bnd.any() else one)
        n_c = n + int(bnd.sum())
        aff = float((x + ap * dx) @ (z + ad * dz)
                    + (v + ap * dv)[bnd] @ (w + ad * dw)[bnd])
        sigma = (aff / gap) ** 3
        mu_t = dtype(sigma * gap / n_c)
        try:
            dx, dy, dz, dv, dw = solve(
                mu_t - x * z - dx * dz,
                np.where(bnd, mu_t - v * w - dv * dw, 0))
        except np.linalg.LinAlgError:
            break
        ap = 0.99995 * min(step(x, dx),
                           step(v[bnd], dv[bnd]) if bnd.any() else one)
        ad = 0.99995 * min(step(z, dz),
                           step(w[bnd], dw[bnd]) if bnd.any() else one)
        x, v = x + dtype(ap) * dx, v + dtype(ap) * dv
        y, z, w = y + dtype(ad) * dy, z + dtype(ad) * dz, w + dtype(ad) * dw
    full = np.array(lo, np.float64)
    full[keep] += x[:int(keep.sum())].astype(np.float64)
    return float(full[-1]), full
