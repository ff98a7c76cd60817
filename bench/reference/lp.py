"""Plain reference for one served replan: the LP relaxation of the
paper's Eq. 4 at one budget, built from a deployment's arrays.

With the setup binaries relaxed (B = A is optimal, since gamma >= 0)
and the billed quanta relaxed to D_i >= G_L,i / rho_i, Eq. 4 becomes

    min F  s.t.  sum_i A_ij = 1                      (each task placed)
                 sum_j c_ij A_ij - F        <= 0     (makespan)
                 sum_j c_ij A_ij - rho_i D_i <= 0    (quanta)
                 sum_i pi_i D_i             <= cap   (budget)
                 0 <= A_ij <= 1, A_ij = 0 on dead platforms, D, F >= 0

with c = beta * n + gamma.  Variables are laid out ``[A (mu*tau,
row-major), D (mu), F]``.  A branch-and-bound node of the same problem
fixes some setup binaries and bounds some quanta: a binary fixed to 0
forces its share to 0; one fixed to 1 pays its setup gamma_ij as a
constant, so its share costs beta_ij n_j alone; a branched quantum keeps
its own bounds on D_i.  With no budget (``cap`` None) the budget row is
left out.  :func:`solve_highs` solves it with HiGHS;
:func:`solve_ipm` with a dense primal-dual interior point written here,
in a chosen float precision (the lower-precision control).
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.optimize import linprog


def build(m: dict, cap, dead=None, fixed0=None, fixed1=None, d_lb=None,
          d_ub=None) -> dict:
    """The relaxation as dense-free arrays: objective, sparse rows, bounds.
    ``fixed0``/``fixed1`` are (mu, tau) masks of setup binaries fixed to
    0/1, ``d_lb``/``d_ub`` (mu,) bounds on the quanta."""
    beta, gamma, n = m["beta"], m["gamma"], m["n"]
    mu, tau = beta.shape
    off = np.zeros((mu, tau), bool)
    if dead is not None:
        off[np.asarray(dead, bool)] = True
    if fixed0 is not None:
        off |= np.asarray(fixed0, bool)
    one = (np.zeros((mu, tau), bool) if fixed1 is None
           else np.asarray(fixed1, bool) & ~off)
    coef = beta * n[None, :] + np.where(one, 0.0, gamma)
    const = (gamma * one).sum(axis=1)
    n_a = mu * tau
    n_x = n_a + mu + 1
    c = np.zeros(n_x)
    c[-1] = 1.0
    rows_eq = sp.hstack([sp.kron(np.ones((1, mu)), sp.eye(tau)),
                         sp.csr_matrix((tau, mu + 1))]).tocsr()
    lat = sp.kron(sp.eye(mu), np.ones((1, tau))) @ sp.diags(coef.ravel())
    mk_rows = sp.hstack([lat, sp.csr_matrix((mu, mu)),
                         -np.ones((mu, 1))])
    q_rows = sp.hstack([lat, sp.diags(-m["rho"]), sp.csr_matrix((mu, 1))])
    rows, rhs = [mk_rows, q_rows], [-const, -const]
    if cap is not None:
        rows.append(sp.hstack([sp.csr_matrix((1, n_a)),
                               sp.csr_matrix(m["pi"][None, :]),
                               sp.csr_matrix((1, 1))]))
        rhs.append([float(cap)])
    a_ub = sp.vstack(rows).tocsr()
    b_ub = np.concatenate(rhs)
    lb = np.zeros(n_x)
    ub = np.full(n_x, np.inf)
    ub[:n_a] = np.where(off, 0.0, 1.0).ravel()
    if d_lb is not None:
        lb[n_a:n_a + mu] = d_lb
    if d_ub is not None:
        ub[n_a:n_a + mu] = d_ub
    return dict(c=c, a_eq=rows_eq, b_eq=np.ones(tau), a_ub=a_ub, b_ub=b_ub,
                lb=lb, ub=ub, mu=mu, tau=tau)


def solve_highs(lp: dict):
    """(objective, x) by HiGHS; raises if HiGHS finds no optimum."""
    bounds = [(float(lo), None if np.isinf(u) else float(u))
              for lo, u in zip(lp["lb"], lp["ub"])]
    res = linprog(lp["c"], A_ub=lp["a_ub"], b_ub=lp["b_ub"], A_eq=lp["a_eq"],
                  b_eq=lp["b_eq"], bounds=bounds, method="highs")
    if res.status != 0:
        raise RuntimeError(f"HiGHS found no optimum: {res.message}")
    return float(res.fun), np.asarray(res.x)


def solve_ipm(lp: dict, dtype=np.float64, max_iters: int = 200,
              tol: float = 1e-10):
    """(objective, x) by a dense Mehrotra predictor-corrector interior
    point on the standard form ``A x = b, 0 <= x <= u``, every step in
    ``dtype``; it stops at its last iterate if the normal matrix breaks
    down in that precision.  Variables are shifted to their lower bounds;
    fixed ones (dead shares) are removed; inequality rows get slacks; rows
    are scaled to unit largest entry."""
    lo = lp["lb"]
    keep = lp["ub"] > lo
    n_x = keep.size
    a_ub, a_eq = lp["a_ub"].toarray(), lp["a_eq"].toarray()
    b = np.concatenate([lp["b_eq"] - a_eq @ lo, lp["b_ub"] - a_ub @ lo])
    a_ub, a_eq = a_ub[:, keep], a_eq[:, keep]
    m_ub = a_ub.shape[0]
    a = np.block([[a_eq, np.zeros((a_eq.shape[0], m_ub))],
                  [a_ub, np.eye(m_ub)]])
    scale = np.abs(a).max(axis=1)
    a, b = a / scale[:, None], b / scale
    c = np.concatenate([lp["c"][keep], np.zeros(m_ub)])
    u = np.concatenate([(lp["ub"] - lo)[keep], np.full(m_ub, np.inf)])
    a, b, c = (v.astype(dtype) for v in (a, b, c))
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
        theta = one / (z / x + w * vinv)
        nrm = (a * theta[None, :]) @ a.T

        def solve(rxz, rvw):
            r = rd - rxz / x + (rvw - w * ru) * vinv
            dy = np.linalg.solve(nrm, rp + a @ (theta * r))
            dx = theta * (a.T @ dy - r)
            dv = np.where(bnd, ru - dx, 0)
            dz = (rxz - z * dx) / x
            dw = np.where(bnd, (rvw - w * dv) * vinv, 0)
            return dx, dy, dz, dv, dw

        try:
            dx, dy, dz, dv, dw = solve(-x * z, np.where(bnd, -v * w, 0))
        except np.linalg.LinAlgError:
            break                    # the normal matrix broke down: stop
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


def residual(lp: dict, x) -> float:
    """Widest violation by ``x`` of the LP's rows and bounds, each row's
    relative to the size of its terms (|a| |x| + |b|), each bound's
    relative to max(1, |bound|)."""
    x = np.asarray(x, np.float64)
    parts = [0.0]
    for a, b, eq in ((lp["a_eq"], lp["b_eq"], True),
                     (lp["a_ub"], lp["b_ub"], False)):
        r = a @ x - b
        r = np.abs(r) if eq else np.maximum(r, 0.0)
        parts.append(float((r / (abs(a) @ np.abs(x) + np.abs(b)
                                 + 1e-300)).max()))
    lo, hi = lp["lb"], lp["ub"]
    parts.append(float((np.maximum(lo - x, 0.0)
                        / np.maximum(1.0, np.abs(lo))).max()))
    fin = np.isfinite(hi)
    parts.append(float((np.maximum(x[fin] - hi[fin], 0.0)
                        / np.maximum(1.0, np.abs(hi[fin]))).max()))
    return max(parts)


def allocation_residual(m: dict, cap: float, dead, alloc, makespan) -> float:
    """Widest violation, relative, of what a relaxed allocation must
    satisfy at its makespan: every task fully placed, no negative share,
    nothing on a dead platform, no platform's latency above the makespan,
    and the relaxed quanta within the budget."""
    alloc = np.asarray(alloc, np.float64)
    coef = m["beta"] * m["n"][None, :] + m["gamma"]
    g_l = (coef * alloc).sum(axis=1)
    dead = np.zeros(alloc.shape[0], bool) if dead is None else np.asarray(
        dead, bool)
    parts = [np.abs(alloc.sum(axis=0) - 1.0).max(),
             max(0.0, float(-alloc.min())),
             float(np.abs(alloc[dead]).sum()) if dead.any() else 0.0,
             max(0.0, float((g_l.max() - makespan) / makespan)),
             max(0.0, float(((m["pi"] * g_l / m["rho"]).sum() - cap) / cap))]
    return float(max(parts))
