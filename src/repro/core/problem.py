"""The allocation problem (paper Eq. 3) and its linearisation (Eq. 4).

``AllocationProblem`` carries the fitted model matrices.  Two builders emit
solver-ready forms:

* :meth:`AllocationProblem.node_lp` — the *structure-exploiting* LP
  relaxation used by our B&B (DESIGN.md §2): the binary setup matrix B and
  the integer quanta vector D are substituted out of the relaxation
  (B* = A, D* = G_L/rho at any LP optimum), so a node LP has only
  (A, D, F_L) variables and ~tau + 2*mu + 1 rows.  It is held compact
  (:class:`NodeLP`): the latency coefficients, bounds and budget, O(mu*tau).

* :meth:`AllocationProblem.full_milp_arrays` — the untransformed Eq. 4
  (A real, B binary, D integer, F_L real) as dense arrays for
  scipy.optimize.milp / HiGHS, used as an independent oracle.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import numpy as np

BIG_M_SLACK = 1.0 + 1e-9


class NodeRows(NamedTuple):
    """The constraint rows of a node LP, held by their structure.

    One row per task j places it, ``sum_i A_ij = 1``; then, per platform
    i, a latency row ``coef_i . A_i - F_L`` and a quanta row ``coef_i .
    A_i - rho_i D_i``; then, with a budget, ``pi . D``.  Each leaf may
    carry a leading batch axis (a stack of node LPs)."""
    coef: np.ndarray            # (mu, tau)
    rho: np.ndarray             # (mu,)
    pi: np.ndarray              # (mu,)


class NodeLP(NamedTuple):
    """A B&B node's LP relaxation in compact form:

        min F_L  s.t.  sum_i A_ij = 1                      (each task j)
                       coef_i . A_i - F_L       <= -const_i  (latency)
                       coef_i . A_i - rho_i D_i <= -const_i  (quanta)
                       pi . D                   <= cap       (budget)
                       lb <= x <= ub

    Variable layout: x = [A.ravel() (mu*tau), D (mu), F_L (1)].  With
    ``cap`` None the budget row is left out.  The dense view
    ``min c.x  s.t.  A_eq x = b_eq,  G x <= h`` is built on read
    (``c``, ``a_eq``, ``b_eq``, ``g``, ``h``); the solver never builds
    it (:func:`repro.core.lp.solve_node_lps_stacked` takes ``rows``).
    """
    coef: np.ndarray            # (mu, tau) latency coefficient of A
    const: np.ndarray           # (mu,) latency of setups fixed to 1
    rho: np.ndarray             # (mu,)
    pi: np.ndarray              # (mu,)
    cap: Optional[float]
    lb: np.ndarray              # (n,)
    ub: np.ndarray              # (n,)

    @property
    def rows(self) -> NodeRows:
        return NodeRows(self.coef, self.rho, self.pi)

    @property
    def c(self) -> np.ndarray:
        c = np.zeros(self.lb.shape[0])
        c[-1] = 1.0
        return c

    @property
    def b_eq(self) -> np.ndarray:
        return np.ones(self.coef.shape[1])

    @property
    def h(self) -> np.ndarray:
        cap = [] if self.cap is None else [float(self.cap)]
        return np.concatenate([-self.const, -self.const, cap])

    @property
    def a_eq(self) -> np.ndarray:
        return self.sparse_rows()[0].toarray()

    @property
    def g(self) -> np.ndarray:
        return self.sparse_rows()[1].toarray()

    def sparse_rows(self):
        """(A_eq, G) as scipy CSR matrices: the rows a host LP solver
        takes, laid out by :func:`node_border` as the solver's are."""
        import scipy.sparse as sp
        mu, tau = self.coef.shape
        p, small = node_border(np.asarray(self.rho, float),
                               np.asarray(self.pi, float),
                               2 * mu + (self.cap is not None))
        a_eq = sp.hstack([sp.kron(np.ones((1, mu)), sp.eye(tau)),
                          sp.csr_matrix((tau, mu + 1))])
        lat = sp.block_diag([row[None, :] for row in self.coef])
        g = sp.hstack([sp.csr_matrix(p) @ lat, sp.csr_matrix(small)])
        return a_eq.tocsr(), g.tocsr()


def node_border(rho, pi, m_b: int, xp=np):
    """The border rows of a node LP, ``(p, small)``: ``m_b`` is 2*mu
    (latency rows, then quanta rows) or 2*mu + 1 (then the budget row).
    Border row k weighs platform i's latency sum ``coef_i . A_i`` by
    ``p[k, i]`` and the columns D (mu) and F_L (1) by ``small[k]``.
    ``xp`` is numpy or jax.numpy: the host's sparse rows and the
    solver's operator are built from this one description."""
    mu = rho.shape[-1]
    dt = rho.dtype
    eye = xp.eye(mu, dtype=dt)
    p = xp.concatenate([eye, eye, xp.zeros((m_b - 2 * mu, mu), dt)])
    d_cols = xp.concatenate([xp.zeros((mu, mu), dt), -xp.diag(rho),
                             pi[None, :].astype(dt)])[:m_b]
    f_col = xp.concatenate([-xp.ones((mu, 1), dt),
                            xp.zeros((mu + 1, 1), dt)])[:m_b]
    return p, xp.concatenate([d_cols, f_col], axis=1)


@dataclasses.dataclass(frozen=True)
class AllocationProblem:
    """tau divisible tasks across mu platforms (paper Eq. 3).

    beta, gamma: (mu, tau) seconds.  n: (tau,) work units.  rho: (mu,)
    billing quantum seconds.  pi: (mu,) $ per quantum.
    """
    beta: np.ndarray
    gamma: np.ndarray
    n: np.ndarray
    rho: np.ndarray
    pi: np.ndarray
    platform_names: Optional[Tuple[str, ...]] = None
    task_names: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        beta = np.asarray(self.beta, dtype=np.float64)
        gamma = np.asarray(self.gamma, dtype=np.float64)
        n = np.asarray(self.n, dtype=np.float64)
        rho = np.asarray(self.rho, dtype=np.float64)
        pi = np.asarray(self.pi, dtype=np.float64)
        if beta.shape != gamma.shape:
            raise ValueError(f"beta {beta.shape} vs gamma {gamma.shape}")
        mu, tau = beta.shape
        if n.shape != (tau,):
            raise ValueError(f"n must be (tau,)={tau}, got {n.shape}")
        if rho.shape != (mu,) or pi.shape != (mu,):
            raise ValueError("rho/pi must be (mu,)")
        if (beta < 0).any() or (gamma < 0).any() or (rho <= 0).any() or (pi < 0).any():
            raise ValueError("model coefficients must be non-negative (rho > 0)")
        object.__setattr__(self, "beta", beta)
        object.__setattr__(self, "gamma", gamma)
        object.__setattr__(self, "n", n)
        object.__setattr__(self, "rho", rho)
        object.__setattr__(self, "pi", pi)

    # -- derived quantities -------------------------------------------------
    @property
    def mu(self) -> int:
        return self.beta.shape[0]

    @property
    def tau(self) -> int:
        return self.beta.shape[1]

    @property
    def beta_n(self) -> np.ndarray:
        """(mu, tau): seconds for the WHOLE of task j on platform i."""
        return self.beta * self.n[None, :]

    def single_platform_latency(self) -> np.ndarray:
        """(mu,) latency if one platform runs the entire workload."""
        return (self.beta_n + self.gamma).sum(axis=1)

    def single_platform_cost(self) -> np.ndarray:
        lat = self.single_platform_latency()
        return np.ceil(lat / self.rho) * self.pi

    def d_max(self, makespan_ub: Optional[float] = None) -> np.ndarray:
        """Safe per-platform upper bounds for the quanta variable D."""
        if makespan_ub is None:
            makespan_ub = float(self.single_platform_latency().max())
        return np.ceil(makespan_ub / self.rho) + 1.0

    # ------------------------------------------------------------------
    # Structure-exploiting node LP (B&B relaxation)
    # ------------------------------------------------------------------
    def node_lp(self,
                cost_cap: Optional[float],
                b_fixed0: Optional[np.ndarray] = None,
                b_fixed1: Optional[np.ndarray] = None,
                d_lb: Optional[np.ndarray] = None,
                d_ub: Optional[np.ndarray] = None) -> NodeLP:
        """Build the relaxation LP at a B&B node.

        b_fixed0 / b_fixed1: (mu, tau) bool masks of setup binaries branched
        to 0 / 1.  Free binaries are relaxed with the exact substitution
        B = A (valid lower bound because gamma >= 0).  Branched-to-1
        binaries contribute gamma as a constant; branched-to-0 force A = 0.
        d_lb / d_ub: (mu,) branch bounds on the integer quanta.
        """
        mu, tau = self.mu, self.tau
        if b_fixed0 is None:
            b_fixed0 = np.zeros((mu, tau), dtype=bool)
        if b_fixed1 is None:
            b_fixed1 = np.zeros((mu, tau), dtype=bool)
        if (b_fixed0 & b_fixed1).any():
            raise ValueError("a binary cannot be fixed to both 0 and 1")
        n_a = mu * tau
        # latency coefficient for A_ij in G_L,i:
        #   free binary    -> (beta_n + gamma) * A   (relaxed B = A)
        #   fixed to 1     -> beta_n * A + gamma (constant)
        #   fixed to 0     -> A forced 0 via ub
        coef = self.beta_n + np.where(b_fixed1 | b_fixed0, 0.0, self.gamma)
        const = (self.gamma * b_fixed1).sum(axis=1)    # (mu,)

        lb = np.zeros(n_a + mu + 1)
        ub = np.full(n_a + mu + 1, np.inf)
        ub[:n_a] = np.where(b_fixed0, 0.0, 1.0).ravel()
        dmax = self.d_max()
        ub[n_a:n_a + mu] = dmax if d_ub is None else np.minimum(d_ub, dmax)
        if d_lb is not None:
            lb[n_a:n_a + mu] = d_lb
        return NodeLP(coef, const, self.rho, self.pi,
                      None if cost_cap is None else float(cost_cap), lb, ub)

    def split_node_x(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray, float]:
        """x -> (A (mu,tau), D (mu,), F_L)."""
        n_a = self.mu * self.tau
        a = x[:n_a].reshape(self.mu, self.tau)
        d = x[n_a:n_a + self.mu]
        return a, d, float(x[n_a + self.mu])

    # ------------------------------------------------------------------
    # Untransformed Eq. 4 for HiGHS (oracle / large-scale backend)
    # ------------------------------------------------------------------
    def full_milp_arrays(self, cost_cap: Optional[float]):
        """Dense arrays for scipy.optimize.milp implementing Eq. 4 verbatim.

        Variable layout: [A (mu*tau) real, B (mu*tau) binary, D (mu) int,
        F_L real].  Returns dict(c, integrality, lb, ub, a_ub, b_ub,
        a_eq, b_eq).
        """
        mu, tau = self.mu, self.tau
        n_a = mu * tau
        idx_b = n_a
        idx_d = 2 * n_a
        idx_f = 2 * n_a + mu
        n_x = idx_f + 1

        c = np.zeros(n_x)
        c[idx_f] = 1.0
        integrality = np.zeros(n_x)
        integrality[idx_b:idx_d] = 1.0   # B binary (with ub 1)
        integrality[idx_d:idx_f] = 1.0   # D integer

        lb = np.zeros(n_x)
        ub = np.full(n_x, np.inf)
        ub[:idx_d] = 1.0                 # A, B <= 1
        ub[idx_d:idx_f] = self.d_max()

        a_eq = np.zeros((tau, n_x))
        for j in range(tau):
            a_eq[j, j:n_a:tau] = 1.0
        b_eq = np.ones(tau)

        rows, rhs = [], []
        bn = self.beta_n
        # G_L,i - F_L <= 0  with  G_L,i = sum_j bn_ij A_ij + gamma_ij B_ij
        for i in range(mu):
            row = np.zeros(n_x)
            row[i * tau:(i + 1) * tau] = bn[i]
            row[idx_b + i * tau: idx_b + (i + 1) * tau] = self.gamma[i]
            row[idx_f] = -1.0
            rows.append(row); rhs.append(0.0)
        # A_ij - B_ij <= 0
        for k in range(n_a):
            row = np.zeros(n_x)
            row[k] = 1.0
            row[idx_b + k] = -1.0
            rows.append(row); rhs.append(0.0)
        # G_L,i / rho_i - D_i <= 0
        for i in range(mu):
            row = np.zeros(n_x)
            row[i * tau:(i + 1) * tau] = bn[i] / self.rho[i]
            row[idx_b + i * tau: idx_b + (i + 1) * tau] = self.gamma[i] / self.rho[i]
            row[idx_d + i] = -1.0
            rows.append(row); rhs.append(0.0)
        # cost
        if cost_cap is not None:
            row = np.zeros(n_x)
            row[idx_d:idx_f] = self.pi
            rows.append(row); rhs.append(float(cost_cap))

        return dict(c=c, integrality=integrality, lb=lb, ub=ub,
                    a_ub=np.stack(rows), b_ub=np.asarray(rhs),
                    a_eq=a_eq, b_eq=b_eq,
                    idx=dict(a=0, b=idx_b, d=idx_d, f=idx_f))
