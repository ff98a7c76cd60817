"""Primal-dual interior-point LP solver in JAX.

Solves   min c.x   s.t.  A_eq x = b_eq,  G x <= h,  lb <= x <= ub
via Mehrotra's predictor-corrector method on the bounded standard form

    min c.x   s.t.  A x = b,  0 <= x <= u        (u_i may be +inf)

with the box bounds handled *inside* the KKT system (duals z for x >= 0 and
w for x <= u), so the normal-equation matrix stays (m x m) with
m = #rows(A_eq) + #rows(G) — this is what makes the B&B node solves cheap
(DESIGN.md §2).  jit-compiled with ``lax.while_loop``; ``vmap``-able across a
batch of right-hand sides (the epsilon-constraint cost grid).

The iteration runs against a constraint operator: :class:`DenseOp` holds a
generic LP's matrix; :class:`NodeOp` holds a node LP by its structure
(``tau`` placement rows, each share in exactly one, and a border of
2*mu+1 rows), so each Newton system reduces to a (2*mu+1)-square Schur
complement and no dense node matrix is ever built (docs/solver.md "Node
LPs: the operator and the border").

Two stacked execution drivers share the same per-iteration math:

* the **monolithic** driver — one jitted, vmapped call whose lockstep
  ``while_loop`` iterates until the SLOWEST active row converges (every
  row pays every trip, select-masked once retired);
* the **chunked** driver (``compact=True``) — Newton steps run in
  fixed-size chunks and between chunks the batch is *compacted*: rows
  that converged are written out and the survivors are gathered into the
  smallest buffer of a fixed power-of-two width ladder, so late trips
  are paid only by the stragglers.  Every ladder width is pre-compiled
  on first use, keeping :func:`stacked_compile_count` flat thereafter.
  ``compact_mode="device"`` (default) performs the between-chunk gather
  INSIDE the compiled program (stable argsort+gather; only two scalars
  per chunk cross the host boundary) and returns device arrays in input
  row order; ``compact_mode="host"`` keeps the legacy NumPy round-trip
  as a parity oracle.

Both drivers optionally shard the batch (row) axis over a device mesh
(``solve_lp_stacked(mesh=, row_spec=)``, via ``shard_map``): rows are
independent, so each shard runs the same driver on its own block — a
shard's lockstep while-loop retires as soon as ITS slowest row
converges, and compaction stays shard-local (the only cross-shard
traffic is the two per-chunk host scalars, a pmax and a psum).  See
docs/solver.md "Sharded megabatches".

Orthogonally, ``newton_dtype="float32"`` switches the Newton
normal-equation solves to a mixed-precision path: factor/solve in
float32 with one float64 iterative-refinement step, falling back to the
full float64 path per row once the barrier parameter is small (the
normal matrix conditioning grows like 1/mu^2) or whenever the refined
residual exceeds tolerance.

The power-of-two ladder is also a public batching contract —
:func:`ladder_widths` / :func:`next_ladder_width` /
:func:`solve_node_lps_ladder` / :func:`warm_ladder` — used by the
serving layer (:mod:`repro.serving`) to coalesce multi-tenant requests
while keeping :func:`stacked_compile_count` flat.  Knob-by-knob
reference: docs/solver.md.
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.problem import NodeRows, node_border

_ETA = 0.99995          # fraction-to-boundary
# Node LPs need more iterations as the book grows (~25 at 128 tasks,
# 45–150 at 3,072–4,096): below the cap, such a row would end
# unconverged and go to the host's LP solver
_MAX_ITERS = 200
_TOL = 1e-9
_INF_UB = 1e30          # finite stand-in for +inf upper bounds
_CHUNK_ITERS = 8        # default chunk length of the compacted driver

# Pluggable Newton linear-system backends.  The normal matrix
# A Theta^-1 A^T is SPD by construction (A has full row rank), so every
# backend is a Cholesky solve.  "xla" is XLA's Cholesky + two triangular solves (the
# default; compiles on every platform, float64 included); "ref" is the
# pure-jnp Cholesky oracle (kernels/ref.py); "pallas" is the batched-
# Cholesky Pallas kernel (kernels/batched_chol.py), compiled on TPU —
# float32 only, so there it pairs with newton_dtype="float32" — and
# interpret-mode on CPU; "pallas-interpret" forces interpret mode
# everywhere (the CI validation path).
LINSOLVES = ("xla", "ref", "pallas", "pallas-interpret")

# Newton normal-equation precisions.  "float64" is the direct solve;
# "float32" is the mixed-precision path: f32 factor/solve + one f64
# iterative-refinement step per solve, with a per-row fall-back to full
# f64 once mu <= _F32_SWITCH_MU (the normal matrix conditions like
# 1/mu^2, so a float32 factorisation cannot polish to tight tolerances)
# or as soon as a refined residual exceeds _F32_REFINE_RTOL.
NEWTON_DTYPES = ("float64", "float32")
_F32_SWITCH_MU = 1e-5
_F32_REFINE_RTOL = 1e-6


def _canon_newton_dtype(newton_dtype) -> str:
    """Normalise a ``newton_dtype`` knob ("f32", jnp.float32, ...) to one
    of :data:`NEWTON_DTYPES`."""
    if newton_dtype is None:
        return "float64"
    if isinstance(newton_dtype, str):
        s = {"f32": "float32", "f64": "float64"}.get(newton_dtype,
                                                    newton_dtype)
    else:
        s = jnp.dtype(newton_dtype).name
    if s not in NEWTON_DTYPES:
        raise ValueError(f"unknown newton_dtype {newton_dtype!r}; "
                         f"expected one of {NEWTON_DTYPES}")
    return s


def _newton_linsolve(linsolve: str, m_mat, rhs):
    """One normal-equation solve ``M dy = rhs`` under the chosen backend.
    Called inside the (possibly vmapped) IPM iteration: under ``vmap`` the
    Pallas path batches into ONE kernel launch over the stacked (B, m, m)
    matrices instead of B independent solves.  The solve runs in the
    dtype of ``m_mat`` (the mixed-precision path passes float32 here)."""
    if linsolve == "xla":
        from jax.scipy.linalg import cho_factor, cho_solve
        return cho_solve(cho_factor(m_mat, lower=True), rhs)
    if linsolve in ("ref", "pallas"):
        # ops.chol_solve owns the interpret-vs-compiled device dispatch
        from repro.kernels import ops as _kops
        return _kops.chol_solve(m_mat, rhs, use_pallas=linsolve == "pallas")
    if linsolve == "pallas-interpret":
        from repro.kernels import batched_chol as _bc
        return _bc.chol_solve(m_mat, rhs, interpret=True)
    raise ValueError(f"unknown linsolve backend {linsolve!r}; "
                     f"expected one of {LINSOLVES}")


def _chol_factor32(linsolve: str, m32):
    """Float32 Cholesky factor of one SPD normal matrix through the
    chosen backend's factorisation machinery (the O(m^3) part of the
    mixed-precision solve; the refinement reuses this factor)."""
    if linsolve == "xla":
        return jnp.linalg.cholesky(m32)
    if linsolve == "ref":
        from repro.kernels import ref as _kref
        return _kref.chol_factor_ref(m32)
    if linsolve in ("pallas", "pallas-interpret"):
        from repro.kernels import batched_chol as _bc
        from repro.kernels import ops as _kops
        interpret = linsolve == "pallas-interpret" or not _kops._on_tpu()
        return _bc.chol_factor(m32, interpret=interpret)
    raise ValueError(f"unknown linsolve backend {linsolve!r}; "
                     f"expected one of {LINSOLVES}")


def _newton_solve(linsolve: str, newton_dtype: str, m_mat, rhs):
    """One Newton solve at the requested precision.

    Returns ``(dy, rel_resid)``: the f64 path solves directly and reports
    a zero residual; the f32 path factors ONCE in float32 and reuses the
    factor for both the initial solve and the float64 iterative-
    refinement step (two O(m^2) triangular solves against one O(m^3)
    factorisation), reporting the refined residual norm relative to
    ``rhs`` — the IPM body uses it to flag rows for the full-f64
    fallback.
    """
    if newton_dtype == "float64":
        return _newton_linsolve(linsolve, m_mat, rhs), jnp.zeros((),
                                                                 m_mat.dtype)
    from jax.scipy.linalg import solve_triangular
    l32 = _chol_factor32(linsolve, m_mat.astype(jnp.float32))

    def solve32(r):
        y = solve_triangular(l32, r.astype(jnp.float32), lower=True)
        x = solve_triangular(l32.T, y, lower=False)
        return x.astype(m_mat.dtype)

    dy = solve32(rhs)
    r = rhs - m_mat @ dy
    dy = dy + solve32(r)
    r = rhs - m_mat @ dy
    rel = jnp.linalg.norm(r) / (jnp.linalg.norm(rhs) + 1e-30)
    return dy, rel


class LPSolution(NamedTuple):
    x: jnp.ndarray          # primal solution in ORIGINAL variables
    obj: jnp.ndarray        # c.x
    y: jnp.ndarray          # duals of [A_eq; G]
    iters: jnp.ndarray
    primal_res: jnp.ndarray
    dual_res: jnp.ndarray
    gap: jnp.ndarray        # max(mean complementarity, gap / (1+|c.x|))

    @property
    def converged(self):
        return ((self.primal_res < 1e-6) & (self.dual_res < 1e-6)
                & (self.gap < 1e-6))


class DenseOp(NamedTuple):
    """A standard-form constraint matrix held densely (generic LPs): the
    operator interface the interior point runs against."""
    a: jnp.ndarray

    @property
    def shape(self):
        return self.a.shape

    @property
    def dtype(self):
        return self.a.dtype

    def matvec(self, x):
        return self.a @ x

    def rmatvec(self, y):
        return self.a.T @ y

    def col_absmax(self):
        return jnp.abs(self.a).max(axis=0)

    def row_absmax(self):
        return jnp.abs(self.a).max(axis=1)

    def scale_cols(self, s):
        return DenseOp(self.a * s[None, :])

    def scale_rows(self, s):
        return DenseOp(self.a * s[:, None])

    def normal_solve(self, d, rhs, linsolve: str, newton_dtype: str,
                     ridge: float):
        """``(A diag(d) A^T) dy = rhs`` -> ``(dy, rel_resid)``."""
        m_mat = (self.a * d[None, :]) @ self.a.T
        if ridge:
            m_mat = m_mat + ridge * jnp.eye(self.a.shape[0],
                                            dtype=self.a.dtype)
        return _newton_solve(linsolve, newton_dtype, m_mat, rhs)


class NodeOp(NamedTuple):
    """The standard-form constraint matrix of a node LP, held by its
    structure.  Rows are [E; B]: one placement row per task, then the
    border of 2*mu (+1 with a budget) latency, quanta and budget rows.
    Columns are the mu*tau shares A (platform-major), then the small
    block: D (mu), F_L and a slack per border row.

    E has one entry per share column, ``e[i, j]`` in row j; the border
    weighs platform i's shares by ``p[k, i] * w[i, j]`` and the small
    columns by ``bs``.  So ``E Theta^-1 E^T`` is diagonal, and a Newton
    step reduces to the Schur complement on the border,
    ``S = M_B - C^T D_E^-1 C``, of size 2*mu+1: the first tau steps of a
    dense Cholesky of the normal matrix in this row order, regrouped.
    Row and column scales are diagonal and keep the structure."""
    e: jnp.ndarray              # (mu, tau)
    w: jnp.ndarray              # (mu, tau)
    p: jnp.ndarray              # (m_b, mu)
    bs: jnp.ndarray             # (m_b, mu + 1 + m_b)

    @classmethod
    def of(cls, coef, rho, pi, m_b: int) -> "NodeOp":
        """The unscaled standard form of a node LP's rows
        (:class:`repro.core.problem.NodeRows`) with ``m_b`` border rows:
        2*mu, or 2*mu + 1 with the budget row."""
        p, small = node_border(rho, pi, m_b, jnp)
        bs = jnp.concatenate([small, jnp.eye(m_b, dtype=small.dtype)],
                             axis=1)
        return cls(jnp.ones_like(coef), coef, p, bs)

    @property
    def shape(self):
        mu, tau = self.e.shape
        return (tau + self.p.shape[0], mu * tau + self.bs.shape[1])

    @property
    def dtype(self):
        return self.e.dtype

    def _split(self, v):
        mu, tau = self.e.shape
        return v[:mu * tau].reshape(mu, tau), v[mu * tau:]

    def matvec(self, x):
        xa, xs = self._split(x)
        return jnp.concatenate([(self.e * xa).sum(axis=0),
                                self.p @ (self.w * xa).sum(axis=1)
                                + self.bs @ xs])

    def rmatvec(self, y):
        tau = self.e.shape[1]
        ye, yb = y[:tau], y[tau:]
        ga = self.e * ye[None, :] + self.w * (self.p.T @ yb)[:, None]
        return jnp.concatenate([ga.ravel(), self.bs.T @ yb])

    def col_absmax(self):
        pa = jnp.abs(self.p).max(axis=0)
        ca = jnp.maximum(jnp.abs(self.e), jnp.abs(self.w) * pa[:, None])
        return jnp.concatenate([ca.ravel(), jnp.abs(self.bs).max(axis=0)])

    def row_absmax(self):
        wa = jnp.abs(self.w).max(axis=1)
        rb = jnp.maximum((jnp.abs(self.p) * wa[None, :]).max(axis=1),
                         jnp.abs(self.bs).max(axis=1))
        return jnp.concatenate([jnp.abs(self.e).max(axis=0), rb])

    def scale_cols(self, s):
        sa, ss = self._split(s)
        return NodeOp(self.e * sa, self.w * sa, self.p,
                      self.bs * ss[None, :])

    def scale_rows(self, s):
        tau = self.e.shape[1]
        se, sb = s[:tau], s[tau:]
        return NodeOp(self.e * se[None, :], self.w, self.p * sb[:, None],
                      self.bs * sb[:, None])

    def normal_solve(self, d, rhs, linsolve: str, newton_dtype: str,
                     ridge: float):
        """``(A diag(d) A^T) dy = rhs`` -> ``(dy, rel_resid)`` through the
        border: eliminate the diagonal task block ``D_E``, solve
        ``S dy_B = r_B - C^T D_E^-1 r_E`` with :func:`_newton_solve`
        (``linsolve`` and ``newton_dtype`` act on S), back-substitute
        ``dy_E = D_E^-1 (r_E - C dy_B)``.  ``C = G^T P^T`` with
        ``G = e d w``; ``rel_resid`` is S's."""
        da, ds = self._split(d)
        tau = self.e.shape[1]
        d_e = (self.e * self.e * da).sum(axis=0)
        g = self.e * da * self.w
        if ridge:
            d_e = d_e + ridge
        gd = g / d_e[None, :]
        k = jnp.diag((self.w * self.w * da).sum(axis=1)) - gd @ g.T
        s = self.p @ k @ self.p.T + (self.bs * ds[None, :]) @ self.bs.T
        if ridge:
            s = s + ridge * jnp.eye(s.shape[0], dtype=s.dtype)
        r_e, r_b = rhs[:tau], rhs[tau:]
        dy_b, rel = _newton_solve(linsolve, newton_dtype, s,
                                  r_b - self.p @ (gd @ r_e))
        dy_e = (r_e - g.T @ (self.p.T @ dy_b)) / d_e
        return jnp.concatenate([dy_e, dy_b]), rel


class _StdForm(NamedTuple):
    a: object               # DenseOp or NodeOp
    b: jnp.ndarray
    c: jnp.ndarray
    u: jnp.ndarray          # upper bounds, _INF_UB where unbounded
    n_orig: int
    lb: jnp.ndarray         # original lower bounds (for un-shifting)
    row_scale: jnp.ndarray
    col_scale: jnp.ndarray  # un-standardising: x = col_scale * x' + lb


class _IPMCarry(NamedTuple):
    """Per-row iteration state of the stacked IPM — the chunked driver
    round-trips this through host compaction between chunks."""
    x: jnp.ndarray
    y: jnp.ndarray
    z: jnp.ndarray
    w: jnp.ndarray
    s: jnp.ndarray
    it: jnp.ndarray         # total IPM iterations taken
    it32: jnp.ndarray       # iterations taken on the f32 Newton path
    done: jnp.ndarray       # converged (or started inactive)
    bad: jnp.ndarray        # an f32 refined residual exceeded tolerance
    grad: jnp.ndarray       # graduated to the full-f64 Newton path


def _standardise(c, a_eq, b_eq, g, h, lb, ub) -> _StdForm:
    """Standard form of an LP: shift lb to 0, add slacks for the
    inequality rows, row+column equilibrate (:func:`_equilibrate`).  The
    rows come densely (:class:`DenseOp`), or as a node LP's
    :class:`~repro.core.problem.NodeRows` in ``a_eq`` with ``b_eq`` and
    ``g`` None (:class:`NodeOp`: every task row's rhs is 1, ``h`` holds
    the border's), the same arithmetic on the same rows."""
    m_in = h.shape[0]
    # shift x' = x - lb
    if isinstance(a_eq, NodeRows):
        op = NodeOp.of(a_eq.coef, a_eq.rho, a_eq.pi, m_in)
        b = (jnp.concatenate([jnp.ones((a_eq.coef.shape[1],), h.dtype), h])
             - op.matvec(jnp.concatenate([lb, jnp.zeros((m_in,),
                                                        lb.dtype)])))
    else:
        b = jnp.concatenate([b_eq - a_eq @ lb, h - g @ lb])
        op = DenseOp(jnp.block([
            [a_eq, jnp.zeros((a_eq.shape[0], m_in), a_eq.dtype)],
            [g, jnp.eye(m_in, dtype=g.dtype)],
        ]))
    return _equilibrate(op, b, c, lb, ub, m_in)


def _equilibrate(op, b, c, lb, ub, m_in: int) -> _StdForm:
    """Bounds and objective of the shifted standard form, then two-sided
    equilibration of ``op``.

    The node LPs mix coefficients spanning ~8 orders of magnitude
    (beta*N in the hundreds of seconds next to unit allocation rows);
    two-sided equilibration keeps the Mehrotra iteration from stalling
    around 1e-5 residuals.
    """
    n = c.shape[0]
    # variables pinned by lb == ub (e.g. dead-platform allocations in
    # scenario solves) keep a sliver of interior so the IPM stays finite
    u = jnp.where(jnp.isfinite(ub), jnp.maximum(ub - lb, 1e-9), _INF_UB)
    c2 = jnp.concatenate([c, jnp.zeros((m_in,), c.dtype)])
    u2 = jnp.concatenate([u, jnp.full((m_in,), _INF_UB, u.dtype)])
    # column equilibration: x = col_scale * x'
    col_scale = 1.0 / jnp.clip(op.col_absmax(), 1e-8, 1e8)
    op = op.scale_cols(col_scale)
    c2 = c2 * col_scale
    u2 = jnp.where(u2 < _INF_UB * 0.5, u2 / col_scale, _INF_UB)
    # row equilibration
    row_scale = 1.0 / jnp.maximum(op.row_absmax(), 1e-12)
    op = op.scale_rows(row_scale)
    b = b * row_scale
    # a pinned variable's sliver maps back to its bound: left at up to
    # 1e-9, a share fixed to 0 would still count in its platform's rows
    pinned = jnp.concatenate([ub - lb < 1e-9, jnp.zeros((m_in,), bool)])
    col_scale = jnp.where(pinned, 0.0, col_scale)
    return _StdForm(op, b, c2, u2, n, lb, row_scale, col_scale)


# name of the programs that solve node LPs: their device ops sit in
# module "jit_node_ipm" of a profiler trace
NODE_IPM = "node_ipm"


def _step_len(v, dv, finite=None):
    """max alpha in (0,1] with v + alpha*dv >= 0 (only where ``finite``)."""
    neg = dv < 0
    if finite is not None:
        neg = neg & finite
    ratios = jnp.where(neg, -v / jnp.where(neg, dv, -1.0), jnp.inf)
    return jnp.minimum(1.0, _ETA * ratios.min())


def _ipm_ops(a, b, c, u, tol, linsolve):
    """Closures for ONE (unbatched) IPM instance: cold-start ``init``,
    per-iteration ``make_body(newton_dtype)`` and the residual ``report``
    — shared verbatim by the monolithic ``_solve_std`` while-loop and the
    chunked driver's per-chunk stepper, so both drivers run the exact
    same row math.  ``a`` is the constraint operator (:class:`DenseOp`
    or :class:`NodeOp`)."""
    m, n = a.shape
    dtype = a.dtype
    has_ub = u < _INF_UB * 0.5
    b_norm = 1.0 + jnp.linalg.norm(b)
    c_norm = 1.0 + jnp.linalg.norm(c)

    def init(active) -> _IPMCarry:
        # -- cold start, interior w.r.t. both bounds.  The floor must stay
        # strictly inside (0, u) even for tiny upper bounds (scenario
        # solves pin dead-platform variables with ub ~ 0), hence
        # min(1e-2, u/4).
        x0 = jnp.where(has_ub, 0.5 * jnp.minimum(u, 2.0), 1.0)
        x0 = jnp.maximum(x0, jnp.where(has_ub, jnp.minimum(1e-2, 0.25 * u),
                                       1e-2))
        s0 = jnp.where(has_ub, u - x0, 1.0)
        z0 = jnp.ones((n,), dtype)
        w0 = jnp.where(has_ub, 1.0, 0.0).astype(dtype)
        y0 = jnp.zeros((m,), dtype)
        # strong dtypes throughout: the chunked driver round-trips the
        # carry through numpy between chunks, and a weak->strong dtype
        # flip would needlessly recompile the chunk stepper
        false = jnp.array(False)
        it0 = jnp.array(0, dtype=jnp.int32)
        return _IPMCarry(x0, y0, z0, w0, s0, it0, it0,
                         ~jnp.asarray(active, dtype=bool), false, false)

    def residuals(x, y, z, w, s):
        r_p = b - a.matvec(x)
        r_d = c - a.rmatvec(y) - z + w
        r_u = jnp.where(has_ub, u - x - s, 0.0)
        return r_p, r_d, r_u

    def mu_of(x, z, s, w):
        denom = n + has_ub.sum()
        return (x @ z + jnp.where(has_ub, s * w, 0.0).sum()) / denom

    def rel_gap_of(x, z, s, w):
        return ((x @ z + jnp.where(has_ub, s * w, 0.0).sum())
                / (1.0 + jnp.abs(c @ x)))

    def make_body(newton_dtype: str, graduated: bool = False):
        """``graduated`` marks the float64 phase of the mixed-precision
        path: those rows solve through the XLA Cholesky whatever the
        backend, because the compiled Pallas kernel is float32-only."""
        f32 = newton_dtype == "float32"
        row_linsolve = "xla" if graduated else linsolve

        def newton(x, y, z, w, s, r_p, r_d, r_u, rc_xz, rc_sw):
            # theta = z/x + w/s  (w/s only where bounded)
            theta = z / x + jnp.where(has_ub, w / s, 0.0)
            theta_inv = 1.0 / theta
            # rhs of normal equations
            rhat = (r_d - rc_xz / x
                    + jnp.where(has_ub, (rc_sw - w * r_u) / s, 0.0))
            # no ridge on the float64 path: the standard form has full
            # row rank (a slack per G row, disjoint task rows), and an
            # absolute ridge swamps the tiny pivots of degenerate rows
            # near a tight budget — with one, the Cholesky path stalls
            # where LU got through
            rhs = r_p + a.matvec(theta_inv * rhat)
            dy, rel = a.normal_solve(theta_inv, rhs, row_linsolve,
                                     newton_dtype, 1e-11 if f32 else 0.0)
            dx = theta_inv * (a.rmatvec(dy) - rhat)
            dz = (rc_xz - z * dx) / x
            ds = jnp.where(has_ub, r_u - dx, 0.0)
            dw = jnp.where(has_ub, (rc_sw - w * ds) / s, 0.0)
            return dx, dy, dz, dw, ds, rel

        def body(carry: _IPMCarry) -> _IPMCarry:
            x, y, z, w, s = carry.x, carry.y, carry.z, carry.w, carry.s
            r_p, r_d, r_u = residuals(x, y, z, w, s)
            mu = mu_of(x, z, s, w)
            # predictor (affine)
            dx_a, dy_a, dz_a, dw_a, ds_a, rel_a = newton(
                x, y, z, w, s, r_p, r_d, r_u, -x * z,
                jnp.where(has_ub, -s * w, 0.0))
            ap = jnp.minimum(_step_len(x, dx_a), _step_len(s, ds_a, has_ub))
            ad = jnp.minimum(_step_len(z, dz_a), _step_len(w, dw_a, has_ub))
            mu_aff = ((x + ap * dx_a) @ (z + ad * dz_a)
                      + (jnp.where(has_ub,
                                   (s + ap * ds_a) * (w + ad * dw_a),
                                   0.0)).sum()
                      ) / (n + has_ub.sum())
            sigma = jnp.clip((mu_aff / jnp.maximum(mu, 1e-300)) ** 3,
                             0.0, 1.0)
            # corrector
            rc_xz = sigma * mu - x * z - dx_a * dz_a
            rc_sw = jnp.where(has_ub, sigma * mu - s * w - ds_a * dw_a, 0.0)
            dx, dy, dz, dw, ds, rel_c = newton(x, y, z, w, s, r_p, r_d, r_u,
                                               rc_xz, rc_sw)
            ap = jnp.minimum(_step_len(x, dx), _step_len(s, ds, has_ub))
            ad = jnp.minimum(_step_len(z, dz), _step_len(w, dw, has_ub))
            # a Cholesky factorisation of a too-ill-conditioned normal
            # matrix (f32 anywhere; f64 on the pallas/ref backends near
            # singularity) yields NaNs: REJECT the whole update — keep
            # the intact iterate rather than poisoning the row.  On the
            # f32 path the row additionally graduates, so the f64 phase
            # recomputes this iteration from the pre-failure state.
            ok = (jnp.isfinite(rel_a) & jnp.isfinite(rel_c)
                  & jnp.isfinite(ap) & jnp.isfinite(ad)
                  & jnp.all(jnp.isfinite(dx)) & jnp.all(jnp.isfinite(dy))
                  & jnp.all(jnp.isfinite(dz)) & jnp.all(jnp.isfinite(dw))
                  & jnp.all(jnp.isfinite(ds)))
            ap = jnp.where(ok, ap, 0.0)
            ad = jnp.where(ok, ad, 0.0)
            dx = jnp.where(ok, dx, 0.0)
            dy = jnp.where(ok, dy, 0.0)
            dz = jnp.where(ok, dz, 0.0)
            dw = jnp.where(ok, dw, 0.0)
            ds = jnp.where(ok, ds, 0.0)
            x = x + ap * dx
            s = jnp.where(has_ub, s + ap * ds, s)
            y = y + ad * dy
            z = z + ad * dz
            w = jnp.where(has_ub, w + ad * dw, w)
            # convergence check
            r_p2, r_d2, _ = residuals(x, y, z, w, s)
            mu2 = mu_of(x, z, s, w)
            # the mean complementarity alone lets the objective's error
            # grow with the number of columns (to ~1e-5 at 4,096 tasks
            # for mu < 1e-7): the duality gap relative to the objective
            # must pass the tolerance too
            done = ((jnp.linalg.norm(r_p2) / b_norm < tol)
                    & (jnp.linalg.norm(r_d2) / c_norm < tol)
                    & (mu2 < tol) & (rel_gap_of(x, z, s, w) < tol))
            if f32:
                bad = (carry.bad | (~ok) | (rel_a > _F32_REFINE_RTOL)
                       | (rel_c > _F32_REFINE_RTOL))
                # graduation is sticky: once a row needs the f64 path it
                # never returns to f32 (mu is not monotone step-to-step)
                grad = carry.grad | (mu2 <= _F32_SWITCH_MU) | bad
                it32 = carry.it32 + 1
            else:
                bad, grad, it32 = carry.bad, carry.grad, carry.it32
                # a rejected float64 step leaves the iterate as it was,
                # so every later iteration would repeat it (an infeasible
                # node diverges until its Newton system breaks down): the
                # row stops where it is, and ``report`` says whether that
                # is close enough to count as converged
                done = done | ~ok
            return _IPMCarry(x, y, z, w, s, carry.it + 1, it32, done, bad,
                             grad)

        return body

    def report(carry: _IPMCarry):
        """Relative primal and dual residuals, and the larger of the mean
        complementarity and the duality gap relative to the objective: a
        row that stops short of ``tol`` (cap or rejected step) counts as
        converged (:attr:`LPSolution.converged`) only if its objective is
        that close too."""
        x, z, s, w = carry.x, carry.z, carry.s, carry.w
        r_p, r_d, _ = residuals(x, carry.y, z, w, s)
        return (jnp.linalg.norm(r_p) / b_norm,
                jnp.linalg.norm(r_d) / c_norm,
                jnp.maximum(mu_of(x, z, s, w), rel_gap_of(x, z, s, w)))

    return init, make_body, report


def _run_ipm(carry: _IPMCarry, make_body, iter_cap, newton_dtype: str
             ) -> _IPMCarry:
    """Iterate one IPM instance to ``iter_cap`` total iterations (a traced
    per-row cap under the chunked driver).  The mixed-precision path runs
    two phases: f32 Newton until the row graduates (small mu or a bad
    refined residual), then f64 Newton, through the XLA Cholesky, to
    convergence."""
    if newton_dtype == "float32":
        body32 = make_body("float32")

        def cond32(cr: _IPMCarry):
            return (~cr.done) & (~cr.grad) & (cr.it < iter_cap)

        carry = jax.lax.while_loop(cond32, body32, carry)
    body = make_body("float64", graduated=newton_dtype == "float32")

    def cond(cr: _IPMCarry):
        return (~cr.done) & (cr.it < iter_cap)

    return jax.lax.while_loop(cond, body, carry)


@functools.partial(jax.jit, static_argnames=("max_iters", "linsolve",
                                             "newton_dtype"))
def _solve_std(a, b, c, u, tol=_TOL, active=True, *,
               max_iters: int = _MAX_ITERS, linsolve: str = "xla",
               newton_dtype: str = "float64"):
    """``tol`` is a traced scalar (changing it does not recompile): B&B
    node solves bound at ~1e-7 while reference solves keep 1e-9.

    ``active`` (traced bool) is the per-row early-exit hook: an inactive
    solve starts with its ``done`` flag already set, so under ``vmap`` it
    contributes zero iterations to the batch (the while-loop trip count is
    the max over ACTIVE rows) and reports ``iters == 0``.  ``linsolve``
    (static) picks the Newton normal-equation backend (:data:`LINSOLVES`)
    and ``newton_dtype`` (static) its precision (:data:`NEWTON_DTYPES`).
    """
    init, make_body, report = _ipm_ops(a, b, c, u, tol, linsolve)
    carry = _run_ipm(init(active), make_body, max_iters, newton_dtype)
    rp, rd, mu = report(carry)
    return (carry.x, carry.y, carry.it, rp, rd, mu, carry.it32, carry.bad)


def _base_ndims(a_eq, b_eq, g) -> tuple:
    """The unbatched ndim of each of an LP's seven arguments, a pytree
    like them: a node LP's :class:`~repro.core.problem.NodeRows` in
    ``a_eq`` (``b_eq`` and ``g`` None), or dense rows."""
    if isinstance(a_eq, NodeRows):
        if b_eq is not None or g is not None:
            raise ValueError("node rows (NodeRows in a_eq) take b_eq=None "
                             "and g=None")
        return (1, NodeRows(2, 1, 1), None, None, 1, 1, 1)
    return (1, 2, 1, 2, 1, 1, 1)


def _on_device(args, dt):
    """An LP's seven arguments as ``dt`` device arrays (None stays)."""
    return jax.tree.map(lambda v: jnp.asarray(v, dt), tuple(args))


def solve_lp(c, a_eq, b_eq, g, h, lb, ub, *, max_iters: int = _MAX_ITERS,
             linsolve: str = "xla", newton_dtype: str = "float64"
             ) -> LPSolution:
    """Solve the bounded LP.  All inputs numpy/JAX arrays; float64 advised.
    The rows come densely, or as a node LP's :class:`NodeRows` in ``a_eq``
    (``b_eq`` and ``g`` None), solved through :class:`NodeOp`."""
    dt = jnp.float64
    newton_dtype = _canon_newton_dtype(newton_dtype)
    _base_ndims(a_eq, b_eq, g)          # refuses node rows with b_eq or g
    arrs = _on_device((c, a_eq, b_eq, g, h, lb, ub), dt)
    if isinstance(a_eq, NodeRows):
        solve = _single_node_solver(max_iters, linsolve, newton_dtype)
        return solve(jnp.asarray(_TOL, dt), jnp.asarray(True), *arrs)[0]
    std = _standardise(*arrs)
    x, y, it, rp, rd, gap, _, _ = _solve_std(std.a, std.b, std.c, std.u,
                                             max_iters=max_iters,
                                             linsolve=linsolve,
                                             newton_dtype=newton_dtype)
    x_orig = x[:std.n_orig] * std.col_scale[:std.n_orig] + std.lb
    y_orig = y * std.row_scale
    obj = arrs[0] @ x_orig
    return LPSolution(x_orig, obj, y_orig, it, rp, rd, gap)


def solve_node_lp(node, *, max_iters: int = _MAX_ITERS,
                  linsolve: str = "xla", newton_dtype: str = "float64"
                  ) -> LPSolution:
    """Solve one :class:`repro.core.problem.NodeLP` through its border."""
    return solve_lp(node.c, node.rows, None, None, node.h, node.lb,
                    node.ub, max_iters=max_iters, linsolve=linsolve,
                    newton_dtype=newton_dtype)


# ---------------------------------------------------------------------------
# Batched engine
# ---------------------------------------------------------------------------
# -- mesh helpers (row-sharded megabatches; docs/solver.md "Sharded
# megabatches").  LP rows are embarrassingly data-parallel, so sharding
# is pure row partitioning: each shard runs the SAME driver on its own
# row block and the only cross-shard traffic is the two per-chunk host
# scalars of the compacted driver (a pmax and a psum).

def _lp_row_axes(mesh, row_spec=None):
    from repro.runtime.sharding import lp_row_axes
    return lp_row_axes(mesh, row_spec)


def mesh_n_shards(mesh, row_spec=None) -> int:
    """Number of row shards ``mesh`` yields for stacked megabatches (the
    product of its row-axis sizes; 1 when ``mesh is None``)."""
    if mesh is None:
        return 1
    return _n_shards_of(mesh, _lp_row_axes(mesh, row_spec))


def _n_shards_of(mesh, row_axes) -> int:
    return int(np.prod([mesh.shape[a] for a in row_axes], dtype=np.int64)) \
        if mesh is not None else 1


def _mesh_shape_of(mesh, row_axes):
    """Logical mesh identity recorded in every stacked compile-event
    config (the ``mesh_shape`` key): ``((axis, size), ...)`` over the
    row axes, or None for unsharded solves — so attribution filters
    built for one mesh can never silently match solves run under
    another (or under no mesh at all)."""
    if mesh is None:
        return None
    return tuple((a, int(mesh.shape[a])) for a in row_axes)


def _mesh_shape_key(mesh, row_spec=None):
    return (None if mesh is None
            else _mesh_shape_of(mesh, _lp_row_axes(mesh, row_spec)))


def _mesh_key_of(mesh, row_axes):
    """jit-cache identity of a mesh: logical shape PLUS device ids — the
    same logical mesh over different devices is a different executable."""
    if mesh is None:
        return None
    return (_mesh_shape_of(mesh, row_axes),
            tuple(int(d.id) for d in mesh.devices.flat))


def _partitioner(mesh):
    """GSPMD for sharded dispatches (see ``solver_partitioner``)."""
    if mesh is None:
        return contextlib.nullcontext()
    from repro.runtime.sharding import solver_partitioner
    return solver_partitioner()


def _row_pspec(row_axes):
    from jax.sharding import PartitionSpec as PS
    return PS(row_axes if len(row_axes) > 1 else row_axes[0])


# jit'd stacked-solver variants (monolithic vmapped IPMs, chunk preps,
# chunk steppers, ...) keyed by configuration, plus the set of distinct
# call signatures (pattern + shapes) seen so far — the basis of
# :func:`stacked_compile_count`, which lets long-running consumers (the
# spot-market simulator's replan loop) ASSERT that a fixed-width problem
# representation really does reuse one compiled solver.
_STACKED_SOLVERS: dict = {}
_STACKED_SIGNATURES: set = set()


def _registered_jit(key, build):
    fn = _STACKED_SOLVERS.get(key)
    if fn is None:
        fn = build()
        _STACKED_SOLVERS[key] = fn
    return fn


def _is_node(axes) -> bool:
    """Whether a batching pattern (the seven arguments' axes) is a node
    LP's: its ``a_eq`` entry is a :class:`NodeRows`."""
    return isinstance(axes[1], NodeRows)


def _named(fn, node: bool, suffix: str = ""):
    """Name a program that solves node LPs so its device ops are told
    apart in a trace (module ``jit_node_ipm*``); others keep their
    names."""
    if node:
        fn.__name__ = NODE_IPM + suffix
    return fn


def _stacked_one(max_iters: int, linsolve: str, newton_dtype: str):
    """One row of the monolithic stacked solve: standardise, run the IPM
    to convergence, un-standardise.  Shared by the single-device
    jit(vmap) driver and the per-shard body of the sharded driver.  The
    row's seven arguments come as :func:`_standardise` takes them,
    objective first."""
    def one(tol, active, *arrs):
        std = _standardise(*arrs)
        x, y, it, rp, rd, gap, it32, bad = _solve_std(
            std.a, std.b, std.c, std.u, tol, active,
            max_iters=max_iters, linsolve=linsolve,
            newton_dtype=newton_dtype)
        xo = x[:std.n_orig] * std.col_scale[:std.n_orig] + std.lb
        return (LPSolution(xo, arrs[0] @ xo, y * std.row_scale, it, rp, rd,
                           gap), it32, bad)

    return one


@functools.lru_cache(maxsize=None)
def _single_node_solver(max_iters: int, linsolve: str, newton_dtype: str):
    """jit of one node LP's whole solve, standardisation included: a
    single node LP costs one dispatch instead of the standardisation's
    eager ops."""
    return jax.jit(_named(_stacked_one(max_iters, linsolve, newton_dtype),
                          True))


def _stacked_solver(axes, max_iters: int, linsolve: str, newton_dtype: str):
    """jit(vmap(IPM)) for a given batching pattern; cached so the whole
    batched sweep compiles exactly once per (pattern, shape).  The per-row
    ``active`` mask always batches (axis 0): inactive rows retire at
    iteration zero, and under the Pallas backend each Newton step of the
    whole batch is ONE blocked batched-Cholesky kernel launch."""
    def build():
        one = _named(_stacked_one(max_iters, linsolve, newton_dtype),
                     _is_node(axes))
        return jax.jit(jax.vmap(one, in_axes=(None, 0) + axes))

    return _registered_jit((axes, max_iters, linsolve, newton_dtype), build)


def _stacked_solver_sharded(axes, max_iters: int, linsolve: str,
                            newton_dtype: str, mesh, row_axes):
    """jit(shard_map(vmap(IPM))) over the mesh's row axes: every shard
    runs the monolithic lockstep driver on its own row block, so a
    shard's while-loop retires as soon as ITS slowest row converges —
    stragglers stall only the shard that holds them, which is also why
    sharding speeds up even a lockstep (CPU/SIMD) backend.  LP rows are
    independent, so the program contains NO collectives
    (``check_vma=False`` because the replication checker has no rule
    for ``lax.while_loop``)."""
    from jax.sharding import PartitionSpec as PS

    def build():
        one = _stacked_one(max_iters, linsolve, newton_dtype)
        vmapped = jax.vmap(one, in_axes=(None, 0) + axes)
        rspec = _row_pspec(row_axes)
        in_specs = (PS(), rspec) + jax.tree.map(
            lambda ax: rspec if ax == 0 else PS(), axes,
            is_leaf=lambda ax: ax is None)
        return jax.jit(_named(jax.shard_map(vmapped, mesh=mesh,
                                            in_specs=in_specs,
                                            out_specs=rspec,
                                            check_vma=False),
                              _is_node(axes)))

    return _registered_jit(("sharded", axes, max_iters, linsolve,
                            newton_dtype, _mesh_key_of(mesh, row_axes)),
                           build)


def stacked_compile_count() -> int:
    """Number of distinct compiled variants of the stacked IPM solver in
    this process (monolithic vmapped solvers AND every chunked-driver
    prep/init/chunk variant).  Uses the jit cache size when the runtime
    exposes it; otherwise counts distinct call signatures (``jax.jit``
    guarantees a cache hit for an identical signature, so both measure
    recompiles).  A fixed-shape caller can assert this stays flat across
    calls."""
    sizes = [getattr(fn, "_cache_size", None)
             for fn in _STACKED_SOLVERS.values()]
    if sizes and all(s is not None for s in sizes):
        return sum(int(s()) for s in sizes)
    return len(_STACKED_SIGNATURES)


# Newton-row accounting for the per-row early-exit / chunked-compaction
# paths.  One "Newton row" is one row of the stacked batch paying one IPM
# iteration.  The lockstep baseline charges every row for every iteration
# of its call (the SIMD batch iterates until its slowest active member
# converges); the early-exit ledger charges each row only for the
# iterations it actually ran, and ``compact_rows`` records what the
# chunked driver really paid (buffer width x chunk trips, summed).
# ``solver_bench`` reports the reductions.
#
# The ledger lives in the process-wide ``repro.obs`` metrics registry
# (counters ``lp.newton.*`` plus the raw per-row iteration histogram
# ``lp.newton.iters``): every record is one atomic registry update, so
# concurrent recorders (an ``AllocationServer`` scheduler thread next to
# the main thread) never lose counts, and ``obs.snapshot()`` reports the
# ledger alongside serving/market metrics.  The functions below keep the
# historical dict-shaped API.
_NEWTON_KEYS = ("calls", "lockstep_rows", "active_rows", "compact_rows",
                "f32_rows", "f64_rows", "fallback_rows",
                "nonconverged_rows")


def reset_newton_row_stats() -> None:
    obs.REGISTRY.reset("lp.newton")


def newton_row_stats() -> dict:
    """Snapshot of the Newton-row ledger since the last reset:

    * ``calls`` — stacked solver calls recorded;
    * ``lockstep_rows`` — what pure lockstep would pay (batch width times
      the slowest active row, per call);
    * ``active_rows`` — what per-row early exit pays (each row charged
      only its own iterations);
    * ``compact_rows`` — what the executing driver actually paid: equal
      to ``lockstep_rows`` for monolithic calls, and the sum of (buffer
      width x chunk trip count) for chunked/compacted calls;
    * ``f32_rows`` / ``f64_rows`` — active row-iterations taken on the
      float32 vs float64 Newton path;
    * ``fallback_rows`` — rows whose refined f32 residual exceeded
      tolerance and fell back to the full-f64 path;
    * ``nonconverged_rows`` — active rows whose FINAL residuals missed
      tolerance (residual-classified: a row that converged exactly at
      ``max_iters`` does not count);
    * ``hist`` — per-row IPM-iteration histogram (10-iteration buckets).

    Use :func:`newton_ledger` to scope accumulation to one top-level
    solve or benchmark run.
    """
    out = {k: int(obs.read_counter(f"lp.newton.{k}")) for k in _NEWTON_KEYS}
    hist: dict = {}
    for it in obs.read_hist("lp.newton.iters"):
        b = 10 * int(it // 10)
        hist[b] = hist.get(b, 0) + 1
    out["hist"] = hist
    return out


@contextlib.contextmanager
def newton_ledger():
    """Scope the Newton-row ledger to a with-block.

    Counters accumulate from zero inside the block; on exit the yielded
    dict is filled with the scoped totals and the surrounding ledger is
    restored with the scoped counts merged in (so an outer scope still
    sees everything).  Back-to-back benchmark runs each get their own
    ledger instead of mixing into the module-level counters::

        with lp.newton_ledger() as led:
            pareto.milp_tradeoff_batched(problem, ...)
        print(led["active_rows"], led["lockstep_rows"])

    This is a thin wrapper over the generic ``obs.scope()`` registry
    frame — the scope covers EVERY metric recorded inside the block, so
    serving/market counters nest the same way; the yielded dict keeps
    the historical ledger shape.
    """
    with obs.scope():
        scoped: dict = {}
        try:
            yield scoped
        finally:
            scoped.update(newton_row_stats())


def _record_newton_rows(iters, active, converged=None, it32=None, bad=None,
                        compact_rows=None) -> dict:
    """Record one stacked call in the ledger; returns the call's
    ``active_rows``, ``paid_rows`` (the ledger's ``compact_rows``: what
    the executing driver paid) and ``iters_max``."""
    iters = np.asarray(iters)
    active = np.asarray(active)
    act = iters[active]
    if act.size == 0:
        return dict(active_rows=0, paid_rows=0, iters_max=0)
    lockstep = int(iters.shape[0] * act.max())
    n_act = int(act.sum())
    paid = lockstep if compact_rows is None else int(compact_rows)
    counters = {
        "lp.newton.calls": 1,
        "lp.newton.lockstep_rows": lockstep,
        "lp.newton.active_rows": n_act,
        "lp.newton.compact_rows": paid,
    }
    if it32 is not None:
        f32 = int(np.asarray(it32)[active].sum())
        counters["lp.newton.f32_rows"] = f32
        counters["lp.newton.f64_rows"] = n_act - f32
    else:
        counters["lp.newton.f64_rows"] = n_act
    if bad is not None:
        counters["lp.newton.fallback_rows"] = \
            int(np.asarray(bad)[active].sum())
    if converged is not None:
        counters["lp.newton.nonconverged_rows"] = \
            int((~np.asarray(converged))[active].sum())
    # one atomic registry update per stacked call: concurrent recorders
    # (server scheduler thread + main thread) cannot interleave halves
    obs.update(counters=counters,
               observations={"lp.newton.iters": act.tolist()})
    return dict(active_rows=n_act, paid_rows=paid, iters_max=int(act.max()))


# ---------------------------------------------------------------------------
# Chunked driver: mid-call batch compaction over a fixed width ladder
# ---------------------------------------------------------------------------

def _ladder_widths(batch: int) -> list:
    """Fixed buffer-width ladder for mid-call compaction: the full batch
    width plus every power of two below it.  One compiled chunk-stepper
    variant per width, shared across chunks, calls and episodes — this is
    what bounds :func:`stacked_compile_count` by the number of distinct
    widths rather than the (data-dependent) number of compactions."""
    widths = {batch}
    w = 1
    while w < batch:
        widths.add(w)
        w <<= 1
    return sorted(widths, reverse=True)


def _next_width(n_active: int, widths) -> int:
    return min(w for w in widths if w >= n_active)


def _chunk_prep(axes):
    """jit(vmap(standardise)) for a batching pattern: broadcasts every
    LP array to the full batch so the compaction gather is a plain row
    permutation of the standard-form buffers."""
    def build():
        def prep(*arrs):
            std = _standardise(*arrs)
            return (std.a, std.b, std.c, std.u, std.lb, std.row_scale,
                    std.col_scale)

        return jax.jit(jax.vmap(_named(prep, _is_node(axes), "_prep"),
                                in_axes=axes))

    return _registered_jit(("chunk-prep", axes), build)


def _chunk_init():
    """Vmapped cold start over standard-form buffers."""
    def build():
        def init_one(a, b, c, u, active):
            init, _, _ = _ipm_ops(a, b, c, u, jnp.asarray(_TOL, a.dtype),
                                  "xla")
            return init(active)

        return jax.jit(jax.vmap(init_one))

    return _registered_jit(("chunk-init",), build)


def _chunk_step_one(chunk_iters: int, max_iters: int, linsolve: str,
                    newton_dtype: str):
    """One row's chunk step: advance by up to ``chunk_iters`` further IPM
    iterations (capped at the row's own ``it + chunk_iters`` and globally
    at ``max_iters``) and report the end-of-chunk residuals.  Shared by
    the host-compaction stepper and the fused device-side merge step so
    both compaction modes run the exact same row math."""
    def step_one(tol, a, b, c, u, carry):
        _, make_body, report = _ipm_ops(a, b, c, u, tol, linsolve)
        cap = jnp.minimum(carry.it + chunk_iters, max_iters)
        out = _run_ipm(carry, make_body, cap, newton_dtype)
        rp, rd, mu = report(out)
        return out, rp, rd, mu

    return step_one


def _chunk_stepper(chunk_iters: int, max_iters: int, linsolve: str,
                   newton_dtype: str, node: bool):
    """Vmapped chunk step over a whole buffer (host-compaction mode);
    ``node`` names the program (:func:`_named`)."""
    def build():
        step_one = _chunk_step_one(chunk_iters, max_iters, linsolve,
                                   newton_dtype)
        return jax.jit(jax.vmap(_named(step_one, node, "_chunk"),
                                in_axes=(None, 0, 0, 0, 0, 0)))

    return _registered_jit(("chunk-step", chunk_iters, max_iters, linsolve,
                            newton_dtype, node), build)


def _chunk_merge_stepper(width: int, chunk_iters: int, max_iters: int,
                         linsolve: str, newton_dtype: str, node: bool,
                         mesh=None, row_axes=None):
    """Fused per-width device program for in-jit compaction: gather the
    ``width``-row alive prefix of the full-batch buffers, step it, write
    it back, and compact — a stable argsort over the whole buffer moves
    the still-alive rows to the front and carries the slot→original-row
    permutation along.  Everything (carry, residuals, permutation) stays
    on device in strong dtypes; only TWO scalars (alive count, lockstep
    trip count) ever reach the host per chunk, so the ladder's
    width-selection control flow costs one tiny transfer instead of the
    legacy full-carry round-trip.

    Under a ``mesh`` the whole program runs inside ``shard_map`` over
    the row axes and ``width`` is the PER-SHARD buffer width: survivors
    never cross shards (the argsort+gather compaction is shard-local, a
    pure row permutation of the shard's own block), so the hot loop has
    no collectives — only the two host scalars do: the next buffer
    width must hold the LARGEST shard's survivor count (``pmax``) and
    the trip accounting SUMS the per-shard lockstep trips (``psum``).
    ``node`` names the program (:func:`_named`)."""
    step_one = _chunk_step_one(chunk_iters, max_iters, linsolve,
                               newton_dtype)

    def merge(tol, a_f, b_f, c_f, u_f, carry, rp_f, rd_f, mu_f, perm):
        idx = perm[:width]
        prev = jax.tree.map(lambda f: f[:width], carry)
        it_prev, it32_prev = prev.it, prev.it32
        out, rp_w, rd_w, mu_w = jax.vmap(
            step_one, in_axes=(None, 0, 0, 0, 0, 0))(
            tol, jax.tree.map(lambda f: f[idx], a_f), b_f[idx], c_f[idx],
            u_f[idx], prev)
        carry = jax.tree.map(lambda f, pre: f.at[:width].set(pre),
                             carry, out)
        rp_f = rp_f.at[:width].set(rp_w)
        rd_f = rd_f.at[:width].set(rd_w)
        mu_f = mu_f.at[:width].set(mu_w)
        # a mixed-precision chunk serialises an f32 phase and an f64
        # phase: the lockstep trips actually executed are the max f32
        # advance PLUS the max f64 advance over the prefix
        d32 = out.it32 - it32_prev
        d64 = (out.it - out.it32) - (it_prev - it32_prev)
        trips = (jnp.maximum(jnp.max(d32), 0)
                 + jnp.maximum(jnp.max(d64), 0))
        alive_w = (~out.done) & (out.it < max_iters)
        n_alive = jnp.sum(alive_w.astype(jnp.int32))
        batch = perm.shape[0]
        alive_f = jnp.zeros((batch,), bool).at[:width].set(alive_w)
        order = jnp.argsort(~alive_f, stable=True)
        carry = jax.tree.map(lambda f: f[order], carry)
        if mesh is not None:
            n_alive = jax.lax.pmax(n_alive, row_axes)
            trips = jax.lax.psum(trips, row_axes)
        return (carry, rp_f[order], rd_f[order], mu_f[order],
                perm[order], n_alive, trips)

    def build():
        if mesh is None:
            return jax.jit(_named(merge, node, "_chunk"))
        from jax.sharding import PartitionSpec as PS
        rspec = _row_pspec(row_axes)
        return jax.jit(_named(jax.shard_map(
            merge, mesh=mesh, in_specs=(PS(),) + (rspec,) * 9,
            out_specs=(rspec,) * 5 + (PS(), PS()), check_vma=False),
            node, "_chunk"))

    return _registered_jit(("chunk-merge", width, chunk_iters, max_iters,
                            linsolve, newton_dtype, node,
                            _mesh_key_of(mesh, row_axes)), build)


def _chunk_finalize(n_orig: int, mesh=None, row_axes=None,
                    c_batched: bool = True):
    """On-device epilogue of the device-compacted driver: invert the
    slot→row permutation and un-standardise, so the caller receives
    device arrays already restored to the INPUT row order (no host
    scatter, no NumPy round-trip).  Under a ``mesh`` the inversion runs
    inside ``shard_map``: the permutation holds SHARD-LOCAL slot
    indices, so a global argsort would interleave rows across shards —
    each shard must invert (and gather) only its own block."""
    def fin(carry, rp, rd, mu, perm, c0, lb, csc, rsc):
        inv = jnp.argsort(perm)
        xo = (carry.x[inv][:, :n_orig] * csc[:, :n_orig]) + lb
        obj = (xo @ c0 if c0.ndim == 1
               else jnp.einsum("bn,bn->b", c0, xo))
        return (xo, obj, carry.y[inv] * rsc, carry.it[inv], rp[inv],
                rd[inv], mu[inv], carry.it32[inv], carry.bad[inv])

    def build():
        if mesh is None:
            return jax.jit(fin)
        from jax.sharding import PartitionSpec as PS
        rspec = _row_pspec(row_axes)
        c_spec = rspec if c_batched else PS()
        return jax.jit(jax.shard_map(
            fin, mesh=mesh,
            in_specs=(rspec,) * 5 + (c_spec,) + (rspec,) * 3,
            out_specs=rspec, check_vma=False))

    return _registered_jit(("chunk-finalize", n_orig,
                            _mesh_key_of(mesh, row_axes), c_batched), build)


# (row shapes, chunk config, widths) ladders already pre-compiled
_WARMED_LADDERS: set = set()


def _warm_compact_ladder(widths, a_h, b_h, c_h, u_h, init_fn, step_fn,
                         tol_dev) -> None:
    """Pre-compile every ladder width with an all-retired dummy buffer
    (while-loop trip count zero, so each warm call costs one compile and
    microseconds of run time).  After the FIRST chunked call for a given
    shape/config, ``stacked_compile_count`` is already final: compaction
    can never recompile mid-call or mid-episode."""
    for w in widths:
        aw, bw, cw, uw = jax.tree.map(
            lambda v: jnp.asarray(np.broadcast_to(v[:1], (w,) + v.shape[1:])),
            (a_h, b_h, c_h, u_h))
        carry = init_fn(aw, bw, cw, uw, jnp.zeros((w,), dtype=bool))
        step_fn(tol_dev, aw, bw, cw, uw, carry)


def _row_shapes(tree) -> tuple:
    """Per-row shapes of a batched operator or array (a cache key)."""
    return tuple(v.shape[1:] for v in jax.tree.leaves(tree))


def _solve_stacked_compact(arrs, axes, batch: int, tol, active, *,
                           max_iters: int, chunk_iters: int, linsolve: str,
                           newton_dtype: str, compact_mode: str = "device",
                           mesh=None, row_axes=None):
    """The chunked stacked driver (``compact=True``).

    Newton steps run in chunks of ``chunk_iters``; between chunks the
    still-active rows are gathered to the front of the smallest ladder
    buffer that holds them (tail padded with retired rows) so the late
    while-loop trips are paid only by the stragglers.  Row math is
    identical to the monolithic driver (vmapped rows are independent and
    chunk boundaries do not change the iteration), and the output is
    restored to the ORIGINAL row order.

    ``compact_mode`` picks where the between-chunk gather runs:
    ``"device"`` (default) keeps carry/residual/permutation state on
    device and compacts with an in-jit stable argsort+gather — one
    two-scalar transfer per chunk; ``"host"`` is the legacy path that
    round-trips the whole carry through NumPy between chunks (useful as
    a parity oracle and on hosts where tiny transfers are cheap).

    Returns ``(LPSolution, it32, bad, compact_rows)`` with batch-ordered
    fields; ``compact_rows`` is the Newton-row cost actually paid
    (sum over chunks of buffer width x trip count).
    """
    dt = jnp.float64
    a, b, c, u, lb, rsc, csc = _chunk_prep(axes)(*arrs)
    n_orig = arrs[0].shape[-1]
    n_shards = _n_shards_of(mesh, row_axes)
    # per-SHARD ladder: each shard compacts its own block, so the widths
    # that matter (and compile) are local; global width = local x shards
    widths = _ladder_widths(batch // n_shards)
    init_fn = _chunk_init()
    tol_dev = jnp.asarray(tol, dt)
    if compact_mode == "device":
        return _compact_device(
            arrs, a, b, c, u, lb, rsc, csc, batch, n_orig, widths, init_fn,
            tol_dev, active, max_iters=max_iters, chunk_iters=chunk_iters,
            linsolve=linsolve, newton_dtype=newton_dtype, mesh=mesh,
            row_axes=row_axes)
    step_fn = _chunk_stepper(chunk_iters, max_iters, linsolve, newton_dtype,
                             isinstance(a, NodeOp))

    a_h, b_h, c_h, u_h = jax.tree.map(np.asarray, (a, b, c, u))
    warm_key = ("host", _row_shapes(a_h), chunk_iters, max_iters, linsolve,
                newton_dtype, tuple(widths))
    if warm_key not in _WARMED_LADDERS:
        with obs.span("lp.warm_compact_ladder", widths=tuple(widths),
                      mode="host"):
            _warm_compact_ladder(widths, a_h, b_h, c_h, u_h, init_fn,
                                 step_fn, tol_dev)
        _WARMED_LADDERS.add(warm_key)

    carry = init_fn(a, b, c, u, jnp.asarray(active, dtype=bool))
    cur = (a, b, c, u)
    width = batch
    orig = np.arange(batch)              # buffer slot -> original row
    it_prev = np.zeros(batch, dtype=np.int64)
    it32_prev = np.zeros(batch, dtype=np.int64)
    out = {
        "x": np.zeros((batch, c_h.shape[1])),
        "y": np.zeros((batch, b_h.shape[1])),
        "it": np.zeros(batch, dtype=np.int64),
        "it32": np.zeros(batch, dtype=np.int64),
        "bad": np.zeros(batch, dtype=bool),
        "rp": np.zeros(batch), "rd": np.zeros(batch), "mu": np.zeros(batch),
    }
    compact_rows = 0
    # every chunk advances every active row by >= 1 iteration, so
    # max_iters chunks always suffice; +2 pads the all-retired first call
    for _ in range(max_iters + 2):
        with obs.span("lp.chunk", width=width):
            carry, rp, rd, mu = step_fn(tol_dev, *cur, carry)
            # one transfer per chunk
            host = jax.device_get((carry, rp, rd, mu))
        ch = dict(zip(_IPMCarry._fields, host[0]))
        rp_h, rd_h, mu_h = host[1:]
        valid = orig >= 0
        vi = orig[valid]
        out["x"][vi] = ch["x"][valid]
        out["y"][vi] = ch["y"][valid]
        out["it"][vi] = ch["it"][valid]
        out["it32"][vi] = ch["it32"][valid]
        out["bad"][vi] = ch["bad"][valid]
        out["rp"][vi], out["rd"][vi] = rp_h[valid], rd_h[valid]
        out["mu"][vi] = mu_h[valid]
        # a mixed-precision chunk serialises an f32 phase and an f64
        # phase: the lockstep trips actually executed are the max f32
        # advance PLUS the max f64 advance over the buffer (a plain max
        # of total advances would under-count when rows split phases)
        d32 = ch["it32"] - it32_prev
        d64 = (ch["it"] - ch["it32"]) - (it_prev - it32_prev)
        trips = (int(max(d32.max(initial=0), 0))
                 + int(max(d64.max(initial=0), 0)))
        compact_rows += width * trips
        alive = valid & ~ch["done"] & (ch["it"] < max_iters)
        idx = np.flatnonzero(alive)
        if idx.size == 0:
            break
        w_next = _next_width(int(idx.size), widths)
        if w_next < width:
            # compact: survivors to the front, tail padded with retired
            # copies of the first survivor (done=True -> zero trips)
            with obs.span("lp.compact_gather", from_width=width,
                          to_width=w_next, survivors=int(idx.size)):
                take = np.concatenate([idx, np.repeat(idx[:1],
                                                      w_next - idx.size)])
                fields = {f: np.array(ch[f][take])
                          for f in _IPMCarry._fields}
                fields["done"][idx.size:] = True
                carry = _IPMCarry(**{f: jnp.asarray(v)
                                     for f, v in fields.items()})
                # the std-form buffers live in ORIGINAL row order: gather
                # by the surviving rows' original indices, not buffer
                # slots
                src = orig[take]
                cur = jax.tree.map(lambda v: jnp.asarray(v[src]),
                                   (a_h, b_h, c_h, u_h))
                orig = src
                orig[idx.size:] = -1
                width = w_next
                it_prev = fields["it"][:]
                it32_prev = fields["it32"][:]
        else:
            it_prev = ch["it"]
            it32_prev = ch["it32"]

    lb_h = np.broadcast_to(np.asarray(lb), (batch, n_orig))
    csc_h = np.broadcast_to(np.asarray(csc), (batch,) + csc.shape[1:])
    rsc_h = np.broadcast_to(np.asarray(rsc), (batch,) + rsc.shape[1:])
    xo = out["x"][:, :n_orig] * csc_h[:, :n_orig] + lb_h
    c0 = np.asarray(arrs[0], dtype=np.float64)
    obj = xo @ c0 if c0.ndim == 1 else np.einsum("bn,bn->b", c0, xo)
    sol = LPSolution(jnp.asarray(xo), jnp.asarray(obj),
                     jnp.asarray(out["y"] * rsc_h), jnp.asarray(out["it"]),
                     jnp.asarray(out["rp"]), jnp.asarray(out["rd"]),
                     jnp.asarray(out["mu"]))
    return sol, out["it32"], out["bad"], compact_rows


def _compact_device(arrs, a, b, c, u, lb, rsc, csc, batch, n_orig, widths,
                    init_fn, tol_dev, active, *, max_iters: int,
                    chunk_iters: int, linsolve: str, newton_dtype: str,
                    mesh=None, row_axes=None):
    """Device-side compaction: the full-batch standard-form buffers stay
    resident on device in ORIGINAL row order and the carry lives at full
    width, permuted alive-rows-first.  Each chunk runs ONE fused compiled
    program per ladder width (gather prefix → step → write back → stable
    argsort+gather compact); the host only reads two scalars per chunk to
    pick the next width, and a jitted epilogue inverts the permutation so
    the returned :class:`LPSolution` holds device arrays already in input
    row order.  All carried state uses strong dtypes — the ROADMAP's
    named pitfall — so :func:`stacked_compile_count` stays flat after the
    first (warmed) call.

    Under a ``mesh``, ``widths`` is the per-shard ladder and every fused
    chunk/finalize program is shard_mapped over the row axes (see
    :func:`_chunk_merge_stepper`); the permutation buffer holds
    SHARD-LOCAL slot indices (``tile(arange(local), n_shards)``), so the
    in-shard gathers stay in bounds and compaction never moves a row
    across shards."""
    n_shards = _n_shards_of(mesh, row_axes)
    local = batch // n_shards
    merge_fns = {w: _chunk_merge_stepper(w, chunk_iters, max_iters,
                                         linsolve, newton_dtype,
                                         isinstance(a, NodeOp), mesh=mesh,
                                         row_axes=row_axes)
                 for w in widths}
    fin_fn = _chunk_finalize(n_orig, mesh=mesh, row_axes=row_axes,
                             c_batched=arrs[0].ndim == 2)
    zeros = jnp.zeros((batch,), jnp.float64)
    perm0 = jnp.asarray(np.tile(np.arange(local, dtype=np.int32), n_shards))

    warm_key = ("device", _row_shapes(a), chunk_iters, max_iters,
                linsolve, newton_dtype, tuple(widths),
                _mesh_key_of(mesh, row_axes))
    if warm_key not in _WARMED_LADDERS:
        # all-retired warm call per width: zero while-loop trips, so each
        # costs one compile + microseconds; after the FIRST device-
        # compacted call the compile count is final
        with obs.span("lp.warm_compact_ladder", widths=tuple(widths),
                      mode="device"):
            cold = init_fn(a, b, c, u, jnp.zeros((batch,), dtype=bool))
            for w in widths:
                merge_fns[w](tol_dev, a, b, c, u, cold, zeros, zeros,
                             zeros, perm0)
            fin_fn(cold, zeros, zeros, zeros, perm0, arrs[0], lb, csc, rsc)
        _WARMED_LADDERS.add(warm_key)

    carry = init_fn(a, b, c, u, jnp.asarray(active, dtype=bool))
    rp = rd = mu = zeros
    perm = perm0
    width = local
    compact_rows = 0
    # every chunk advances every active row by >= 1 iteration, so
    # max_iters chunks always suffice; +2 pads the all-retired first call
    for _ in range(max_iters + 2):
        with obs.span("lp.chunk", width=width, mode="device"):
            carry, rp, rd, mu, perm, n_alive, trips = merge_fns[width](
                tol_dev, a, b, c, u, carry, rp, rd, mu, perm)
            # the ONLY per-chunk host transfer: two scalars
            n_alive, trips = (int(v) for v in
                              jax.device_get((n_alive, trips)))
        compact_rows += width * trips
        if n_alive == 0:
            break
        w_next = _next_width(n_alive, widths)
        if w_next < width:
            # the gather itself already ran inside the fused chunk; emit
            # a zero-length marker span so trace consumers still see the
            # ladder descent
            t_ns = time.perf_counter_ns()
            obs.add_span("lp.compact_gather", t_ns, t_ns, from_width=width,
                         to_width=w_next, survivors=n_alive, mode="device")
        width = w_next
    xo, obj, yo, it, rp, rd, mu, it32, bad = fin_fn(
        carry, rp, rd, mu, perm, arrs[0], lb, csc, rsc)
    sol = LPSolution(xo, obj, yo, it, rp, rd, mu)
    return sol, it32, bad, compact_rows


def solve_lp_stacked(c, a_eq, b_eq, g, h, lb, ub,
                     *, max_iters: int = _MAX_ITERS,
                     tol: float = _TOL, linsolve: str = "xla",
                     row_active=None, compact: bool = False,
                     chunk_iters=None, newton_dtype: str = "float64",
                     compact_mode: str = "device", mesh=None,
                     row_spec=None) -> LPSolution:
    """Solve a whole stack of LPs as ONE jitted, vmapped interior-point call.

    Any of the seven arrays may carry a leading batch dimension (detected
    by ndim); the rest are broadcast.  This is the engine behind both the
    epsilon-constraint budget sweep (only ``h`` batched) and scenario
    sweeps (``g``/``h``/``ub`` batched — scenarios perturb the constraint
    MATRIX, not just the rhs).  All fields of the returned
    :class:`LPSolution` gain a leading batch axis.

    Node LPs come in compact form: a
    :class:`~repro.core.problem.NodeRows` in ``a_eq``, with ``b_eq`` and
    ``g`` None and ``h`` the border rows' rhs.  They are solved through
    :class:`NodeOp` (each Newton system reduced to its 2*mu+1 border) in
    programs named :data:`NODE_IPM`; their copy to the device counts
    in the ``lp.put_bytes`` and ``lp.put_rows`` counters, and the
    ``lp.solve_stacked`` span gains attributes ``mu`` and ``tau``.

    ``linsolve`` selects the Newton normal-equation backend (see
    :data:`LINSOLVES`); with ``"pallas"`` every Newton step of the batch
    is one blocked batched-Cholesky kernel launch.  ``row_active`` is an
    optional (B,) bool mask: inactive rows (e.g. the fixed-width padding
    of a lockstep B&B round) retire at iteration zero instead of paying
    the whole batch's Newton work; their solution rows are garbage and
    must be discarded by the caller.  The mask is a traced argument —
    changing it never recompiles.

    ``compact=True`` switches to the CHUNKED driver: iterations run in
    chunks of ``chunk_iters`` (default 8) and between chunks the batch is
    compacted over a fixed power-of-two width ladder, so once most rows
    have converged the remaining while-loop trips are paid only by the
    stragglers — this converts the early-exit ledger's saved Newton rows
    into wall-clock speedup on lockstep (CPU/SIMD) backends.  The row
    MATH is identical to the monolithic driver and outputs keep the
    input row order; numerically stable rows replay bit-identically,
    while an ill-conditioned straggler that lands in a smaller ladder
    buffer (a different compiled executable) may drift at the last-ulp
    level and re-converge within ~1e-8 of the monolithic answer.  Every
    ladder width is pre-compiled on first use, so
    :func:`stacked_compile_count` stays flat afterwards.

    ``compact_mode`` selects where the between-chunk gather runs:
    ``"device"`` (default) compacts inside the compiled program (stable
    argsort+gather; two scalars per chunk cross to the host; returned
    arrays are device-resident in input row order), ``"host"`` keeps the
    legacy NumPy round-trip (parity oracle; see docs/solver.md for the
    trade-off).

    ``newton_dtype="float32"`` enables the mixed-precision Newton path:
    float32 factor/solve plus one float64 iterative-refinement step per
    solve, with a per-row fallback to full float64 once the barrier
    parameter is small or whenever the refined residual exceeds
    tolerance.  Convergence checks always run in float64.

    ``mesh`` shards the batch (row) axis over a device mesh with
    ``shard_map`` — rows are independent, so each shard runs the chosen
    driver on its own block and a shard's lockstep while-loop retires as
    soon as ITS slowest row converges.  Row placement uses the mesh's
    ``lp_rows`` axis (:func:`repro.launch.mesh.make_solver_mesh`), its
    ('pod', 'data') batch axes, or an explicit ``row_spec``; batches not
    divisible by the shard count are internally padded with retired rows
    and sliced back.  ``compact=True`` composes (the ladder becomes
    per-shard — see docs/solver.md "Sharded megabatches");
    ``compact_mode="host"`` does not (its NumPy round-trip has no
    sharded layout) and raises.
    """
    dt = jnp.float64
    newton_dtype = _canon_newton_dtype(newton_dtype)
    chunk_iters = _CHUNK_ITERS if chunk_iters is None else int(chunk_iters)
    if chunk_iters < 1:
        raise ValueError(f"chunk_iters must be >= 1, got {chunk_iters}")
    node = isinstance(a_eq, NodeRows)
    bases = _base_ndims(a_eq, b_eq, g)
    with obs.span("lp.put"):
        arrs = _on_device((c, a_eq, b_eq, g, h, lb, ub), dt)
    # one axis per array leaf (None args have none), then as a pytree
    # like the arguments (vmap's in_axes)
    leaves, treedef = jax.tree.flatten(arrs)
    flat_axes = tuple(0 if a.ndim == base + 1 else None
                      for a, base in zip(leaves, jax.tree.leaves(bases)))
    for a, base, ax in zip(leaves, jax.tree.leaves(bases), flat_axes):
        if ax is None and a.ndim != base:
            raise ValueError(f"array has ndim {a.ndim}, expected {base} "
                             f"or {base + 1} (batched)")
    if not any(ax == 0 for ax in flat_axes):
        raise ValueError("solve_lp_stacked needs at least one batched array; "
                         "use solve_lp for a single LP")
    axes = treedef.unflatten(flat_axes)
    sizes = {a.shape[0] for a, ax in zip(leaves, flat_axes) if ax == 0}
    if len(sizes) != 1:
        raise ValueError(f"inconsistent batch sizes: {sorted(sizes)}")
    (batch,) = sizes
    span_kw = {}
    if node:
        obs.update(counters={"lp.put_bytes": sum(a.nbytes for a in leaves),
                             "lp.put_rows": batch})
        mu, tau = arrs[1].coef.shape[-2:]
        span_kw = dict(mu=int(mu), tau=int(tau))
    if row_active is None:
        active = jnp.ones((batch,), dtype=bool)
    else:
        active = jnp.asarray(row_active, dtype=bool)
        if active.shape != (batch,):
            raise ValueError(f"row_active shaped {active.shape}, "
                             f"expected ({batch},)")
    row_shape = tuple(a.shape[1:] if ax == 0 else a.shape
                      for a, ax in zip(leaves, flat_axes))
    row_axes = _lp_row_axes(mesh, row_spec) if mesh is not None else None
    n_shards = _n_shards_of(mesh, row_axes)
    mesh_shape = _mesh_shape_of(mesh, row_axes)
    mesh_key = _mesh_key_of(mesh, row_axes)
    # pad to a shard multiple with retired first-row copies; sliced back
    # below.  Callers that care about compile-count flatness should size
    # their batches to the shard count themselves (the serving ladder
    # does, via ladder_widths(n_shards=)).
    n_req, pad = batch, (-batch) % n_shards
    if pad:
        leaves = [jnp.concatenate(
            [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])])
            if ax == 0 else a for a, ax in zip(leaves, flat_axes)]
        arrs = treedef.unflatten(leaves)
        active = jnp.concatenate([active, jnp.zeros((pad,), bool)])
        batch += pad
    if compact:
        if compact_mode not in ("device", "host"):
            raise ValueError(f"unknown compact_mode {compact_mode!r}; "
                             f"expected 'device' or 'host'")
        if mesh is not None and compact_mode == "host":
            raise ValueError(
                "compact_mode='host' does not compose with mesh=: the "
                "NumPy round-trip has no sharded layout; use the default "
                "compact_mode='device'")
        sig = ("compact", compact_mode, axes, max_iters, chunk_iters,
               linsolve, newton_dtype, tuple(a.shape for a in leaves),
               mesh_key)
        if sig not in _STACKED_SIGNATURES:
            _STACKED_SIGNATURES.add(sig)
            obs.record_compile("compact", width=batch, axes=flat_axes,
                               max_iters=max_iters, linsolve=linsolve,
                               newton_dtype=newton_dtype, compact=True,
                               chunk_iters=chunk_iters, row_shape=row_shape,
                               compact_mode=compact_mode,
                               mesh_shape=mesh_shape)
        with obs.span("lp.solve_stacked", width=batch, compact=True,
                      linsolve=linsolve, newton_dtype=newton_dtype,
                      compact_mode=compact_mode, n_shards=n_shards,
                      **span_kw) as sp, _partitioner(mesh):
            with obs.span("lp.dispatch"):
                sol, it32, bad, compact_rows = _solve_stacked_compact(
                    arrs, axes, batch, tol, active, max_iters=max_iters,
                    chunk_iters=chunk_iters, linsolve=linsolve,
                    newton_dtype=newton_dtype, compact_mode=compact_mode,
                    mesh=mesh, row_axes=row_axes)
            with obs.span("lp.device_wait"):
                jax.block_until_ready((sol, it32, bad))
            with obs.span("lp.ledger"):
                sp.set(**_record_newton_rows(
                    sol.iters, active, converged=sol.converged, it32=it32,
                    bad=bad, compact_rows=compact_rows))
        return LPSolution(*(f[:n_req] for f in sol)) if pad else sol
    sig = (axes, max_iters, linsolve, newton_dtype,
           tuple(a.shape for a in leaves), mesh_key)
    if sig not in _STACKED_SIGNATURES:
        _STACKED_SIGNATURES.add(sig)
        obs.record_compile("stacked", width=batch, axes=flat_axes,
                           max_iters=max_iters, linsolve=linsolve,
                           newton_dtype=newton_dtype, compact=False,
                           chunk_iters=None, row_shape=row_shape,
                           mesh_shape=mesh_shape)
    # the span covers the (possibly compiling) dispatch AND the wait for
    # the async device result — so the measured time is real solve time,
    # not lazy-dispatch time
    with obs.span("lp.solve_stacked", width=batch, compact=False,
                  linsolve=linsolve, newton_dtype=newton_dtype,
                  n_shards=n_shards, **span_kw) as sp, _partitioner(mesh):
        solver = (_stacked_solver(axes, max_iters, linsolve, newton_dtype)
                  if mesh is None else
                  _stacked_solver_sharded(axes, max_iters, linsolve,
                                          newton_dtype, mesh, row_axes))
        with obs.span("lp.dispatch"):
            sol, it32, bad = solver(jnp.asarray(tol, dt), active, *arrs)
        with obs.span("lp.device_wait"):
            jax.block_until_ready((sol, it32, bad))
        with obs.span("lp.ledger"):
            sp.set(**_record_newton_rows(sol.iters, active,
                                         converged=sol.converged,
                                         it32=it32, bad=bad))
    return LPSolution(*(f[:n_req] for f in sol)) if pad else sol


# the fields of a NodeLP a stacked solve copies, one row per node
_NODE_FIELDS = ("coef", "rho", "pi", "h", "lb", "ub")


def solve_node_lps_stacked(nodes, *, max_iters: int = _MAX_ITERS,
                           tol: float = _TOL, linsolve: str = "xla",
                           row_active=None, compact: bool = False,
                           chunk_iters=None, newton_dtype: str = "float64",
                           compact_mode: str = "device", mesh=None,
                           row_spec=None) -> LPSolution:
    """Stack a sequence of same-shape :class:`~repro.core.problem.NodeLP`
    relaxations (e.g. one per scenario x budget point) and solve them in a
    single batched IPM call through :class:`NodeOp`.  Only the compact
    fields are stacked and copied (``coef``, ``rho``, ``pi``, ``h``,
    ``lb``, ``ub``; the objective, the same for every node, once)."""
    nodes = list(nodes)
    if not nodes:
        raise ValueError("empty node stack")
    with obs.span("lp.put"):
        coef, rho, pi, h, lb, ub = (
            np.stack([np.asarray(getattr(n, f), np.float64) for n in nodes])
            for f in _NODE_FIELDS)
    return solve_lp_stacked(nodes[0].c, NodeRows(coef, rho, pi), None, None,
                            h, lb, ub, max_iters=max_iters, tol=tol,
                            linsolve=linsolve, row_active=row_active,
                            compact=compact, chunk_iters=chunk_iters,
                            newton_dtype=newton_dtype,
                            compact_mode=compact_mode, mesh=mesh,
                            row_spec=row_spec)


def stacked_attribution_key(node, *, max_iters: int = _MAX_ITERS,
                            linsolve: str = "xla", compact: bool = False,
                            chunk_iters=None,
                            newton_dtype: str = "float64", mesh=None,
                            row_spec=None) -> dict:
    """The width-independent compile-attribution config that
    :func:`solve_node_lps_stacked` calls for ``node``-shaped stacks emit
    (see ``obs.record_compile``): kind + axes + solver knobs + per-row
    array shapes, WITHOUT the batch width.

    Consumers pass it as the ``**match`` filter of
    ``obs.compile_events`` to count only compiles attributable to their
    own problem shape and solver config — e.g.
    ``AllocationServer.recompiles_since_warmup`` additionally requires
    the event width to be one of its ladder widths.  Deterministic, so
    a server that warmed against an already-hot jit cache (no compile
    events of its own) can still build its filter.

    The filter includes the mesh identity (``mesh_shape``: row-axis
    names and sizes, None for unsharded solves), so a query built for
    one mesh never matches solves dispatched under a different mesh —
    or under none.
    """
    newton_dtype = _canon_newton_dtype(newton_dtype)
    chunk_iters = (_CHUNK_ITERS if chunk_iters is None
                   else int(chunk_iters)) if compact else None
    # as solve_node_lps_stacked passes them: the objective once, the
    # compact fields one row per node
    row_shape = ((node.lb.shape[0],),) + tuple(
        np.shape(getattr(node, f)) for f in _NODE_FIELDS)
    return {
        "kind": "compact" if compact else "stacked",
        "axes": (None,) + (0,) * 6,
        "max_iters": int(max_iters),
        "linsolve": linsolve,
        "newton_dtype": newton_dtype,
        "compact": bool(compact),
        "chunk_iters": chunk_iters,
        "row_shape": row_shape,
        "mesh_shape": _mesh_shape_key(mesh, row_spec),
    }


# ---------------------------------------------------------------------------
# Width-ladder batch merging (the serving admission policy)
# ---------------------------------------------------------------------------

def ladder_widths(batch: int, n_shards: int = 1) -> list:
    """Public view of the fixed buffer-width ladder for a maximum batch
    width: ``batch`` itself plus every power of two below it, descending.

    This is the same ladder the chunked driver compacts over; the
    serving layer (:mod:`repro.serving`) uses it as its ADMISSION
    policy — coalesced request batches are padded up to the smallest
    ladder width that holds them, so the jit cache only ever sees a
    fixed set of batch shapes and :func:`stacked_compile_count` is
    bounded by ``len(ladder_widths(ladder_max))`` per solver config.

    ``n_shards`` (> 1 for mesh-sharded dispatch) makes the ladder
    PER-SHARD: every global width is a per-shard power-of-two times the
    shard count, so each shard's block is itself a ladder width and the
    compiled set stays one program per local width.  ``batch`` must
    divide evenly into shards.
    """
    if batch < 1:
        raise ValueError(f"ladder needs batch >= 1, got {batch}")
    n_shards = int(n_shards)
    if n_shards < 1:
        raise ValueError(f"ladder needs n_shards >= 1, got {n_shards}")
    if batch % n_shards:
        raise ValueError(f"ladder_max {batch} must be divisible by "
                         f"n_shards {n_shards}")
    return [w * n_shards for w in _ladder_widths(int(batch) // n_shards)]


def next_ladder_width(n_rows: int, ladder_max: int,
                      n_shards: int = 1) -> int:
    """Smallest width in :func:`ladder_widths(ladder_max, n_shards)`
    that holds ``n_rows`` — the buffer a merged batch of ``n_rows`` LP
    rows is padded to."""
    widths = ladder_widths(ladder_max, n_shards)
    if not 1 <= n_rows <= ladder_max:
        raise ValueError(f"n_rows={n_rows} outside ladder "
                         f"[1, {ladder_max}]")
    return _next_width(int(n_rows), widths)


def solve_node_lps_ladder(nodes, *, ladder_max: int, row_active=None,
                          max_iters: int = _MAX_ITERS, tol: float = _TOL,
                          linsolve: str = "xla", compact: bool = False,
                          chunk_iters=None, newton_dtype: str = "float64",
                          compact_mode: str = "device", mesh=None,
                          row_spec=None) -> LPSolution:
    """Batch-merge entry point: solve up to ``ladder_max`` same-shape
    node LPs as ONE stacked call padded to a ladder width.

    The node stack (e.g. several tenants' budget sweeps, concatenated)
    is padded with retired copies of its first row up to
    :func:`next_ladder_width` and solved through
    :func:`solve_lp_stacked` with the padding marked inactive in
    ``row_active`` — padding rows cost zero IPM iterations and the
    returned :class:`LPSolution` is sliced back to ``len(nodes)`` rows.
    Because the batch shape is always one of the fixed ladder widths,
    :func:`stacked_compile_count` stays FLAT across arbitrary request
    mixes once each width has compiled (or been warmed via
    :func:`warm_ladder`).

    ``row_active`` optionally retires a subset of the real rows too
    (same semantics as :func:`solve_lp_stacked`); the ladder padding is
    appended to it.

    With a ``mesh``, widths come from the PER-SHARD ladder
    (``ladder_widths(ladder_max, n_shards)``) so each dispatched batch
    splits evenly across shards with no internal re-padding — the
    compile set stays one program per local width.
    """
    nodes = list(nodes)
    k = len(nodes)
    width = next_ladder_width(k, ladder_max, mesh_n_shards(mesh, row_spec))
    padded = nodes + [nodes[0]] * (width - k)
    active = np.zeros(width, dtype=bool)
    active[:k] = True if row_active is None else \
        np.asarray(row_active, dtype=bool)
    sol = solve_node_lps_stacked(padded, max_iters=max_iters, tol=tol,
                                 linsolve=linsolve, row_active=active,
                                 compact=compact, chunk_iters=chunk_iters,
                                 newton_dtype=newton_dtype,
                                 compact_mode=compact_mode, mesh=mesh,
                                 row_spec=row_spec)
    # slice, don't round-trip: the fields stay device arrays so callers
    # (the serving slice path) never pay a hidden NumPy transfer here
    return LPSolution(*(f[:k] for f in sol))


def warm_ladder(node, ladder_max: int, *, widths=None,
                max_iters: int = _MAX_ITERS,
                tol: float = _TOL, linsolve: str = "xla",
                compact: bool = False, chunk_iters=None,
                newton_dtype: str = "float64",
                compact_mode: str = "device", mesh=None,
                row_spec=None) -> list:
    """AOT-warm every ladder width for one node-LP shape: one
    ALL-RETIRED call per width (every row starts with its ``done`` flag
    set, so the while-loop trip count is zero and each call costs one
    compile plus microseconds of run time — the same trick
    ``compact=True`` plays per-call in ``_warm_compact_ladder``).
    ``widths`` warms only those widths instead (the lockstep B&B's two
    dispatch rungs).

    After this returns, a server dispatching merged batches of this
    shape at any ladder width never compiles again:
    :func:`stacked_compile_count` is already final.  Returns the warmed
    widths (descending).
    """
    if widths is None:
        widths = ladder_widths(ladder_max, mesh_n_shards(mesh, row_spec))
    for w in widths:
        with obs.span("lp.warm_width", width=w, linsolve=linsolve,
                      compact=compact):
            solve_node_lps_stacked([node] * w, max_iters=max_iters,
                                   tol=tol, linsolve=linsolve,
                                   row_active=np.zeros(w, dtype=bool),
                                   compact=compact, chunk_iters=chunk_iters,
                                   newton_dtype=newton_dtype,
                                   compact_mode=compact_mode, mesh=mesh,
                                   row_spec=row_spec)
    return widths


# Back-compat variant: same constraint structure, different rhs h (the
# epsilon-constraint cost grid).  Thin wrapper over the stacked engine.
def solve_lp_batched(c, a_eq, b_eq, g, h_batch, lb, ub,
                     *, max_iters: int = _MAX_ITERS, linsolve: str = "xla",
                     compact: bool = False, chunk_iters=None,
                     newton_dtype: str = "float64",
                     compact_mode: str = "device", mesh=None,
                     row_spec=None):
    return solve_lp_stacked(c, a_eq, b_eq, g, h_batch, lb, ub,
                            max_iters=max_iters, linsolve=linsolve,
                            compact=compact, chunk_iters=chunk_iters,
                            newton_dtype=newton_dtype,
                            compact_mode=compact_mode, mesh=mesh,
                            row_spec=row_spec)


def scipy_reference_lp(c, a_eq, b_eq, g, h, lb, ub):
    """HiGHS reference solution (oracle for tests / IPM fallback).  The
    rows ``a_eq`` and ``g`` may be dense or scipy sparse matrices."""
    import scipy.sparse as sp
    from scipy.optimize import linprog

    def rows(m):
        return m if sp.issparse(m) else np.asarray(m, float)

    bounds = list(zip(np.asarray(lb, float),
                      [b if np.isfinite(b) else None for b in np.asarray(ub, float)]))
    res = linprog(np.asarray(c, float), A_ub=rows(g),
                  b_ub=np.asarray(h, float), A_eq=rows(a_eq),
                  b_eq=np.asarray(b_eq, float), bounds=bounds, method="highs")
    return res
