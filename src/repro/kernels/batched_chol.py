"""Pallas kernel: batched Cholesky solve for the stacked IPM.

The interior-point LP engine (:mod:`repro.core.lp`) reduces every Newton
step to one symmetric positive-definite normal-equation solve per batch
row: ``M dy = r`` with ``M = A Theta^{-1} A^T`` of shape
``(m, m)``, ``m`` = #constraint rows (tens to a few hundred).  On TPU the
natural shape is one kernel launch over the stacked ``(B, m, m)``
matrices with each grid cell factoring its matrix entirely in VMEM.

Design: a right-looking Cholesky over a ``fori_loop`` of columns.  Step
``j`` reads column and row ``j`` of the trailing matrix with masked
reductions over an iota, forms ``l_j = a[:, j] / sqrt(a[j, j])`` and
subtracts the rank-1 outer product ``l_j l_j^T`` from the whole matrix
(``l_j`` is zero above row ``j``, so only the trailing block moves).
Every read and write is a ``jnp.where`` select against an iota — no
scatter and no dynamic slice — which is what Mosaic lowers; the loop
index is a traced scalar, so the compiled kernel does not grow with
``m``.  Forward substitution rides along with the factorisation (the
right-hand side is updated with the same ``l_j``), and backward
substitution is a second column loop over the stored factor.  Vectors
are kept as ``(1, m)`` rows so the lane dimension carries them.

The compiled kernel runs in float32 only (Mosaic has no float64 vector
unit); :func:`chol_solve` / :func:`chol_factor` raise on other dtypes
when not interpreting.  The IPM's mixed-precision path
(``newton_dtype="float32"``) is the one that feeds it on TPU.  Interpret
mode (the CPU test path) is dtype-generic.

``jax.vmap`` of the single-matrix call batches the grid (this is how the
vmapped IPM turns B per-row solves into ONE batched-Cholesky call); the
public :func:`chol_solve` also accepts stacked inputs directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# the system is padded (identity tail) to a multiple of this — the
# float32 sublane tile; the solution does not depend on it
DEFAULT_BLOCK = 8


# ---------------------------------------------------------------------------
# In-kernel building blocks
# ---------------------------------------------------------------------------

def _factor(a, b):
    """Right-looking Cholesky of ``a`` (mp, mp) with forward substitution
    of the row vector ``b`` (1, mp) fused in.  Returns ``(l, d, y)``: the
    lower factor, its diagonal as a (1, mp) row and ``y = L^{-1} b^T``
    as a (1, mp) row."""
    mp = a.shape[0]
    dt = a.dtype
    ri = jax.lax.broadcasted_iota(jnp.int32, (mp, mp), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (mp, mp), 1)
    rc = jax.lax.broadcasted_iota(jnp.int32, (mp, 1), 0)
    cr = jax.lax.broadcasted_iota(jnp.int32, (1, mp), 1)
    zero = jnp.zeros((), dt)

    def step(j, carry):
        a, l, d, b, y = carry
        col = jnp.sum(jnp.where(ci == j, a, zero), axis=1, keepdims=True)
        row = jnp.sum(jnp.where(ri == j, a, zero), axis=0, keepdims=True)
        djj = jnp.sqrt(jnp.sum(jnp.where(cr == j, row, zero), axis=1,
                               keepdims=True))                   # (1, 1)
        lcol = jnp.where(rc >= j, col / djj, zero)               # (mp, 1)
        lrow = jnp.where(cr >= j, row / djj, zero)               # (1, mp)
        a = a - lcol * lrow
        l = l + jnp.where(ci == j, lcol, zero)
        d = d + jnp.where(cr == j, djj, zero)
        yj = jnp.sum(jnp.where(cr == j, b, zero), axis=1,
                     keepdims=True) / djj                        # (1, 1)
        b = b - yj * lrow
        y = y + jnp.where(cr == j, yj, zero)
        return a, l, d, b, y

    zrow = jnp.zeros((1, mp), dt)
    # int32 loop bounds: under x64 a Python-int loop index is int64,
    # which Mosaic does not lower
    _, l, d, _, y = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(mp), step,
        (a, jnp.zeros_like(a), zrow, b, zrow))
    return l, d, y


def _backward(l, d, y):
    """Solve ``L^T x = y`` (rows (1, mp)) by a descending column loop."""
    mp = l.shape[0]
    dt = l.dtype
    ri = jax.lax.broadcasted_iota(jnp.int32, (mp, mp), 0)
    cr = jax.lax.broadcasted_iota(jnp.int32, (1, mp), 1)
    zero = jnp.zeros((), dt)

    def step(k, carry):
        r, x = carry
        j = mp - 1 - k
        lrow = jnp.sum(jnp.where(ri == j, l, zero), axis=0, keepdims=True)
        xj = (jnp.sum(jnp.where(cr == j, r, zero), axis=1, keepdims=True)
              / jnp.sum(jnp.where(cr == j, d, zero), axis=1, keepdims=True))
        r = r - xj * jnp.where(cr < j, lrow, zero)
        x = x + jnp.where(cr == j, xj, zero)
        return r, x

    _, x = jax.lax.fori_loop(jnp.int32(0), jnp.int32(mp), step,
                             (y, jnp.zeros_like(y)))
    return x


def _chol_solve_kernel(a_ref, b_ref, x_ref):
    l, d, y = _factor(a_ref[...], b_ref[...])
    x_ref[...] = _backward(l, d, y)


def _chol_factor_kernel(a_ref, l_ref):
    a = a_ref[...]
    l, _, _ = _factor(a, jnp.zeros((1, a.shape[0]), a.dtype))
    l_ref[...] = l


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------

def _whole(shape):
    """Whole-array block with an int32 index map: the default map returns
    Python-int zeros, which trace to int64 under x64 and which Mosaic
    refuses once ``vmap`` adds the batch grid axis."""
    zeros = (np.int32(0),) * len(shape)
    return pl.BlockSpec(shape, lambda: zeros)


def _check_compiled_dtype(dtype, interpret: bool) -> None:
    if not interpret and jnp.dtype(dtype) != jnp.float32:
        raise ValueError(
            f"the compiled Pallas Cholesky kernel runs in float32 only, got "
            f"{jnp.dtype(dtype).name}; on TPU use linsolve='pallas' with "
            f"newton_dtype='float32' (rows that graduate to float64 then "
            f"solve through the XLA Cholesky), or linsolve='xla'")


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chol_solve_padded(a, b, *, interpret: bool):
    mp = a.shape[0]
    return pl.pallas_call(
        _chol_solve_kernel,
        out_shape=jax.ShapeDtypeStruct((1, mp), a.dtype),
        in_specs=[_whole((mp, mp)), _whole((1, mp))],
        out_specs=_whole((1, mp)),
        interpret=interpret,
        name="batched_chol_solve",
    )(a, b)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chol_factor_padded(a, *, interpret: bool):
    mp = a.shape[0]
    return pl.pallas_call(
        _chol_factor_kernel,
        out_shape=jax.ShapeDtypeStruct((mp, mp), a.dtype),
        in_specs=[_whole((mp, mp))],
        out_specs=_whole((mp, mp)),
        interpret=interpret,
        name="batched_chol_factor",
    )(a)


def _pad_spd(a, b, mp):
    """Pad (m, m) SPD + (m,) rhs to (mp, mp)/(mp,) with an identity tail
    (keeps the factorisation well-defined; padded solution entries are 0)."""
    m = a.shape[-1]
    if mp == m:
        return a, b
    pad = mp - m
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, pad), (0, pad)])
    eye = jnp.eye(mp, dtype=a.dtype) * (jnp.arange(mp) >= m).astype(a.dtype)
    a = a + eye
    b = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, pad)])
    return a, b


def _padded_size(m: int, block: int) -> int:
    return max(-(-m // block) * block, block)


def chol_solve_one(a, b, *, block: int = DEFAULT_BLOCK,
                   interpret: bool = True, dtype=None):
    """Solve one SPD system ``a x = b`` (a: (m, m), b: (m,)) through the
    Pallas kernel.  ``jax.vmap`` of this call becomes one batched kernel
    launch — it is the function the IPM's vmapped Newton step closes
    over.  The kernel runs in the dtype of ``a`` (float32 inputs stay
    float32 — the mixed-precision Newton path feeds those); ``dtype``
    casts both operands first."""
    if dtype is not None:
        a = a.astype(dtype)
        b = b.astype(dtype)
    _check_compiled_dtype(a.dtype, interpret)
    mp = _padded_size(a.shape[-1], block)
    ap, bp = _pad_spd(a, b, mp)
    x = _chol_solve_padded(ap, bp[None, :], interpret=interpret)
    return x[0, :a.shape[-1]]


def chol_solve(mats, rhs, *, block: int = DEFAULT_BLOCK,
               interpret: bool = True, dtype=None):
    """Batched SPD solve: ``mats`` (B, m, m) or (m, m), ``rhs`` (B, m) or
    (m,).  The batch runs as ONE Pallas launch (vmap adds the grid axis).
    ``dtype`` (optional) casts the inputs before the solve; interpret
    mode is dtype-generic, the compiled kernel takes float32 only."""
    mats = jnp.asarray(mats)
    rhs = jnp.asarray(rhs)
    if mats.ndim == 2:
        return chol_solve_one(mats, rhs, block=block, interpret=interpret,
                              dtype=dtype)
    one = functools.partial(chol_solve_one, block=block, interpret=interpret,
                            dtype=dtype)
    return jax.vmap(one)(mats, rhs)


def chol_factor(mats, *, block: int = DEFAULT_BLOCK, interpret: bool = True,
                dtype=None):
    """Batched Cholesky factor L (lower; L @ L.T == mats) — the
    factorisation the mixed-precision Newton path reuses for its
    refinement step.  ``dtype`` casts the input stack first (float32
    runs the whole factorisation in float32)."""
    mats = jnp.asarray(mats)
    if dtype is not None:
        mats = mats.astype(dtype)
    _check_compiled_dtype(mats.dtype, interpret)
    single = mats.ndim == 2
    if single:
        mats = mats[None]
    m = mats.shape[-1]
    mp = _padded_size(m, block)

    def one(a):
        ap, _ = _pad_spd(a, jnp.zeros((m,), mats.dtype), mp)
        return _chol_factor_padded(ap, interpret=interpret)

    ls = jax.vmap(one)(mats)[:, :m, :m]
    return ls[0] if single else ls
