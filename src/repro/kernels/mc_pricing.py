"""Pallas TPU kernel for Monte Carlo GBM option pricing.

TPU-native design (DESIGN.md §2): paths are tiled into (8, 128) VMEM
blocks (sublane x lane aligned); randomness comes from an in-kernel
Philox4x32-10 keyed on (path, step, task, seed) so no RNG state ever
touches HBM; each grid cell reduces its 1024 paths to two scalars
(payoff sum, payoff sum-of-squares) and adds them into its task's
(8, 128) output tile, which stays resident across the path-block axis,
so HBM traffic is O(tasks) not O(paths).  The per-task parameters sit
in SMEM as one flat row-major vector and are read as scalars.
Elementwise GBM work maps to the VPU; there is no matmul so the MXU is
intentionally idle — this kernel is bandwidth-trivial and
compute(VPU)-bound, like the paper's "compute bound ... random number
generation accounting for the bulk" workload.

grid = (tasks, path_blocks); one pallas_call per (kind, steps) group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import philox
from repro.pricing.options import KIND_IDS, N_PARAM_COLS

BLOCK_ROWS = 8
BLOCK_LANES = 128
BLOCK_PATHS = BLOCK_ROWS * BLOCK_LANES


def _payoff(kind_id: int, log_s, asian_acc, knocked, strike, steps):
    s_t = jnp.exp(log_s)
    if kind_id == KIND_IDS["european_call"]:
        return jnp.maximum(s_t - strike, 0.0)
    if kind_id == KIND_IDS["european_put"]:
        return jnp.maximum(strike - s_t, 0.0)
    if kind_id == KIND_IDS["asian_call"]:
        avg = asian_acc * np.float32(1.0 / steps)
        return jnp.maximum(avg - strike, 0.0)
    if kind_id == KIND_IDS["barrier_up_out_call"]:
        return jnp.where(knocked, np.float32(0.0),
                         jnp.maximum(s_t - strike, 0.0))
    raise ValueError(kind_id)


def _mc_kernel(params_ref, sum_ref, sumsq_ref, *, kind_id: int, steps: int,
               seed: int):
    task = pl.program_id(0)
    blk = pl.program_id(1)
    shape = (BLOCK_ROWS, BLOCK_LANES)

    # scalars are broadcast to a tile BEFORE any arithmetic: the TPU
    # scalar unit has no transcendentals (log/sqrt/exp below)
    def param(c):
        return jnp.full(shape, params_ref[task * N_PARAM_COLS + c],
                        jnp.float32)

    s0, strike, rate, sigma, maturity, barrier, n_paths = (
        param(c) for c in range(7))

    dt = maturity * np.float32(1.0 / steps)
    drift = (rate - np.float32(0.5) * sigma * sigma) * dt
    vol = sigma * jnp.sqrt(dt)

    # counters are built in int32 and bitcast: Mosaic lowers no
    # int32 -> uint32 conversion (values are < 2^31, so bits agree)
    def u32(v):
        return jax.lax.bitcast_convert_type(v, jnp.uint32)

    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    path_i = blk * BLOCK_PATHS + row * BLOCK_LANES + col
    path = u32(path_i)
    task_u = u32(jnp.full(shape, task, jnp.int32))

    log_s = jnp.log(s0)
    asian = jnp.zeros(shape, jnp.float32)
    # the knock-out flag rides the loop as 0/1 float32: Mosaic cannot
    # carry a bool vector through scf.for
    knocked = jnp.zeros(shape, jnp.float32)

    def step_fn(i, carry):
        log_s, asian, knocked = carry
        z, _ = philox.normal_pair(path, u32(jnp.full(shape, i, jnp.int32)),
                                  task_u, np.uint32(seed),
                                  np.uint32(0xF3), np.uint32(0xC10D))
        log_s = log_s + drift + vol * z
        s = jnp.exp(log_s)
        asian = asian + s
        knocked = jnp.where(s >= barrier, np.float32(1.0), knocked)
        return log_s, asian, knocked

    log_s, asian, knocked = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(steps), step_fn, (log_s, asian, knocked))

    pay = _payoff(kind_id, log_s, asian, knocked > 0, strike, steps)
    pay = pay * jnp.exp(-rate * maturity)
    live = path_i.astype(jnp.float32) < n_paths
    pay = jnp.where(live, pay, 0.0)

    @pl.when(blk == 0)
    def _():
        sum_ref[...] = jnp.zeros(shape, jnp.float32)
        sumsq_ref[...] = jnp.zeros(shape, jnp.float32)

    sum_ref[...] += jnp.broadcast_to(
        jnp.sum(pay, axis=(0, 1), keepdims=True), shape)
    sumsq_ref[...] += jnp.broadcast_to(
        jnp.sum(pay * pay, axis=(0, 1), keepdims=True), shape)


@functools.partial(jax.jit,
                   static_argnames=("kind_id", "steps", "n_blocks", "seed",
                                    "interpret"))
def mc_price_sums(params: jnp.ndarray, *, kind_id: int, steps: int,
                  n_blocks: int, seed: int = 0, interpret: bool = True):
    """Payoff sums for a group of tasks sharing (kind, steps).

    params: (tasks, N_PARAM_COLS) float32 (see options.PARAM_COLS).
    Returns (sum, sumsq): each (tasks,) float32, reduced over blocks in
    block order.
    """
    tasks = params.shape[0]
    assert params.shape[1] == N_PARAM_COLS
    kern = functools.partial(_mc_kernel, kind_id=kind_id, steps=steps,
                             seed=seed)
    # one (8, 128) output tile per task, revisited by every path block
    # (the block axis is sequential); int32 indices, since Python ints
    # trace to int64 under x64
    tile = pl.BlockSpec((BLOCK_ROWS, BLOCK_LANES),
                        lambda t, b: (t, np.int32(0)))
    out_shape = [
        jax.ShapeDtypeStruct((tasks * BLOCK_ROWS, BLOCK_LANES), jnp.float32),
        jax.ShapeDtypeStruct((tasks * BLOCK_ROWS, BLOCK_LANES), jnp.float32),
    ]
    sums, sumsqs = pl.pallas_call(
        kern,
        grid=(tasks, n_blocks),
        in_specs=[pl.BlockSpec((tasks * N_PARAM_COLS,),
                               lambda t, b: (np.int32(0),),
                               memory_space=pltpu.SMEM)],
        out_specs=[tile, tile],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mc_price_sums",
    )(params.reshape(-1))
    return sums[::BLOCK_ROWS, 0], sumsqs[::BLOCK_ROWS, 0]
