"""Compile the main path's device programs for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which is installed with
JAX, compiles for a topology that is described and not attached, and
refuses what the chip's compiler would refuse (an unlowerable kernel op,
a misaligned block, a program that does not fit).  Interpret-mode tests
on the CPU cannot see those faults.  Shapes are the paper's deployment:
128 option tasks x the 16 Table II platforms, whose node LPs have
``a_eq`` (128, 2065) and ``g`` (33, 2065), served at ladder width 32.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lp
from repro.core.problem import AllocationProblem, NodeRows
from repro.kernels import batched_chol as bc
from repro.kernels import mc_pricing, ops
from repro.pricing.options import KIND_IDS, N_PARAM_COLS

MU, TAU, WIDTH = 16, 128, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the kernels onto their compiled (non-interpret) branch, as
    on a TPU backend, with fresh trace caches on both sides so no
    interpret-mode trace leaks in and no TPU trace leaks out."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _node_shapes():
    """Row shapes of one paper-size frontier node LP."""
    rng = np.random.default_rng(0)
    p = AllocationProblem(rng.uniform(1e-6, 1e-4, (MU, TAU)),
                          rng.uniform(0.1, 5.0, (MU, TAU)),
                          rng.uniform(1e5, 1e7, TAU),
                          rng.uniform(60, 600, MU),
                          rng.uniform(0.01, 0.1, MU))
    node = p.node_lp(cost_cap=1e4)
    return [np.asarray(getattr(node, f)).shape
            for f in ("c", "a_eq", "b_eq", "g", "h", "lb", "ub")]


def _stacked_args(sharding):
    shapes = _node_shapes()
    assert shapes[1] == (TAU, MU * TAU + MU + 1)
    f64 = jnp.float64
    return ([jax.ShapeDtypeStruct((), f64, sharding=sharding),
             jax.ShapeDtypeStruct((WIDTH,), jnp.bool_, sharding=sharding)]
            + [jax.ShapeDtypeStruct((WIDTH,) + s, f64, sharding=sharding)
               for s in shapes])


def _stacked_program(linsolve, newton_dtype):
    """A fresh jit(vmap) of the served path's monolithic stacked IPM row
    (the program ``solve_node_lps_ladder`` dispatches per ladder width)."""
    one = lp._stacked_one(lp._MAX_ITERS, linsolve, newton_dtype)
    return jax.jit(jax.vmap(one, in_axes=(None, 0) + (0,) * 7))


def test_stacked_ipm_default_backend_compiles_at_width_32(one_chip):
    compiled = _stacked_program("xla", "float64").lower(
        *_stacked_args(one_chip)).compile()
    mem = compiled.memory_analysis()
    # the whole program has to fit one chip's 16 GB of HBM
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def _node_args(sharding, mu, tau, width):
    """Argument shapes of the node-LP program at one dispatch width: the
    objective once, each node's compact fields stacked."""
    rng = np.random.default_rng(0)
    p = AllocationProblem(rng.uniform(1e-6, 1e-4, (mu, tau)),
                          rng.uniform(0.1, 5.0, (mu, tau)),
                          rng.uniform(1e5, 1e7, tau),
                          rng.uniform(60, 600, mu),
                          rng.uniform(0.01, 0.1, mu))
    node = p.node_lp(cost_cap=1e4)
    f64 = jnp.float64

    def rows(f):
        return jax.ShapeDtypeStruct((width,) + np.shape(getattr(node, f)),
                                    f64, sharding=sharding)

    return [jax.ShapeDtypeStruct((), f64, sharding=sharding),
            jax.ShapeDtypeStruct((width,), jnp.bool_, sharding=sharding),
            jax.ShapeDtypeStruct(node.lb.shape, f64, sharding=sharding),
            NodeRows(rows("coef"), rows("rho"), rows("pi")), None, None,
            rows("h"), rows("lb"), rows("ub")]


def _node_program(linsolve, newton_dtype):
    """A fresh jit(vmap) of the node-LP program the lockstep B&B and the
    server dispatch (objective unbatched, compact fields batched)."""
    one = lp._stacked_one(lp._MAX_ITERS, linsolve, newton_dtype)
    return jax.jit(jax.vmap(one, in_axes=(None, 0, None, NodeRows(0, 0, 0),
                                          None, None, 0, 0, 0)))


@pytest.mark.parametrize("tau", [TAU, 4096])
def test_node_ipm_compiles_at_width_16(one_chip, tau):
    """The structured node-LP program at the B&B's top rung, at the
    paper's 128 tasks and at a 4,096-option book: it fits one chip, and
    holds no dense node matrix (at 4,096 tasks the normal matrices alone
    would be 16 x 136 MB, the equality rows 16 x 2.15 GB)."""
    compiled = _node_program("xla", "float64").lower(
        *_node_args(one_chip, MU, tau, 16)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 1e9


def test_node_ipm_pallas_float32_compiles_the_kernel(one_chip,
                                                     compiled_kernels):
    compiled = _node_program("pallas", "float32").lower(
        *_node_args(one_chip, MU, TAU, 16)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stacked_ipm_pallas_float32_compiles_the_kernel(one_chip,
                                                        compiled_kernels):
    compiled = _stacked_program("pallas", "float32").lower(
        *_stacked_args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_row_sharded_stacked_ipm_compiles_on_2x2(topo):
    """The --four-chips path: the stacked IPM sharded over a 4-chip
    solver mesh, dispatched under the partitioner the solver uses (the
    Shardy partitioner refuses float64 Cholesky here).  Small LPs: the
    fault is in partitioning, not in size."""
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as PS

    from repro.runtime.sharding import solver_partitioner
    mesh = Mesh(np.array(topo.devices), ("lp_rows",),
                axis_types=(AxisType.Auto,))
    rows = NamedSharding(mesh, PS("lp_rows"))
    shapes = [(8,), (3, 8), (3,), (4, 8), (4,), (8,), (8,)]
    args = ([jax.ShapeDtypeStruct((), jnp.float64,
                                  sharding=NamedSharding(mesh, PS())),
             jax.ShapeDtypeStruct((8,), jnp.bool_, sharding=rows)]
            + [jax.ShapeDtypeStruct((8,) + s, jnp.float64, sharding=rows)
               for s in shapes])
    solver = lp._stacked_solver_sharded((0,) * 7, lp._MAX_ITERS, "xla",
                                        "float64", mesh, ("lp_rows",))
    with solver_partitioner():
        compiled = solver.lower(*args).compile()
    assert len(compiled.output_shardings[0].x.device_set) == 4


def test_pallas_float64_is_refused_on_tpu(compiled_kernels):
    a = jnp.eye(4)[None] * 2.0
    with pytest.raises(ValueError, match="newton_dtype='float32'"):
        jax.jit(lambda m, r: lp._newton_linsolve("pallas", m, r))(
            a, jnp.ones((1, 4)))


@pytest.mark.parametrize("fn", ["solve", "factor"])
def test_batched_chol_kernel_compiles_float32(one_chip, fn):
    m = 2 * MU + 1 + TAU                   # 161 normal-equation rows
    mats = jax.ShapeDtypeStruct((WIDTH, m, m), jnp.float32,
                                sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((WIDTH, m), jnp.float32, sharding=one_chip)
    if fn == "solve":
        lowered = jax.jit(lambda a, b: bc.chol_solve(
            a, b, interpret=False)).lower(mats, rhs)
    else:
        lowered = jax.jit(lambda a: bc.chol_factor(
            a, interpret=False)).lower(mats)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_mc_price_kernel_compiles(one_chip):
    params = jax.ShapeDtypeStruct((TAU // 4, N_PARAM_COLS), jnp.float32,
                                  sharding=one_chip)
    lowered = jax.jit(lambda p: mc_pricing.mc_price_sums(
        p, kind_id=KIND_IDS["asian_call"], steps=64, n_blocks=64,
        interpret=False)).lower(params)
    assert "tpu_custom_call" in lowered.compile().as_text()
