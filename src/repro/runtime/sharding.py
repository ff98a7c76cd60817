"""Logical-axis -> mesh-axis rules and activation shardings.

Single place that decides the parallelism layout:
  * params: vocab/heads/mlp/experts -> 'model' (TP/EP), layers unsharded;
  * activations: batch -> ('pod','data'); optionally seq -> 'data'
    (context parallelism for the long_500k decode cells, where batch=1
    cannot use the data axis);
  * LP megabatches: the stacked-IPM row axis -> 'lp_rows' on a solver
    mesh (:func:`repro.launch.mesh.make_solver_mesh`), falling back to
    the ('pod', 'data') batch axes on a production mesh — see
    :func:`lp_row_axes` and ``repro.core.lp.solve_lp_stacked(mesh=)``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

def solver_partitioner():
    """Partitioner context for dispatching a sharded stacked-IPM program.

    Sharded solves partition with GSPMD rather than Shardy: on TPU the
    Shardy-partitioned program is refused at XLA's float64 Cholesky ("A
    tuple parameter that is being flattened shouldn't have frontend
    attributes", jax 0.9 / libtpu 0.0.34), while GSPMD compiles it.  The
    choice is part of jit's cache key, so every dispatch of a sharded
    program runs inside this context."""
    from jax._src import config as jax_config   # no public handle
    return jax_config.use_shardy_partitioner(False)


def logical_rules(mesh, *, shard_seq: bool = False, mode: str = "train"
                  ) -> Dict[str, object]:
    """mode="train": 1D tensor parallel params (batch uses the data axes
    for activations / optimizer redundancy is acceptable).
    mode="serve"/"2d": 2D-sharded params (embed dim over the data/pod
    axes too — FSDP x TP): a trillion-parameter MoE must spread weights
    over ALL chips (serving has no optimizer state to shard; training
    giants cannot afford data-axis parameter redundancy)."""
    axes = set(mesh.axis_names)
    batch = tuple(a for a in ("pod", "data") if a in axes)
    embed_rule = None
    if mode in ("serve", "2d") and batch:
        embed_rule = batch if len(batch) > 1 else batch[0]
    rules = {
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "qdim": None,
        "kvdim": None,
        "mlp": "model",
        "experts": "model",
        "experts_r": None,
        "embed": embed_rule,
        "layers": None,
        # activation axes
        "batch": batch if len(batch) > 1 else (batch[0] if batch else None),
        "act_seq": "data" if (shard_seq and "data" in axes) else None,
        # stacked-IPM row megabatches: a dedicated solver mesh carries an
        # 'lp_rows' axis; on a production mesh the rows ride the data axes
        "lp_rows": ("lp_rows" if "lp_rows" in axes
                    else (batch if len(batch) > 1
                          else (batch[0] if batch else None))),
    }
    return rules


def batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def lp_row_axes(mesh, row_spec=None) -> Tuple[str, ...]:
    """Mesh axes carrying the stacked-IPM row (batch) dimension.

    ``row_spec`` overrides the rule table: a mesh axis name, a tuple of
    axis names, or a ``PartitionSpec`` whose first entry names the row
    axes.  Without it, a dedicated solver mesh's ``lp_rows`` axis wins,
    else the ('pod', 'data') activation-batch axes of a production mesh.
    """
    if row_spec is not None:
        if isinstance(row_spec, PS):
            row_spec = row_spec[0] if len(row_spec) else None
        if row_spec is None:
            axes: Tuple[str, ...] = ()
        elif isinstance(row_spec, str):
            axes = (row_spec,)
        else:
            axes = tuple(row_spec)
    else:
        rule = logical_rules(mesh)["lp_rows"]
        if rule is None:
            axes = ()
        elif isinstance(rule, str):
            axes = (rule,)
        else:
            axes = tuple(rule)
    missing = [a for a in axes if a not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"row axes {missing} not in mesh axes {mesh.axis_names}")
    if not axes:
        raise ValueError(
            "mesh has no row axis for LP megabatches: expected an "
            "'lp_rows' axis (make_solver_mesh) or ('pod','data') batch "
            "axes, or pass row_spec= explicitly")
    return axes


def batch_sharding(mesh, shape, *, shard_seq: bool = False,
                   seq_dim: int = 1):
    """NamedSharding for (B, L, ...) activations / token batches.
    ``shape`` is the concrete array shape — axes that do not divide their
    dim are dropped (batch=1 long-context cells fall back to replicated
    batch + optionally sharded seq)."""
    b = batch_axes(mesh)
    data_sz = int(np.prod([mesh.shape[a] for a in b])) if b else 1
    spec = [None] * len(shape)
    if b and shape[0] % data_sz == 0:
        spec[0] = b if len(b) > 1 else b[0]
    elif b and len(shape) > seq_dim and shape[seq_dim] % data_sz == 0:
        spec[seq_dim] = b if len(b) > 1 else b[0]   # context parallelism
    if shard_seq and spec[seq_dim] is None and "data" in mesh.axis_names \
            and spec[0] not in ("data", ("data",)) \
            and shape[seq_dim] % mesh.shape["data"] == 0:
        spec[seq_dim] = "data"
    return NamedSharding(mesh, PS(*spec))


def replicated(mesh):
    return NamedSharding(mesh, PS())


def cache_sharding(mesh, cache_example, cfg):
    """Shardings for KV / SSM caches: batch dim sharded over data axes,
    heads over 'model' when divisible.

    Cache leaves are recognised by rank:
      (L, B, H, S, D) kv- or mem-cache; (L, B, C, K) conv; (L, B, H, P, N)
      ssm state; () scalars.
    """
    b = batch_axes(mesh)
    model_sz = mesh.shape.get("model", 1)
    data_sz = int(np.prod([mesh.shape[a] for a in b])) if b else 1

    def one(x):
        if x.ndim == 5:
            # (L, B, heads, S, D): batch over data axes when divisible,
            # heads over 'model' when divisible; when either is not
            # available (MQA kv=1, or batch=1 long-context cells) the
            # sequence dim absorbs the idle axes (context parallelism).
            batch_dim, h, s = x.shape[1], x.shape[2], x.shape[3]
            use_batch = bool(b) and batch_dim % max(data_sz, 1) == 0
            b_spec = (b if len(b) > 1 else b[0]) if use_batch else None
            h_spec = "model" if (model_sz > 1 and h % model_sz == 0) else None
            seq_axes = []
            if h_spec is None and model_sz > 1 and s % model_sz == 0:
                seq_axes.append("model")
            if not use_batch and b:
                sz = data_sz * (model_sz if "model" in seq_axes else 1)
                if s % sz == 0:
                    seq_axes.extend(b)
            s_spec = (tuple(seq_axes) if len(seq_axes) > 1
                      else (seq_axes[0] if seq_axes else None))
            return NamedSharding(mesh, PS(None, b_spec, h_spec, s_spec, None))
        if x.ndim == 4:
            batch_dim, c = x.shape[1], x.shape[2]
            use_batch = bool(b) and batch_dim % max(data_sz, 1) == 0
            b4 = (b if len(b) > 1 else b[0]) if use_batch else None
            c_spec = "model" if (model_sz > 1 and c % model_sz == 0) else None
            return NamedSharding(mesh, PS(None, b4, c_spec, None))
        return NamedSharding(mesh, PS())

    return jax.tree.map(one, cache_example)
