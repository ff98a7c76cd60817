"""Mixture-of-Experts layer: expert-parallel over the ``model`` mesh axis.

TPU-native design (DESIGN.md §4): instead of the GSPMD capacity-einsum
dispatch (whose (tokens, experts, capacity) one-hot tensor is intractable
at 32k sequence lengths), the layer is a ``shard_map`` region:

  router (replicated) -> top-k -> sort assignments by destination shard
  -> capacity-bounded send buffer -> all_to_all over 'model'
  -> local sort by expert -> ragged_dot (MXU grouped matmul)
  -> all_to_all back -> gate-weighted scatter-add combine.

``ragged_dot`` is the TPU grouped-matmul primitive (MegaBlocks analogue);
it has full AD support so the same code path trains.  When the model
axis is absent/size-1 (smoke tests) the identical math runs locally
without collectives.

Capacity drops: tokens beyond ``cap = ceil(T*k/n_shards * capacity_factor)``
per destination shard are dropped (standard MoE practice); tests use a
capacity factor large enough for zero drops and compare against the dense
reference in `moe_ref`.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.params import P

_EP_AXIS = "model"


def moe_defs(cfg) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    defs = {
        "router": P((d, e), ("embed", "experts_r")),   # replicated
        "w_gate": P((e, d, f), ("experts", "embed", "mlp")),
        "w_up": P((e, d, f), ("experts", "embed", "mlp")),
        "w_down": P((e, f, d), ("experts", "mlp", "embed")),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        defs["shared"] = {
            "w_gate": P((d, fs), ("embed", "mlp")),
            "w_up": P((d, fs), ("embed", "mlp")),
            "w_down": P((fs, d), ("mlp", "embed")),
        }
    return defs


def _group_sizes(expert_ids: jnp.ndarray, n_groups: int) -> jnp.ndarray:
    """Counts per expert id (rows must later be sorted by id)."""
    return (expert_ids[None, :] == jnp.arange(n_groups, dtype=expert_ids.dtype)[:, None]
            ).sum(axis=1).astype(jnp.int32)


def _expert_ffn(xs, w_gate, w_up, w_down, gs):
    """Grouped SwiGLU via ragged_dot. xs: (m, d) sorted by group."""
    g = jax.lax.ragged_dot(xs, w_gate, gs,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(xs, w_up, gs,
                           preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(xs.dtype)
    return jax.lax.ragged_dot(h, w_down, gs,
                              preferred_element_type=jnp.float32
                              ).astype(xs.dtype)


def _local_moe(x_flat, params, cfg, n_shards: int, use_all_to_all: bool,
               psum_axis: str | None = None):
    """Per-shard body. x_flat: (T, d) local tokens.

    ``psum_axis``: when expert weights arrive f-sliced over another mesh
    axis (2D serving layout), the down-projection yields partial sums
    that are reduced over that axis — the weights never move."""
    t, d = x_flat.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    e_loc = e // n_shards

    logits = jnp.einsum("td,de->te", x_flat, params["router"]
                        ).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, k)            # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32),
                       axis=0)
    aux = e * jnp.mean(density * probs.mean(axis=0))

    a = t * k                                              # assignments
    flat_expert = expert_idx.reshape(a)
    flat_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_gate = gates.reshape(a)

    dest = flat_expert // e_loc                            # target shard
    order = jnp.argsort(dest, stable=True)
    sd = dest[order]
    cap = int(np.ceil(a / n_shards * cfg.capacity_factor))
    starts = jnp.searchsorted(sd, jnp.arange(n_shards, dtype=sd.dtype))
    rank = jnp.arange(a, dtype=jnp.int32) - starts[sd].astype(jnp.int32)
    keep = rank < cap

    buf_x = jnp.zeros((n_shards, cap, d), x_flat.dtype)
    buf_e = jnp.full((n_shards, cap), e_loc, jnp.int32)    # e_loc == invalid
    src_tok = flat_token[order]
    buf_x = buf_x.at[sd, rank].set(
        jnp.where(keep[:, None], x_flat[src_tok], 0.0), mode="drop")
    buf_e = buf_e.at[sd, rank].set(
        jnp.where(keep, (flat_expert[order] % e_loc).astype(jnp.int32), e_loc),
        mode="drop")

    if use_all_to_all:
        recv_x = jax.lax.all_to_all(buf_x, _EP_AXIS, 0, 0)
        recv_e = jax.lax.all_to_all(buf_e, _EP_AXIS, 0, 0)
    else:
        recv_x, recv_e = buf_x, buf_e

    r = n_shards * cap
    rx = recv_x.reshape(r, d)
    re = recv_e.reshape(r)
    order2 = jnp.argsort(re, stable=True)
    xs = rx[order2]
    gs = _group_sizes(re[order2], e_loc)
    ys = _expert_ffn(xs, params["w_gate"], params["w_up"], params["w_down"],
                     gs)
    if psum_axis is not None:
        ys = jax.lax.psum(ys, psum_axis)
    valid_rows = (re[order2] < e_loc)[:, None]
    ys = jnp.where(valid_rows, ys, 0.0)
    ry = jnp.zeros_like(rx).at[order2].set(ys)
    ry = ry.reshape(n_shards, cap, d)

    if use_all_to_all:
        back = jax.lax.all_to_all(ry, _EP_AXIS, 0, 0)
    else:
        back = ry

    y_assign = back[sd, rank]                              # sorted order
    y_assign = jnp.where(keep[:, None], y_assign, 0.0)
    w = flat_gate[order].astype(y_assign.dtype)
    out = jnp.zeros_like(x_flat).at[src_tok].add(y_assign * w[:, None])
    return out, aux


def moe_apply(params, x, cfg, ctx) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, L, d) -> (out, aux_loss).  ctx: runtime ModelContext."""
    b, l, d = x.shape
    n_shards = ctx.axis_size(_EP_AXIS)

    if n_shards == 1:
        out, aux = _local_moe(x.reshape(b * l, d), params, cfg, 1, False)
        out = out.reshape(b, l, d)
    elif (ctx.moe_impl == "2d" and ctx.axis_size("data") > 1
          and b * l <= 4096):
        # Weight-stationary 2D serving path (decode): expert weights stay
        # (experts->'model', d_ff->'data') sharded where they live; the
        # small token batch is replicated over 'data' instead of
        # all-gathering ~GBs of expert weights every step.  The down-proj
        # partial sums are psum'ed over 'data'.
        from jax.sharding import PartitionSpec as PS
        f_axis = "data"
        in_specs = (
            {"router": PS(None, None),
             "w_gate": PS(_EP_AXIS, None, f_axis),
             "w_up": PS(_EP_AXIS, None, f_axis),
             "w_down": PS(_EP_AXIS, f_axis, None)},
            PS(None, None, None),          # tokens replicated over data
        )
        out_specs = (PS(None, None, None), PS())
        pmean_axes = tuple(a for a in (_EP_AXIS,)
                           if ctx.axis_size(a) > 1)

        def body2d(p, xb):
            bb, lb, _ = xb.shape
            o, aux = _local_moe(xb.reshape(bb * lb, d), p, cfg, n_shards,
                                True, psum_axis=f_axis)
            if pmean_axes:
                aux = jax.lax.pmean(aux, pmean_axes)
            return o.reshape(bb, lb, d), aux

        routed = {k: params[k] for k in
                  ("router", "w_gate", "w_up", "w_down")}
        out, aux = jax.shard_map(body2d, mesh=ctx.mesh, in_specs=in_specs,
                                 out_specs=out_specs,
                                 check_vma=False)(routed, x)
    else:
        from jax.sharding import PartitionSpec as PS
        batch_axes = ctx.batch_mesh_axes()

        router_spec = PS(None, None)
        expert_spec = PS(_EP_AXIS, None, None)
        in_specs = (
            {"router": router_spec, "w_gate": expert_spec,
             "w_up": expert_spec, "w_down": expert_spec},
            PS(batch_axes, None, None),
        )
        out_specs = (PS(batch_axes, None, None), PS())

        pmean_axes = tuple(a for a in (_EP_AXIS,) + tuple(ctx.batch_axes)
                           if ctx.axis_size(a) > 1)

        def body(p, xb):
            bb, lb, _ = xb.shape
            o, aux = _local_moe(xb.reshape(bb * lb, d), p, cfg, n_shards,
                                True)
            # aux is per-shard; average over every mesh axis it varies on
            if pmean_axes:
                aux = jax.lax.pmean(aux, pmean_axes)
            return o.reshape(bb, lb, d), aux

        routed = {k: params[k] for k in
                  ("router", "w_gate", "w_up", "w_down")}
        out, aux = jax.shard_map(body, mesh=ctx.mesh, in_specs=in_specs,
                                 out_specs=out_specs,
                                 check_vma=False)(routed, x)

    if cfg.n_shared_experts and "shared" in params:
        from repro.models import layers
        out = out + layers.swiglu(params["shared"], x)
    return out, aux


def moe_ref(params, x, cfg) -> jnp.ndarray:
    """Dense O(T*E) reference (tests only): loop over every expert."""
    b, l, d = x.shape
    xf = x.reshape(-1, d)
    e, k = cfg.n_experts, cfg.experts_per_token
    logits = (xf @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    out = jnp.zeros_like(xf)
    for ei in range(e):
        h = jax.nn.silu((xf @ params["w_gate"][ei]).astype(jnp.float32))
        h = h * (xf @ params["w_up"][ei]).astype(jnp.float32)
        y = (h.astype(xf.dtype) @ params["w_down"][ei]).astype(jnp.float32)
        w = ((idx == ei) * gates).sum(-1)[:, None]
        out = out + (w * y).astype(out.dtype)
    if cfg.n_shared_experts and "shared" in params:
        from repro.models import layers
        out = out + layers.swiglu(params["shared"], xf)
    return out.reshape(b, l, d)
