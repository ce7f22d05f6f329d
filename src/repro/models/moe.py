"""Token-choice top-k Mixture-of-Experts with capacity-bounded,
index-based dispatch.

Dispatch uses gather/scatter with token indices (a cumsum position inside
each expert's capacity), NOT a one-hot dispatch einsum — the (E, C, d)
buffers are the only materialized intermediates, which keeps per-shard
memory linear in tokens (a one-hot (B,S,E,C) mask would be quadratic).

Sharding:
  "ep": expert dim of the weights and buffers on the model axis (true
        expert parallelism; dispatch/combine lower to cross-shard
        collectives).  Requires num_experts % model_axis == 0.
  "tp": d_ff on the model axis, experts replicated (grok-1: 8 experts on
        a 16-way axis).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.dist.sharding import shard
from repro.models.param import ParamDef


def moe_defs(cfg: ModelConfig, n_layers: int) -> Dict[str, ParamDef]:
    m = cfg.moe
    d = cfg.d_model
    # expert splitting: weights stored as virtual (E*r, d, f/r) children
    xv = m.virtual_experts
    fv = cfg.d_ff // m.split_factor
    e_ax = "expert" if m.sharding == "ep" else None
    f_ax = None if m.sharding == "ep" else "ff"
    L = n_layers
    return {
        # router is tiny (PARENT experts): replicated so the shard_map
        # path can read it locally
        "router": ParamDef((L, d, m.num_experts), ("layers", None, None), dtype="float32"),
        "w_gate": ParamDef((L, xv, d, fv), ("layers", e_ax, "embed", f_ax), init="fan_in", scale=1.0),
        "w_up": ParamDef((L, xv, d, fv), ("layers", e_ax, "embed", f_ax), init="fan_in", scale=1.0),
        "w_down": ParamDef((L, xv, fv, d), ("layers", e_ax, f_ax, "embed"), init="fan_in", scale=1.0),
    }


def capacity(num_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(num_tokens * m.top_k * m.capacity_factor / m.num_experts)
    c = max(8, -(-c // 8) * 8)  # round up to a multiple of 8
    return min(c, num_tokens)


def _route(gates, m):
    """Top-k over PARENT experts, then expand to virtual children (each
    selected parent routes the token to all `split_factor` children with
    the same gate — the children's partial outputs sum to the parent's
    full FFN output). Returns (top_e_virtual (n, k*r), top_g_virtual)."""
    r = m.split_factor
    top_g, top_e = jax.lax.top_k(gates, m.top_k)          # (n, k) parents
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)
    if r == 1:
        return top_e, top_g, top_e, top_g
    kids = jnp.arange(r, dtype=top_e.dtype)
    top_e_v = (top_e[..., None] * r + kids).reshape(top_e.shape[0], -1)
    top_g_v = jnp.repeat(top_g, r, axis=-1)
    return top_e_v, top_g_v, top_e, top_g


def _dispatch_local(xf, top_e, m, cap):
    """Local (per-shard) capacity dispatch over VIRTUAL experts.
    xf: (n, d); top_e: (n, k_v). Returns (ein (E_v, C, d), pos2 (n, k_v))."""
    n, d = xf.shape
    e = m.virtual_experts
    kv = top_e.shape[1]
    flat_e = top_e.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    token_ids = jnp.repeat(jnp.arange(n, dtype=jnp.int32), kv)
    idx = jnp.zeros((e, cap), dtype=jnp.int32)
    idx = idx.at[flat_e, flat_pos].set(token_ids, mode="drop")
    ein = xf[idx]                                         # (E_v, C, d)
    return ein, flat_pos.reshape(n, kv)


def _combine_local(o, top_e, pos2, top_g, cap):
    """o: (E, C, d); returns y (n, d)."""
    e, c, d = o.shape
    n, k = top_e.shape
    kept = pos2 < cap
    slot = jnp.where(kept, top_e * cap + pos2, 0)
    picked = o.reshape(e * c, d)[slot]                    # (n, k, d)
    comb_w = (top_g * kept).astype(o.dtype)
    return jnp.einsum("nk,nkd->nd", comb_w, picked)


def _aux_loss(gates, top_e, m):
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, m.num_experts, dtype=jnp.float32), axis=1),
        axis=0,
    ) / m.top_k
    return m.num_experts * jnp.sum(me * ce) * m.aux_loss_weight


def moe_apply_xla(
    p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d). Returns (y, aux_loss)."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    cap = capacity(n, cfg)

    xf = x.reshape(n, d)
    logits = jnp.einsum(
        "nd,dX->nX", xf.astype(jnp.float32), p["router"]
    )                                                     # (N, E) f32
    gates = jax.nn.softmax(logits, axis=-1)

    top_e_v, top_g_v, top_e_p, _ = _route(gates, m)
    ein, pos2 = _dispatch_local(xf, top_e_v, m, cap)
    # capacity dim sharded over the batch axes; expert dim over model (EP)
    e_ax = "expert" if m.sharding == "ep" else None
    ein = shard(ein, e_ax, "batch", None)

    g = jnp.einsum("xcd,xdf->xcf", ein, p["w_gate"])
    u = jnp.einsum("xcd,xdf->xcf", ein, p["w_up"])
    f_ax = None if m.sharding == "ep" else "ff"
    g = shard(g, e_ax, "batch", f_ax)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(ein.dtype) * u
    o = jnp.einsum("xcf,xfd->xcd", h, p["w_down"])        # (E_v, C, d)
    o = shard(o, e_ax, "batch", None)

    y = _combine_local(o, top_e_v, pos2, top_g_v, cap)
    y = y.reshape(b, s, d).astype(x.dtype)
    return y, _aux_loss(gates, top_e_p, m)


# ---------------------------------------------------------------------------
# shard_map path: dispatch/combine stay LOCAL to each device; experts talk
# through explicit collectives.  This is the TPU-native adaptation of the
# token->expert shuffle (no XLA auto-partitioned global scatter, which
# replicates the (E, C, d) buffers and all-reduces the combine).
#
#   "ep": all_to_all over the model axis moves capacity slices to the
#         expert's home shard (requires num_experts % model == 0).
#   "tp": d_ff sharded over the model axis; partial outputs psum'd.
#   Both: weights all-gathered over the FSDP ("data") axis on entry and
#         their grads reduce-scattered on the way back (AD transpose).
# ---------------------------------------------------------------------------


def moe_apply_shard_map(
    p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig, mesh
) -> Tuple[jax.Array, jax.Array]:
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    b, s, d = x.shape
    axes = mesh.axis_names
    bd = tuple(a for a in ("pod", "data") if a in axes)
    sizes = dict(zip(axes, mesh.devices.shape))
    n_model = sizes.get("model", 1)
    all_axes = tuple(axes)

    ep = m.sharding == "ep" and m.virtual_experts % n_model == 0
    # EP: tokens seq-split over model; the a2a moves them to their expert's
    #     home shard.
    # TP: tokens REPLICATED over model — every model shard computes its
    #     d_ff/n slice for ALL local tokens, psum combines. (Seq-splitting
    #     here would psum partials of DIFFERENT token sets — wrong.)
    seq_ax = "model" if (ep and n_model > 1) else None

    def local(xl, router, wg, wu, wd):
        bl, sl, _ = xl.shape
        nl = bl * sl
        xf = xl.reshape(nl, d)
        logits = jnp.einsum("nd,dX->nX", xf.astype(jnp.float32), router)
        gates = jax.nn.softmax(logits, axis=-1)
        cap = capacity(nl, cfg)
        top_e_v, top_g_v, top_e_p, _ = _route(gates, m)
        ein, pos2 = _dispatch_local(xf, top_e_v, m, cap)

        if ep and seq_ax:
            # (E, C, d) -> (E/n, C*n, d): capacity slices travel to the
            # expert's home model-shard
            ein = jax.lax.all_to_all(
                ein, "model", split_axis=0, concat_axis=1, tiled=True
            )
        # FSDP: weights arrive (E_loc, d/data, f_loc); gather d
        wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)

        g = jnp.einsum("xcd,xdf->xcf", ein, wg)
        u = jnp.einsum("xcd,xdf->xcf", ein, wu)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(ein.dtype) * u
        o = jnp.einsum("xcf,xfd->xcd", h, wd)

        if ep and seq_ax:
            o = jax.lax.all_to_all(
                o, "model", split_axis=1, concat_axis=0, tiled=True
            )
        elif not ep and n_model > 1:
            # tp: partial over the sharded d_ff contraction
            o = jax.lax.psum(o, "model")

        y = _combine_local(o, top_e_v, pos2, top_g_v, cap)
        aux = _aux_loss(gates, top_e_p, m)
        aux = jax.lax.pmean(aux, all_axes)
        return y.reshape(bl, sl, d).astype(xl.dtype), aux

    if ep:
        w_specs = (P("model", "data", None), P("model", "data", None),
                   P("model", None, "data"))
    else:
        w_specs = (P(None, "data", "model"), P(None, "data", "model"),
                   P(None, "model", "data"))

    # the replication checker is off: the psum/a2a mix confuses it
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(bd, seq_ax, None), P(None, None)) + w_specs,
        out_specs=(P(bd, seq_ax, None), P()),
        check_vma=False,
    )
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_apply(
    p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    from repro.dist.sharding import get_mesh, get_parallel

    mesh = get_mesh()
    if mesh is not None and get_parallel().moe_impl == "shard_map":
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_model = sizes.get("model", 1)
        # decode (seq not splittable over model) uses the XLA path — the
        # buffers are tiny there
        if x.shape[1] % n_model == 0:
            return moe_apply_shard_map(p, x, cfg, mesh)
    return moe_apply_xla(p, x, cfg)
