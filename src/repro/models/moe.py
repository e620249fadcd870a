"""Mixture-of-Experts FFN with two production dispatch modes.

``ep`` — expert parallelism: experts sharded over the "model" mesh axis;
GShard-style capacity-bucketed dispatch with `all_to_all` inside shard_map.
Each data shard builds an (E, C, d) send buffer (C = local capacity per
expert, token dropping beyond), all_to_all splits the E axis across model
shards and returns a per-local-expert buffer of every sender's bucket; a
dense grouped einsum applies the local experts; the reverse all_to_all +
combine weights restore token order.  Collective cost: 2 × all_to_all of
activations — the term §Roofline attributes to MoE cells.

``tp`` — tensor parallelism: every expert's d_ff is sliced over "model"
(weights (E, d, F/16) per shard), tokens stay data-local, top-k dispatch is
a sorted gather + `jax.lax.ragged_dot` grouped GEMM, and the FFN output is
psum-reduced like a dense layer.  No token dropping (dropless); higher
weight-memory traffic under FSDP.  Kept as the §Perf comparison point.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import _init

def init_moe(key, cfg, dtype, fsdp: bool):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    row = "data" if fsdp else None
    p = {"router": _init(k1, (d, e), scale=0.02, dtype=jnp.float32),
         "wi": _init(k2, (e, d, f), dtype=dtype),
         "wg": _init(k3, (e, d, f), dtype=dtype),
         "wo": _init(k4, (e, f, d), dtype=dtype)}
    if cfg.moe_mode == "ep":
        s = {"router": P(row, None),
             "wi": P("model", row, None), "wg": P("model", row, None),
             "wo": P("model", row, None)}
    else:  # tp: slice d_ff
        s = {"router": P(row, None),
             "wi": P(None, row, "model"), "wg": P(None, row, "model"),
             "wo": P(None, "model", row)}
    return p, s


def _route(x2d, router, k):
    """x2d (T, d) -> (weights (T, k), experts (T, k), aux_loss)."""
    logits = x2d.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # load-balance auxiliary loss (Switch-style)
    e = router.shape[1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e), axis=0)
    aux = e * jnp.sum(me * ce)
    return w, idx, aux


# ------------------------------------------------------------------- EP --
def _ep_ffn_local(x2d, router, wi, wg, wo, *, k, cf, axis):
    """Runs inside shard_map: x2d (T_loc, d); wi/wg/wo local expert slices
    (E_loc, d, f).  Experts are sharded over mesh axis `axis`."""
    n_shards = jax.lax.axis_size(axis)
    T, d = x2d.shape
    e_loc = wi.shape[0]
    E = e_loc * n_shards
    w, idx, aux = _route(x2d, router, k)

    cap = int(max(8, round(cf * T * k / E)))
    flat_e = idx.reshape(-1)                          # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    # position of each (token, expert) pair within its expert bucket
    one_hot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)       # (T*k, E)
    pos_in_e = jnp.cumsum(one_hot, axis=0) * one_hot - 1
    pos = jnp.max(pos_in_e, axis=1)                            # (T*k,)
    keep = pos < cap                                           # token dropping
    # send buffer (E, cap, d)
    send = jnp.zeros((E, cap, d), x2d.dtype)
    send = send.at[flat_e, jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], x2d[flat_t], 0))
    # all_to_all: split E across shards, gather sender axis
    recv = jax.lax.all_to_all(send.reshape(n_shards, e_loc, cap, d),
                              axis, split_axis=0, concat_axis=0,
                              tiled=False)           # (n_shards, e_loc, cap, d)
    toks = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_shards * cap, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", toks, wi)) * \
        jnp.einsum("ecd,edf->ecf", toks, wg)
    out = jnp.einsum("ecf,efd->ecd", h, wo)          # (e_loc, n_shards*cap, d)
    back = out.reshape(e_loc, n_shards, cap, d).transpose(1, 0, 2, 3)
    ret = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                             tiled=False).reshape(E, cap, d)
    # combine: gather each kept pair's output, weight, and sum per token
    gathered = ret[flat_e, jnp.where(keep, pos, 0)]  # (T*k, d)
    gathered = jnp.where(keep[:, None], gathered, 0)
    contrib = gathered * w.reshape(-1)[:, None].astype(gathered.dtype)
    y = jnp.zeros_like(x2d).at[flat_t].add(contrib)
    return y, aux  # averaged over data shards by the caller's pmean


def moe_ffn(x, p, cfg, mesh_axes):
    """x (B, S, d) -> (y, aux_loss).  mesh_axes: dict with data/model axis
    names present in the enclosing mesh (see launch/mesh.py)."""
    B, S, d = x.shape
    if cfg.moe_mode == "tp":
        return _moe_ffn_tp(x, p, cfg)
    data_axes = mesh_axes["data"]          # e.g. ("pod", "data") or ("data",)
    model_axis = mesh_axes["model"]
    mesh = mesh_axes["mesh"]
    pspec_x = P(data_axes, None, None)
    pspec_r = P(None, None)
    pspec_w = P("model", None, None)

    @functools.partial(
        jax.shard_map, check_vma=False, mesh=mesh,
        in_specs=(pspec_x, pspec_r, pspec_w, pspec_w, pspec_w),
        out_specs=(pspec_x, P()))
    def run(xb, router, wi, wg, wo):
        T = xb.shape[0] * xb.shape[1]
        y, aux = _ep_ffn_local(xb.reshape(T, d), router, wi, wg, wo,
                               k=cfg.experts_per_token,
                               cf=cfg.capacity_factor, axis=model_axis)
        aux = jax.lax.pmean(aux, axis_name=model_axis)
        for ax in (data_axes if isinstance(data_axes, tuple) else (data_axes,)):
            aux = jax.lax.pmean(aux, axis_name=ax)
        return y.reshape(xb.shape), aux

    # FSDP gathering of expert weights happens via the in_specs on the
    # "data" dim being replicated inside shard_map: we re-constrain outside.
    return run(x, p["router"], p["wi"], p["wg"], p["wo"])


def _moe_ffn_tp(x, p, cfg):
    """Dropless sorted ragged_dot path; d_ff sliced over "model" by GSPMD."""
    B, S, d = x.shape
    T = B * S
    x2d = x.reshape(T, d)
    w, idx, aux = _route(x2d, p["router"], cfg.experts_per_token)
    k = cfg.experts_per_token
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e)
    inv = jnp.argsort(order)
    xs = x2d[jnp.repeat(jnp.arange(T), k)][order]          # (T*k, d) sorted
    group_sizes = jnp.bincount(flat_e, length=cfg.n_experts).astype(jnp.int32)
    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["wi"], group_sizes)) * \
        jax.lax.ragged_dot(xs, p["wg"], group_sizes)
    ys = jax.lax.ragged_dot(h, p["wo"], group_sizes)       # (T*k, d)
    y = (ys[inv] * w.reshape(-1)[:, None].astype(ys.dtype))
    y = jnp.sum(y.reshape(T, k, d), axis=1)
    return y.reshape(B, S, d), aux
