"""Mixture-of-Experts FFN with sort-based capacity dispatch.

TPU-native dispatch: instead of the GShard [T, E, C] one-hot dispatch
tensor (O(T*E*C) memory — infeasible at 128 experts), tokens are routed by
argsort(expert_id) + rank-within-expert into a fixed [E, C, d] buffer,
expert GEMMs run as one batched einsum over the stacked expert weights,
and results scatter back weighted by router probabilities. Overflow
(rank >= capacity) drops tokens — standard capacity-factor semantics.

Sharding: the [E, C, d] buffer is constrained to the expert axis, so the
token->buffer scatter lowers to the EP all-to-all under pjit. An auxiliary
load-balance loss (Switch) and router z-loss are returned for training;
the SDE's CountMin expert-load synopsis consumes the same assignment
stream for monitoring.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from .layers import init_dense, init_mlp, mlp_forward

def init_moe(key, cfg: ModelConfig) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = dict(
        router=init_dense(ks[0], (d, e)).astype(jnp.float32),
        wg=init_dense(ks[1], (e, d, f), in_axis=1),
        wu=init_dense(ks[2], (e, d, f), in_axis=1),
        wd=init_dense(ks[3], (e, f, d), in_axis=1),
    )
    if cfg.moe_dense_residual:
        p["dense"] = init_mlp(ks[4], d, cfg.d_ff_dense or cfg.d_ff)
    return p


def capacity_of(cfg: ModelConfig, n_tokens: int) -> int:
    """Per-shard expert capacity. Dispatch is shard-LOCAL (shard_map), so
    no mesh-divisibility constraint applies — keep the floor small: a
    64-floor made arctic's decode GEMMs 32x larger than the routed
    tokens (§Perf)."""
    cap = int(np.ceil(n_tokens * cfg.top_k * cfg.capacity_factor
                      / cfg.n_experts))
    return max(8, int(np.ceil(cap / 8)) * 8)


def _dispatch_plan(flat: jax.Array, router: jax.Array, e: int, k: int,
                   cap: int):
    """Local routing: top-k, rank-within-expert via argsort, capacity
    masking. Pure local compute — no collectives."""
    t = flat.shape[0]
    logits = flat.astype(jnp.float32) @ router                  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                        # [T, k]
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    e_flat = topi.reshape(t * k)
    w_flat = topw.reshape(t * k)
    tok_idx = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    is_start = jnp.concatenate(
        [jnp.ones((1,), jnp.int32),
         (sorted_e[1:] != sorted_e[:-1]).astype(jnp.int32)])
    run_id = jnp.cumsum(is_start) - 1
    start_pos = jnp.where(is_start == 1, jnp.arange(t * k), 0)
    start_of_run = jax.ops.segment_max(start_pos, run_id,
                                       num_segments=t * k)
    rank_sorted = jnp.arange(t * k) - start_of_run[run_id]
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted)

    keep = rank < cap
    dest_e = jnp.where(keep, e_flat, 0)
    # dropped assignments go OUT OF BOUNDS (mode="drop" discards them);
    # routing them to slot (0,0) would zero-clobber a real token's slot
    dest_c = jnp.where(keep, rank, cap)
    return dict(logits=logits, probs=probs, topi=topi, keep=keep,
                dest_e=dest_e, dest_c=dest_c, tok_idx=tok_idx,
                w_flat=w_flat)


def _expert_compute(flat, plan, p_wg, p_wu, p_wd, e, cap):
    """Scatter -> batched expert GEMMs -> gather/combine. All LOCAL."""
    t, d = flat.shape
    buf = jnp.zeros((e, cap, d), flat.dtype)
    vals = jnp.where(plan["keep"][:, None], flat[plan["tok_idx"]], 0)
    # non-keep entries carry dest_c == cap (out of bounds) -> dropped
    buf = buf.at[plan["dest_e"], plan["dest_c"]].set(vals, mode="drop")
    g = jnp.einsum("ecd,edf->ecf", buf, p_wg)
    u = jnp.einsum("ecd,edf->ecf", buf, p_wu)
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, p_wd)
    back = out_buf[plan["dest_e"], plan["dest_c"]]
    back = jnp.where(plan["keep"][:, None],
                     back * plan["w_flat"][:, None].astype(back.dtype),
                     0).astype(flat.dtype)
    return jnp.zeros((t, d), flat.dtype).at[plan["tok_idx"]].add(back)


def _aux_losses(plan, e):
    load = jnp.mean(jax.nn.one_hot(plan["topi"][:, 0], e,
                                   dtype=jnp.float32), 0)
    imp = jnp.mean(plan["probs"], axis=0)
    return dict(
        lb_loss=e * jnp.sum(load * imp),
        z_loss=jnp.mean(jax.nn.logsumexp(plan["logits"], axis=-1) ** 2),
        expert_load=jax.lax.stop_gradient(load),
    )


def moe_ffn(x: jax.Array, p: Dict, cfg: ModelConfig,
            constrain=lambda t, axes: t) -> Tuple[jax.Array, Dict]:
    """Reference (single-mesh / smoke-test) path: x [B,S,d] ->
    (out [B,S,d], aux). Distributed runs use moe_ffn_shardmap — same
    math, shard-local dispatch."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = capacity_of(cfg, t)
    flat = x.reshape(t, d)
    plan = _dispatch_plan(flat, p["router"], e, k, cap)
    out = _expert_compute(flat, plan, p["wg"], p["wu"], p["wd"], e, cap)
    if p.get("dense") is not None:
        out = out + mlp_forward(flat, p["dense"], cfg.mlp_act)
    return out.reshape(b, s, d), _aux_losses(plan, e)


def moe_ffn_shardmap(x: jax.Array, p: Dict, cfg: ModelConfig, mesh,
                     rules, mode: str = "train") -> Tuple[jax.Array, Dict]:
    """Distributed MoE (§Perf iteration 1 — see EXPERIMENTS.md).

    The pjit scatter/gather dispatch lowers to catastrophic all-reduces
    ([2M, 4096] f32 per layer). This path instead runs dispatch/combine
    SHARD-LOCALLY under shard_map:

      tokens   sharded over ("pod","data")       — local top-k + scatter
      experts  batched (E unsharded)             — works for E=8/16/128
      d_ff     sharded over "model"              — Megatron-style TP
      d_model  weights sharded over "data" (FSDP), all-gathered on use

    Collectives per layer: weight all-gather (FSDP) + ONE bf16 psum of
    [T_loc, d] for the f-contraction. No all-to-all, no giant gathers.
    Per-shard capacity doubles as shard-level load balancing.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_batch = max(_axis_size(mesh, batch_axes), 1)
    if b % n_batch != 0:
        # tiny/odd batches (long_500k b=1): replicated reference path
        return moe_ffn(x, p, cfg)
    tp = "model"
    t_loc = (b // n_batch) * s
    cap = capacity_of(cfg, t_loc)

    if mode == "train":
        fsdp = getattr(rules, "fsdp", "data") is not None
    else:
        # serving: keep expert weights resident when their TP shard fits
        fsdp = cfg.expert_param_count() * 2 / mesh.shape.get(
            "model", 1) > 12e9

    def local_fn(x_loc, router, wg, wu, wd, dense):
        bl, sl, _ = x_loc.shape
        flat = x_loc.reshape(bl * sl, d)
        if fsdp:
            # FSDP: gather the d_model shard of the weights on use
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
        plan = _dispatch_plan(flat, router, e, k, cap)
        out = _expert_compute(flat, plan, wg, wu, wd, e, cap)
        if dense is not None:
            dg, du, dd = dense["wg"], dense["wu"], dense["wd"]
            if fsdp:
                dg = jax.lax.all_gather(dg, "data", axis=0, tiled=True)
                du = jax.lax.all_gather(du, "data", axis=0, tiled=True)
                dd = jax.lax.all_gather(dd, "data", axis=1, tiled=True)
            out = out + mlp_forward(flat, dict(wg=dg, wu=du, wd=dd),
                                    cfg.mlp_act)
        # f-contraction partial sums -> one psum over the TP axis
        out = jax.lax.psum(out, tp)
        aux = _aux_losses(plan, e)
        aux = jax.tree.map(lambda v: jax.lax.pmean(v, batch_axes), aux)
        return out.reshape(bl, sl, d), aux

    batch_spec = batch_axes if batch_axes else None
    dax = "data" if fsdp else None
    dense_spec = (dict(wg=P(dax, tp), wu=P(dax, tp), wd=P(tp, dax))
                  if p.get("dense") is not None else None)
    in_specs = (
        P(batch_spec, None, None),                  # x: tokens over batch
        P(None, None),                              # router: replicated
        P(None, dax, tp),                           # wg [E, d, f]
        P(None, dax, tp),                           # wu
        P(None, tp, dax),                           # wd [E, f, d]
        dense_spec,                                 # arctic residual
    )
    out_specs = (P(batch_spec, None, None),
                 dict(lb_loss=P(), z_loss=P(), expert_load=P(None)))
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False)
    out, aux = fn(x, p["router"], p["wg"], p["wu"], p["wd"],
                  p.get("dense"))
    return out, aux


def _axis_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def moe_ffn_ep_decode(x: jax.Array, p: Dict, cfg: ModelConfig, mesh,
                      rules) -> Tuple[jax.Array, Dict]:
    """Expert-parallel DECODE path (§Perf iteration 13).

    For the MoE giants (arctic 937 GB of expert weights), the serving
    bottleneck is re-gathering FSDP-sharded weights every token. Here the
    weights stay RESIDENT: experts sharded over "data" (E/16 per shard)
    and d_ff over "model" (f/16) — 3.7 GB/device for arctic. The decode
    batch is tiny (128 tokens), so instead of an all-to-all we simply
    all-gather the tokens (~2 MB), let every shard run ITS experts on the
    tokens routed to them, and psum the partial outputs over both axes.

    Requires E % data_size == 0; caller falls back otherwise.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_data = mesh.shape["data"]
    e_local = e // n_data
    t_glob = b * s
    cap = capacity_of(cfg, t_glob)

    def _my_batch_start(bl):
        pos = jnp.int32(0)
        mul = 1
        for a in reversed(batch_axes):
            pos = pos + jax.lax.axis_index(a) * mul
            mul = mul * mesh.shape[a]
        return pos * bl

    def local_fn(x_loc, router, wg, wu, wd, dense):
        # gather ALL tokens (tiny at decode batch sizes: ~2 MB)
        xg = jax.lax.all_gather(x_loc, batch_axes, axis=0, tiled=True)
        flat = xg.reshape(t_glob, d)
        plan = _dispatch_plan(flat, router, e, k, cap)
        # keep only assignments owned by MY expert shard
        shard = jax.lax.axis_index("data")
        mine = plan["dest_e"] // e_local == shard
        plan = dict(plan, keep=plan["keep"] & mine,
                    dest_e=jnp.where(mine, plan["dest_e"] % e_local, 0),
                    dest_c=jnp.where(mine, plan["dest_c"], cap))
        out = _expert_compute(flat, plan, wg, wu, wd, e_local, cap)
        if dense is not None:
            # dense residual: d_ff over model; count it on data-shard 0
            dres = mlp_forward(flat, dense, cfg.mlp_act)
            out = out + jnp.where(shard == 0, dres, 0).astype(out.dtype)
        # sum expert partials (data axis) AND f-contraction (model axis)
        out = jax.lax.psum(out, ("data", "model"))
        out = out.reshape(xg.shape)
        idx = _my_batch_start(x_loc.shape[0])
        out = jax.lax.dynamic_slice_in_dim(out, idx, x_loc.shape[0], 0)
        aux = dict(lb_loss=jnp.zeros((), jnp.float32),
                   z_loss=jnp.zeros((), jnp.float32),
                   expert_load=jnp.zeros((e,), jnp.float32))
        return out, aux

    batch_spec = batch_axes if batch_axes else None
    dense_spec = (dict(wg=P(None, "model"), wu=P(None, "model"),
                       wd=P("model", None))
                  if p.get("dense") is not None else None)
    in_specs = (
        P(batch_spec, None, None),
        P(None, None),
        P("data", None, "model"),                   # wg [E, d, f] resident
        P("data", None, "model"),
        P("data", "model", None),                   # wd [E, f, d]
        dense_spec,
    )
    out_specs = (P(batch_spec, None, None),
                 dict(lb_loss=P(), z_loss=P(), expert_load=P(None)))
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False)
    out, aux = fn(x, p["router"], p["wg"], p["wu"], p["wd"],
                  p.get("dense"))
    return out, aux
