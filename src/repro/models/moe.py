"""Mixture-of-experts layer: sort-based (MegaBlocks-style) capacity dispatch.

Instead of the GShard one-hot dispatch einsum (O(T·E·C·d) FLOPs — which would
dwarf the expert compute for E=384), tokens are ranked within their routed
expert via an argsort, scattered into a capacity-bounded [E, C, d] buffer,
processed with batched expert matmuls, and gathered back weighted by the
router probabilities. Under GSPMD the [E, C, d] buffer is sharded E→'model'
(expert parallelism) and C→'data'; the scatter lowers to an all-to-all.

Supports Arctic-style dense residual branches and DeepSeek/Kimi-style shared
experts, per ``MoEConfig``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import MoEConfig
from repro.models.layers import ffn
from repro.runtime.pspec import logical_constraint


def capacity(n_tokens: int, cfg: MoEConfig) -> int:
    c = int(math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly tiling


def route(router_w: jax.Array, x: jax.Array, cfg: MoEConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x:[T,d] -> (top_probs [T,k], top_idx [T,k], aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # [T,E]
    top_p, top_i = lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing loss
    T = x.shape[0]
    me = probs.mean(0)                                            # [E]
    one_hot_top1 = jax.nn.one_hot(top_i[:, 0], cfg.n_experts, dtype=jnp.float32)
    ce = one_hot_top1.mean(0)
    aux = cfg.n_experts * jnp.sum(me * ce)
    return top_p, top_i, aux


def dispatch_indices(top_i: jax.Array, n_experts: int, cap: int):
    """Ranks each (token, slot) assignment within its expert.

    Returns (expert_id [A], slot [A], keep [A]) with A = T*k; assignments
    beyond expert capacity are dropped (slot clamped, keep=False).
    """
    A = top_i.shape[0] * top_i.shape[1]
    e_flat = top_i.reshape(A)
    order = jnp.argsort(e_flat)                                   # stable
    e_sorted = e_flat[order]
    counts = jnp.bincount(e_flat, length=n_experts)
    starts = jnp.cumsum(counts) - counts                          # [E]
    rank_sorted = jnp.arange(A) - starts[e_sorted]
    rank = jnp.zeros((A,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = rank < cap
    slot = jnp.minimum(rank, cap - 1)
    return e_flat, slot, keep


def _routed_local(xt, router_w, wg, wu, wd, cfg: MoEConfig, gated: bool,
                  expert_offset: int, n_local_experts: int,
                  batch_axes, model_axis: Optional[str]):
    """Per-device routed-expert compute (runs inside shard_map, or globally
    when no mesh is active with offset=0/n_local=E/axes empty).

    xt: [T_loc, d]; wg/wu/wd hold only this rank's experts (and may need no
    gathering — the caller hands them fully materialized on the feature dim).
    """
    T_loc, d = xt.shape
    top_p, top_i, aux = route(router_w, xt, cfg)
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)
    cap = capacity(T_loc, cfg)
    e_flat, slot, keep = dispatch_indices(top_i, cfg.n_experts, cap)
    # keep only this rank's experts
    if model_axis is not None:
        own = (e_flat >= expert_offset) & (e_flat < expert_offset + n_local_experts)
        keep = keep & own
    e_loc = jnp.clip(e_flat - expert_offset, 0, n_local_experts - 1)

    tok = jnp.arange(e_flat.shape[0]) // cfg.top_k
    e_scatter = jnp.where(keep, e_loc, n_local_experts)      # OOB => dropped
    buf = jnp.zeros((n_local_experts, cap, d), xt.dtype)
    buf = buf.at[e_scatter, slot].set(xt[tok], mode="drop")

    if gated:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(xt.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wu.astype(xt.dtype))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, wu.astype(xt.dtype)))
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd.astype(xt.dtype))

    got = out_buf[e_loc, slot]                               # [A, d]
    w = (top_p.reshape(-1) * keep).astype(jnp.float32)
    y = (got.astype(jnp.float32) * w[:, None]).reshape(T_loc, cfg.top_k, d).sum(1)
    # combine in model dtype: halves the dominant cross-model all-reduce
    # bytes (per-rank partials are ≤top_k-expert sums — bf16-safe)
    y = y.astype(xt.dtype)
    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
    return y, aux


def _routed_shardmap(params, xt: jax.Array, cfg: MoEConfig, gated: bool):
    """Expert-parallel routed experts via shard_map: tokens stay sharded on
    the batch axes (replicated over 'model'); each model rank owns E/|model|
    experts, dispatches locally (per-shard capacity), and the combine is one
    psum over 'model'. Avoids GSPMD's replicated giant gather/scatter."""
    from repro.runtime import pspec as PS
    mesh = PS.active_mesh()
    spec_x = PS.resolve(("batch", None), shape=xt.shape)
    spec_router = PS.resolve((None, None))
    spec_wg = PS.resolve(("expert", "fsdp", None))
    spec_wd = PS.resolve(("expert", None, "fsdp"))
    model_axis = spec_wg[0]
    batch_axes = spec_x[0]
    n_model = mesh.shape[model_axis] if model_axis else 1
    assert cfg.n_experts % n_model == 0, (cfg.n_experts, n_model)
    e_loc = cfg.n_experts // n_model
    fsdp_axis = spec_wg[1]

    def local_fn(xt_l, router_w, wg_l, wu_l, wd_l):
        if fsdp_axis is not None:
            # FSDP all-gather of this rank's expert weights (feature dim)
            wg_f = jax.lax.all_gather(wg_l, fsdp_axis, axis=1, tiled=True)
            wu_f = jax.lax.all_gather(wu_l, fsdp_axis, axis=1, tiled=True)
            wd_f = jax.lax.all_gather(wd_l, fsdp_axis, axis=2, tiled=True)
        else:
            wg_f, wu_f, wd_f = wg_l, wu_l, wd_l
        off = (jax.lax.axis_index(model_axis) * e_loc) if model_axis else 0
        return _routed_local(xt_l, router_w, wg_f, wu_f, wd_f, cfg, gated,
                             off, e_loc, batch_axes, model_axis)

    wg = params.get("wg", params["wu"])
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(spec_x, spec_router, spec_wg, spec_wg, spec_wd),
        out_specs=(spec_x, jax.sharding.PartitionSpec()),
        check_vma=False)
    y, aux = fn(xt, params["router"], wg, params["wu"], params["wd"])
    return y, aux


def moe_ffn(params, x: jax.Array, cfg: MoEConfig, *, gated: bool = True,
            d_ff_dense: int = 0) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y [B, S, d], aux_loss scalar)."""
    from repro.runtime import pspec as PS
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)

    mesh = PS.active_mesh()
    if mesh is not None:
        y, aux = _routed_shardmap(params, xt, cfg, gated)
        if cfg.n_shared_experts:
            y = y + ffn({"wg": params.get("shared_wg"), "wu": params["shared_wu"],
                         "wd": params["shared_wd"]}, xt, gated=gated)
        if cfg.dense_residual:
            y = y + ffn({"wg": params.get("dense_wg"), "wu": params["dense_wu"],
                         "wd": params["dense_wd"]}, xt, gated=gated)
        return y.reshape(B, S, d), aux

    top_p, top_i, aux = route(params["router"], xt, cfg)
    cap = capacity(T, cfg)
    e_flat, slot, keep = dispatch_indices(top_i, cfg.n_experts, cap)

    # scatter tokens -> [E, C, d]; dropped assignments scatter out of bounds
    tok = jnp.arange(e_flat.shape[0]) // cfg.top_k
    e_scatter = jnp.where(keep, e_flat, cfg.n_experts)            # OOB => dropped
    buf = jnp.zeros((cfg.n_experts, cap, d), x.dtype)
    buf = buf.at[e_scatter, slot].set(xt[tok], mode="drop")
    buf = logical_constraint(buf, ("expert", "capacity", None))

    # batched expert FFN: [E,C,d] @ [E,d,f] -> [E,C,f] @ [E,f,d]
    if gated:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["wg"].astype(x.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, params["wu"].astype(x.dtype))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, params["wu"].astype(x.dtype)))
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["wd"].astype(x.dtype))
    out_buf = logical_constraint(out_buf, ("expert", "capacity", None))

    # gather back, weight by router prob, zero dropped
    got = out_buf[e_flat, slot]                                   # [A, d]
    w = (top_p.reshape(-1) * keep).astype(jnp.float32)
    y = (got.astype(jnp.float32) * w[:, None]).reshape(T, cfg.top_k, d).sum(1)
    y = y.astype(x.dtype)

    # shared experts (always-on)
    if cfg.n_shared_experts:
        y = y + ffn({"wg": params["shared_wg"], "wu": params["shared_wu"],
                     "wd": params["shared_wd"]}, xt, gated=gated)
    # Arctic dense residual branch (parallel full-width FFN)
    if cfg.dense_residual:
        y = y + ffn({"wg": params["dense_wg"], "wu": params["dense_wu"],
                     "wd": params["dense_wd"]}, xt, gated=gated)
    return y.reshape(B, S, d), aux
