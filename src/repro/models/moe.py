"""Mixture-of-Experts FFN with capacity-based dispatch.

Distribution is explicit (shard_map), not left to GSPMD: sparse dispatch via
scatter lowers badly under automatic propagation, and the collective pattern
(all-to-all for EP) is exactly what the roofline analysis must see.

Two sharded modes, chosen by expert-count divisibility:
  * EP  (num_experts % model_axis == 0): experts live on model shards;
    dispatch buffers are exchanged with two all-to-alls per direction
    (GShard-style).
  * TP  (otherwise, e.g. granite's 40 experts on a 16-way axis): every shard
    holds all experts but only a 1/M slice of d_ff; the down-projection's
    partial sums are combined with a psum over the model axis.

On a single device (smoke tests) the same local math runs without shard_map.

Top-k routing uses k slot-wise top-1 dispatches: each slot scatters its token
into an (E, C, D) capacity buffer (local scatter — exact, deterministic,
token-dropping beyond capacity, GShard semantics).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.configs.base import ModelConfig

from .layers import ApplyCtx
from .params import P

Array = jax.Array


def moe_spec(cfg: ModelConfig) -> Dict[str, P]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    spec = {
        "router": P((d, e), ("embed", "experts"), scale=0.01),
        "wi": P((e, d, f), ("experts", "embed", "mlp")),
        "wg": P((e, d, f), ("experts", "embed", "mlp")),
        "wo": P((e, f, d), ("experts", "mlp", "embed")),
    }
    if cfg.moe_residual:
        spec["res_wi"] = P((d, f), ("embed", "mlp"))
        spec["res_wg"] = P((d, f), ("embed", "mlp"))
        spec["res_wo"] = P((f, d), ("mlp", "embed"))
    return spec


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    cap = int(tokens * cfg.experts_per_token * cfg.capacity_factor / cfg.num_experts)
    return max(cap, 1)


def _dispatch_local(
    x: Array,  # (T, D)
    gates: Array,  # (T, k) combine weights
    experts: Array,  # (T, k) int32 expert ids
    num_experts: int,
    capacity: int,
) -> Tuple[Array, Array, Array, Array]:
    """Scatter tokens into per-expert capacity buffers (local, exact).

    Returns (buffers (E, C, D), expert_ids (T,k), slot_pos (T,k), keep (T,k)).
    """
    t, k = gates.shape
    # position of each (token, slot) within its expert queue: cumulative count
    # over the flattened slot-major order (slot 0 of all tokens first — slot 0
    # carries the highest gate, so it wins capacity contention).
    e_flat = experts.T.reshape(-1)  # (k*T,) slot-major
    onehot = jax.nn.one_hot(e_flat, num_experts, dtype=jnp.int32)  # (kT, E)
    pos_flat = jnp.cumsum(onehot, axis=0) - 1  # (kT, E)
    pos_flat = jnp.take_along_axis(pos_flat, e_flat[:, None], axis=1)[:, 0]  # (kT,)
    keep_flat = pos_flat < capacity
    pos = pos_flat.reshape(k, t).T  # (T, k)
    keep = keep_flat.reshape(k, t).T  # (T, k)

    buffers = jnp.zeros((num_experts, capacity, x.shape[-1]), x.dtype)
    for slot in range(k):
        contrib = jnp.where(keep[:, slot, None], x, 0.0)
        idx_pos = jnp.where(keep[:, slot], pos[:, slot], 0)
        buffers = buffers.at[experts[:, slot], idx_pos].add(contrib)
    return buffers, experts, pos, keep


def _combine_local(
    y_buffers: Array,  # (E, C, D)
    gates: Array,  # (T, k)
    experts: Array,  # (T, k)
    pos: Array,  # (T, k)
    keep: Array,  # (T, k)
) -> Array:
    t, k = gates.shape
    out = jnp.zeros((t, y_buffers.shape[-1]), y_buffers.dtype)
    for slot in range(k):
        got = y_buffers[experts[:, slot], jnp.where(keep[:, slot], pos[:, slot], 0)]
        w = jnp.where(keep[:, slot], gates[:, slot], 0.0)
        out = out + got * w[:, None].astype(got.dtype)
    return out


def _expert_ffn(cfg: ModelConfig, wi, wg, wo, xs: Array) -> Array:
    """xs: (E_loc, C_tot, D) -> (E_loc, C_tot, D); weights (E_loc, D, F[...])."""
    up = jnp.einsum("ecd,edf->ecf", xs, wi)
    gate = jnp.einsum("ecd,edf->ecf", xs, wg)
    h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _route(cfg: ModelConfig, router_w: Array, x_flat: Array) -> Tuple[Array, Array, Array]:
    """Router: softmax-then-topk-renormalize (Mixtral convention)."""
    logits = (x_flat.astype(jnp.float32)) @ router_w.astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.experts_per_token)  # (T, k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return probs, gates.astype(x_flat.dtype), experts.astype(jnp.int32)


def _moe_local(cfg: ModelConfig, params, x_flat: Array) -> Tuple[Array, Array]:
    """Single-shard MoE (smoke tests / 1 device). Returns (y, router_probs)."""
    probs, gates, experts = _route(cfg, params["router"], x_flat)
    cap = _capacity(x_flat.shape[0], cfg)
    buffers, e_ids, pos, keep = _dispatch_local(
        x_flat, gates, experts, cfg.num_experts, cap
    )
    y_buf = _expert_ffn(cfg, params["wi"], params["wg"], params["wo"], buffers)
    y = _combine_local(y_buf, gates, e_ids, pos, keep)
    return y, probs


def _moe_ep_shard(cfg: ModelConfig, data_axes, model_axis,
                  router_w, wi, wg, wo, x_flat):
    """EP over the data axes x TP(d_ff) over the model axis.

    Tokens live on data shards; experts are sharded E/n_data per data shard
    (arctic: 128 experts / 16 = 8), with each expert's d_ff further split
    over the model axis (psum-combined) — this is the only layout that fits
    480B expert weights in 16 GB/chip HBM (954 GB bf16 / 256 chips).

    Collectives per layer: 2 all-to-alls over data (capacity buffers) +
    1 psum over model (down-projection partials).
    """
    probs, gates, experts = _route(cfg, router_w, x_flat)
    cap = _capacity(x_flat.shape[0], cfg)
    buffers, e_ids, pos, keep = _dispatch_local(
        x_flat, gates, experts, cfg.num_experts, cap
    )
    # (E, C, D) --a2a over the data axes--> (E/n_data, C*n_data, D): every
    # data shard receives the capacity buffers of its expert block.
    recv = jax.lax.all_to_all(
        buffers, data_axes, split_axis=0, concat_axis=1, tiled=True
    )
    recv = jax.ad_checkpoint.checkpoint_name(recv, "moe_recv")
    y_loc = _expert_ffn(cfg, wi, wg, wo, recv)  # F sliced over model
    if model_axis is not None:
        y_loc = jax.lax.psum(y_loc, model_axis)
    # inverse exchange: (E/n_data, C*n_data, D) -> (E, C, D)
    back = jax.lax.all_to_all(
        y_loc, data_axes, split_axis=1, concat_axis=0, tiled=True
    )
    back = jax.ad_checkpoint.checkpoint_name(back, "moe_back")
    y = _combine_local(back, gates, e_ids, pos, keep)
    return y, probs


def _moe_tp_shard(cfg: ModelConfig, model_axis, n_model: int,
                  router_w, wi, wg, wo, x_flat):
    """Inside shard_map: experts replicated, d_ff sharded (psum combine).

    Fallback for expert counts that don't divide the data axes (granite's 40
    experts on 16-way shards)."""
    probs, gates, experts = _route(cfg, router_w, x_flat)
    cap = _capacity(x_flat.shape[0], cfg)
    buffers, e_ids, pos, keep = _dispatch_local(
        x_flat, gates, experts, cfg.num_experts, cap
    )
    y_buf = _expert_ffn(cfg, wi, wg, wo, buffers)  # F sliced -> partial sums
    if model_axis is not None and n_model > 1:
        y_buf = jax.lax.psum(y_buf, model_axis)
    y = _combine_local(y_buf, gates, e_ids, pos, keep)
    return y, probs


def moe_ffn(
    cfg: ModelConfig,
    params: Dict[str, Array],
    x: Array,  # (B, T, D)
    ctx: ApplyCtx,
) -> Tuple[Array, Array]:
    """MoE FFN sublayer.  Returns (y (B,T,D), router_probs (B*T_local, E))."""
    b, t, d = x.shape
    mi = ctx.mesh_info

    n_data = 1
    if mi is not None:
        for a in mi.batch_axes:
            n_data *= mi.mesh.shape[a]

    if mi is None or (mi.model_axis is None and n_data == 1):
        x_flat = x.reshape(b * t, d)
        y, probs = _moe_local(cfg, params, x_flat)
        y = y.reshape(b, t, d)
    else:
        n_model = mi.mesh.shape[mi.model_axis] if mi.model_axis else 1
        ep = n_data > 1 and cfg.num_experts % n_data == 0
        tp_f = (
            mi.model_axis is not None and cfg.d_ff % n_model == 0 and n_model > 1
        )
        f_ax = mi.model_axis if tp_f else None
        probs_spec = PS(mi.batch_axes, None)
        x_spec = PS(mi.batch_axes, None, None)
        if ep:
            fn = partial(
                _moe_ep_shard, cfg, mi.batch_axes, f_ax
            )
            w_specs = (
                PS(None, None),  # router replicated
                PS(mi.batch_axes, None, f_ax),  # wi: E over data, F over model
                PS(mi.batch_axes, None, f_ax),  # wg
                PS(mi.batch_axes, f_ax, None),  # wo: F contraction sharded
            )
        else:
            fn = partial(_moe_tp_shard, cfg, mi.model_axis, n_model)
            w_specs = (
                PS(None, None),
                PS(None, None, mi.model_axis),  # wi: d_ff sharded
                PS(None, None, mi.model_axis),  # wg
                PS(None, mi.model_axis, None),  # wo: d_ff sharded (contraction)
            )

        def wrapped(router_w, wi, wg, wo, xb):
            xf = xb.reshape(-1, d)
            y, probs = fn(router_w, wi, wg, wo, xf)
            return y.reshape(xb.shape), probs

        y, probs = jax.shard_map(
            wrapped,
            mesh=mi.mesh,
            in_specs=(*w_specs, x_spec),
            out_specs=(x_spec, probs_spec),
            check_vma=False,
        )(params["router"], params["wi"], params["wg"], params["wo"], x)

    if cfg.moe_residual:
        up = x @ params["res_wi"]
        gate = x @ params["res_wg"]
        y = y + (jax.nn.silu(gate) * up) @ params["res_wo"]
    return y, probs


def load_balance_loss(cfg: ModelConfig, probs: Array) -> Array:
    """Switch-style auxiliary loss from router probabilities (T, E)."""
    probs = probs.astype(jnp.float32)
    e = cfg.num_experts
    # fraction of router mass per expert and fraction of top-1 dispatches
    mean_probs = jnp.mean(probs, axis=0)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=0)
    return e * jnp.sum(mean_probs * frac)
