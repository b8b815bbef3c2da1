"""Public jit'd wrappers around the Pallas kernels.

On a TPU backend the kernels lower to Mosaic kernels; the posterior-grid
kernel takes the (K, N) telemetry as it is and picks its tile from the
shapes (see ``posterior_grid``).  On any other backend they run with
``interpret=True``, which emulates the kernel body and exists for the CPU
tests only.  The switch follows ``jax.default_backend``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_pallas
from .lru_scan import lru_scan_pallas
from .posterior_grid import posterior_grid_fleet_pallas

Array = jax.Array


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def use_pallas_default() -> bool:
    """Auto policy for routing the estimation stack through the kernels.

    On TPU the Mosaic lowering is the production path; elsewhere the XLA
    oracle is faster than interpret-mode emulation, so callers that pass
    ``use_pallas=None`` get the kernel exactly where it wins.
    """
    return jax.default_backend() == "tpu"


def posterior_grid_fleet(
    grid: Array,
    t: Array,
    f: Array,
    mu: Array,
    lam: Array,
    alpha: Array,
    beta: Array,
    alpha_prior,
    beta_prior,
    mask: Optional[Array] = None,
    *,
    sharding=None,
    active_idx: Optional[Array] = None,
    out_prev: Optional[Array] = None,
) -> Array:
    """Both exponent posteriors for a whole fleet in one kernel launch.

    Signature mirrors ``repro.core.moments.log_posterior_grid``: t/f/mask
    (K, N), per-worker scalars (K,) -> (K, 2, G).

    ``active_idx`` (an (M,) int array, M static) launches the kernel over the
    gathered M-worker slab only: inputs are gathered, the fused kernel runs
    on (M, N) rows, and the (M, 2, G) result is scattered back into
    ``out_prev`` (a persistent (K, 2, G) grid cache; zeros when omitted) via
    ``lax.scatter``.  With ``active_idx = arange(K)`` the output rows are
    bitwise the dense launch — per-worker math never mixes fleet rows.
    Single-device only (the gather is a cross-shard op); combine with
    ``sharding=None``.

    Stacked leading axes are folded into the fleet axis before the launch:
    a workflow DAG's (S, K, N) telemetry block (per-stage scalars (S, K))
    is presented to the kernel as one S*K-worker fleet and the (S*K, 2, G)
    output is unfolded back — the kernel itself never changes, and the whole
    DAG still costs ONE launch.

    ``sharding`` (a ``repro.core.sharding.ShardingConfig``, duck-typed so
    this bottom layer stays import-free of ``core``) partitions the
    (possibly folded) fleet axis across the mesh's workers axis with
    ``shard_map``: each device runs the same fused kernel on its K/n_shards
    rows against the replicated grid, telemetry never leaves its shard, and
    only the tiny (K, 2, G) log-posterior output crosses devices — lazily,
    when a consumer (moment integration, proposal solving) gathers it.
    K % n_shards != 0 pads with masked-out rows, sliced off on return.
    """
    if mask is None:
        mask = jnp.ones_like(t)
    if active_idx is not None and t.ndim == 2:
        if sharding is not None:
            raise ValueError(
                "active_idx is a single-device path; pass sharding=None"
            )
        take_kn = lambda x: x[active_idx]
        take_k = lambda x: jnp.broadcast_to(
            jnp.asarray(x, jnp.float32), t.shape[:1]
        )[active_idx]
        slab = posterior_grid_fleet(
            grid, take_kn(t), take_kn(f),
            take_k(mu), take_k(lam), take_k(alpha), take_k(beta),
            type(alpha_prior)(take_k(alpha_prior.a), take_k(alpha_prior.b)),
            type(beta_prior)(take_k(beta_prior.a), take_k(beta_prior.b)),
            take_kn(mask),
        )
        base = (
            jnp.zeros((t.shape[0],) + slab.shape[1:], slab.dtype)
            if out_prev is None else out_prev
        )
        return base.at[active_idx].set(slab)
    lead = t.shape[:-1]
    if t.ndim > 2:
        n = t.shape[-1]
        flat_kn = lambda x: jnp.reshape(x, (-1, n))
        flat_k = lambda x: jnp.reshape(
            jnp.broadcast_to(jnp.asarray(x, jnp.float32), lead), (-1,)
        )
        out = posterior_grid_fleet(
            grid, flat_kn(t), flat_kn(f),
            flat_k(mu), flat_k(lam), flat_k(alpha), flat_k(beta),
            type(alpha_prior)(flat_k(alpha_prior.a), flat_k(alpha_prior.b)),
            type(beta_prior)(flat_k(beta_prior.a), flat_k(beta_prior.b)),
            flat_kn(mask),
            sharding=sharding,
        )
        return jnp.reshape(out, lead + out.shape[1:])

    per_k = lambda x: jnp.broadcast_to(
        jnp.asarray(x, jnp.float32), t.shape[:1]
    )
    args = (
        t, f, mask,
        per_k(mu), per_k(lam), per_k(alpha), per_k(beta),
        per_k(alpha_prior.a), per_k(alpha_prior.b),
        per_k(beta_prior.a), per_k(beta_prior.b),
    )
    def launch(*a):
        with jax.named_scope("posterior_grid"):
            return posterior_grid_fleet_pallas(grid, *a, interpret=_interpret())

    if sharding is None:
        return launch(*args)

    from repro.core.sharding import (  # deferred: keeps the layer acyclic
        shard_fleet_call,
    )

    # Rows added by the pad (K % n_shards != 0) are fully masked: they
    # yield a prior-only posterior row that is sliced off and never
    # consulted.
    return shard_fleet_call(launch, sharding, args, mask_index=2)


def decode_attention(
    q: Array,
    k: Array,
    v: Array,
    length: Optional[Array] = None,
    *,
    block_s: int = 512,
) -> Array:
    """Flash-decode GQA attention (B,H,D) x (B,S,KVH,D) -> (B,H,D)."""
    if length is None:
        length = jnp.full((q.shape[0],), k.shape[1], jnp.int32)
    return decode_attention_pallas(
        q, k, v, length, block_s=block_s, interpret=_interpret()
    )


def lru_scan(a: Array, b: Array, h0: Optional[Array] = None, *, block_t: int = 128) -> Array:
    """Linear-recurrence scan h_t = a_t h_{t-1} + b_t (RG-LRU / SSM core)."""
    if h0 is None:
        h0 = jnp.zeros((a.shape[0], a.shape[2]), a.dtype)
    return lru_scan_pallas(a, b, h0, block_t=block_t, interpret=_interpret())
