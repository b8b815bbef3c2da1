"""Pure-jnp oracles for the decode-attention and LRU-scan kernels.

These are the ground truth the kernels are validated against (interpret=True
on CPU, real lowering on TPU).  They are deliberately straightforward.  The
posterior-grid kernel's oracle is ``repro.core.moments.log_posterior_grid``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


def decode_attention_ref(
    q: Array,  # (B, H, D)
    k: Array,  # (B, S, KVH, D)
    v: Array,  # (B, S, KVH, D)
    length: Optional[Array] = None,  # (B,) valid cache lengths
    scale: Optional[float] = None,
) -> Array:
    """Single-token GQA attention against a KV cache.  Returns (B, H, D)."""
    b, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    groups = h // kvh
    scale = (d**-0.5) if scale is None else scale

    qg = q.reshape(b, kvh, groups, d)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    if length is not None:
        pos = jnp.arange(s)
        valid = pos[None, :] < length[:, None]  # (B, S)
        logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def lru_scan_ref(a: Array, b: Array, h0: Array) -> Array:
    """h_t = a_t * h_{t-1} + b_t via associative scan (log-depth oracle)."""
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    # fold h0 into the first step
    b32 = b32.at[:, 0].add(a32[:, 0] * h0.astype(jnp.float32))

    def combine(c1, c2):
        a1, x1 = c1
        a2, x2 = c2
        return a1 * a2, a2 * x1 + x2

    _, h = jax.lax.associative_scan(combine, (a32, b32), axis=1)
    return h.astype(a.dtype)
