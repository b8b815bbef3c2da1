"""Device-resident telemetry ring buffer: fixed capacity, jit-native.

A fleet serving heavy traffic produces telemetry continuously; the estimator
consumes it in batches.  ``TelemetryRing`` decouples the two rates without
ever leaving the device or changing a shape:

  * every leaf is a fixed-capacity array — ``push`` writes one slot with a
    dynamic-index ``.at[slot].set``, ``push_rows`` writes a block of rows
    with one scatter (the path of ``ServiceLoop``, which stages its rows on
    the host and writes them once a tick), and ``drain`` reads the whole
    buffer with a masked tail, so none of them changes a shape or
    host-syncs;
  * the buffer is a plain pytree (NamedTuple of arrays): it rides through
    ``jax.jit`` (with buffer donation for zero-copy advance), checkpoints
    through ``CheckpointManager``, and vmaps for multi-tenant deployments;
  * overflow drops the OLDEST entries (the freshest telemetry is the most
    informative for a drifting system) and counts them in ``dropped`` — a
    monitorable signal that the drain cadence is too slow, never a silent
    truncation.

Drains preserve push order (oldest first) and pad the tail with masked
slots — exactly the layout ``core.gibbs.fit`` feeds its ``lax.scan``, so a
sequence of ring drains advanced through ``gibbs_batch`` reproduces the
synchronous ``fit`` over the same observations bitwise (``tests/test_serve``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


class TelemetryRing(NamedTuple):
    """Fixed-capacity ring of (fracs, times) observations; a pytree.

    Leaves are ``(capacity,)`` for a single unit or ``(capacity, K)`` for a
    K-worker fleet (slot-major so one ``push`` writes one row).  ``head`` is
    the next write slot (monotone, wrapped at use), ``count`` the number of
    un-drained entries (saturates at capacity), ``dropped`` / ``total`` the
    lifetime overflow and push counters.
    """

    fracs: Array  # (C,) or (C, K)
    times: Array  # (C,) or (C, K)
    valid: Array  # (C,) or (C, K) float32 — per-element validity
    head: Array  # int32 scalar, next write slot (mod capacity)
    count: Array  # int32 scalar, entries buffered since last drain
    dropped: Array  # int32 scalar, lifetime entries overwritten un-drained
    total: Array  # int32 scalar, lifetime pushes

    @property
    def capacity(self) -> int:
        return int(self.times.shape[0])

    @property
    def num_workers(self) -> Optional[int]:
        return int(self.times.shape[1]) if self.times.ndim == 2 else None


class DrainedBatch(NamedTuple):
    """One whole-buffer drain in estimator layout: gibbs-ready, masked tail.

    ``times`` / ``fracs`` / ``mask`` are ``(K, capacity)`` for a fleet ring
    (``(capacity,)`` for a single unit) with observations in push order and
    ``mask`` zero on empty/invalid slots — the exact (t, f, mask) triple
    ``gibbs_batch`` and ``sched.observe`` accept.  ``count`` is how many
    slots carry real telemetry.
    """

    times: Array
    fracs: Array
    mask: Array
    count: Array  # int32 scalar


def ring_init(
    capacity: int, num_workers: Optional[int] = None, dtype=jnp.float32
) -> TelemetryRing:
    """An empty ring; ``num_workers=None`` builds a single-unit (C,) ring."""
    if capacity < 1:
        raise ValueError("capacity must be >= 1")
    shape = (capacity,) if num_workers is None else (capacity, num_workers)
    z = jnp.zeros(shape, dtype)
    zero = jnp.zeros((), jnp.int32)
    # Empty slots carry interior dummy values (f=0.5, t=1.0) so a fully
    # masked drain is an exact no-op on every masked reduction downstream.
    return TelemetryRing(
        fracs=jnp.full(shape, 0.5, dtype),
        times=jnp.full(shape, 1.0, dtype),
        valid=z,
        head=zero,
        count=zero,
        dropped=zero,
        total=zero,
    )


def push(
    ring: TelemetryRing,
    fracs: Array,
    times: Array,
    valid: Optional[Array] = None,
) -> TelemetryRing:
    """Append one observation row; jit-compatible, no host sync.

    ``fracs`` / ``times`` are scalars for a single-unit ring or ``(K,)`` for
    a fleet ring.  ``valid`` optionally marks elements invalid (non-finite
    telemetry from a failed worker) so they never reach the estimator.  When
    the ring is full the oldest un-drained entry is overwritten and counted
    in ``dropped``.
    """
    cap = ring.capacity
    slot = ring.head % cap
    f = jnp.asarray(fracs, ring.fracs.dtype)
    t = jnp.asarray(times, ring.times.dtype)
    if valid is None:
        v = jnp.ones(t.shape, ring.valid.dtype)
    else:
        v = jnp.broadcast_to(jnp.asarray(valid, ring.valid.dtype), t.shape)
    # Invalid elements get interior dummies: inf/nan must never be stored
    # (0 * inf = nan would leak through the drain mask).
    f = jnp.where(v > 0, f, 0.5)
    t = jnp.where(v > 0, t, 1.0)
    full = (ring.count == cap).astype(jnp.int32)
    return TelemetryRing(
        fracs=ring.fracs.at[slot].set(f),
        times=ring.times.at[slot].set(t),
        valid=ring.valid.at[slot].set(v),
        head=(ring.head + 1) % cap,
        count=jnp.minimum(ring.count + 1, cap),
        dropped=ring.dropped + full,
        total=ring.total + 1,
    )


def push_rows(
    ring: TelemetryRing,
    fracs: Array,
    times: Array,
    n: Array,
    valid: Optional[Array] = None,
) -> TelemetryRing:
    """Append the first ``n`` rows of an ``(R, ...)`` block; jit-compatible.

    The result is exactly that of ``n`` calls of :func:`push` on rows
    ``0 .. n-1`` in order: row ``i`` lands in slot ``(head + i) % capacity``,
    ``count`` saturates at capacity, every row that finds the ring full
    counts in ``dropped``, and invalid elements are stored as the dummies
    ``push`` stores.  Rows ``i >= n`` are padding and never written, so one
    compiled program serves every ``n <= R``; ``R`` may not exceed the
    capacity.  ``n`` is traced.
    """
    cap = ring.capacity
    r = times.shape[0]
    if r > cap:
        raise ValueError(f"a block of {r} rows exceeds the capacity {cap}")
    f = jnp.asarray(fracs, ring.fracs.dtype)
    t = jnp.asarray(times, ring.times.dtype)
    if valid is None:
        v = jnp.ones(t.shape, ring.valid.dtype)
    else:
        v = jnp.broadcast_to(jnp.asarray(valid, ring.valid.dtype), t.shape)
    f = jnp.where(v > 0, f, 0.5)
    t = jnp.where(v > 0, t, 1.0)
    n = jnp.asarray(n, jnp.int32)
    i = jnp.arange(r, dtype=jnp.int32)
    # Padding rows aim at slot ``cap``, out of bounds, which ``drop`` skips.
    slots = jnp.where(i < n, (ring.head + i) % cap, cap)
    put = lambda buf, rows: buf.at[slots].set(rows, mode="drop")
    return TelemetryRing(
        fracs=put(ring.fracs, f),
        times=put(ring.times, t),
        valid=put(ring.valid, v),
        head=(ring.head + n) % cap,
        count=jnp.minimum(ring.count + n, cap),
        dropped=ring.dropped + jnp.maximum(ring.count + n - cap, 0),
        total=ring.total + n,
    )


def drain(ring: TelemetryRing) -> Tuple[DrainedBatch, TelemetryRing]:
    """Empty the ring into one gibbs-ready batch; jit-compatible.

    The batch is whole-buffer (static shape = capacity) with observations in
    push order — oldest first — and a masked tail, matching the padded-batch
    layout of ``core.gibbs.fit``.  The returned ring is logically empty
    (``count=0``); buffers are reused in place by the next pushes.
    """
    cap = ring.capacity
    start = (ring.head - ring.count) % cap
    order = (start + jnp.arange(cap)) % cap  # oldest -> newest
    slot_mask = (jnp.arange(cap) < ring.count).astype(ring.valid.dtype)
    t = jnp.take(ring.times, order, axis=0)
    f = jnp.take(ring.fracs, order, axis=0)
    v = jnp.take(ring.valid, order, axis=0)
    if t.ndim == 2:  # fleet ring: slot-major storage -> worker-major batch
        mask = (slot_mask[:, None] * v).T
        t, f = t.T, f.T
    else:
        mask = slot_mask * v
    batch = DrainedBatch(times=t, fracs=f, mask=mask, count=ring.count)
    return batch, ring._replace(count=jnp.zeros((), jnp.int32))
