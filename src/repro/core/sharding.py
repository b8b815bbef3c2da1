"""Fleet-axis sharding of the estimation engine (multi-host / multi-device).

The paper's Gibbs estimator treats each processing unit's (alpha, beta)
posterior independently, so the fleet axis K of the fused estimation engine
is embarrassingly parallel: sharding K across a 1-D ``workers`` device mesh
with ``shard_map`` splits every per-worker quantity — telemetry (K, N),
chain states (K, ...), the O(K*G*N) grid-posterior evaluation — while the
tiny exponent grid (G,) stays replicated.  Each shard runs the SAME fused
program (one Pallas launch on TPU, the cache-blocked XLA oracle elsewhere)
on its K/n_shards workers; only the small per-worker outputs (the (K, 2, G)
log-posteriors, the chain states, the log-likelihoods) ever cross shard
boundaries, and only when a consumer (moment integration outside the kernel
wrapper, ``sched.propose``'s fleet-wide solve, the anomaly median) actually
gathers them.

``ShardingConfig`` is the one value threaded through the stack:

    core.gibbs.gibbs_batch / fit_fleet / fit_dag      sharding=...
    kernels.ops.posterior_grid_fleet                  sharding=...
    sched.SchedulerConfig(mesh=...) -> observe / observe_dag

``None`` everywhere means the single-device behavior is bit-for-bit
unchanged.  A fleet whose K does not divide the shard count is padded with
masked-out dummy workers (mask rows of zeros; duplicated state rows) and
sliced back after the mapped region — real workers' chains are unaffected.

Frozen and hashable (``jax.sharding.Mesh`` hashes structurally), so it rides
through ``jax.jit`` as a static argument, including inside the equally-static
``sched.SchedulerConfig``.

>>> import jax
>>> cfg = ShardingConfig.auto()            # 1-D mesh over all local devices
>>> cfg.num_shards == jax.device_count()
True
>>> cfg.pad(10) == (-10) % jax.device_count()
True
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array

FLEET_AXIS = "workers"


@dataclasses.dataclass(frozen=True)
class ShardingConfig:
    """How to partition the estimation fleet axis across devices.

    ``mesh`` must contain ``axis``; the fleet axis K (or the folded S*K
    stage-fleet axis of a workflow DAG) is partitioned across it, everything
    else — the exponent grid, per-shard scalars — is replicated.  Hashable:
    valid as a jit-static argument.
    """

    mesh: Mesh
    axis: str = FLEET_AXIS

    def __post_init__(self):
        if self.axis not in self.mesh.axis_names:
            raise ValueError(
                f"mesh {self.mesh.axis_names} has no {self.axis!r} axis"
            )

    @staticmethod
    def auto(
        num_devices: Optional[int] = None, axis: str = FLEET_AXIS
    ) -> "ShardingConfig":
        """1-D mesh over the first ``num_devices`` local devices (default all).

        The zero-config entry point: on a CPU host started with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` this gives an
        8-way fleet mesh; on a TPU slice, one shard per chip.
        """
        devs = jax.devices()
        if num_devices is not None:
            devs = devs[:num_devices]
        return ShardingConfig(mesh=Mesh(np.array(devs), (axis,)), axis=axis)

    @property
    def num_shards(self) -> int:
        return self.mesh.shape[self.axis]

    def pad(self, k: int) -> int:
        """Dummy workers needed to make a K-fleet divide the shard count."""
        return (-k) % self.num_shards

    def spec(self, ndim: int = 1) -> P:
        """PartitionSpec sharding the leading (fleet) axis, rest replicated."""
        return P(self.axis, *([None] * (ndim - 1)))

    def fleet_sharding(self, ndim: int = 1) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(ndim))


def pad_fleet_axis(tree, pad: int):
    """Append ``pad`` dummy rows to every leaf's leading (fleet) axis.

    Dummy rows duplicate the last real row — always finite, always the right
    dtype — so the padded program computes harmless garbage that callers
    slice off with :func:`unpad_fleet_axis`.  Telemetry padding should
    instead carry ``mask=0`` rows so the dummies can never influence even
    their own (discarded) posterior row.
    """
    if pad == 0:
        return tree
    grow = lambda x: jnp.concatenate(
        [x, jnp.broadcast_to(x[-1:], (pad,) + x.shape[1:])], axis=0
    )
    return jax.tree_util.tree_map(grow, tree)


def unpad_fleet_axis(tree, k: int):
    """Inverse of :func:`pad_fleet_axis`: keep the first ``k`` fleet rows."""
    return jax.tree_util.tree_map(lambda x: x[:k], tree)


def shard_fleet_map(fn, sharding: ShardingConfig, *, out_specs=None):
    """``shard_map`` a fleet-batched function over the workers axis.

    Every argument and result must carry the fleet axis leading; replicated
    extras (the grid) should be closed over.  ``check_vma`` is off because
    the per-worker math is embarrassingly parallel by construction — there
    is nothing cross-shard to verify.
    """
    spec_of = lambda tree: jax.tree_util.tree_map(
        lambda _: P(sharding.axis), tree
    )

    def wrapped(*args):
        return jax.shard_map(
            fn,
            mesh=sharding.mesh,
            in_specs=tuple(spec_of(a) for a in args),
            out_specs=(
                spec_of(jax.eval_shape(fn, *args))
                if out_specs is None
                else out_specs
            ),
            check_vma=False,
        )(*args)

    return wrapped


def shard_fleet_call(fn, sharding: ShardingConfig, args, *, mask_index=None):
    """Pad -> shard_map -> unpad in one place (the fleet-call pattern).

    Every positional arg (pytree leaves included) must carry the fleet axis
    leading.  If K does not divide the shard count, all args are padded with
    duplicated edge rows; ``mask_index`` names the arg holding the validity
    mask, whose padded rows are zeroed so dummy workers contribute nothing
    even to their own (discarded) output rows.  Outputs are sliced back to
    K.  Both ``gibbs.gibbs_batch`` and ``kernels.ops.posterior_grid_fleet``
    route their sharded paths through here so padding semantics cannot
    diverge between the engine and the kernel wrapper.
    """
    k = jax.tree_util.tree_leaves(args[0])[0].shape[0]
    pad = sharding.pad(k)
    if pad:
        args = pad_fleet_axis(tuple(args), pad)
        if mask_index is not None:
            m = args[mask_index].at[k:].set(0)
            args = args[:mask_index] + (m,) + args[mask_index + 1:]
    out = shard_fleet_map(fn, sharding)(*args)
    return unpad_fleet_axis(out, k) if pad else out


def constrain_fleet(tree, sharding: Optional[ShardingConfig], *, axis: int = 0):
    """Attach fleet-axis sharding constraints to a pytree's leaves.

    Usable inside jit (``lax.with_sharding_constraint``) and a no-op when
    ``sharding`` is None, so state constructors can call it unconditionally.
    Leaves whose fleet-axis extent does not divide the shard count are left
    unconstrained (the mapped compute path pads for itself; placement of the
    stored state is only a locality hint).  ``axis`` selects which leaf axis
    is the fleet axis — 1 for (S, K, ...) workflow-DAG leaves.
    """
    if sharding is None:
        return tree
    n = sharding.num_shards

    def one(x):
        if x.ndim <= axis or x.shape[axis] % n != 0:
            return x
        spec = P(*([None] * axis), sharding.axis)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(sharding.mesh, spec)
        )

    return jax.tree_util.tree_map(one, tree)
