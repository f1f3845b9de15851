"""Model averaging (Eq. 2) and participant-parallel training wrappers.

Two equivalent distributed implementations are provided (both tested):

1. ``average_pjit`` — a plain mean over the leading participant dim of
   stacked parameter pytrees; under pjit with that dim sharded over the
   ``pod`` mesh axis this lowers to an all-reduce over the inter-pod links.
2. ``average_shard_map`` — explicit `shard_map` psum over the ``pod`` axis,
   for when the collective schedule should be pinned rather than inferred.

``participant_step`` wraps a single-participant train step with
``jax.vmap(..., spmd_axis_name='pod')`` so each pod trains its own replica
with gradient reductions kept *inside* the pod — the paper's "local
training" phase in SPMD form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def stack_participants(params, K: int):
    """Replicate a params pytree into K stacked participant copies."""
    return jax.tree.map(lambda t: jnp.broadcast_to(t[None], (K, *t.shape)), params)


@jax.jit
def _gather_slot(stacked, k):
    return jax.tree.map(lambda t: t[k], stacked)


def unstack_participant(stacked, k: int):
    """Slot k of a stacked (K, ...) pytree.

    Inside a trace the python int stays a static slice. Eager calls go
    through a jitted gather with the index staged explicitly: an eager
    python-int slice dispatches dynamic_slice with implicitly-transferred
    start scalars, which trips ``guards.no_transfer()`` on the round loop.
    The index is traced, so the gather compiles once per params geometry.
    """
    leaves = jax.tree.leaves(stacked)
    if leaves and isinstance(leaves[0], jax.core.Tracer):
        return jax.tree.map(lambda t: t[k], stacked)
    if not isinstance(k, jax.Array):
        import numpy as np
        k = jax.device_put(np.int32(k))
    return _gather_slot(stacked, k)


def average_pjit(stacked):
    """Eq. 2: w̄ = (1/K) Σ_k w_k, broadcast back to all K slots."""
    def avg(t):
        m = jnp.mean(t.astype(jnp.float32), axis=0, keepdims=True)
        return jnp.broadcast_to(m, t.shape).astype(t.dtype)
    return jax.tree.map(avg, stacked)


def average_mean(stacked):
    """Eq. 2 returning the un-stacked average (host-side convenience)."""
    return jax.tree.map(
        lambda t: jnp.mean(t.astype(jnp.float32), axis=0).astype(t.dtype), stacked)


def make_average_shard_map(mesh, param_specs, axis="pod"):
    """Explicit-collective averaging: psum over the participant mesh axis.

    param_specs: pytree of PartitionSpecs for the *stacked* params, whose
    leading dim is sharded over ``axis``.
    """
    K = mesh.shape[axis]

    def _avg(local):
        # local arrays have leading dim K/mesh.shape[axis] == 1 per shard
        def one(t):
            s = jax.lax.psum(t.astype(jnp.float32), axis) / K
            return jnp.broadcast_to(s, t.shape).astype(t.dtype)
        return jax.tree.map(one, local)

    return jax.jit(jax.shard_map(
        _avg, mesh=mesh, in_specs=(param_specs,), out_specs=param_specs,
        check_vma=False))


def participant_step(step_fn):
    """vmap a per-participant step over the leading K dim.

    step_fn(params, batch, *args) -> (params', metrics). The vmapped version
    takes stacked params (K, ...) and per-participant batches (K, B_k, ...);
    ``spmd_axis_name='pod'`` pins the participant dim to the pod mesh axis so
    XLA never reduces across it during local training.
    """
    return jax.vmap(step_fn, spmd_axis_name="pod")


def participant_step_sim(step_fn):
    """Simulation variant (single host, K participants, no pod axis)."""
    return jax.vmap(step_fn)
