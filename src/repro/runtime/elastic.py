"""Elastic scaling: rebuild the mesh for whatever devices survive and reshard
the checkpointed state onto it.

Checkpoints are mesh-agnostic (global numpy leaves + the rules table is
re-derived from the config), so growing 256 -> 512 chips or shrinking after
losing a host is the same operation: make a new mesh, recompute shardings,
device_put.  The only global invariant the caller must keep is
`global_batch % batch_shards == 0` — `elastic_mesh` picks the largest
(data, model) factorization that preserves it.
"""

from __future__ import annotations

import logging
from typing import NamedTuple

import jax
from jax.sharding import Mesh

from repro import dist
from repro.launch.mesh import make_mesh

logger = logging.getLogger(__name__)


class MeshPlan(NamedTuple):
    """What `elastic_mesh` decided: the (data, model) factorization plus the
    devices it could NOT use — dropped devices are REPORTED, never silently
    truncated away (a 7-survivor cluster quietly running on 4 devices is a
    capacity bug, not a convenience)."""

    data: int
    model: int
    used: int
    dropped: int


def mesh_plan(n_devices: int, *, model_parallel: int = 1,
              global_batch: int | None = None) -> MeshPlan:
    """Largest usable (data, model) factorization of `n_devices` preserving
    `global_batch % data == 0`, with the dropped-device count."""
    model = model_parallel
    while model > 1 and n_devices % model != 0:
        model //= 2
    data = n_devices // model
    if global_batch is not None:
        while data > 1 and global_batch % data != 0:
            data //= 2
    used = data * model
    return MeshPlan(data, model, used, n_devices - used)


def elastic_mesh(n_devices: int, *, model_parallel: int = 1,
                 global_batch: int | None = None) -> Mesh:
    """Largest usable (data, model) mesh on `n_devices`.  When the
    factorization cannot use every device, the dropped count is logged
    (see `mesh_plan` for the programmatic report)."""
    plan = mesh_plan(n_devices, model_parallel=model_parallel,
                     global_batch=global_batch)
    if plan.dropped:
        logger.warning(
            "elastic_mesh: dropping %d of %d devices (largest usable mesh "
            "is data=%d x model=%d%s)", plan.dropped, n_devices, plan.data,
            plan.model,
            f" under global_batch={global_batch}" if global_batch else "")
    return make_mesh((plan.data, plan.model), ("data", "model"),
                     devices=jax.devices()[: plan.used])


def reshard_state(state, cfg, mesh: Mesh):
    """device_put every leaf with shardings re-derived for `mesh`.

    Works for the (params, opt_state) training pytree: params get the rules
    table; opt moments mirror the params; scalars replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rules = dist.make_rules(cfg, mesh)
    params, opt = state
    p_sh = dist.param_shardings(params, cfg, mesh, rules)
    o_sh = {"m": p_sh, "v": p_sh,
            "step": NamedSharding(mesh, P())}
    params = jax.device_put(params, p_sh)
    opt = jax.device_put(opt, o_sh)
    return params, opt


def reshard_dist(old_dspec, dstate, new_dspec, mesh: Mesh):
    """Re-shard a big-atomic `DistState` onto a new mesh / shard count,
    preserving logical values AND per-cell versions.

    Versions are load-bearing across a recovery boundary: an executor
    resuming from a checkpoint replays batches whose LinkCtx rows hold
    version numbers from BEFORE the fault — if resharding re-initialized
    versions to zero, every outstanding LL link would spuriously die (or
    worse, spuriously survive).  So the global [n] version vector is
    extracted, the state is rebuilt at the new geometry, and the versions
    are split back per-shard (inverse of `distributed.versions`)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from repro.core import distributed as d2
    if old_dspec.is_hash or new_dspec.is_hash:
        raise TypeError("reshard_dist reshards tables")
    if (old_dspec.n_global, old_dspec.inner.k) != \
            (new_dspec.n_global, new_dspec.inner.k):
        raise ValueError(f"geometry change: {old_dspec.inner} -> "
                         f"{new_dspec.inner}")
    vals = np.asarray(d2.logical(old_dspec, dstate))
    vers = np.asarray(d2.versions(old_dspec, dstate))
    new = d2.init_dist(mesh, new_dspec, vals)
    s, nl = new_dspec.n_shards, new_dspec.n_local
    per = (vers.reshape(nl, s).T if new_dspec.interleave
           else vers.reshape(s, nl))
    local = new.local._replace(
        version=jnp.asarray(np.ascontiguousarray(per)))
    local = jax.device_put(local, NamedSharding(mesh, d2._pspec(new_dspec)))
    return d2.DistState(local)
