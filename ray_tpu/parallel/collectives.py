"""Process-group-style collectives — the reference's ray.util.collective
surface (ray: python/ray/util/collective/collective.py:
init_collective_group, allreduce, allgather, reducescatter, broadcast,
barrier, send/recv over NCCL/GLOO groups), rebuilt TPU-native.

On TPU a "collective group" is a mesh axis; the ops are jax collectives
that only mean something inside a shard_map/jitted program, where XLA
lowers them to ICI all-reduce/all-gather/... directly — there is no
NCCL-style out-of-band channel to manage, no rendezvous, no group
teardown. The CollectiveGroup object exists to give library code (Train,
RLlib learner groups) the same call shape the reference has.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence


@dataclasses.dataclass(frozen=True)
class CollectiveGroup:
    """A named mesh axis treated as a communicator group. world_size()
    is only meaningful inside a traced (shard_map/jit) context, where the
    axis is bound — it returns a concrete int (axis sizes are static)."""
    axis_name: str

    def world_size(self) -> int:
        import jax.lax as lax
        return lax.psum(1, self.axis_name)

    def rank(self):
        import jax.lax as lax
        return lax.axis_index(self.axis_name)


# The ops below are used INSIDE shard_map'd / jitted functions, exactly
# like lax.p* — thin veneer so library code reads like the reference API.

def allreduce(x, group: "CollectiveGroup | str", op: str = "sum"):
    import jax.lax as lax

    axis = group.axis_name if isinstance(group, CollectiveGroup) else group
    if op == "sum":
        return lax.psum(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    raise ValueError(f"unsupported allreduce op: {op}")


def allgather(x, group: "CollectiveGroup | str", axis: int = 0,
              tiled: bool = True):
    import jax.lax as lax

    name = group.axis_name if isinstance(group, CollectiveGroup) else group
    return lax.all_gather(x, name, axis=axis, tiled=tiled)


def reducescatter(x, group: "CollectiveGroup | str", axis: int = 0):
    import jax.lax as lax

    name = group.axis_name if isinstance(group, CollectiveGroup) else group
    return lax.psum_scatter(x, name, scatter_dimension=axis, tiled=True)


def broadcast(x, group: "CollectiveGroup | str", root: int = 0):
    """Every member gets root's shard."""
    import jax
    import jax.lax as lax

    name = group.axis_name if isinstance(group, CollectiveGroup) else group
    idx = lax.axis_index(name)
    masked = jax.numpy.where(idx == root, x, jax.numpy.zeros_like(x))
    return lax.psum(masked, name)


def barrier(group: "CollectiveGroup | str"):
    """A data-dependence barrier: returns a token whose value is the
    world size; consuming it orders the program across the axis."""
    import jax.lax as lax

    name = group.axis_name if isinstance(group, CollectiveGroup) else group
    return lax.psum(1, name)


def send_recv(x, group: "CollectiveGroup | str", shift: int = 1):
    """Ring shift over the axis (ppermute): member i's shard goes to
    member (i+shift) % world. The building block of ring attention and
    pipeline microbatch rotation."""
    import jax.lax as lax

    name = group.axis_name if isinstance(group, CollectiveGroup) else group
    n = lax.psum(1, name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, name, perm)


def shard_map_norep(f, *, mesh, in_specs, out_specs):
    """jax.shard_map with replication (varying-manual-axes) checking
    off: the manual-collective ops — ring attention, MoE dispatch,
    pipelining — produce outputs the checker cannot type."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
