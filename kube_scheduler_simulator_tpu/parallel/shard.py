"""Sharding the encoded cluster over the mesh's 'nodes' axis.

Node-axis placement is by FIELD NAME, not shape inspection: a field whose
leading dimension coincidentally equals N (a claim or disk vocabulary the
same size as the node count) must stay replicated, so the authoritative
list of node-axis fields lives here and a unit test asserts it complete
against the dataclasses (tests/test_parallel.py).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.encode import EncodedCluster

# Fields of ClusterArrays / SchedState / PodRelArrays whose axis 0 is the
# node axis [N, ...]. Everything else is replicated across 'nodes'.
NODE_AXIS_FIELDS = frozenset(
    {
        # ClusterArrays
        "node_alloc",
        "node_unsched",
        "node_mask",
        "taint_key",
        "taint_val",
        "taint_effect",
        "label_val",
        "label_num",
        "label_num_ok",
        "img_contrib",
        "vb_code",
        "vz_code",
        # SchedState
        "requested",
        "s_requested",
        "n_pods",
        "used_pair",
        "used_wild",
        "used_trip",
        "node_disk_any",
        "node_disk_rw",
        "node_vol3",
        # PodRelArrays
        "node_pair",
    }
)


def _shardings(obj, mesh: Mesh):
    """The dataclass `obj` with each array field replaced by its
    NamedSharding: node-axis fields split over 'nodes', everything else
    replicated. Nested chex dataclasses recurse."""
    updates = {}
    for name in obj.__dataclass_fields__:
        leaf = getattr(obj, name)
        if hasattr(leaf, "__dataclass_fields__"):
            updates[name] = _shardings(leaf, mesh)
        elif name in NODE_AXIS_FIELDS:
            spec = P("nodes", *([None] * (leaf.ndim - 1)))
            updates[name] = NamedSharding(mesh, spec)
        else:
            updates[name] = NamedSharding(mesh, P())
    return obj.replace(**updates)


def encoded_shardings(enc: EncodedCluster, mesh: Mesh):
    """(arrays, state0, queue) shardings on `mesh` — what
    `shard_encoded` places, without placing anything (a chip-compile
    rehearsal lowers against a described mesh that holds no arrays).

    The node capacity must divide the 'nodes' mesh axis; encode with
    `node_capacity=k * mesh.shape['nodes']`.
    """
    n_shards = mesh.shape["nodes"]
    if enc.N % n_shards != 0:
        raise ValueError(
            f"node capacity {enc.N} not divisible by the {n_shards}-way "
            "'nodes' mesh axis; pad with node_capacity="
        )
    return (
        _shardings(enc.arrays, mesh),
        _shardings(enc.state0, mesh),
        NamedSharding(mesh, P()),
    )


def shard_encoded(enc: EncodedCluster, mesh: Mesh):
    """Returns (arrays, state0, queue) placed on the mesh: node axis split
    over 'nodes', pod-axis and vocabulary arrays replicated."""
    import jax.numpy as jnp

    return jax.device_put(
        (enc.arrays, enc.state0, jnp.asarray(enc.queue)),
        encoded_shardings(enc, mesh),
    )
