"""Chip-compile rehearsals: the main path's programs, compiled at real
widths for a described (not attached) TPU v5e, so the chip's compiler
refuses nothing before a chip run does.

The topology is described inside a module fixture, never at import
time: only one process at a time may load the TPU library, and the
worker that runs this file keeps it until it exits. The persistent
compile cache is off around these compiles (an entry written for a
described chip cannot be read back without one), and so is the suite's
x64 mode: the chip runs the 32-bit program."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kube_scheduler_simulator_tpu.engine import TPU32, encode_cluster
from kube_scheduler_simulator_tpu.engine.engine import (
    BatchedScheduler,
    supported_config,
)
from kube_scheduler_simulator_tpu.engine.gang import GangScheduler
from kube_scheduler_simulator_tpu.parallel import WeightSweep, encoded_shardings
from kube_scheduler_simulator_tpu.synth import synthetic_cluster

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = (jax.config.jax_enable_compilation_cache, jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_enable_x64", prev[1])
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    """ShapeDtypeStructs of `tree`, each with its sharding (one for the
    whole tree, or a matching tree of shardings)."""
    if not isinstance(sharding, (tuple, list)) and not hasattr(
        sharding, "__dataclass_fields__"
    ):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                np.shape(x), jnp.asarray(x).dtype, sharding=sharding
            ),
            tree,
        )
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(
            np.shape(x), jnp.asarray(x).dtype, sharding=s
        ),
        tree,
        sharding,
    )


def _fits(compiled) -> None:
    m = compiled.memory_analysis()
    used = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes
    )
    assert 0 < used < HBM_BYTES, used


def _encoded(n_nodes, n_pods):
    nodes, pods = synthetic_cluster(n_nodes, n_pods, seed=0)
    return encode_cluster(nodes, pods, supported_config(), policy=TPU32)


def test_seq_run_10k_pods_1k_nodes(one_chip):
    enc = _encoded(1024, 10_000)
    s = BatchedScheduler(enc, record=False)
    args = (enc.arrays, enc.state0, jnp.asarray(enc.queue), s.weights)
    _fits(jax.jit(s.run_fn).lower(*_shapes(args, one_chip)).compile())


def test_gang_fixpoint_2k_pods_1k_nodes(one_chip):
    enc = _encoded(1024, 2048)
    g = GangScheduler(enc, chunk=256)
    order, _ = g.order_arrays()
    args = (enc.arrays, enc.state0, order, g.weights)
    _fits(jax.jit(g.fixpoint_fn).lower(*_shapes(args, one_chip)).compile())


def test_sequential_sweep_on_a_2x2_mesh(topo):
    """The `chip_smoke.py --chips 4` sweep's scan: 32 variants over
    'replicas', the node axis over 'nodes'."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("replicas", "nodes"))
    enc = _encoded(1024, 2048)
    a_sh, s_sh, q_sh = encoded_shardings(enc, mesh)
    sweep = WeightSweep(enc)
    until, _ = sweep._build_event_programs()
    weights = np.stack([np.asarray(sweep.sched.weights)] * 32)
    program = jax.vmap(until, in_axes=(None, None, None, 0, None))
    compiled = jax.jit(program).lower(
        _shapes(enc.arrays, a_sh),
        _shapes(enc.state0, s_sh),
        _shapes(jnp.asarray(enc.queue), q_sh),
        _shapes(weights, NamedSharding(mesh, P("replicas", None))),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
    ).compile()
    _fits(compiled)
