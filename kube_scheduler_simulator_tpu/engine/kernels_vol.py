"""Volume-family filter kernels (VolumeBinding, VolumeZone,
VolumeRestrictions, EBS/GCEPD/Azure limits, NodeVolumeLimits).

Static plugins (VolumeBinding, VolumeZone) are one-gather kernels over the
host-precomputed verdict tables (encode_vol.py); dynamic ones read the
volume counters in `SchedState`. Reference semantics:
sched/oracle_plugins.py:781-980 (upstream VolumeBinding/VolumeZone/
VolumeRestrictions/NodeVolumeLimits re-derivation); reference records
them via the wrapped Filter plugins
(simulator/scheduler/plugin/wrappedplugin.go:491-516).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..sched.oracle_plugins import _VOLUME_LIMITS
from .encode import ClusterArrays, EncodedCluster, SchedState
from .encode_vol import VOL_LIMIT_PLUGINS

# VolumeRestrictions reason codes (decode table below).
_VR_RWOP = 1
_VR_DISK = 2
_VR_MESSAGES = {
    _VR_RWOP: (
        "node has pod using PersistentVolumeClaim with the same name and "
        "ReadWriteOncePod access mode"
    ),
    _VR_DISK: "node(s) conflicted with the pod's volumes",
}


def _vol_message(code: int, enc: EncodedCluster, node_idx: int = -1) -> str:
    return enc.aux["vol_messages"][code]


def build_volume_binding_prefilter(enc: EncodedCluster):
    def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
        return a.vb_pf[p]

    return kernel


def decode_volume_binding_prefilter(code: int, enc: EncodedCluster) -> str:
    return enc.aux["vol_messages"][code]


def _build_static_table_filter(field: str):
    def build(enc: EncodedCluster):
        def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
            row = a.vb_row[p]
            codes = getattr(a, field)[:, jnp.maximum(row, 0)]  # [N]
            return jnp.where(row >= 0, codes, 0).astype(jnp.int32)

        return kernel

    return build


def build_volume_restrictions_filter(enc: EncodedCluster):
    def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
        # ReadWriteOncePod: any bound pod anywhere using one of p's RWOP
        # claims fails every node (node-independent in the oracle too).
        rwop = (a.pod_claim[p] & (s.used_claims > 0)).any()
        # exclusive disks: conflict unless both mounts are read-only
        mine_any = a.pod_disk_any[p] > 0  # [D]
        mine_rw = a.pod_disk_rw[p] > 0
        disk = (
            (mine_any[None, :] & (s.node_disk_rw > 0))
            | (mine_rw[None, :] & (s.node_disk_any > 0))
        ).any(axis=1)  # [N]
        return jnp.where(rwop, _VR_RWOP, jnp.where(disk, _VR_DISK, 0)).astype(
            jnp.int32
        )

    return kernel


def decode_volume_restrictions(code: int, enc: EncodedCluster, node_idx: int) -> str:
    return _VR_MESSAGES[code]


def _build_volume_limits_filter(plugin: str):
    idx = VOL_LIMIT_PLUGINS.index(plugin)
    _, limit = _VOLUME_LIMITS[plugin]

    def build(enc: EncodedCluster):
        def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
            want = a.pod_vol3[p, idx]
            fail = (want > 0) & (s.node_vol3[:, idx] + want > limit)
            return fail.astype(jnp.int32)

        return kernel

    return build


def decode_volume_limits(code: int, enc: EncodedCluster, node_idx: int) -> str:
    return "node(s) exceed max volume count"


def build_node_volume_limits_filter(enc: EncodedCluster):
    # CSI limits need CSINode objects, which the store (like the
    # reference's 7 watched kinds) does not model — pass-through, matching
    # oracle node_volume_limits_filter.
    def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
        return jnp.zeros(a.node_mask.shape[0], jnp.int32)

    return kernel


def decode_never(code: int, enc: EncodedCluster, node_idx: int) -> str:
    raise AssertionError("NodeVolumeLimits never fails")


# -- preemption row implementations (engine/preempt.py contract) ------------


class VolRestrictionsRow:
    """VolumeRestrictions under victim removal."""

    def __init__(self, enc: EncodedCluster):
        pass

    def prepare(self, a, state, p):
        return ()

    def node_init(self, a, ctx, state, n):
        return {
            "used_claims": state.used_claims,
            "disk_any": state.node_disk_any[n],
            "disk_rw": state.node_disk_rw[n],
        }

    def add_back(self, a, ctx, cnt, v, n, by=1):
        return {
            "used_claims": cnt["used_claims"] + by * a.pod_claim[v].astype(jnp.int32),
            "disk_any": cnt["disk_any"] + by * a.pod_disk_any[v],
            "disk_rw": cnt["disk_rw"] + by * a.pod_disk_rw[v],
        }

    def check(self, a, ctx, cnt, p, n):
        rwop = (a.pod_claim[p] & (cnt["used_claims"] > 0)).any()
        mine_any = a.pod_disk_any[p] > 0
        mine_rw = a.pod_disk_rw[p] > 0
        disk = (
            (mine_any & (cnt["disk_rw"] > 0)) | (mine_rw & (cnt["disk_any"] > 0))
        ).any()
        return ~(rwop | disk)


class _VolLimitsRow:
    def __init__(self, enc: EncodedCluster, idx: int, limit: int):
        self.idx = idx
        self.limit = limit

    def prepare(self, a, state, p):
        return ()

    def node_init(self, a, ctx, state, n):
        return {"cnt": state.node_vol3[n, self.idx]}

    def add_back(self, a, ctx, cnt, v, n, by=1):
        return {"cnt": cnt["cnt"] + by * a.pod_vol3[v, self.idx]}

    def check(self, a, ctx, cnt, p, n):
        want = a.pod_vol3[p, self.idx]
        return ~((want > 0) & (cnt["cnt"] + want > self.limit))


def make_vol_limits_row(plugin: str):
    idx = VOL_LIMIT_PLUGINS.index(plugin)
    _, limit = _VOLUME_LIMITS[plugin]
    return lambda enc: _VolLimitsRow(enc, idx, limit)
