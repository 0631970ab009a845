"""Balancer: the TPU brain that replaces the reference's gossip + stealing.

The reference approximates global state with a 0.1 s ring-gossiped status
vector and makes per-server greedy decisions (qmstat/RFR/push, reference
``src/adlb.c:806-822,1802-2070``). Here servers stream fixed-shape queue-state
snapshots to a balancer, which computes a *global* task->requester assignment
as one vectorized solve under ``jax.jit`` — on TPU the compatibility matrix
and conflict resolution map onto the MXU/VPU. The distributed variant
(``adlb_tpu.balancer.distributed``) shards the task table over a device mesh
with ``shard_map`` + ``all_gather``.

Importing this package imports no JAX: every server rank takes its
``SnapshotStore`` from :mod:`.ledger`, and only the planner's host (the
master rank, the sidecar's process) goes on to :mod:`.engine` and
:mod:`.solve`.
"""


def __getattr__(name: str):
    if name == "AssignmentSolver":  # the package's one export, on demand
        from adlb_tpu.balancer.solve import AssignmentSolver

        return AssignmentSolver
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
