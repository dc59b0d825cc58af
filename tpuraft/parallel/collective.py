"""Replica-axis quorum plane: XLA collectives over ICI as the vote fabric.

The BASELINE.json north-star configuration ("16K groups, 3 replicas —
vote-matrix psum over v5e-8 ICI mesh"): each slice of the mesh's
``replica`` axis holds one raft replica's LOCAL view of all G groups
(its matchIndex row, its vote).  Quorum math then rides ICI:

- vote counting   = ``psum`` of grant indicators over the replica axis;
- commit point    = ``all_gather`` of match rows over the replica axis,
  then the q-th order statistic — the [G, P] matrix never exists on any
  single chip until the gather, and XLA pipelines the gather with the sort.

This is the TPU-native analog of the reference's NCCL-free Bolt RPC vote
traffic (SURVEY.md §6): the protocol plane (host RPC over DCN) establishes
*what* each replica has durably; the math plane reduces it over ICI.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def replica_vote_count(granted_block: jnp.ndarray,
                       axis_name: str = "replica") -> jnp.ndarray:
    """Inside shard_map: granted_block bool [R_local, G_local] are this
    mesh slice's replicas' grants; returns votes int32 [1, G_local] =
    total granting replicas across the axis."""
    local = granted_block.astype(jnp.int32).sum(axis=0, keepdims=True)
    return jax.lax.psum(local, axis_name)


def replica_commit_point(match_block: jnp.ndarray, n_replicas: int,
                         axis_name: str = "replica") -> jnp.ndarray:
    """Inside shard_map: match_block int32 [R_local, G_local] holds this
    slice's replicas' durable matchIndex rows; returns the quorum commit
    point [1, G_local] (q-th largest across all replicas, q = n//2+1)."""
    gathered = jax.lax.all_gather(match_block, axis_name, axis=0,
                                  tiled=True)  # [R, G_local]
    sorted_desc = -jnp.sort(-gathered, axis=0)
    q = n_replicas // 2 + 1
    return sorted_desc[q - 1][None, :]


def replicated_tick(mesh: Mesh, n_replicas: int,
                    replica_axis: str = "replica",
                    group_axis: str = "groups"):
    """Build the jitted cross-replica quorum step over a 2D mesh
    (replica, groups).

    Inputs (global shapes):
      match:   int32 [R, G]  — row r = replica r's durable matchIndex
      granted: bool  [R, G]  — row r = replica r's current-election vote
    Outputs (global):
      commit:  int32 [G] — quorum commit point per group
      votes:   int32 [G] — vote counts per group
    """
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(replica_axis, group_axis), P(replica_axis, group_axis)),
        out_specs=(P(None, group_axis), P(None, group_axis)),
        # outputs ARE replica-identical (post-psum/gather)
        check_vma=False,
    )
    def step(match_block, granted_block):
        # blocks: [R_local, G_local]; local rows fold first, then the
        # collectives ride the replica axis (ICI on hardware)
        commit = replica_commit_point(match_block, n_replicas, replica_axis)
        votes = replica_vote_count(granted_block, replica_axis)
        return commit, votes

    def run(match: jnp.ndarray, granted: jnp.ndarray):
        commit, votes = step(match, granted)
        return commit[0], votes[0]

    return jax.jit(run)
