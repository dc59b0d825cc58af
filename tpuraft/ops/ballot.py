"""Ballot/quorum kernels: the vectorized BallotBox.

Replaces the reference's ``core:core/BallotBox#commitAt`` / ``Ballot#grant``
per-index loop (SURVEY.md §4.2 hot path) with order statistics over the
``[G, P]`` matchIndex matrix, and election tallying in
``core:core/NodeImpl#handleRequestVoteResponse`` with a masked popcount.

Everything is pure jnp — jit/vmap/shard_map friendly, no data-dependent
shapes.  P (peer slots) is small (<= 16 in practice); a full sort along the
last axis lowers to an O(P log P) sorting network on the VPU, negligible
against the [G]-axis parallelism.
"""

from __future__ import annotations

import jax.numpy as jnp

# Sentinel for masked-out peer slots. Using iinfo.min would overflow under
# arithmetic; half-range is safely below any valid relative index (>= -1).
# A plain int: a jnp scalar here would initialise the JAX backend (and
# take the chip) in every process that merely imports the package.
NEG_INF_I32 = -(2**30)


def _masked_desc_sort(values: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Sort each row descending with masked slots pushed to the end."""
    v = jnp.where(mask, values.astype(jnp.int32), NEG_INF_I32)
    return -jnp.sort(-v, axis=-1)


def quorum_match_index(match: jnp.ndarray, voter_mask: jnp.ndarray) -> jnp.ndarray:
    """Per-group largest index replicated on a quorum of voters.

    match: int32 [..., P] relative matchIndex per peer slot (leader's own
      slot must contain its lastLogIndex).
    voter_mask: bool [..., P] — True for slots that are voters in the
      current configuration.

    Returns int32 [...]: the q-th largest matchIndex among voters, where
    q = floor(n_voters/2) + 1; NEG_INF_I32 for groups with zero voters.
    """
    sorted_desc = _masked_desc_sort(match, voter_mask)
    n_voters = voter_mask.sum(axis=-1).astype(jnp.int32)
    quorum = n_voters // 2 + 1
    q_idx = jnp.clip(quorum - 1, 0, match.shape[-1] - 1)
    picked = jnp.take_along_axis(sorted_desc, q_idx[..., None], axis=-1)[..., 0]
    return jnp.where(n_voters > 0, picked, NEG_INF_I32)


def joint_quorum_match_index(
    match: jnp.ndarray,
    voter_mask: jnp.ndarray,
    old_voter_mask: jnp.ndarray,
) -> jnp.ndarray:
    """Joint-consensus commit point: needs a quorum of BOTH configurations.

    Groups not in joint mode should pass an all-False ``old_voter_mask``
    row — it is ignored for those rows (reference: ``Ballot`` with empty
    oldConf grants on the new conf alone).
    """
    new_q = quorum_match_index(match, voter_mask)
    old_q = quorum_match_index(match, old_voter_mask)
    in_joint = old_voter_mask.any(axis=-1)
    return jnp.where(in_joint, jnp.minimum(new_q, old_q), new_q)


def vote_quorum(granted: jnp.ndarray, voter_mask: jnp.ndarray) -> jnp.ndarray:
    """Per-group: does the granted-vote set reach a quorum of voters?

    granted: bool [..., P]; voter_mask: bool [..., P].
    Mirrors ``Ballot#isGranted`` for election and pre-vote tallies.
    """
    n_voters = voter_mask.sum(axis=-1).astype(jnp.int32)
    votes = (granted & voter_mask).sum(axis=-1).astype(jnp.int32)
    return (n_voters > 0) & (votes >= n_voters // 2 + 1)


def joint_vote_quorum(
    granted: jnp.ndarray, voter_mask: jnp.ndarray, old_voter_mask: jnp.ndarray
) -> jnp.ndarray:
    """Election quorum under joint consensus: both configs must grant."""
    new_ok = vote_quorum(granted, voter_mask)
    old_ok = vote_quorum(granted, old_voter_mask)
    in_joint = old_voter_mask.any(axis=-1)
    return jnp.where(in_joint, new_ok & old_ok, new_ok)


def witness_commit_clamp(
    quorum_idx: jnp.ndarray,
    match: jnp.ndarray,
    voter_mask: jnp.ndarray,
    old_voter_mask: jnp.ndarray,
    witness_mask: jnp.ndarray,
) -> jnp.ndarray:
    """Clamp the commit point to the best DATA-replica match for groups
    with witness voters.

    Witnesses (util.quorum.witness_minority: a strict minority of
    metadata-only voters) count toward vote and ack quorums but hold no
    log payload, so an index acked only by witnesses must not commit —
    the host BallotBox clamps its quorum index to ``max(match[data])``
    (ballot_box.commit_point), and this is that clamp vectorized over
    the [G] axis.  Data peers are every voter (either config — the
    joint union mirrors the host's ``conf.data_peers + old_conf
    .data_peers``) not marked witness; groups without witnesses pass
    through untouched.  The max over an all-False data row is 0, like
    the host's ``max(..., default=0)`` — a witness-only quorum can
    never commit anything.
    """
    voters = voter_mask | old_voter_mask
    has_witness = (voters & witness_mask).any(axis=-1)
    data = voters & ~witness_mask
    data_best = jnp.where(data, match.astype(jnp.int32),
                          jnp.int32(0)).max(axis=-1)
    return jnp.where(has_witness, jnp.minimum(quorum_idx, data_best),
                     quorum_idx)


def quorum_ack_time(last_ack: jnp.ndarray, voter_mask: jnp.ndarray) -> jnp.ndarray:
    """q-th most recent peer ack timestamp — the leader-lease / step-down
    primitive (reference: ``NodeImpl#checkDeadNodes``): the leader's lease
    extends ``election_timeout`` past the time a quorum last responded.

    Identical math to :func:`quorum_match_index`; exposed under its own
    name because timestamps and log indexes are different host quantities.
    """
    return quorum_match_index(last_ack, voter_mask)


def joint_quorum_ack_time(
    last_ack: jnp.ndarray, voter_mask: jnp.ndarray, old_voter_mask: jnp.ndarray
) -> jnp.ndarray:
    """Lease/step-down ack point under joint consensus: the leader holds
    its lease only while a quorum of BOTH configurations is responsive
    (reference: ``NodeImpl#checkDeadNodes`` iterates conf and oldConf), so
    take the older (min) of the two configs' quorum ack times."""
    new_q = quorum_ack_time(last_ack, voter_mask)
    old_q = quorum_ack_time(last_ack, old_voter_mask)
    in_joint = old_voter_mask.any(axis=-1)
    return jnp.where(in_joint, jnp.minimum(new_q, old_q), new_q)
