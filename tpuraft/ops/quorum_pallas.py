"""Pallas TPU kernel for the fused quorum hot path.

One kernel pass computes, for every raft group, the three [G,P]→[G]
reductions of the tick (SURVEY.md §4.2 — ``BallotBox#commitAt`` +
vote tally + ``NodeImpl#checkDeadNodes``):

  quorum_idx  — q-th largest voter matchIndex (joint-consensus aware)
  elected     — vote quorum reached (joint-consensus aware)
  q_ack       — q-th newest voter ack timestamp (joint-consensus aware;
                lease / step-down)

Design notes:
  - Arrays enter transposed as [P, G] so the large G axis lies on the
    128-lane dimension (P <= 16 would waste 112/128 lanes the other way).
  - The q-th order statistic uses rank counting, not sorting: for slot j,
    cnt_j = #{k : v_k >= v_j}; the q-th largest = max{v_j : cnt_j >= q}.
    That is P broadcast-compare-accumulates over [P, TILE_G] tiles — pure
    VPU work, no gather/sort, and P is a static Python loop (fully
    unrolled at trace time, as the guide prescribes for tiny axes).
  - Masks arrive as int32 (bool tiles would demand 32 sublanes; P < 32),
    and no select ever PRODUCES a bool vector: Mosaic has no i8->i1
    truncation, so the vote tally stays int32 0/1 until it leaves the
    kernel (booleans appear only as select/and conditions).
  - One G-tile per grid step; all five inputs for a tile sit in VMEM
    (5 * P * TILE_G * 4B = 128KB at P=16, TILE_G=512 — far under 16MB).

The XLA path (tpuraft.ops.ballot) stays the source of truth for
semantics and the default; tests drive both (the kernel under
``interpret=True`` on CPU) over randomized states and assert
bit-equality, and ``chip_smoke.py`` compiles the kernel with Mosaic on
the chip at the served shapes and asserts the same.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpuraft.ops.ballot import (
    NEG_INF_I32,
    joint_quorum_ack_time,
    joint_quorum_match_index,
    joint_vote_quorum,
)

TILE_G = 512


def _qth_largest(v: jnp.ndarray, mask: jnp.ndarray, p: int) -> jnp.ndarray:
    """[P, T] masked values -> [1, T] q-th largest among mask, else NEG_INF."""
    vm = jnp.where(mask, v, jnp.int32(NEG_INF_I32))
    n_voters = mask.astype(jnp.int32).sum(axis=0, keepdims=True)   # [1, T]
    q = n_voters // 2 + 1
    cnt = jnp.zeros(v.shape, jnp.int32)                            # [P, T]
    for k in range(p):  # static unroll: P broadcast-compares on the VPU
        cnt = cnt + (vm[k:k + 1, :] >= vm).astype(jnp.int32)
    ok = mask & (cnt >= q)
    picked = jnp.where(ok, vm, jnp.int32(NEG_INF_I32)).max(axis=0, keepdims=True)
    return jnp.where(n_voters > 0, picked, jnp.int32(NEG_INF_I32))


def _vote_quorum(granted: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """[P, T] bool grants/mask -> [1, T] int32 0/1 (see module note)."""
    n_voters = mask.astype(jnp.int32).sum(axis=0, keepdims=True)
    votes = (granted & mask).astype(jnp.int32).sum(axis=0, keepdims=True)
    return ((n_voters > 0) & (votes >= n_voters // 2 + 1)).astype(jnp.int32)


def _fused_quorum_kernel(match_ref, granted_ref, ack_ref, vm_ref, ovm_ref,
                         qidx_ref, elected_ref, qack_ref):
    p = match_ref.shape[0]
    vm = vm_ref[:] != 0
    ovm = ovm_ref[:] != 0
    granted = granted_ref[:] != 0
    in_joint = ovm_ref[:].max(axis=0, keepdims=True) > 0  # [1, T]

    qi_new = _qth_largest(match_ref[:], vm, p)
    qi_old = _qth_largest(match_ref[:], ovm, p)
    qidx_ref[:] = jnp.where(in_joint, jnp.minimum(qi_new, qi_old), qi_new)

    el_new = _vote_quorum(granted, vm)
    el_old = _vote_quorum(granted, ovm)
    elected_ref[:] = jnp.where(in_joint, el_new * el_old, el_new)

    qa_new = _qth_largest(ack_ref[:], vm, p)
    qa_old = _qth_largest(ack_ref[:], ovm, p)
    qack_ref[:] = jnp.where(in_joint, jnp.minimum(qa_new, qa_old), qa_new)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_quorum_pallas(match, granted, last_ack, voter_mask, old_voter_mask,
                         interpret: bool = False):
    g, p = match.shape
    # lane tiles must be 128-multiples: round G up to 128, cap the tile at
    # TILE_G, then round G up again to a whole number of tiles
    tile = min(TILE_G, -(-g // 128) * 128)
    pad = (-g) % tile
    # pad G to a tile multiple with inactive groups (all-False masks)
    if pad:
        zi = lambda a: jnp.pad(a, ((0, pad), (0, 0)))  # noqa: E731
        match, last_ack = zi(match), zi(last_ack)
        granted = jnp.pad(granted, ((0, pad), (0, 0)))
        voter_mask = jnp.pad(voter_mask, ((0, pad), (0, 0)))
        old_voter_mask = jnp.pad(old_voter_mask, ((0, pad), (0, 0)))
    gp = g + pad
    t = lambda a: a.T.astype(jnp.int32)  # noqa: E731 — [G,P] -> [P,G] lanes
    spec_in = pl.BlockSpec((p, tile), lambda i: (0, i))
    spec_out = pl.BlockSpec((1, tile), lambda i: (0, i))
    qidx, elected, qack = pl.pallas_call(
        _fused_quorum_kernel,
        grid=(gp // tile,),
        in_specs=[spec_in] * 5,
        out_specs=[spec_out] * 3,
        out_shape=[jax.ShapeDtypeStruct((1, gp), jnp.int32)] * 3,
        interpret=interpret,
    )(t(match), t(granted), t(last_ack), t(voter_mask), t(old_voter_mask))
    return qidx[0, :g], elected[0, :g] != 0, qack[0, :g]


def _fused_quorum_xla(match, granted, last_ack, voter_mask, old_voter_mask):
    qidx = joint_quorum_match_index(match, voter_mask, old_voter_mask)
    elected = joint_vote_quorum(granted, voter_mask, old_voter_mask)
    qack = joint_quorum_ack_time(last_ack, voter_mask, old_voter_mask)
    return qidx, elected, qack


def fused_quorum(match, granted, last_ack, voter_mask, old_voter_mask,
                 impl: str | None = None):
    """(quorum_idx[G], elected[G], q_ack[G]) from the [G,P] state planes.

    impl: "pallas" (TPU kernel), "pallas_interpret" (CPU-debuggable
    kernel), "xla" (pure jnp), or None = $TPURAFT_QUORUM_IMPL, default
    "xla".  The default stays XLA on every backend: the kernel compiles
    on the chip and is bit-equal, but no trace has yet shown it moving
    an end-to-end number (ROADMAP C2 decides between the two).
    """
    if impl is None:
        impl = os.environ.get("TPURAFT_QUORUM_IMPL", "xla")
    if impl == "pallas":
        return _fused_quorum_pallas(match, granted, last_ack,
                                    voter_mask, old_voter_mask)
    if impl == "pallas_interpret":
        return _fused_quorum_pallas(match, granted, last_ack,
                                    voter_mask, old_voter_mask,
                                    interpret=True)
    if impl == "xla":
        return _fused_quorum_xla(match, granted, last_ack,
                                 voter_mask, old_voter_mask)
    raise ValueError(f"unknown quorum impl: {impl}")
