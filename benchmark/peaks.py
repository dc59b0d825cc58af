"""Peaks of the chips the benchmark may run on, and the least work a kernel
needs, computed from its shapes.  One table, keyed by ``device_kind`` as JAX
reports it; a device that is not in it is an error, never a default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": one chip
    "TPU v5 lite": {"hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "source": "Google Cloud documentation, TPU v5e "
                              "(cloud.google.com/tpu/docs/v5e)"},
}

def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"has {sorted(PEAKS)}")
    return PEAKS[device_kind]


def raft_tick_min_bytes(max_groups: int, max_peers: int) -> int:
    """The bytes one tick of ``[G, P]`` consensus state has to move, whatever
    implements it: every input row read once, every output row written once.

    Inputs per group: eight int32 rows (role, commit, pending, four deadlines,
    fence start) and one bool (quiescent); per peer slot two int32 (match,
    last ack) and four bool masks (granted, voter, old voter, witness); four
    int32 protocol parameters.  Outputs per group: two int32 (commit, quorum
    ack time) and nine bool event masks."""
    g, p = max_groups, max_peers
    inputs = g * (8 * 4 + 1) + g * p * (2 * 4 + 4) + g * 4 * 4
    outputs = g * (2 * 4 + 9)
    return inputs + outputs


WORK_FNS = {"raft_tick_min_bytes": raft_tick_min_bytes}


def memory_bound_seconds(device_kind: str, n_bytes: int) -> float:
    """The least seconds the chip could take to move ``n_bytes``.  The tick
    is integer compares, selects and a P-wide sort: no matrix or floating
    point work, so the published FLOP peaks say nothing about it and its
    roofline is the memory one."""
    return n_bytes / peaks_for(device_kind)["hbm_bytes_per_s"]
