"""The generator: seeded, with the stated shares and skew; the arithmetic."""

import json
import os

import numpy as np
import pytest
from bench_helpers import REPO

from benchmark import check_manifest, peaks, plugins, stats
from benchmark.traffic import (READ, NotImplementedTraffic, OpStream, Values)


def _mix(name):
    with open(os.path.join(REPO, "benchmark", "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, reads", [("ycsb_a", 0.5), ("ycsb_b", 0.95)])
def test_same_seed_same_operations_with_the_stated_shares_and_skew(name,
                                                                   reads):
    big = 2 ** 31 + 12345            # the driver's seeds pass 32 signed bits
    a = OpStream(_mix(name), 16384, big, n=1 << 17)
    b = OpStream(_mix(name), 16384, big, n=1 << 17)
    c = OpStream(_mix(name), 16384, big + 1, n=1 << 17)
    assert np.array_equal(a.kinds, b.kinds)
    assert np.array_equal(a.records, b.records)
    assert not np.array_equal(a.records, c.records)
    assert abs((a.kinds == READ).mean() - reads) < 0.01
    # zipfian 0.99 over 16,384 records: the hottest takes about 1/H = 9.7 %,
    # the ten hottest about 28 %; scrambled, so they are not records 0..9
    counts = np.bincount(a.records, minlength=16384) / a.n
    top = np.sort(counts)[::-1]
    assert 0.085 < top[0] < 0.11 and 0.25 < top[:10].sum() < 0.32
    assert set(np.argsort(counts)[-10:]) != set(range(10))
    assert a.records.min() >= 0 and a.records.max() < 16384


def test_what_no_cell_uses_yet_is_refused_by_name(manifest_root):
    """Kinds of operation by the generator; a fault schedule by the lookup,
    unless the mix's loop module says that it runs it; a loop of a kind that
    is no module by the manifest's check."""
    for change, word in (({"scan_share": 0.1, "read_share": 0.4}, "scan"),
                         ({"request_distribution": "latest"}, "latest")):
        with pytest.raises(NotImplementedTraffic, match=word):
            OpStream(dict(_mix("ycsb_a"), **change), 64, 1, n=16)
    bm = check_manifest.check(manifest_root)
    for name in ("ycsb_a", "ycsb_a_open"):
        assert plugins.loop_of(bm, _mix(name)).IMPLEMENTS == {"faults": [[]]}
        with pytest.raises(NotImplementedTraffic, match="faults="):
            plugins.loop_of(bm, dict(_mix(name),
                                     faults=[{"kill_store": 1}]))
    errs: list = []
    check_manifest.check_traffic_file(
        dict(_mix("ycsb_a"), loop={"kind": "bursty", "rate": 100}),
        "a_mix.json", errs, manifest_root)
    assert any("loop.kind 'bursty'" in e and "no such file" in e
               for e in errs)
    uniform = OpStream(dict(_mix("ycsb_a"), request_distribution="uniform"),
                       64, 1, n=1 << 14)
    assert np.bincount(uniform.records, minlength=64).min() > 150


def test_values_name_their_writer_and_every_byte_is_checked():
    v = Values(2 ** 31 + 7, 1000)
    x = v.make(17, 3, 9001)
    assert len(x) == 1000 and v.parse(x) == (17, 3, 9001)
    assert v.parse(x[:-1] + bytes([x[-1] ^ 1])) is None
    assert v.parse(x[:999]) is None and v.parse(None) is None
    assert Values(2 ** 31 + 8, 1000).parse(x) is None   # another seed's data


def test_percentile_rate_and_spread_on_known_samples():
    sample = list(range(1, 101))
    assert stats.percentile(sample, 95) == 95
    assert stats.percentile(sample[::-1], 50) == 50
    assert stats.percentile([7.0], 99) == 7.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2      # nearest rank
    assert stats.rate(300, 20.0) == 15.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    # quartiles as statistics.quantiles(n=4) gives them: 2.25 and 6.75 of 1..8
    assert stats.spread([1, 2, 3, 4, 5, 6, 7, 8]) == pytest.approx(1.0)
    assert stats.stat([1.0, 2.0, 9.0], "median") == 2.0
    assert stats.stat([1.0, 2.0, 9.0], "mean") == 4.0


def test_peaks_table_and_the_tick_bytes():
    pk = peaks.peaks_for("TPU v5 lite")
    assert pk["hbm_bytes_per_s"] == 819e9 and pk["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for("TPU v9")
    # per group: 33 + 12 P in, 16 of parameters, 17 out
    assert peaks.raft_tick_min_bytes(2048, 4) == 2048 * (33 + 48 + 16 + 17)
    least = peaks.memory_bound_seconds("TPU v5 lite",
                                       peaks.raft_tick_min_bytes(2048, 4))
    assert least == pytest.approx(233472 / 819e9)
