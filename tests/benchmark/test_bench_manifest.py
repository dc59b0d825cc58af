"""The manifest's self-check, and that the benchmark grows by adding files."""

import json
import os

import pytest
from bench_helpers import (GROWN_CELLS, GROWN_CONFIGS, GROWN_METRICS,
                           PARTITION_FAULTS, REPO, TINY_CELL, extended_copy,
                           grown_copy)

from benchmark import check_manifest, plugins
from benchmark.check_manifest import ManifestError
from benchmark.loops import open_faults
from benchmark.traffic import NotImplementedTraffic


def test_the_committed_manifest_passes(manifest_root):
    bm = check_manifest.check(manifest_root)
    assert bm["command"][:3] == ["python3", "-m", "benchmark.run"]
    for w in bm["workloads"]:
        names = {m["name"] for m in check_manifest.metrics_of(
            bm, w["name"], "end_to_end")}
        assert {"setup_s", "ops_per_s"} <= names
        assert check_manifest.metrics_of(bm, w["name"], "per_layer")


def test_a_cell_config_mix_and_metric_are_added_as_files_only(tmp_path):
    bm = check_manifest.check(extended_copy(str(tmp_path)))
    cell, cfg, mix = check_manifest.cell(bm, TINY_CELL)
    assert (cfg["regions"], mix["loop"]["clients"]) == (8, 16)
    layer = {m["name"]: m for m in check_manifest.metrics_of(
        bm, TINY_CELL, "per_layer")}
    assert layer["srv_propose_ms"]["_reader"]["span"] == "srv_propose"
    assert "raft_tick_roofline" in layer    # no workloads key: every cell


def test_the_grown_copy_appends_a_deployment_of_each_kind(tmp_path):
    """The second root of ``manifest_root``: the committed manifest's entries
    first and in order, every committed file as it is, and after them a
    configuration, a cell on it whose loop module runs a fault schedule that
    ``open_faults.py`` does not, a cell on a configuration that was there, a
    four-chip cell, and two per-layer metrics: one for the new cell alone,
    one for every cell."""
    root = grown_copy(str(tmp_path))
    bm, committed = check_manifest.check(root), check_manifest.check(REPO)
    for key, grown in (("configs", GROWN_CONFIGS), ("workloads", GROWN_CELLS),
                       ("per_layer", GROWN_METRICS)):
        assert [e["name"] for e in bm[key]] == [
            e["name"] for e in committed[key]] + list(grown)
    for dirpath, dirs, files in os.walk(os.path.join(REPO, "benchmark")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fn in files:
            rel = os.path.relpath(os.path.join(dirpath, fn), REPO)
            with open(os.path.join(REPO, rel), "rb") as a, \
                    open(os.path.join(root, rel), "rb") as b:
                assert a.read() == b.read(), rel
    _, cfg, mix = check_manifest.cell(bm, GROWN_CELLS[0])
    assert mix["faults"] == PARTITION_FAULTS
    assert PARTITION_FAULTS not in open_faults.IMPLEMENTS["faults"]
    assert plugins.loop_of(bm, mix).IMPLEMENTS == {
        "faults": [PARTITION_FAULTS]}
    with pytest.raises(NotImplementedTraffic, match="faults="):
        plugins.loop_of(bm, dict(mix, loop=dict(mix["loop"],
                                                kind="open_faults")))
    assert cfg["name"] == GROWN_CONFIGS[0]
    assert [check_manifest.cell(bm, c)[0]["chips"] for c in GROWN_CELLS] \
        == [1, 1, 4]
    for w in bm["workloads"]:
        names = {m["name"] for m in check_manifest.metrics_of(
            bm, w["name"], "per_layer")}
        assert GROWN_METRICS[1] in names
        assert (GROWN_METRICS[0] in names) == (w["name"] == GROWN_CELLS[0])


def _edit(root, rel, fn):
    path = os.path.join(root, rel)
    with open(path) as f:
        data = json.load(f)
    fn(data)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.mark.parametrize("rel, edit, says", [
    # PR 22's refusal: a source that is not 1 to 200 printable characters
    ("BENCHMARK.json",
     lambda bm: bm["configs"][0].update(source="x" * 201), "source"),
    ("BENCHMARK.json",
     lambda bm: bm["configs"][0].update(source="3 × 1,024"), "ASCII"),
    ("benchmark/configs/kv3x8.json",
     lambda c: c.update(source="tab\there"), "source"),
    ("BENCHMARK.json",
     lambda bm: bm["workloads"][0].update(name="has space"), "not a name"),
    ("BENCHMARK.json",
     lambda bm: bm["end_to_end"][0].update(unit="ops per second"), "unit"),
    ("BENCHMARK.json",
     lambda bm: bm["per_layer"][0].update(moves="tick_host_ms"),
     "no end-to-end metric"),
    ("BENCHMARK.json",
     lambda bm: bm["workloads"][0].update(traffic="ycsb_z"), "no such file"),
    ("BENCHMARK.json",
     lambda bm: bm["per_layer"][0].update(why="because"), "unknown keys"),
    ("BENCHMARK.json",
     lambda bm: bm["end_to_end"][0].update(bound=0.4), "bound"),
    ("benchmark/traffic/ycsb_a16.json",
     lambda t: t.update(think_time_ms=5), "unknown keys"),
    ("benchmark/traffic/ycsb_a16.json",
     lambda t: t.update(read_share=0.7), "sum to 1"),
    ("benchmark/layer_metrics/srv_propose_ms.json",
     lambda m: m["reader"].update(kind="regex"), "reader.kind"),
    # a named module has to be a file beside the data
    ("benchmark/configs/kv3x8.json",
     lambda c: c.update(cluster="five_stores"),
     "cluster 'five_stores': benchmark/clusters/five_stores.py: no such file"),
    ("benchmark/configs/kv3x8.json",
     lambda c: c.update(cluster="../cluster"), "not a name"),
    ("benchmark/configs/kv3x8.json",
     lambda c: c.update(options=[1]), "options must be an object"),
    ("benchmark/traffic/ycsb_a16.json",
     lambda t: t.update(loop={"kind": "bursty", "clients": 4}),
     "loop.kind 'bursty': benchmark/loops/bursty.py: no such file"),
    ("benchmark/traffic/ycsb_a_open300.json",
     lambda t: t["loop"].update(rate=0), "above 0"),
    ("benchmark/traffic/ycsb_a_open300.json",
     lambda t: t["loop"].update(clients=4), "just 'rate'"),
])
def test_the_check_refuses(tmp_path, rel, edit, says):
    root = extended_copy(str(tmp_path))
    _edit(root, rel, edit)
    with pytest.raises(ManifestError, match=says):
        check_manifest.check(root)
