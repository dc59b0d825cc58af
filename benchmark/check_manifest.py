"""The manifest's self-check: ``BENCHMARK.json`` and the data files it names.

    python -m benchmark.check_manifest [root]

``benchmark.run`` calls :func:`check` before anything else, so a string
the driver would refuse (PR 22 lost the whole benchmark to one) fails
here, in a second, before a chip is touched.  It also loads the data:
:func:`check` gives the harness the manifest with each configuration,
traffic mix and per-layer metric read from the file of its own that the
manifest's names lead to (under ``_data``, ``_traffic``, ``_reader``), so adding a cell, a configuration, a mix or a
metric is adding files and manifest entries, never an edit here.  A mix's
loop and a configuration's optional ``cluster`` name modules, which have to be
files beside the data (``loops/<kind>.py``, ``clusters/<name>.py``);
``benchmark/plugins.py`` loads them from the tree checked here (``_root``).
"""

from __future__ import annotations

import json
import os
import re
import sys

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
E2E_SOURCES = ("host_clock", "device_trace")
MAX_BOUND = 0.25
MAX_RUN_SECONDS = 51
READER_KINDS = ("counter_ratio", "span", "histogram", "trace_event",
                "roofline", "device_idle", "latency")

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}

# what a configuration file states, and the values the harness builds today
CONFIG_FIELDS = {"name", "source", "why", "regions", "stores", "replicas",
                 "engine", "election_timeout_ms", "log_scheme", "kv_store",
                 "read_mode", "transport", "record_count", "field_count",
                 "field_bytes", "guarantees", "chips", "layout", "assumed",
                 "reduced", "reduced_why"}
CONFIG_OPTIONAL = {"cluster", "options"}    # the module that builds it; its own
ENGINE_FIELDS = {"backend", "max_groups", "max_peers", "tick_interval_ms",
                 "mesh_devices"}
TRAFFIC_FIELDS = {"name", "why", "read_share", "update_share",
                  "insert_share", "scan_share", "scan_length", "rmw_share",
                  "request_distribution", "zipfian_constant", "scrambled",
                  "loop", "warm_seconds", "faults"}
DISTRIBUTIONS = ("zipfian", "uniform", "latest")


class ManifestError(ValueError):
    pass


def _line(text, what: str, errs: list) -> None:
    """1 to 200 printable ASCII characters on one line (PR 22's refusal)."""
    if (not isinstance(text, str) or not 1 <= len(text) <= 200
            or any(not 32 <= ord(c) < 127 for c in text)):
        errs.append(f"{what} must be 1 to 200 printable ASCII characters "
                    f"on one line, not {text!r:.80}")


def _name(text, what: str, errs: list) -> None:
    if not isinstance(text, str) or not NAME_RE.match(text):
        errs.append(f"{what}: {text!r} is not a name (letters, digits, "
                    f"'_', '.', '-', at most 64)")


def _keys(entry: dict, want: set, what: str, errs: list,
          optional: set = frozenset()) -> None:
    have = set(entry)
    if have - want - optional:
        errs.append(f"{what}: unknown keys {sorted(have - want - optional)}")
    if want - have:
        errs.append(f"{what}: missing keys {sorted(want - have)}")


def _read_json(root: str, rel: str, errs: list):
    path = os.path.join(root, rel)
    if not os.path.isfile(path):
        errs.append(f"{rel}: no such file")
        return None
    try:
        with open(path, encoding="ascii") as f:
            return json.load(f)
    except (ValueError, UnicodeDecodeError) as e:
        errs.append(f"{rel}: not ASCII JSON: {e}")
        return None


def _module_file(root: str, base: str, kind_dir: str, name, what: str,
                 errs: list) -> None:
    """``name`` names a module: a file ``<base>/<kind_dir>/<name>.py``."""
    _name(name, what, errs)
    if isinstance(name, str) and NAME_RE.match(name):
        rel = f"{base}/{kind_dir}/{name}.py"
        if not os.path.isfile(os.path.join(root, rel)):
            errs.append(f"{what} {name!r}: {rel}: no such file")


def check_config_file(cfg: dict, rel: str, errs: list, root: str = ".",
                      base: str = "benchmark") -> None:
    _keys(cfg, CONFIG_FIELDS, rel, errs, optional=CONFIG_OPTIONAL)
    if "cluster" in cfg:
        _module_file(root, base, "clusters", cfg["cluster"],
                     f"{rel}: cluster", errs)
    if not isinstance(cfg.get("options", {}), dict):
        errs.append(f"{rel}: options must be an object")
    _line(cfg.get("source"), f"{rel}: source", errs)
    eng = cfg.get("engine")
    if not isinstance(eng, dict):
        errs.append(f"{rel}: engine must be an object")
    else:
        _keys(eng, ENGINE_FIELDS, f"{rel}: engine", errs)
    for key in ("regions", "stores", "replicas", "record_count",
                "field_count", "field_bytes", "election_timeout_ms"):
        if not isinstance(cfg.get(key), int) or cfg.get(key, 0) < 1:
            errs.append(f"{rel}: {key} must be a positive whole number")
    for key in cfg.get("reduced", []):
        _name(key, f"{rel}: reduced key", errs)


def check_traffic_file(tr: dict, rel: str, errs: list, root: str = ".",
                       base: str = "benchmark") -> None:
    _keys(tr, TRAFFIC_FIELDS, rel, errs)
    shares = [tr.get(k, 0.0) for k in ("read_share", "update_share",
                                       "insert_share", "scan_share",
                                       "rmw_share")]
    if any(not isinstance(s, (int, float)) or s < 0 for s in shares) \
            or abs(sum(shares) - 1.0) > 1e-9:
        errs.append(f"{rel}: the shares must be non-negative and sum to 1")
    if tr.get("request_distribution") not in DISTRIBUTIONS:
        errs.append(f"{rel}: request_distribution must be one of "
                    f"{DISTRIBUTIONS}")
    loop = tr.get("loop")
    if not isinstance(loop, dict):
        errs.append(f"{rel}: loop must be an object with a 'kind'")
        loop = {}
    else:
        # the kind names the module that sends the mix; a kind not known
        # here has its other keys checked by that module
        _module_file(root, base, "loops", loop.get("kind"),
                     f"{rel}: loop.kind", errs)
    if loop.get("kind") == "closed" and (set(loop) != {"kind", "clients"}
                                       or not isinstance(loop["clients"], int)
                                       or loop["clients"] < 1):
        errs.append(f"{rel}: a closed loop has just 'clients', a positive "
                    f"whole number")
    elif loop.get("kind") == "open" and (
            set(loop) != {"kind", "rate"}
            or not isinstance(loop["rate"], (int, float))
            or isinstance(loop["rate"], bool) or loop["rate"] <= 0):
        errs.append(f"{rel}: an open loop has just 'rate', operations a "
                    f"second, above 0")
    if not isinstance(tr.get("faults"), list):
        errs.append(f"{rel}: faults must be a list")


def check_layer_file(lm: dict, entry: dict, rel: str, errs: list) -> None:
    _keys(lm, LAYER_KEYS | {"reader"}, rel, errs, optional={"workloads"})
    for key in sorted(LAYER_KEYS | ({"workloads"} & set(entry))):
        if lm.get(key) != entry.get(key):
            errs.append(f"{rel}: {key} is {lm.get(key)!r} but BENCHMARK.json "
                        f"says {entry.get(key)!r}")
    reader = lm.get("reader")
    if not isinstance(reader, dict) or reader.get("kind") not in READER_KINDS:
        errs.append(f"{rel}: reader.kind must be one of {READER_KINDS}")


def _metric(m: dict, what: str, errs: list) -> None:
    _name(m.get("name"), what, errs)
    if not isinstance(m.get("unit"), str) or not UNIT_RE.match(m["unit"]):
        errs.append(f"{what}: unit {m.get('unit')!r} is not 1 to 16 of "
                    f"letters, digits, '_', '/', '%', '.', '-'")
    if m.get("better") not in ("lower", "higher"):
        errs.append(f"{what}: better must be 'lower' or 'higher'")


def check(root: str = ".") -> dict:
    """Validate ``<root>/BENCHMARK.json`` and every file it names; return
    the manifest.  Raises :class:`ManifestError` listing every fault."""
    errs: list = []
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise ManifestError(f"{path}: no such file")
    if os.path.getsize(path) > 64 * 1024:
        errs.append("BENCHMARK.json is over 64 KiB")
    with open(path, "rb") as f:
        raw = f.read()
    try:
        bm = json.loads(raw.decode("ascii"))
    except (ValueError, UnicodeDecodeError) as e:
        raise ManifestError(f"BENCHMARK.json: not ASCII JSON: {e}") from e
    if not isinstance(bm, dict):
        raise ManifestError("BENCHMARK.json: not an object")
    _keys(bm, TOP_KEYS, "BENCHMARK.json", errs)
    if errs:
        raise ManifestError("; ".join(errs))

    cmd, paths = bm["command"], bm["paths"]
    if (not isinstance(cmd, list) or not 1 <= len(cmd) <= 32):
        errs.append("command must be a list of 1 to 32 strings")
    else:
        for word in cmd:
            _line(word, "command word", errs)
            if isinstance(word, str) and (word.startswith("/")
                                          or ".." in word.split("/")):
                errs.append(f"command word {word!r} leaves the repo")
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        errs.append("paths must list 1 to 16 directories")
        paths = []
    base = paths[0] if paths and isinstance(paths[0], str) else "benchmark"
    for p in paths:
        if (not isinstance(p, str) or not PATH_RE.match(p)
                or p.startswith("/") or ".." in p.split("/")):
            errs.append(f"path {p!r} is not a relative path of letters, "
                        f"digits, '_', '.', '-', '/'")
        elif not os.path.isdir(os.path.join(root, p)):
            errs.append(f"path {p!r}: no such directory")
        else:
            for dirpath, dirs, files in os.walk(os.path.join(root, p)):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                for fn in files:
                    rel = os.path.relpath(os.path.join(dirpath, fn), root)
                    if not PATH_RE.match(rel) and not fn.endswith(".pyc"):
                        errs.append(f"file {rel!r} has a character outside "
                                    f"letters, digits, '_', '.', '-', '/'")
    rs = bm["run_seconds"]
    if not isinstance(rs, int) or not 1 <= rs <= MAX_RUN_SECONDS:
        errs.append(f"run_seconds must be a whole number from 1 to "
                    f"{MAX_RUN_SECONDS}")

    def under_paths(rel: str) -> bool:
        return any(rel.startswith(p.rstrip("/") + "/") for p in paths)

    # -- configurations -----------------------------------------------------
    configs: dict = {}
    files_seen: set = set()
    if not 1 <= len(bm["configs"]) <= 24:
        errs.append("configs must hold 1 to 24 entries")
    for c in bm["configs"]:
        what = f"config {c.get('name')}"
        _keys(c, CONFIG_KEYS, what, errs)
        _name(c.get("name"), what, errs)
        _line(c.get("source"), f"{what}: source", errs)
        _line(c.get("why"), f"{what}: why", errs)
        if c.get("name") in configs:
            errs.append(f"{what}: named twice")
        configs[c.get("name")] = c
        red = c.get("reduced")
        if not isinstance(red, list) or len(red) > 16:
            errs.append(f"{what}: reduced must be a list of at most 16 keys")
            red = []
        for key in red:
            _name(key, f"{what}: reduced key", errs)
        rel = c.get("file")
        if not isinstance(rel, str) or not under_paths(rel):
            errs.append(f"{what}: file {rel!r} is not under paths")
            continue
        if rel in files_seen:
            errs.append(f"{what}: file {rel} is another configuration's")
        files_seen.add(rel)
        cfg = _read_json(root, rel, errs)
        if cfg is None:
            continue
        check_config_file(cfg, rel, errs, root, base)
        if cfg.get("name") != c.get("name"):
            errs.append(f"{rel}: name {cfg.get('name')!r} is not "
                        f"{c.get('name')!r}")
        if cfg.get("source") != c.get("source"):
            errs.append(f"{rel}: source differs from BENCHMARK.json's")
        if sorted(cfg.get("reduced", [])) != sorted(red):
            errs.append(f"{rel}: reduced differs from BENCHMARK.json's")
        c["_data"] = cfg

    # -- end-to-end metrics -------------------------------------------------
    e2e: dict = {}
    if not 1 <= len(bm["end_to_end"]) <= 16:
        errs.append("end_to_end must hold 1 to 16 metrics")
    for m in bm["end_to_end"]:
        what = f"end-to-end metric {m.get('name')}"
        _keys(m, E2E_KEYS, what, errs, optional={"workloads"})
        _metric(m, what, errs)
        if m.get("source") not in E2E_SOURCES:
            errs.append(f"{what}: source must be one of {E2E_SOURCES}")
        b = m.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= MAX_BOUND:
            errs.append(f"{what}: bound must be from 0.01 to {MAX_BOUND}")
        if m.get("name") in e2e:
            errs.append(f"{what}: named twice")
        e2e[m.get("name")] = m
    if "setup_s" not in e2e:
        errs.append("end_to_end must include setup_s")

    # -- cells --------------------------------------------------------------
    cells: dict = {}
    pairs: set = set()
    traffic_dir = f"{base}/traffic"
    if not 1 <= len(bm["workloads"]) <= 24:
        errs.append("workloads must hold 1 to 24 cells")
    for w in bm["workloads"]:
        what = f"cell {w.get('name')}"
        _keys(w, WORKLOAD_KEYS, what, errs)
        _name(w.get("name"), what, errs)
        _name(w.get("traffic"), f"{what}: traffic", errs)
        _line(w.get("why"), f"{what}: why", errs)
        if w.get("chips") not in (1, 4):
            errs.append(f"{what}: chips must be 1 or 4")
        if w.get("name") in cells:
            errs.append(f"{what}: named twice")
        cells[w.get("name")] = w
        if w.get("config") not in configs:
            errs.append(f"{what}: config {w.get('config')!r} is not listed")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            errs.append(f"{what}: the pair {pair} appears twice")
        pairs.add(pair)
        if isinstance(w.get("traffic"), str):
            rel = f"{traffic_dir}/{w['traffic']}.json"
            tr = _read_json(root, rel, errs)
            if tr is not None:
                check_traffic_file(tr, rel, errs, root, base)
                if tr.get("name") != w["traffic"]:
                    errs.append(f"{rel}: name is not {w['traffic']!r}")
                w["_traffic"] = tr
        cfg = configs.get(w.get("config"), {}).get("_data")
        if cfg is not None and cfg.get("chips") != w.get("chips"):
            errs.append(f"{what}: chips {w.get('chips')} but the "
                        f"configuration's layout is for {cfg.get('chips')}")
    four = sum(1 for w in bm["workloads"] if w.get("chips") == 4)
    if four > max(1, len(bm["workloads"]) // 2):
        errs.append("more than half of the cells ask for four chips")
    for c in bm["configs"]:
        if not any(w.get("config") == c.get("name") for w in bm["workloads"]):
            errs.append(f"config {c.get('name')}: used by no cell")

    def cells_reporting(metric: dict) -> set:
        return set(metric.get("workloads", cells))

    for m in bm["end_to_end"]:
        for name in m.get("workloads", []):
            if name not in cells:
                errs.append(f"end-to-end metric {m.get('name')}: cell "
                            f"{name!r} is not listed")
    for name in cells:
        if not any(name in cells_reporting(m) for m in bm["end_to_end"]
                   if m.get("name") != "setup_s"):
            errs.append(f"cell {name}: reports no end-to-end metric besides "
                        f"setup_s")

    # -- per-layer metrics --------------------------------------------------
    layer_dir = f"{base}/layer_metrics"
    names: set = set(e2e)
    covered: set = set()
    if not 1 <= len(bm["per_layer"]) <= 128:
        errs.append("per_layer must hold 1 to 128 metrics")
    for m in bm["per_layer"]:
        what = f"per-layer metric {m.get('name')}"
        _keys(m, LAYER_KEYS, what, errs, optional={"workloads"})
        _metric(m, what, errs)
        _line(m.get("layer"), f"{what}: layer", errs)
        if m.get("source") not in SOURCES:
            errs.append(f"{what}: source must be one of {SOURCES}")
        if m.get("name") in names:
            errs.append(f"{what}: named twice")
        names.add(m.get("name"))
        moved = e2e.get(m.get("moves"))
        if moved is None:
            errs.append(f"{what}: moves {m.get('moves')!r}, which is no "
                        f"end-to-end metric")
            continue
        mine = set(m["workloads"]) if "workloads" in m \
            else cells_reporting(moved)
        for name in mine:
            if name not in cells:
                errs.append(f"{what}: cell {name!r} is not listed")
            elif name not in cells_reporting(moved):
                errs.append(f"{what}: cell {name} does not report "
                            f"{m['moves']}")
        covered |= mine
        if isinstance(m.get("name"), str) and NAME_RE.match(m["name"]):
            rel = f"{layer_dir}/{m['name']}.json"
            lm = _read_json(root, rel, errs)
            if lm is not None:
                check_layer_file(lm, m, rel, errs)
                m["_reader"] = lm.get("reader")
        if (isinstance(m.get("name"), str) and m.get("unit") == "%"
                and ("roofline" in m["name"] or "mfu" in m["name"])
                and m.get("better") != "higher"):
            errs.append(f"{what}: a share of a roofline is better higher")
    for name in cells:
        if name not in covered:
            errs.append(f"cell {name}: reports no per-layer metric")

    if errs:
        raise ManifestError("; ".join(errs))
    bm["_root"] = os.path.abspath(root)
    return bm


def cell(bm: dict, name: str) -> tuple:
    """(cell, configuration data, traffic data) of the cell ``name``."""
    for w in bm["workloads"]:
        if w["name"] == name:
            cfg = next(c for c in bm["configs"] if c["name"] == w["config"])
            return w, cfg["_data"], w["_traffic"]
    raise ManifestError(f"no cell {name!r} in BENCHMARK.json; it has "
                        f"{[w['name'] for w in bm['workloads']]}")


def metrics_of(bm: dict, cell_name: str, group: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics the cell reports."""
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    out = []
    for m in bm[group]:
        if "workloads" in m:
            mine = cell_name in m["workloads"]
        elif group == "per_layer":
            mine = cell_name in e2e[m["moves"]].get("workloads", [cell_name])
        else:
            mine = True
        if mine:
            out.append(m)
    return out


def main(argv=None) -> int:
    root = (argv if argv is not None else sys.argv[1:]) or ["."]
    try:
        bm = check(root[0])
    except ManifestError as e:
        print(f"check_manifest: {e}", file=sys.stderr)
        return 1
    print(f"check_manifest: ok: {len(bm['workloads'])} cells, "
          f"{len(bm['configs'])} configurations, "
          f"{len(bm['end_to_end'])} end-to-end and "
          f"{len(bm['per_layer'])} per-layer metrics")
    return 0


if __name__ == "__main__":
    sys.exit(main())
