"""The two lookups by name: a mix's arrival loop and a configuration's cluster.

A deployment that is more than other numbers arrives as files: a mix names its
loop (``loop.kind`` -> ``loops/<kind>.py``, a module with ``run_window``) and a
configuration may name its cluster (``cluster`` -> ``clusters/<name>.py``, a
module with a ``Cluster``; without the key, ``benchmark/cluster.py``).  Both
live in the benchmark's first directory of ``paths``, beside the data files,
in the tree the manifest was read from.  A guarded field is refused, by name,
unless the module the file names says that it runs that value:
``IMPLEMENTS = {field: [values]}`` on the module, or ``"any"`` for a field the
module checks itself.
"""

from __future__ import annotations

import importlib.util
import os

from benchmark.cluster import NotImplementedConfig
from benchmark.traffic import NotImplementedTraffic

# one value of each is built by benchmark/cluster.py; a cluster module that
# builds another says so
CONFIG_GUARDED = ("stores", "replicas", "read_mode", "transport",
                  "log_scheme", "kv_store", "engine.backend",
                  "engine.mesh_devices")
TRAFFIC_GUARDED = ("faults",)

_loaded: dict = {}


def _module(bm: dict, kind_dir: str, name: str):
    rel = os.path.join(bm["paths"][0], kind_dir, name + ".py")
    path = os.path.join(bm["_root"], rel)
    if path not in _loaded:
        spec = importlib.util.spec_from_file_location(
            f"benchmark.{kind_dir}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path], rel


def _refuse(data: dict, fields: tuple, mod, rel: str, what: str, exc) -> None:
    for field in fields:
        value = data
        for part in field.split("."):
            value = value[part]
        runs = getattr(mod, "IMPLEMENTS", {}).get(field, [])
        if runs != "any" and value not in runs:
            raise exc(f"{what}: {field}={value!r} is not implemented, only "
                      f"{runs} ({rel}: IMPLEMENTS)")


def loop_of(bm: dict, mix: dict):
    """The module whose ``run_window`` sends the mix ``mix``."""
    mod, rel = _module(bm, "loops", mix["loop"]["kind"])
    _refuse(mix, TRAFFIC_GUARDED, mod, rel, f"traffic {mix['name']}",
            NotImplementedTraffic)
    return mod


def cluster_of(bm: dict, cfg: dict):
    """The ``Cluster`` class that builds the configuration ``cfg``."""
    if "cluster" in cfg:
        mod, rel = _module(bm, "clusters", cfg["cluster"])
    else:
        from benchmark import cluster as mod
        rel = os.path.join(bm["paths"][0], "cluster.py")
    _refuse(cfg, CONFIG_GUARDED, mod, rel, f"config {cfg['name']}",
            NotImplementedConfig)
    return mod.Cluster
