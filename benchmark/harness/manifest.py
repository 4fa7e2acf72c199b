"""Where everything is: ``BENCHMARK.json`` and the data files it names.

No Python file lists cells, configurations, mixes, metrics or
architectures. A cell's entry in ``BENCHMARK.json`` names its
configuration and its traffic mix; this module finds the configuration's
``file``, ``traffic/<traffic>.json`` and ``layer_metrics/<metric>.json``
under the benchmark's own directory by those names, and four kinds of
code by a name in a data file: a driver by a mix's ``kind``, a reader by
a per-layer metric's ``reader``, and an architecture (its plain
reference, its counts, the serve check's calls into the program, its
kernels' work) by a configuration's ``architecture``. So a later PR adds
a cell, a configuration, a mix, a per-layer metric or an architecture by
adding files and appending entries, and edits no file that is there.
``BENCHMARK.json`` alone says which cells report a metric.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    """``BENCHMARK.json`` with its files resolved. ``root`` is the
    checkout; a test builds one in a temporary directory."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.doc["paths"][0])

    def path(self, kind: str, name: str) -> str:
        return os.path.join(self.bench_dir, kind, name + ".json")

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(it has {[w['name'] for w in self.doc['workloads']]})")

    def config(self, name: str) -> dict:
        entry = next(c for c in self.doc["configs"] if c["name"] == name)
        return _load(os.path.join(self.root, entry["file"]))

    def traffic(self, name: str) -> dict:
        return _load(self.path("traffic", name))

    def metrics_of(self, cell: str, group: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports:
        those with no ``workloads`` key, and those that list it."""
        return [m for m in self.doc[group]
                if cell in m.get("workloads", [cell])]

    def layer_metric(self, name: str) -> dict:
        return _load(self.path("layer_metrics", name))

    def architecture(self, conf: dict):
        """The module a configuration's file names: no default."""
        return plugin("architectures", conf["architecture"], self.bench_dir)


def plugin(package: str, name: str, bench_dir: str = BENCH_DIR):
    """``benchmark.<package>.<name>``: drivers by a mix's ``kind``,
    readers by a per-layer metric's ``reader``, architectures by a
    configuration's ``architecture``. A new kind is a new file. A
    manifest elsewhere than this checkout (a test's copy) may bring a
    file of its own, which is found before this checkout's."""
    full = f"benchmark.{package}.{name}"
    own = os.path.join(bench_dir, package, name + ".py")
    if full not in sys.modules and os.path.isfile(own) \
            and os.path.abspath(bench_dir) != BENCH_DIR:
        spec = importlib.util.spec_from_file_location(full, own)
        sys.modules[full] = module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    return importlib.import_module(full)


def seeded_params(family, cfg, seed: int):
    """Every weight made on the device by one jitted call from the seed,
    in the type the configuration runs in."""
    import jax

    key = jax.random.PRNGKey(seed % (2 ** 31))
    params = jax.jit(lambda k: family.init_params(cfg, k))(key)
    return jax.block_until_ready(params)


def build_config(conf: dict, block: str):
    """The program's config object from a configuration file: the class
    named in ``build``, each of its fields taken from the file's own
    top-level key (the source's key names), then the block's ``set``."""
    import jax.numpy as jnp

    b = conf["build"]
    cls = getattr(importlib.import_module(b["module"]), b["config_class"])
    kw = {field: conf[key] for field, key in b["fields"].items()}
    kw.update(b.get("derived", {}))
    kw.update(conf[block].get("set", {}))
    for k in ("dtype",):
        if isinstance(kw.get(k), str):
            kw[k] = getattr(jnp, kw[k])
    return importlib.import_module(b["module"]), cls(**kw)
