"""Resolve a cell of BENCHMARK.json into the files that define it.

Everything that belongs to one configuration, traffic mix, metric or
strategy sits in a file of its own, found by name:

    bench/configs/<config>.json       sizes of the model and population
    bench/models/<model>.py           the model: its program config, data,
                                      weights, header, reference loss,
                                      FLOPs (the configuration's `model`)
    bench/traffic/<traffic>.json      strategy, topology, chunk size
    bench/limits/<workload>.json      limits of the correctness check
    bench/metrics/<metric>.py         reader of one per-layer metric
    bench/reference/<strategy>.py     plain reference of one strategy

so a later change adds a cell, or a model, by adding files and entries
only. A benchmark root is a directory that holds BENCHMARK.json and
these files under bench/.

A model may also bring weights that every client shares and none
trains, such as a frozen trunk under per-client adapters: its
`init_shared(sizes, key)` makes them, one tree with no client axis in
the configuration's dtype (`shared_shapes(sizes)` gives its layout), and
its `example_losses(params, batch, sizes, quant, shared=...)` reads them,
each operation at float32. The per-client tree (`init_population`,
`param_shapes`) is then what a client trains, and `extractor_params` and
`header_width` count one client's share. `flops(sizes)` is unchanged in
form: its `train_extractor` and `train_full` count the backward pass
through the frozen weights down to the lowest trained parameter. A model
without `init_shared` has no shared weights.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# file keys that describe a configuration rather than size it
META = ("name", "source", "model", "reduced", "assumed")


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def cell(name: str, root: str = ROOT) -> dict:
    """Everything one run of cell `name` needs: the merged sizes
    (configuration, then traffic), its metrics by kind, its limits, and
    the bench/ directory of `root` that holds its files."""
    bm = benchmark(root)
    bench = os.path.join(root, "bench")
    wl = {w["name"]: w for w in bm["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    conf = next(c for c in bm["configs"] if c["name"] == w["config"])
    sizes = read_json(os.path.join(root, conf["file"]))
    traffic = read_json(os.path.join(bench, "traffic",
                                     f"{w['traffic']}.json"))

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "name": name, "chips": w["chips"], "model": sizes["model"],
        "bench": bench,
        "sizes": {**{k: v for k, v in sizes.items() if k not in META},
                  **traffic},
        "end_to_end": [m for m in bm["end_to_end"] if applies(m)],
        "per_layer": [m for m in bm["per_layer"] if applies(m)],
        "limits": read_json(os.path.join(bench, "limits", f"{name}.json")),
    }


def model(c: dict):
    """The cell's model: bench/models/<model>.py of its root."""
    return load_module("models", c["model"], c["bench"])


def has_shared(model) -> bool:
    """Whether the model brings weights that every client shares."""
    return hasattr(model, "init_shared")


_LOADED = {}


def load_module(kind: str, name: str, bench: str = HERE):
    """<bench>/<kind>/<name>.py as a module (a model, a per-layer metric
    reader), loaded once a process, as an import is."""
    path = os.path.join(bench, kind, f"{name}.py")
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]
