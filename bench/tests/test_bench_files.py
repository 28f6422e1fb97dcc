"""BENCHMARK.json against the benchmark's contract, and every file that a
cell needs found by its name."""
import bench_paths  # noqa: F401  (bench/ and src/ on the path)
import json
import os
import re

import pytest

import workload

BM = workload.benchmark()
NAME = workload.NAME
UNIT = workload.UNIT
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head|width|"
                   r"expansion|experts_per_tok|_dim$|_rank$)")


def test_top_level_keys():
    assert set(BM) == TOP
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert isinstance(BM["run_seconds"], int) and 1 <= BM["run_seconds"] <= 51


@pytest.mark.parametrize("section,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entries_have_the_contracts_keys(section, keys):
    for e in BM[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") \
            else set()
        assert keys <= set(e) <= keys | extra, e


def test_names_units_and_lines():
    names = [e["name"] for s in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BM[s]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for s in ("end_to_end", "per_layer"):
        for m in BM[s]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    for e in BM["configs"] + BM["workloads"] + BM["per_layer"]:
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k], e


def test_bounds():
    names = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in names
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("w", [w["name"] for w in BM["workloads"]])
def test_workload_resolves(w):
    cell = workload.cell(w)
    assert cell["chips"] in (1, 4)
    assert cell["per_layer"] and len(cell["end_to_end"]) >= 2
    for m in cell["per_layer"]:
        mod = workload.load_module("metrics", m["name"])
        assert callable(mod.read)
        assert m["moves"] in {e["name"] for e in BM["end_to_end"]}
    assert {"rounds", "window_compiles"} <= set(cell["limits"])
    assert all(v >= 0 for v in cell["limits"].values())
    assert os.path.exists(os.path.join(workload.HERE, "reference",
                                       f"{cell['sizes']['strategy']}.py"))


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_files(c):
    path = os.path.join(workload.ROOT, c["file"])
    assert path.startswith(os.path.join(workload.ROOT, "bench") + os.sep)
    with open(path) as fh:
        sizes = json.load(fh)
    assert sizes["name"] == c["name"] and sizes["source"] == c["source"]
    assert sizes["reduced"] == c["reduced"]
    for k in c["reduced"]:
        assert k in sizes and NAME.match(k) and not WIDTH.search(k), k
    from repro.configs import get_config

    # the file's widths and dtype against the registry, as its model
    # (bench/models/<model>.py) reads them; raises on a mismatch
    model = workload.load_module("models", sizes["model"])
    model.check_config(sizes, get_config(sizes["model"]))


def test_every_config_and_traffic_is_used():
    used = {w["config"] for w in BM["workloads"]}
    assert used == {c["name"] for c in BM["configs"]}
    for w in BM["workloads"]:
        assert os.path.exists(os.path.join(workload.HERE, "traffic",
                                           f"{w['traffic']}.json"))
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_per_layer_workloads_exist():
    cells = {w["name"] for w in BM["workloads"]}
    for m in BM["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
