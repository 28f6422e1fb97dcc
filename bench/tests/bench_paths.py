"""Imported first by the benchmark's tests: puts bench/ and src/ on the
path, and makes the tiny cells the CPU can run."""
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(name: str, root: str = ROOT) -> dict:
    """Cell `name` of `root`'s BENCHMARK.json at its model's tiny size
    (the model's `TINY`)."""
    import workload

    cell = workload.cell(name, root)
    cell["sizes"].update(workload.model(cell).TINY)
    return cell


def build_root(dest: str, fixture: str) -> str:
    """A benchmark root at `dest`: bench/ and BENCHMARK.json as they are,
    plus the files (models/, configs/, limits/) and the BENCHMARK.json
    entries (entries.json) of the fixture directory `fixture`."""
    import workload

    bench = os.path.join(dest, "bench")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for kind in ("models", "configs", "limits"):
        for f in os.listdir(os.path.join(fixture, kind)):
            assert not os.path.exists(os.path.join(bench, kind, f)), f
            shutil.copy(os.path.join(fixture, kind, f),
                        os.path.join(bench, kind, f))
    bm = workload.benchmark()
    with open(os.path.join(fixture, "entries.json")) as fh:
        entries = json.load(fh)
    for section, added in entries.items():
        bm[section] += added
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as fh:
        json.dump(bm, fh)
    return dest


def check_against_program(sizes: dict, model) -> None:
    """A model's file against the registry (`check_config`), its HEADER
    against what the program's split keeps per client, and one client's
    weights as the model makes them against the layout, shapes and dtype
    of the program's own init."""
    import jax

    from repro.configs import get_config
    from repro.models import model as program
    from repro.models import split

    base = get_config(sizes["model"])
    model.check_config(sizes, base)
    cfg = model.program_config(base, sizes)
    assert tuple(model.HEADER) == tuple(split.header_keys(cfg))
    assert set(model.HEADER) <= set(model.param_shapes(sizes))
    theirs = jax.eval_shape(lambda k: program.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    ours = jax.eval_shape(lambda k: model.init_population(
        {**sizes, "num_clients": 1}, k), jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(ours) \
        == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        assert (a.shape[1:], a.dtype) == (b.shape, b.dtype)
