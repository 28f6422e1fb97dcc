"""A language-model client through the benchmark with new files only.

The fixture under fixtures/lm/ is what a configuration that brings its own
model adds: a model (models/starcoder2-7b.py: the registry's starcoder2-7b
at `.reduced()` sizes, in float32, token data, a plain reference), its
configuration file, its limits, and entries for BENCHMARK.json. The tests
build a benchmark root of their own, a copy of bench/ and BENCHMARK.json
with those files and entries added and nothing else changed, and run its
cells through the harness on the CPU: a sound run is correct, the planted
faults of the timed path are not, and the control (the reference in
bfloat16 in the program's place) reads apart from sound runs.
"""
import bench_paths
import os

import jax
import pytest

import faults
import harness
import workload

FIXTURE = os.path.join(bench_paths.BENCH, "tests", "fixtures", "lm")
CELLS = ("pfeddst.lm", "dispfl.lm")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_paths.build_root(str(tmp_path_factory.mktemp("lm_root")),
                                  FIXTURE)


def run(cell, seed, hook=None):
    return harness.run(cell, seed=seed, seconds=0.0, trace=False, t0=0.0,
                       devices=jax.devices()[:1], peak=PEAK,
                       program_hook=hook)


def test_model_agrees_with_the_program(root):
    """The fixture's model, found in the test's root, against the
    registry, and its header and weights' layout against the program's."""
    cell = workload.cell("pfeddst.lm", root)
    model = workload.model(cell)
    assert model.__file__.startswith(root)
    bench_paths.check_against_program({**cell["sizes"],
                                       "model": cell["model"]}, model)


def test_model_sizes_feed_the_arithmetic(root):
    """The FLOP count and the kernels' sizes read the fixture's model:
    extractor and header together are every parameter of a client."""
    import flops
    from roofline import gossip_mix, mask_evolve, select_topk

    for name in CELLS:
        cell = workload.cell(name, root)
        model, sizes = workload.model(cell), cell["sizes"]
        shapes = jax.eval_shape(lambda k: model.init_population(
            {**sizes, "num_clients": 1}, k), jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
        assert model.extractor_params(sizes) + model.header_width(sizes) == n
        assert flops.round_flops(sizes, model) > 0
        for mod in (gossip_mix, mask_evolve, select_topk):
            assert all(o > 0 and b > 0 for o, b in mod.calls(sizes, model))


def test_reference_loss_is_the_programs(root):
    """The plain reference's loss of one batch and the program's loss_fn
    on the same weights agree to float32 rounding."""
    from repro.configs import get_config
    from repro.models import model as program

    cell = workload.cell("pfeddst.lm", root)
    model, sizes = workload.model(cell), cell["sizes"]
    cfg = model.program_config(get_config(cell["model"]), sizes)
    w = jax.tree_util.tree_map(lambda x: x[0], model.init_population(
        sizes, jax.random.PRNGKey(3)))
    batch = {"tokens": model.make_train_data(
        sizes, jax.random.PRNGKey(4))["tokens"][0]}
    ours = float(model.example_losses(w, batch, sizes).mean())
    theirs = float(program.loss_fn(cfg, w, batch)[0])
    assert ours == pytest.approx(theirs, rel=1e-5)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(root, name):
    res = run(bench_paths.tiny_cell(name, root), 4_000_000_321)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 2 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["frozen", "answer"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(root, name, fault):
    cell = bench_paths.tiny_cell(name, root)
    with faults.planted(fault, cell["sizes"]["strategy"]):
        res = run(cell, 12345)
    assert not res["correct"]
    assert [k for k, c in res["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_apart_from_sound_runs(root, name):
    """The reference in bfloat16 in the program's place reads round 1's
    loss far from the reference, where the program agrees with it."""
    prog = harness.Program(bench_paths.tiny_cell(name, root))
    state, stacked, data, keys = prog.first_chunk(77)
    ref = prog.reference(data, keys)
    sound = prog.ref.compare(prog.snapshot(state, stacked, keys), ref)
    ctl = prog.ref.compare(
        prog.reference(data, keys, quant=prog.model.control_quant), ref)
    assert ctl["loss1"] > 10 * max(sound["loss1"], 1e-7), (ctl, sound)
    assert ctl["loss1"] > prog.cell["limits"]["loss1"], ctl
