"""Weights that every client shares: a model may bring a frozen trunk, held
once with no client axis (`init_shared`), under what each client trains.

The fixture under fixtures/lm-adapters/ is such a model: the LM fixture's
decoder (starcoder2-7b at `.reduced()` sizes, float32) as the shared
trunk, rank-4 adapters on each attention output projection and the header
per client. The tests build a benchmark root of their own with it, as
test_bench_lm.py does, and check that the weights reach both references
and the comparison, that today's program, which has no place for them,
stops at set-up, and that a model without them runs as it always did.
"""
import bench_paths
import functools
import os
import types
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops
import harness
import population
import workload
from reference import common as C
from reference import dispfl, pfeddst

FIXTURES = os.path.join(bench_paths.BENCH, "tests", "fixtures")
CELLS = ("pfeddst.adapters", "dispfl.adapters")
REFERENCE = {"pfeddst": pfeddst, "dispfl": dispfl}
SEED = 4_000_000_777


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_paths.build_root(
        str(tmp_path_factory.mktemp("adapters_root")),
        os.path.join(FIXTURES, "lm-adapters"))


@pytest.fixture(scope="module")
def lm_root(tmp_path_factory):
    return bench_paths.build_root(str(tmp_path_factory.mktemp("lm_root")),
                                  os.path.join(FIXTURES, "lm"))


def weights(cell, seed=SEED):
    """The cell's model, the seed's keys, and weights_fn's weights."""
    model = workload.model(cell)
    keys = population.seed_keys(seed)
    w = harness.weights_fn(cell["sizes"], model)(keys["weights"],
                                                 keys["masks"])
    return model, keys, w


def shapes(tree):
    return jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)


@pytest.mark.parametrize("which", ["adapters", "resnet", "lm"])
def test_weights_fn_gives_shared_only_where_the_model_has_them(
        which, root, lm_root):
    name, at = {"adapters": ("pfeddst.adapters", root),
                "resnet": ("pfeddst.m16", bench_paths.ROOT),
                "lm": ("pfeddst.lm", lm_root)}[which]
    cell = bench_paths.tiny_cell(name, at)
    model, _, w = weights(cell)
    assert set(w) == {"params", "masks", "shared"}
    if which != "adapters":
        assert w["shared"] is None
        return
    sizes = cell["sizes"]
    want = jax.tree_util.tree_map(
        lambda s: (s, jnp.dtype(sizes["dtype"])), model.shared_shapes(sizes),
        is_leaf=lambda s: isinstance(s, tuple))
    assert shapes(w["shared"]) == want          # one trunk, no client axis
    for x in jax.tree_util.tree_leaves(w["params"]):
        assert x.shape[0] == sizes["num_clients"]


# the program's pfeddst state with a place for the shared weights, as a
# program that holds them would have it
class HoldingState(NamedTuple):
    extractor: Any
    header: Any
    opt_e: Any
    opt_h: Any
    loss_matrix: Any
    last_selected: Any
    round: Any
    store: Any = None
    shared: Any = None


def holding(strategy: str, model, w):
    """A stand-in strategy whose initial state has a place `shared`, as a
    program that holds shared weights would have it, its optimizer state
    over what the clients train."""
    m = jax.tree_util.tree_leaves(w["params"])[0].shape[0]

    def zeros(t):
        return jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, C.F32), t)

    def init(key):
        if strategy == "dispfl":
            return {"params": w["params"], "opt": {"mu": zeros(w["params"])},
                    "round": jnp.zeros((), jnp.int32), "shared": None}
        e, h = C.split(w["params"], model.HEADER)
        return HoldingState(e, h, {"mu": zeros(e)}, {"mu": zeros(h)},
                            jnp.zeros((m, m), C.F32),
                            jnp.full((m, m), -1, jnp.int32),
                            jnp.zeros((), jnp.int32))

    return types.SimpleNamespace(init=init)


def shared_of(state):
    return state["shared"] if isinstance(state, dict) else state.shared


@pytest.mark.parametrize("name", CELLS)
def test_program_state_holds_shared_or_says_it_cannot(root, name):
    cell = bench_paths.tiny_cell(name, root)
    strategy = cell["sizes"]["strategy"]
    model, keys, w = weights(cell)
    _, _, strat = harness.build(cell["sizes"], cell["model"], model)
    ref = REFERENCE[strategy]
    state = ref.program_state(holding(strategy, model, w), keys["weights"],
                              w, model=model)
    assert shared_of(state) is w["shared"]
    with pytest.raises(ValueError, match=C.NO_SHARED):
        ref.program_state(strat, keys["weights"], w, model=model)


@pytest.mark.parametrize("name", CELLS)
def test_program_without_a_place_stops_at_setup(root, name, monkeypatch):
    """harness.run raises the one set-up error before the compile counter
    is made and before the chunk program is built."""
    from repro.fl import engine

    reached = []
    monkeypatch.setattr(harness, "CompileCounter",
                        lambda: reached.append("counter"))
    monkeypatch.setattr(engine, "make_multi_round",
                        lambda *a, **k: reached.append("chunk"))
    with pytest.raises(ValueError, match=C.NO_SHARED):
        harness.run(bench_paths.tiny_cell(name, root), seed=SEED,
                    seconds=0.0, trace=False, t0=0.0,
                    devices=jax.devices()[:1], peak={})
    assert reached == []


@pytest.mark.parametrize("name", CELLS + ("pfeddst.lm", "dispfl.lm"))
def test_references_run_with_shared_weights(root, lm_root, name):
    """Both references run a chunk over the shared trunk: the clients'
    weights move, and a reading compared with itself reads 0 on every
    number. The reference never updates the trunk, so shared_changed is
    the program's alone (test_shared_changed_reads_one_ulp)."""
    cell = bench_paths.tiny_cell(name, root if name in CELLS else lm_root)
    sizes = cell["sizes"]
    model, keys, _ = weights(cell)
    data = model.make_train_data(sizes, keys["data"])
    init = functools.partial(harness.weights_fn(sizes, model),
                             keys["weights"], keys["masks"])
    ref_mod = REFERENCE[sizes["strategy"]]
    ref = ref_mod.run_reference(sizes, data, init, keys["rounds"],
                                sizes["chunk_rounds"], model)
    numbers = ref_mod.compare(ref, ref)
    assert np.max(ref["change"]) > 0
    assert all(v == 0 for v in numbers.values()), numbers
    assert "shared_changed" not in numbers


def stacked_of(strategy: str, rounds: int, m: int) -> dict:
    """A chunk's stacked metrics, as the snapshot reads them."""
    z, sel = np.zeros(rounds), np.zeros((rounds, m, m), bool)
    if strategy == "pfeddst":
        return {"train_loss_e": z, "train_loss_h": z, "select_mask": sel,
                "s_l_mean": z}
    return {"train_loss": z, "comm_edges": sel}


@pytest.mark.parametrize("name", CELLS)
def test_shared_changed_reads_one_ulp(root, name):
    cell = bench_paths.tiny_cell(name, root)
    sizes = cell["sizes"]
    model, keys, w = weights(cell)
    ref = REFERENCE[sizes["strategy"]]
    state = ref.program_state(holding(sizes["strategy"], model, w),
                              keys["weights"], w, model=model)
    stacked = stacked_of(sizes["strategy"], sizes["chunk_rounds"],
                         sizes["num_clients"])
    w0 = weights(cell)[2]

    def snap(st):
        return ref.snapshot_program(st, stacked, w0["params"], model,
                                    w0["shared"])

    sound = snap(state)
    assert sound["shared_changed"] == 0.0
    embed = w["shared"]["embed"]
    moved = {**w["shared"],
             "embed": embed.at[0, 0].set(jnp.nextafter(embed[0, 0], jnp.inf))}
    if isinstance(state, dict):
        state = {**state, "shared": moved}
    else:
        state = state._replace(shared=moved)
    bad = snap(state)
    assert bad["shared_changed"] > 0
    assert ref.compare(sound, sound)["shared_changed"] == 0.0
    assert ref.compare(bad, sound)["shared_changed"] > 0


def test_zero_adapters_probe_is_the_plain_forward(root):
    """With B = 0 the reference's Eq. 6 probe losses over the trunk, the
    adapters and each header are the LM fixture's plain forward of the
    trunk and that header, to the bit."""
    cell = bench_paths.tiny_cell("pfeddst.adapters", root)
    sizes = cell["sizes"]
    model, keys, w = weights(cell)
    plain = workload.load_module("models", "starcoder2-7b",
                                 os.path.join(FIXTURES, "lm"))
    assert all(not np.any(b) for b in w["params"]["adapters"]["b"])
    data = model.make_train_data(sizes, keys["data"])
    probe = functools.partial(C.probe_losses, data=data, key=keys["rounds"],
                              m=sizes["num_clients"],
                              probe=sizes["probe_size"])
    ours = probe(w["params"], losses=C.bind_losses(model, sizes, None,
                                                   w["shared"]))
    _, header = C.split(w["params"], model.HEADER)
    theirs = probe(header, losses=lambda h, batch: plain.example_losses(
        {**w["shared"], **h}, batch, sizes))
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


@pytest.mark.parametrize("name", CELLS)
def test_adapter_sizes_feed_the_arithmetic(root, name):
    """A client's extractor and header are every weight it trains; the
    trunk counts in the FLOPs only, and training passes back through it."""
    from roofline import gossip_mix, mask_evolve, select_topk

    cell = bench_paths.tiny_cell(name, root)
    model, sizes = workload.model(cell), cell["sizes"]
    _, _, w = weights(cell)
    per_client = sum(x[0].size for x in jax.tree_util.tree_leaves(
        w["params"]))
    assert model.extractor_params(sizes) + model.header_width(sizes) \
        == per_client
    f = model.flops(sizes)
    assert f["forward"] < f["train_extractor"] < f["train_full"]
    assert f["train_header"] < f["train_full"]
    assert flops.round_flops(sizes, model) > 0
    for mod in (gossip_mix, mask_evolve, select_topk):
        assert all(o > 0 and b > 0 for o, b in mod.calls(sizes, model))


# seed_keys of two seeds above 2**31, as the parent of the shared-weights
# path made them: the four keys' uint32 words in the order data, weights,
# masks, rounds
PINNED_KEYS = {
    4_000_000_777: [1140298070, 2553262123, 4051591991, 1279078236,
                    3959413467, 185129689, 2333208745, 3770184422],
    2 ** 31 + 12345: [4117562646, 2742661353, 1197279882, 83085705,
                      596321886, 3212391585, 786486663, 3469031769],
}


@pytest.mark.parametrize("seed", sorted(PINNED_KEYS))
def test_shared_path_leaves_keys_and_weights_alone(seed):
    """seed_keys is as it was, and a model that brings shared weights gets
    per-client weights and masks bitwise equal to the same model without
    them: the shared key is folded from the weights' key, not split."""
    keys = population.seed_keys(seed)
    words = np.concatenate([np.asarray(keys[n]).ravel()
                            for n in ("data", "weights", "masks", "rounds")])
    assert words.tolist() == PINNED_KEYS[seed]
    cell = bench_paths.tiny_cell("dispfl-ring.m16")
    model = workload.model(cell)
    trunk = types.SimpleNamespace(
        **vars(model), shared_shapes=lambda sizes: {"w": (3,)},
        init_shared=lambda sizes, key: {"w": jax.random.normal(key, (3,))})
    plain = harness.weights_fn(cell["sizes"], model)(keys["weights"],
                                                     keys["masks"])
    both = harness.weights_fn(cell["sizes"], trunk)(keys["weights"],
                                                    keys["masks"])
    assert plain["shared"] is None
    np.testing.assert_array_equal(
        both["shared"]["w"], jax.random.normal(
            population.shared_key(keys["weights"]), (3,)))
    for a, b in zip(jax.tree_util.tree_leaves((plain["params"],
                                               plain["masks"])),
                    jax.tree_util.tree_leaves((both["params"],
                                               both["masks"]))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not any(np.array_equal(population.shared_key(keys["weights"]),
                                  keys[n]) for n in keys)
