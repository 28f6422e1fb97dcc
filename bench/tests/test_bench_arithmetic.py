"""The benchmark's FLOP and roofline arithmetic on known shapes, through
the ResNet-18 model's module (bench/models/resnet18-cifar.py)."""
import bench_paths
import math

import jax
import jax.numpy as jnp
import pytest

import flops
import workload
from roofline import gossip_mix, least_seconds, mask_evolve, select_topk

RESNET18 = dict(image_size=32, image_channels=3, cnn_width=64,
                cnn_stages=[2, 2, 2, 2], num_classes=10)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MODEL = workload.load_module("models", "resnet18-cifar")


def fwd_of(cfg):
    return MODEL.flops(cfg)["forward"]


def test_forward_against_xla_cost_analysis():
    """XLA counts 0.986 GFLOP for one 32x32 image, element-wise work
    included; the products alone are 0.964 GFLOP."""
    from repro.configs import get_config
    from repro.models import model

    cfg = get_config("resnet18-cifar")
    params = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
    ca = jax.jit(lambda p, x: model.forward(cfg, p, {"images": x})[0]) \
        .lower(params, x).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    ours = fwd_of(RESNET18)
    assert ours == pytest.approx(963_718_656)
    assert 0.95 * ca["flops"] < ours <= ca["flops"]


def test_round_by_hand():
    """pfeddst.m16: 4 clients x 10 steps x 128 images of phase e, 4 x 2 x
    128 of phase h, 4 x 16 x 16 probe forwards."""
    s = {**workload.cell("pfeddst.m16")["sizes"]}
    fwd = fwd_of(RESNET18)
    head = 2 * 512 * 10
    # valid taps of the 3x3 stem over 32x32: 30 interior rows of 3 taps
    # and 2 border rows of 2, squared
    assert dict(MODEL.layers(RESNET18))["stem"] == (30 * 3 + 2 * 2) ** 2 \
        * 3 * 64
    parts = flops.per_round(s, MODEL)
    assert parts["phase_e"] == pytest.approx(4 * 10 * 128
                                             * (3 * fwd - 2 * MODEL.layers(
                                                 RESNET18)[0][1] - head))
    assert parts["phase_h"] == pytest.approx(4 * 2 * 128 * (fwd + head))
    assert parts["probe"] == pytest.approx(4 * 16 * 16 * fwd)
    assert flops.round_flops(s, MODEL) / 1e12 == pytest.approx(16.76,
                                                               abs=0.01)


def test_dispfl_round_by_hand():
    s = {**workload.cell("dispfl-ring.m16")["sizes"]}
    fwd = fwd_of(RESNET18)
    assert flops.round_flops(s, MODEL) == pytest.approx(
        4 * 10 * 128 * (3 * fwd - 2 * MODEL.layers(RESNET18)[0][1]))


def test_gossip_mix_shapes():
    s = workload.cell("dispfl-ring.m16")["sizes"]
    assert MODEL.extractor_params(s) == 11_167_040
    (ops, nbytes), = gossip_mix.calls(s, MODEL)
    assert ops == 2 * 16 * 3 * 11_167_040
    assert nbytes == 8 * 16 * 11_167_040 + 8 * 16 * 3
    # bound by bandwidth: 1.43 GB at 819 GB/s
    assert least_seconds([(ops, nbytes)], PEAK) == pytest.approx(
        nbytes / 819e9)


def test_select_topk_shapes():
    s = workload.cell("pfeddst.m100")["sizes"]
    assert MODEL.header_width(s) == 5130
    (ops, nbytes), = select_topk.calls(s, MODEL)
    assert ops == 2 * 100 * 100 * 5130 + 10 * 100 * 100
    assert nbytes == 4 * 100 * 5130 + 13 * 100 * 100 + 8 * 100 * 10 + 800


def test_mask_evolve_shapes():
    s = workload.cell("dispfl-ring.m16")["sizes"]
    calls = mask_evolve.calls(s, MODEL)
    n = sum(nb for _, nb in calls) / 6
    assert n == 16 * (11_167_040 + 5130)
    assert len(calls) == 56
    assert math.isclose(least_seconds(calls, PEAK), 6 * n / 819e9)


# each kernel's calls, (count, operations, bytes) summed over a round's
# calls, and the round's FLOPs by part, as the arithmetic computed them
# before the model's sizes moved into bench/models/
PINNED = {
    "pfeddst.m16": {
        "gossip_mix": (1, 1072035840.0, 1429381504.0),
        "mask_evolve": (56, 715018880.0, 1072528320.0),
        "select_topk": (1, 2629120.0, 332288.0),
        "flops": {"phase_e": 14785293844480.0, "phase_h": 986858389504.0,
                  "probe": 986847903744.0}},
    "pfeddst.m100": {
        "gossip_mix": (1, 6700224000.0, 8933634400.0),
        "mask_evolve": (56, 4468868000.0, 6703302000.0),
        "select_topk": (1, 102700000.0, 2190800.0),
        "flops": {"phase_e": 73926469222400.0, "phase_h": 4934291947520.0,
                  "probe": 30838996992000.0}},
    "dispfl-ring.m16": {
        "gossip_mix": (1, 1072035840.0, 1429381504.0),
        "mask_evolve": (56, 715018880.0, 1072528320.0),
        "select_topk": (1, 2629120.0, 332288.0),
        "flops": {"local_train": 14785346273280.0}},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_arithmetic_pinned(name):
    cell = workload.cell(name)
    s, model = cell["sizes"], workload.model(cell)
    for mod in (gossip_mix, mask_evolve, select_topk):
        calls = mod.calls(s, model)
        got = (len(calls), sum(o for o, _ in calls), sum(b for _, b in calls))
        assert got == PINNED[name][mod.__name__.split(".")[-1]], mod
    assert flops.per_round(s, model) == PINNED[name]["flops"]
