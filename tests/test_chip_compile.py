"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a `v5e:2x2` topology
that is described, not attached. Mosaic refuses here what interpret
mode accepts (block shapes off the (8, 128) tiling, VMEM overruns), so
these compiles guard every kernel of the round at its real width:

  * select_topk — the fused Eq. 7–9 selection at the ResNet-18 header
    width (P = 5130), for the M = 16 smoke population and the M = 100
    paper default, with a cost matrix and a candidate mask;
  * raw_gram    — the blocked Eq. 7 Gram at M = 100;
  * gossip_mix  — the packed neighbor mix over the full ResNet-18
    extractor (F = 11,167,040) at ring (D = 3) and torus (D = 5) degree;
  * mask_evolve — DisPFL's mask evolution on a (3, 3, 256, 256) conv leaf.

Each asserts that the kernel is really in the program (`tpu_custom_call`)
and that the compiled program fits one chip's 16 GiB. One more compile
guards the layout of the Eq. 6 probe row, which has no kernel: its bytes
accessed and temp, by the compiler's count, at the paper's M = 100.
"""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.scoring import loss_disparity_row
from repro.kernels.gossip_mix import gossip_mix
from repro.kernels.mask_evolve import mask_evolve
from repro.kernels.peer_score import raw_gram
from repro.kernels.select_score import select_topk
from repro.models import model as model_mod
from repro.models.split import split_params

HBM_BYTES = 16 * 2 ** 30   # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler would otherwise write its logs under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def compile_for_chip(one_chip, no_persistent_cache):
    def run(fn, *args):
        shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                  for a in args]
        compiled = jax.jit(fn).lower(*shapes).compile()
        assert "tpu_custom_call" in compiled.as_text()
        ma = compiled.memory_analysis()
        used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        assert 0 < used < HBM_BYTES
        return compiled

    return run


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def resnet18_extractor_width() -> int:
    cfg = get_config("resnet18-cifar")
    params = jax.eval_shape(lambda k: model_mod.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    extractor, _ = split_params(cfg, params)
    return sum(math.prod(leaf.shape)
               for leaf in jax.tree_util.tree_leaves(extractor))


@pytest.mark.parametrize("m,p,k", [(16, 5130, 4), (100, 5130, 10)])
def test_select_topk_compiles(compile_for_chip, m, p, k):
    def fn(x, last, s_l, t, cost, cand):
        return select_topk(x, last, s_l, t, cost, cand, k=k, alpha=1.0,
                           lam=0.5)

    compile_for_chip(
        fn, _sds((m, p), jnp.float32), _sds((m, m), jnp.int32),
        _sds((m, m), jnp.float32), _sds((), jnp.int32),
        _sds((m, m), jnp.float32), _sds((m, m), jnp.bool_),
    )


def test_raw_gram_compiles(compile_for_chip):
    compile_for_chip(raw_gram, _sds((100, 5130), jnp.float32))


@pytest.mark.parametrize("d", [3, 5])
def test_gossip_mix_compiles_at_resnet18_width(compile_for_chip, d):
    f = resnet18_extractor_width()
    assert f == 11_167_040
    compile_for_chip(gossip_mix, _sds((16, f), jnp.float32),
                     _sds((16, d), jnp.int32), _sds((16, d), jnp.float32))


def test_mask_evolve_compiles(compile_for_chip):
    shape = (3, 3, 256, 256)
    n = math.prod(shape)

    def fn(x, grow):
        return mask_evolve(x, grow, keep=n // 2)

    compile_for_chip(fn, _sds(shape, jnp.float32), _sds(shape, jnp.bool_))


def test_gossip_mix_rows_split_over_four_chips(topo, no_persistent_cache):
    """Under a 4-chip mesh the mix writes each chip's own client rows:
    the per-chip output is a quarter of the (M, F) population. (F is the
    largest ResNet-18 conv leaf: at the whole extractor's width the
    partitioned compile takes over a minute.)"""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops

    mesh = Mesh(np.array(topo.devices), ("data",))
    m, d, f = 16, 3, 3 * 3 * 256 * 256
    rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def fn(x, idx, w):
        return ops.gossip_mix(x, idx, w, impl="pallas", interpret=False)

    with jax.set_mesh(mesh):
        compiled = jax.jit(fn).lower(
            jax.ShapeDtypeStruct((m, f), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((m, d), jnp.int32, sharding=whole),
            jax.ShapeDtypeStruct((m, d), jnp.float32, sharding=whole),
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    # a quarter of the rows, F padded to whole 128-lane tiles
    assert ma.output_size_in_bytes == pytest.approx(m // 4 * f * 4,
                                                    rel=1e-3)
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES


def test_probe_row_compiles_as_one_flat_batch(one_chip, no_persistent_cache):
    """One Eq. 6 probe row at the paper's population: full-width
    ResNet-18 in bf16 on 100 clients' 32-image probes. Vmapped over the
    M probe batches, every activation carried the client axis and each
    conv and group norm copied around it: 306.7 GB accessed and 7.55 GiB
    temp. As one plain batch of 3200 images: 64.5 GB and 2.54 GiB."""
    cfg = get_config("resnet18-cifar")
    m, b = 100, 32

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.eval_shape(lambda k: model_mod.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    probe = {"images": _sds((m, b, cfg.image_size, cfg.image_size,
                             cfg.image_channels), jnp.float32),
             "labels": _sds((m, b), jnp.int32)}
    compiled = jax.jit(lambda p, x: loss_disparity_row(cfg, p, x)).lower(
        jax.tree_util.tree_map(on_chip, params),
        jax.tree_util.tree_map(on_chip, probe),
    ).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["bytes accessed"] < 120e9
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30
