"""Inputs and initial weights of a cell, made on the device from the seed.

The benchmark makes what both the program and the reference start from:

* the clients' training images, a copy of the program's synthetic CIFAR
  generator (`repro.data.synthetic.client_datasets_cifar`: class
  prototypes plus Gaussian noise, the paper's pathological partition into
  single-class shards), generated client by client on the device in one
  jitted call, training split only;
* every client's ResNet-18 weights, He-normal convolutions, unit
  GroupNorm scales, a 0.01-normal header, in the configuration's dtype;
* DisPFL's initial personal masks.

Nothing here imports the program: the reference starts from exactly these
arrays, and the program's state is built around them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int):
    """A key from any non-negative seed below 2**64 (the driver's seeds
    pass 32 bits)."""
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def seed_keys(seed: int) -> dict:
    """The named keys of one run: data, weights, masks, rounds."""
    ks = jax.random.split(base_key(seed), 4)
    return dict(zip(("data", "weights", "masks", "rounds"), ks))


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def shard_layout(cfg: dict) -> dict:
    """Sizes of the pathological partition: every client holds
    `classes_per_client` single-class shards of `shard_size` samples,
    of which the first `test` go to its test split."""
    m, cpc = cfg["num_clients"], cfg["classes_per_client"]
    n_cls, spc = cfg["num_classes"], cfg["samples_per_class"]
    n_shards = m * cpc
    base, extra = divmod(n_shards, n_cls)
    per_class = [base + (1 if c < extra else 0) for c in range(n_cls)]
    shard_size = min(spc // s for s in per_class if s > 0)
    n_test = max(1, int(shard_size * cfg["test_frac"]))
    shard_class = np.repeat(np.arange(n_cls), per_class)
    return {"shard_size": shard_size, "test": n_test,
            "train": shard_size - n_test, "shard_class": shard_class}


def _prototypes(key, n_cls: int, size: int, ch: int, bands: int = 4):
    coeff = jax.random.normal(key, (n_cls, bands, bands, ch))
    xs = np.linspace(0, np.pi, size)
    basis = jnp.asarray(np.stack([np.cos(b * xs) for b in range(bands)]),
                        jnp.float32)
    proto = jnp.einsum("kabc,ah,bw->khwc", coeff, basis, basis,
                       precision=jax.lax.Precision.HIGHEST)
    rms = jnp.sqrt(jnp.mean(jnp.square(proto), axis=(1, 2, 3),
                            keepdims=True))
    return proto / (rms + 1e-6)


def make_train_data(cfg: dict, key):
    """{"images": (M, n, H, W, C) f32, "labels": (M, n) i32}: each
    client's training split, its shards dealt at random from the
    class-aligned pool."""
    lay = shard_layout(cfg)
    m, cpc = cfg["num_clients"], cfg["classes_per_client"]
    size, ch, n_tr = cfg["image_size"], cfg["image_channels"], lay["train"]
    shard_class = jnp.asarray(lay["shard_class"], jnp.int32)

    @jax.jit
    def build(key):
        kp, kd, kn = jax.random.split(key, 3)
        proto = _prototypes(kp, cfg["num_classes"], size, ch)
        cls = jax.random.permutation(kd, shard_class)[: m * cpc]
        cls = cls.reshape(m, cpc)
        labels = jnp.repeat(cls, n_tr, axis=1)                  # (M, n)
        noise = jax.random.normal(kn, labels.shape + (size, size, ch))
        images = proto[labels] + cfg["noise_scale"] * noise
        return {"images": images.astype(jnp.float32),
                "labels": labels.astype(jnp.int32)}

    return build(key)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def widths(cfg: dict) -> list:
    return [cfg["cnn_width"] * 2 ** i for i in range(len(cfg["cnn_stages"]))]


def param_shapes(cfg: dict) -> dict:
    """One client's ResNet-18 parameter layout: the stem, the stages of
    basic blocks (a 1x1 projection where the width changes), the
    header."""
    ch = cfg["image_channels"]
    ws = widths(cfg)

    def gn(c):
        return {"scale": (c,), "bias": (c,)}

    stem = {"conv": (3, 3, ch, ws[0]), "gn": gn(ws[0])}
    stages, cin = [], ws[0]
    for n_blocks, cout in zip(cfg["cnn_stages"], ws):
        blocks = []
        for _ in range(n_blocks):
            b = {"conv1": (3, 3, cin, cout), "gn1": gn(cout),
                 "conv2": (3, 3, cout, cout), "gn2": gn(cout)}
            if cin != cout:
                b["proj"] = (1, 1, cin, cout)
            blocks.append(b)
            cin = cout
        stages.append(blocks)
    return {"stem": stem, "stages": stages,
            "head": {"w": (cin, cfg["num_classes"]),
                     "b": (cfg["num_classes"],)}}


def _is_shape(x):
    return isinstance(x, tuple)


def _init_leaf(path, shape, key, dtype):
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    if name in ("scale",):
        return jnp.ones(shape, dtype)
    if name in ("bias", "b"):
        return jnp.zeros(shape, dtype)
    if name == "w":                                   # the header
        return (jax.random.normal(key, shape) * 0.01).astype(dtype)
    fan_in = shape[0] * shape[1] * shape[2]           # HWIO convolution
    std = float(np.sqrt(2.0 / fan_in))
    return (jax.random.normal(key, shape) * std).astype(dtype)


def init_population(cfg: dict, key):
    """(M, ...) weights of every client, one key per client and leaf."""
    shapes = param_shapes(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    m = cfg["num_clients"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)

    def one(k):
        ks = jax.random.split(k, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [_init_leaf(p, s, kk, dtype)
                      for (p, s), kk in zip(flat, ks)])

    return jax.vmap(one)(jax.random.split(key, m))


def init_masks(params, sparsity: float, key):
    """DisPFL's personal masks: each entry kept with probability
    1 - sparsity, one key per leaf."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    ks = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [jax.random.uniform(k, l.shape) > sparsity
                  for l, k in zip(leaves, ks)])
