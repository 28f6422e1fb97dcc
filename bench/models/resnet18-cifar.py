"""ResNet-18 for CIFAR, the PFedDST paper's model, as the benchmark runs it.

The one place in bench/ that knows this model and its data. A
configuration file whose `model` is "resnet18-cifar" is run through:

* `program_config`: the program's ModelConfig at the file's sizes;
* `make_train_data`: the clients' training images, a copy of the
  program's synthetic CIFAR generator (`repro.data.synthetic.
  client_datasets_cifar`: class prototypes plus Gaussian noise, the
  paper's pathological partition into single-class shards), generated
  on the device in one jitted call, training split only;
* `init_population`: every client's ResNet-18 weights, He-normal
  convolutions, unit GroupNorm scales, a 0.01-normal header, in the
  configuration's dtype;
* `example_losses`: the plain reference's loss (bench/reference/resnet.py);
* `flops`, `extractor_params`, `header_width`, `param_shapes`: what the
  FLOP count and the kernels' rooflines read.

Nothing here imports the program: `program_config` is handed the
registry's config and only sizes it.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import resnet

# the personalised header: the top-level keys of the parameter tree
# that pfeddst keeps per client and that dispfl does not mix
HEADER = ("head",)

# a cell small enough for the CPU: the published block (widths from 64,
# eight-channel groups) at one stage and 8x8 images, 6 clients
TINY = dict(cnn_stages=[1], cnn_width=64, image_size=8, num_clients=6,
            client_sample_ratio=0.5, peers_per_round=2, probe_size=4,
            batch_size=8, samples_per_class=10, steps_per_epoch=1,
            epochs_extractor=1, epochs_header=1, chunk_rounds=2)

# the control: the reference computed and held in float8
control_quant = resnet.fp8_quant


# ---------------------------------------------------------------------------
# the program's config, and the file against the registry
# ---------------------------------------------------------------------------

def program_config(base, sizes: dict):
    """The program's ModelConfig with the configuration file's sizes."""
    return dataclasses.replace(
        base, cnn_stages=tuple(sizes["cnn_stages"]),
        cnn_width=sizes["cnn_width"], image_size=sizes["image_size"],
        image_channels=sizes["image_channels"],
        num_classes=sizes["num_classes"], dtype=sizes["dtype"])


def check_config(sizes: dict, base) -> None:
    """Raises ValueError where the file's widths or dtype are not the
    registry's: a configuration may cut depth or scale, never a width."""
    for key, want in (("cnn_stages", base.cnn_stages),
                      ("cnn_width", base.cnn_width), ("dtype", base.dtype)):
        got = tuple(sizes[key]) if key == "cnn_stages" else sizes[key]
        if got != want:
            raise ValueError(f"{key}: file {got!r}, registry {want!r}")


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


# ---------------------------------------------------------------------------
# the plain reference's loss
# ---------------------------------------------------------------------------

def example_losses(params, batch: dict, sizes: dict, quant=None):
    """(B,) float32 cross-entropy of each image of one client's batch
    (the sizes are in the parameters' shapes)."""
    return resnet.losses(params, batch["images"], batch["labels"], quant)


# ---------------------------------------------------------------------------
# FLOPs and kernel sizes
# ---------------------------------------------------------------------------

def _taps(n_out: int, n_in: int, k: int, stride: int) -> int:
    """Kernel taps inside the input, summed over one axis of outputs
    (SAME padding)."""
    pad = max((n_out - 1) * stride + k - n_in, 0) // 2
    return sum(1 for o in range(n_out) for t in range(k)
               if 0 <= o * stride + t - pad < n_in)


def layers(cfg: dict) -> list:
    """[(name, macs per image)] of every product of the forward pass, in
    order; the header last. Multiply-adds of the convolutions at the taps
    that fall inside the image; padding, normalisation and element-wise
    work do not count."""
    size, ch = cfg["image_size"], cfg["image_channels"]
    ws = widths(cfg)
    out = []

    def conv(name, n_in, cin, cout, k, stride):
        n_out = math.ceil(n_in / stride)
        out.append((name, _taps(n_out, n_in, k, stride) ** 2 * cin * cout))
        return n_out

    n = conv("stem", size, ch, ws[0], 3, 1)
    cin = ws[0]
    for si, (blocks, cout) in enumerate(zip(cfg["cnn_stages"], ws)):
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            n_in = n
            n = conv(f"s{si}b{bi}.conv1", n_in, cin, cout, 3, stride)
            conv(f"s{si}b{bi}.conv2", n, cout, cout, 3, 1)
            if cin != cout:
                conv(f"s{si}b{bi}.proj", n_in, cin, cout, 1, stride)
            cin = cout
    out.append(("head", cin * cfg["num_classes"]))
    return out


def flops(cfg: dict) -> dict:
    """FLOPs of one image (2 a multiply-add): `forward`; `train_full`,
    forward and backward with every layer trained (weight gradients
    everywhere, input gradients everywhere but the stem);
    `train_extractor`, the same under a frozen header, which still passes
    the gradient back but needs no weight gradient of its own;
    `train_header`, the forward pass and the header's weight gradient."""
    ls = layers(cfg)
    fwd = sum(m for _, m in ls)
    full = 2.0 * (3 * fwd - ls[0][1])
    return {"forward": 2.0 * fwd, "train_full": full,
            "train_extractor": full - 2.0 * ls[-1][1],
            "train_header": 2.0 * fwd + 2.0 * ls[-1][1]}


def extractor_params(cfg: dict) -> int:
    """Parameters of one client's extractor: everything but the header."""
    ws = widths(cfg)
    n = 9 * cfg["image_channels"] * ws[0] + 2 * ws[0]
    cin = ws[0]
    for blocks, cout in zip(cfg["cnn_stages"], ws):
        for _ in range(blocks):
            n += 9 * cin * cout + 9 * cout * cout + 4 * cout
            if cin != cout:
                n += cin * cout
            cin = cout
    return n


def header_width(cfg: dict) -> int:
    """Parameters of one client's header, the row that Eq. 7 compares."""
    return widths(cfg)[-1] * cfg["num_classes"] + cfg["num_classes"]
