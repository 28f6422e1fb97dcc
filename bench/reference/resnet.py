"""Plain ResNet-18 (CIFAR variant, GroupNorm) for the reference.

He et al. 2016, as the PFedDST paper trains it on 32x32 images: a 3x3
stem, four stages of two basic blocks (stride 2 and a 1x1 projection where
the width doubles), global average pooling and a linear header. A
normalisation over 8 channel groups replaces BatchNorm, as in the
repository's model (`group_norm` says how it differs from GroupNorm).

Precision follows the configuration's dtype (bfloat16): parameters and
activations are held in it, every convolution and the header multiply
bfloat16 operands into a bfloat16 result (the TPU sums the products in
float32), and GroupNorm and the loss run in float32. Each operation is
written in the order a bfloat16 model computes it, so that the reference
rounds where such a model rounds: bfloat16 training is chaotic, and a
rounding taken elsewhere grows within a few steps into a gap that would
hide a real fault.

`quant` is the control's hook: a function applied to both operands of
every product (None for the reference itself).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GN_GROUPS = 8
F32 = jnp.float32


def conv(x, w, stride: int, quant=None):
    if quant is not None:
        x, w = quant(x), quant(w)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def group_norm(x, p):
    """Each pixel's channels in 8 groups, normalised within each group.
    (This is the model this repository defines; Wu and He's GroupNorm
    also pools over the pixels.)"""
    shape = x.shape
    xf = x.astype(F32).reshape(shape[:-1] + (GN_GROUPS,
                                             shape[-1] // GN_GROUPS))
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + 1e-5)).reshape(shape)
    return (y * p["scale"].astype(F32) + p["bias"].astype(F32)).astype(x.dtype)


def block(p, x, stride: int, quant):
    h = jax.nn.relu(group_norm(conv(x, p["conv1"], stride, quant), p["gn1"]))
    h = group_norm(conv(h, p["conv2"], 1, quant), p["gn2"])
    if "proj" in p:
        x = conv(x, p["proj"], stride, quant)
    elif stride != 1:
        x = x[:, ::stride, ::stride]
    return jax.nn.relu(x + h)


def features(params, images, quant=None):
    dt = params["stem"]["conv"].dtype
    x = conv(images.astype(dt), params["stem"]["conv"], 1, quant)
    x = jax.nn.relu(group_norm(x, params["stem"]["gn"]))
    for si, stage in enumerate(params["stages"]):
        for bi, p in enumerate(stage):
            x = block(p, x, 2 if (bi == 0 and si > 0) else 1, quant)
    return jnp.mean(x, axis=(1, 2))


def logits(params, images, quant=None):
    f, w = features(params, images, quant), params["head"]["w"]
    if quant is not None:
        f, w = quant(f), quant(w)
    return f @ w + params["head"]["b"]


def losses(params, images, labels, quant=None):
    """Each image's cross-entropy, in float32."""
    z = logits(params, images, quant).astype(F32)
    gold = jnp.sum(jnp.where(jnp.arange(z.shape[-1]) == labels[:, None],
                             z, 0.0), axis=-1)
    return jax.nn.logsumexp(z, axis=-1) - gold


def fp8_quant(x):
    """The control's precision: a tensor scaled per tensor into
    float8_e4m3 and back. Applied to each operand of every product and to
    the parameters after each update, as a model computed and held in
    float8 would see them; gradients pass straight through."""

    @jax.custom_vjp
    def q(v):
        vf = v.astype(F32)
        s = jnp.maximum(jnp.max(jnp.abs(vf)), 1e-30) / 448.0
        return ((vf / s).astype(jnp.float8_e4m3fn).astype(F32) * s
                ).astype(v.dtype)

    q.defvjp(lambda v: (q(v), None), lambda _, g: (g,))
    return q(x)
