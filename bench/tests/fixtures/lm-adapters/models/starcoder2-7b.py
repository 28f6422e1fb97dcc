"""Adapters over a frozen decoder that every client shares, as a benchmark
model, for the tests only: the registry's starcoder2-7b at its `.reduced()`
sizes (2 layers, width 256, 4 heads, vocabulary 512), in float32, fine-tuned
as parameter-efficient federated learning (as OpenFedLLM, arXiv 2402.06954,
fine-tunes a language model with LoRA).

It is what a configuration whose clients share a trunk adds under
bench/models/, and it is never a benchmark configuration. The tests copy
it into a benchmark root of their own with its configuration file and
limits (bench/tests/test_bench_shared.py).

* Shared weights (`init_shared`): the decoder without its header, the
  embedding and the layers, initialised as bench/tests/fixtures/lm's
  model initialises them; one tree with no client axis, never trained.
* Per client (`init_population`): rank-`adapter_rank` adapters on each
  layer's attention output projection, A (heads * head_dim, r) normal
  with std (heads * head_dim) ** -0.5, B (r, d_model) zero; and the
  header, the final norm (0) and the output head (normal 0.02). The
  extractor that pfeddst mixes is the adapters.
* Reference: the plain float32 forward of fixtures/lm's model, at
  `highest` matmul precision, with `o @ A @ B` added to each layer's
  attention output projection `o @ wo`. With B = 0 it is that model's
  forward of the trunk and the header, to the bit.
* Data: as fixtures/lm's model, each client's token sequences from a
  unigram domain of its own.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEADER = ("final_norm", "lm_head")
WIDTHS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
          "d_ff", "vocab_size")
SETTINGS = ("rope_theta", "norm_eps")
TINY = {"chunk_rounds": 2}


def control_quant(x):
    """The control: each operand of every product, and the parameters
    after each update, rounded to bfloat16, the precision below float32."""
    return x.astype(jnp.bfloat16).astype(x.dtype)


def program_config(base, sizes: dict):
    return dataclasses.replace(
        base, dtype=sizes["dtype"],
        **{k: sizes[k] for k in WIDTHS + SETTINGS})


def check_config(sizes: dict, base) -> None:
    """The file against the registry's `.reduced()` sizes and settings."""
    small = base.reduced()
    for k in WIDTHS:
        if sizes[k] != getattr(small, k):
            raise ValueError(f"{k}: file {sizes[k]!r}, reduced "
                             f"{getattr(small, k)!r}")
    for k in SETTINGS:
        if sizes[k] != getattr(base, k):
            raise ValueError(f"{k}: file {sizes[k]!r}, registry "
                             f"{getattr(base, k)!r}")


def _padded_vocab(sizes: dict) -> int:
    return -(-sizes["vocab_size"] // 256) * 256


def _is_shape(s) -> bool:
    return isinstance(s, tuple)


def _leaves(shapes) -> list:
    return jax.tree_util.tree_leaves(shapes, is_leaf=_is_shape)


# ---------------------------------------------------------------------------
# data and weights
# ---------------------------------------------------------------------------

def make_train_data(sizes: dict, key):
    """{"tokens": (M, n, S) int32}."""
    m, v = sizes["num_clients"], sizes["vocab_size"]
    shape = (sizes["samples_per_client"], sizes["seq_len"])

    @jax.jit
    def build(key):
        kd, kt = jax.random.split(key)
        domains = sizes["domain_scale"] * jax.random.normal(kd, (m, v))
        toks = jax.vmap(lambda k, lg: jax.random.categorical(
            k, lg, shape=shape))(jax.random.split(kt, m), domains)
        return {"tokens": toks.astype(jnp.int32)}

    return build(key)


def shared_shapes(sizes: dict) -> dict:
    """The trunk every client shares: the embedding and the layers."""
    d, f, n = sizes["d_model"], sizes["d_ff"], sizes["num_layers"]
    q = sizes["num_heads"] * sizes["head_dim"]
    kv = sizes["num_kv_heads"] * sizes["head_dim"]
    layer = {"ln1": (d,), "ln2": (d,),
             "attn": {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                      "wo": (q, d)},
             "mlp": {"wi": (d, f), "wg": (d, f), "wo": (f, d)}}
    return {"embed": (_padded_vocab(sizes), d),
            "layers": jax.tree_util.tree_map(lambda s: (n,) + s, layer,
                                             is_leaf=_is_shape)}


def param_shapes(sizes: dict) -> dict:
    """One client's trained weights: the adapters and the header."""
    d, n, r = sizes["d_model"], sizes["num_layers"], sizes["adapter_rank"]
    q = sizes["num_heads"] * sizes["head_dim"]
    return {"adapters": {"a": (n, q, r), "b": (n, r, d)},
            "final_norm": (d,), "lm_head": (d, _padded_vocab(sizes))}


def _trunk_leaf(path, shape, key, sizes):
    names = [getattr(p, "key", "") for p in path]
    if len(shape) - (names[0] == "layers") == 1:          # a norm's scale
        return jnp.zeros(shape, sizes["dtype"])
    d_in = shape[-2]
    std = 0.02 if d_in > 64 or names[0] == "embed" else d_in ** -0.5
    if names[-1] == "wo":                      # output projections
        std /= np.sqrt(2 * sizes["num_layers"])
    return (jax.random.normal(key, shape) * std).astype(sizes["dtype"])


def init_shared(sizes: dict, key):
    """The trunk, one key per leaf."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shared_shapes(sizes), is_leaf=_is_shape)
    ks = jax.random.split(key, len(flat))
    return jax.tree_util.tree_unflatten(
        treedef, [_trunk_leaf(p, s, k, sizes) for (p, s), k in zip(flat, ks)])


def _client_leaf(path, shape, key, sizes):
    name = getattr(path[-1], "key", "")
    if name in ("b", "final_norm"):
        return jnp.zeros(shape, sizes["dtype"])
    std = shape[-2] ** -0.5 if name == "a" else 0.02
    return (jax.random.normal(key, shape) * std).astype(sizes["dtype"])


def init_population(sizes: dict, key):
    """(M, ...) trained weights of every client, one key per client and
    leaf."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(sizes), is_leaf=_is_shape)

    def one(k):
        ks = jax.random.split(k, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [_client_leaf(p, s, kk, sizes)
                      for (p, s), kk in zip(flat, ks)])

    return jax.vmap(one)(jax.random.split(key, sizes["num_clients"]))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """Rotary position embedding of (B, S, H, hd), halves rotated."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(hd // 2, dtype=np.float32) * 2.0 / hd)
    ang = np.arange(s, dtype=np.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def example_losses(params, batch: dict, sizes: dict, quant=None, *, shared):
    """(B,) float32: each sequence's mean next-token cross-entropy under
    the shared trunk, the client's adapters and its header. The trunk is
    read as given and each of its leaves cast to float32 where it is
    used, one layer at a time."""
    q_ = quant if quant is not None else (lambda a: a)

    def mm(spec, a, b):
        return jnp.einsum(spec, q_(a), q_(b))

    h_q, h_kv, hd = sizes["num_heads"], sizes["num_kv_heads"], \
        sizes["head_dim"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    tokens = batch["tokens"]
    b, s = tokens.shape
    causal = np.tril(np.ones((s, s), bool))
    with jax.default_matmul_precision("highest"):
        x = shared["embed"][tokens].astype(F32)
        for i in range(sizes["num_layers"]):
            p = jax.tree_util.tree_map(lambda a: a[i].astype(F32),
                                       shared["layers"])
            ad = jax.tree_util.tree_map(lambda a: a[i].astype(F32),
                                        params["adapters"])
            h = _rms_norm(x, p["ln1"], eps)
            q = mm("bsd,de->bse", h, p["attn"]["wq"]).reshape(b, s, h_q, hd)
            k = mm("bsd,de->bse", h, p["attn"]["wk"]).reshape(b, s, h_kv, hd)
            v = mm("bsd,de->bse", h, p["attn"]["wv"]).reshape(b, s, h_kv, hd)
            q, k = _rope(q, theta), _rope(k, theta)
            # query head j reads key/value head j // (h_q / h_kv)
            k = jnp.repeat(k, h_q // h_kv, axis=2)
            v = jnp.repeat(v, h_q // h_kv, axis=2)
            sc = jnp.where(causal, mm("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd),
                           -jnp.inf)
            o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)
            o = o.reshape(b, s, h_q * hd)
            x = x + mm("bse,ed->bsd", o, p["attn"]["wo"]) \
                + mm("bsr,rd->bsd", mm("bse,er->bsr", o, ad["a"]), ad["b"])
            h = _rms_norm(x, p["ln2"], eps)
            g = jax.nn.gelu(mm("bsd,df->bsf", h, p["mlp"]["wg"]),
                            approximate=True)
            x = x + mm("bsf,fd->bsd", g * mm("bsd,df->bsf", h, p["mlp"]["wi"]),
                       p["mlp"]["wo"])
        x = _rms_norm(x, params["final_norm"].astype(F32), eps)
        z = mm("bsd,dv->bsv", x, params["lm_head"].astype(F32))
    z, gold = z[:, :-1], tokens[:, 1:]
    nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, gold[..., None], axis=-1)[..., 0]
    return jnp.mean(nll, axis=1)


# ---------------------------------------------------------------------------
# FLOPs and kernel sizes
# ---------------------------------------------------------------------------

def flops(sizes: dict) -> dict:
    """FLOPs of one sequence (2 a multiply-add), counted as fixtures/lm's
    model counts them, with the adapters' products. Training passes the
    gradient back through the frozen trunk down to the lowest adapter,
    the first layer's, which needs the gradient of its attention output
    and of nothing below it; weight gradients are the trained leaves'
    alone: the adapters (`train_extractor`), and the head too
    (`train_full`, every leaf a client holds); `train_header` is the
    forward pass and the head's weight gradient."""
    s, d, f = sizes["seq_len"], sizes["d_model"], sizes["d_ff"]
    n, r = sizes["num_layers"], sizes["adapter_rank"]
    q = sizes["num_heads"] * sizes["head_dim"]
    kv = sizes["num_kv_heads"] * sizes["head_dim"]
    qkv = s * (d * q + 2 * d * kv)
    attn = 2 * q * s * (s + 1) // 2
    rest = s * (q * d + 3 * d * f)             # output projection, MLP
    adapter = s * (q * r + r * d)
    head = s * d * _padded_vocab(sizes)
    fwd = n * (qkv + attn + rest + adapter) + head
    # input gradients: every product above the first layer's attention
    # output; the first adapter's A needs none
    back = fwd - (qkv + attn) - s * q * d - s * q * r
    train_e = 2.0 * (fwd + back + n * adapter)
    return {"forward": 2.0 * fwd, "train_extractor": train_e,
            "train_full": train_e + 2.0 * head,
            "train_header": 2.0 * (fwd + head)}


def extractor_params(sizes: dict) -> int:
    """One client's adapters."""
    return sum(int(np.prod(s))
               for s in _leaves(param_shapes(sizes)["adapters"]))


def header_width(sizes: dict) -> int:
    return sizes["d_model"] * (1 + _padded_vocab(sizes))
