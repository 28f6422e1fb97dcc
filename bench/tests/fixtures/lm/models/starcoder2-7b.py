"""A decoder language model as a benchmark model, for the tests only: the
registry's starcoder2-7b at its `.reduced()` sizes (2 layers, width 256,
4 heads, vocabulary 512), in float32.

It is what a configuration that brings a language model adds under
bench/models/, and it is never a benchmark configuration. The tests copy
it into a benchmark root of their own with its configuration file and
limits (bench/tests/test_bench_lm.py).

* Data: each client's token sequences, drawn independently from a
  unigram domain of its own: softmax(domain_scale * z_c) over the
  vocabulary, z_c standard normal from the client's key.
* Weights: the layout of the program's `transformer.init_decoder` (an
  embedding, layers stacked on a leading axis, a final norm and an output
  head), written out here: normal 0.02 matrices (d_in ** -0.5 where the
  input is 64 wide or less), output projections scaled by 1/sqrt(2 L),
  norm scales 0.
* Reference: a plain float32 forward at `highest` matmul precision of what
  `transformer.decoder_forward` computes for this config, and the
  shifted-token cross-entropy. It departs from StarCoder2 as published
  (arXiv 2402.19173) where the program does: RMSNorm with a (1 + scale)
  gain, not LayerNorm with a bias; a gated MLP with tanh-approximated
  GELU, not a plain one; no biases; full causal attention, no sliding
  window. At these sizes key and value heads are as many as query heads
  (4), so the grouped-query path is exercised with one query head a
  group.
* Header: the final norm and the output head, as `models/split.py` cuts
  a language model.
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
    """The file against the registry's `.reduced()` sizes and settings,
    and the parts of the config that the reference below assumes."""
    small = base.reduced()
    for k in WIDTHS:
        if sizes[k] != getattr(small, k):
            raise ValueError(f"{k}: file {sizes[k]!r}, reduced "
                             f"{getattr(small, k)!r}")
    for k in SETTINGS:
        if sizes[k] != getattr(base, k):
            raise ValueError(f"{k}: file {sizes[k]!r}, registry "
                             f"{getattr(base, k)!r}")
    assumed = {"family": "dense", "act": "gelu", "qkv_bias": False,
               "tie_embeddings": False, "num_experts": 0, "use_mla": False}
    for k, v in assumed.items():
        if getattr(base, k) != v:
            raise ValueError(f"{k}: registry {getattr(base, k)!r}, the "
                             f"reference assumes {v!r}")


def _padded_vocab(sizes: dict) -> int:
    return -(-sizes["vocab_size"] // 256) * 256


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


def param_shapes(sizes: dict) -> dict:
    d, f, n = sizes["d_model"], sizes["d_ff"], sizes["num_layers"]
    q = sizes["num_heads"] * sizes["head_dim"]
    kv = sizes["num_kv_heads"] * sizes["head_dim"]
    vp = _padded_vocab(sizes)
    layer = {"ln1": (d,), "ln2": (d,),
             "attn": {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                      "wo": (q, d)},
             "mlp": {"wi": (d, f), "wg": (d, f), "wo": (f, d)}}
    layers = jax.tree_util.tree_map(lambda s: (n,) + s, layer,
                                    is_leaf=lambda s: isinstance(s, tuple))
    return {"embed": (vp, d), "layers": layers, "final_norm": (d,),
            "lm_head": (d, vp)}


def _init_leaf(path, shape, key, sizes):
    names = [getattr(p, "key", "") for p in path]
    if len(shape) - (names[0] == "layers") == 1:          # a norm's scale
        return jnp.zeros(shape, sizes["dtype"])
    d_in = shape[-2]
    std = 0.02 if d_in > 64 or names[0] == "embed" else d_in ** -0.5
    if names[-1] == "wo":                      # output projections
        std /= np.sqrt(2 * sizes["num_layers"])
    return (jax.random.normal(key, shape) * std).astype(sizes["dtype"])


def init_population(sizes: dict, key):
    """(M, ...) weights of every client, one key per client and leaf."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(sizes), is_leaf=lambda s: isinstance(s, tuple))

    def one(k):
        ks = jax.random.split(k, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [_init_leaf(p, s, kk, sizes)
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


def example_losses(params, batch: dict, sizes: dict, quant=None):
    """(B,) float32: each sequence's mean next-token cross-entropy."""
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
        x = params["embed"].astype(F32)[tokens]
        for i in range(sizes["num_layers"]):
            p = jax.tree_util.tree_map(lambda a: a[i].astype(F32),
                                       params["layers"])
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
            x = x + mm("bse,ed->bsd", o.reshape(b, s, h_q * hd),
                       p["attn"]["wo"])
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
    """FLOPs of one sequence (2 a multiply-add): the projections, the
    causal attention's scores and values over the keys at or before each
    query, and the output head; norms and element-wise work do not count.
    A training step is three times the forward pass (the embedding is a
    lookup, so every product needs its input gradient); a frozen head
    skips its weight gradient, a header step is the forward pass and that
    weight gradient."""
    s, d, f = sizes["seq_len"], sizes["d_model"], sizes["d_ff"]
    q = sizes["num_heads"] * sizes["head_dim"]
    kv = sizes["num_kv_heads"] * sizes["head_dim"]
    per_token = 2 * d * q + 2 * d * kv + 3 * d * f
    attn = 2 * q * s * (s + 1) // 2
    head = s * d * _padded_vocab(sizes)
    fwd = 2.0 * (sizes["num_layers"] * (s * per_token + attn) + head)
    return {"forward": fwd, "train_full": 3 * fwd,
            "train_extractor": 3 * fwd - 2.0 * head,
            "train_header": fwd + 2.0 * head}


def extractor_params(sizes: dict) -> int:
    return sum(int(np.prod(s)) for k, t in param_shapes(sizes).items()
               if k not in HEADER for s in jax.tree_util.tree_leaves(
                   t, is_leaf=lambda x: isinstance(x, tuple)))


def header_width(sizes: dict) -> int:
    return sizes["d_model"] * (1 + _padded_vocab(sizes))
