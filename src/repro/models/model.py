"""Unified model API — family dispatch for init / forward / loss / serve.

batch dicts:
  LM families:  {"tokens": (B,S) int32 [, "prefix_embeds": (B,P,D)]
                 [, "frames": (B,Se,D)]}
  cnn:          {"images": (B,H,W,C), "labels": (B,) int32}

LM loss = next-token cross-entropy (prefix/vision positions masked out).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import cnn as cnn_mod
from repro.models import encdec as encdec_mod
from repro.models import hybrid as hybrid_mod
from repro.models import rwkv as rwkv_mod
from repro.models import transformer as tf_mod
from repro.models.layers import cross_entropy_loss


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key):
    if cfg.family == "cnn":
        return cnn_mod.init_cnn(key, cfg)
    if cfg.family == "ssm":
        return rwkv_mod.init_rwkv(key, cfg)
    if cfg.family == "hybrid":
        return hybrid_mod.init_hybrid(key, cfg)
    if cfg.family == "audio":
        return encdec_mod.init_encdec(key, cfg)
    # dense / moe / vlm
    return tf_mod.init_decoder(key, cfg)


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params, batch, *, backend="auto", remat=False):
    """Returns (logits, aux)."""
    if cfg.family == "cnn":
        return cnn_mod.cnn_forward(params, batch["images"], cfg)
    if cfg.family == "ssm":
        wkv_fn = None
        if backend == "flash":  # Pallas chunked-WKV hot path
            from repro.kernels import ops as kernel_ops

            wkv_fn = kernel_ops.wkv
        elif backend == "chunked":  # XLA chunked path (§Perf iteration)
            wkv_fn = rwkv_mod.wkv_chunked_jax
        return rwkv_mod.rwkv_forward(
            params, batch["tokens"], cfg, remat=remat, wkv_fn=wkv_fn
        )
    if cfg.family == "hybrid":
        return hybrid_mod.hybrid_forward(
            params, batch["tokens"], cfg, backend=backend, remat=remat
        )
    if cfg.family == "audio":
        return encdec_mod.encdec_forward(
            params, batch["tokens"], batch["frames"], cfg,
            backend=backend, remat=remat,
        )
    return tf_mod.decoder_forward(
        params, batch["tokens"], cfg,
        prefix_embeds=batch.get("prefix_embeds"),
        backend=backend, remat=remat,
    )


AUX_WEIGHTS = {"load_balance": 0.01, "router_z": 0.001}


def loss_fn(cfg: ModelConfig, params, batch, *, backend="auto", remat=False):
    """Returns (loss, metrics dict)."""
    logits, aux = forward(cfg, params, batch, backend=backend, remat=remat)
    if cfg.family == "cnn":
        loss = cross_entropy_loss(logits, batch["labels"])
        acc = jnp.mean(
            (jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32)
        )
        return loss, {"loss": loss, "accuracy": acc}
    tokens = batch["tokens"]
    # logits may include a vision/audio prefix — predictions for text token
    # t+1 sit at logit position P + t.
    p = logits.shape[1] - tokens.shape[1]
    loss = cross_entropy_loss(logits[:, p : p + tokens.shape[1] - 1], tokens[:, 1:])
    total = loss
    metrics = {"loss": loss}
    for k, w in AUX_WEIGHTS.items():
        if k in aux:
            total = total + w * aux[k]
            metrics[k] = aux[k]
    return total, metrics


def _task_loss(cfg: ModelConfig, logits, batch):
    """Task loss of one batch's logits: no aux, f32 cross-entropy."""
    if cfg.family == "cnn":
        return cross_entropy_loss(logits, batch["labels"])
    tokens = batch["tokens"]
    p = logits.shape[1] - tokens.shape[1]
    return cross_entropy_loss(logits[:, p : p + tokens.shape[1] - 1], tokens[:, 1:])


def eval_loss(cfg: ModelConfig, params, batch, *, backend="auto"):
    """Pure task loss (no aux) — the s_l scoring signal (paper Eq. 6)."""
    logits, _ = forward(cfg, params, batch, backend=backend)
    return _task_loss(cfg, logits, batch)


def client_losses(cfg: ModelConfig, logits, batches):
    """`eval_loss` of each of M clients' batches, from one flat forward.

    batches: dict of (M, B, ...) arrays; logits: `forward` of one model
    on their (M·B, ...) flattening. → (M,) float32, entry j the task loss
    over client j's B examples alone. MoE capacity groups are formed over
    the flat batch, so when B·S is not a multiple of the group size a
    client's tokens share expert capacity with its neighbours' in the
    batch.
    """
    m, b = jax.tree_util.tree_leaves(batches)[0].shape[:2]
    logits = logits.reshape((m, b) + logits.shape[1:])
    return jax.vmap(lambda lg, bt: _task_loss(cfg, lg, bt))(logits, batches)


def accuracy(cfg: ModelConfig, params, batch):
    """Classification accuracy (cnn) or next-token accuracy (LM)."""
    logits, _ = forward(cfg, params, batch)
    if cfg.family == "cnn":
        return jnp.mean(
            (jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32)
        )
    tokens = batch["tokens"]
    p = logits.shape[1] - tokens.shape[1]
    pred = jnp.argmax(logits[:, p : p + tokens.shape[1] - 1], -1)
    return jnp.mean((pred == tokens[:, 1:]).astype(jnp.float32))


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    """Decode-state pytree for serve_step.

    dense/moe/vlm → stacked KV (or MLA latent) cache of length max_seq;
    ssm → O(1) recurrent state;  hybrid → LRU states + window ring caches;
    audio → decoder self-cache + cross-kv buffers.
    """
    if cfg.family == "cnn":
        raise ValueError("cnn has no decode step")
    if cfg.family == "ssm":
        return rwkv_mod.init_rwkv_model_state(cfg, batch, dtype)
    if cfg.family == "hybrid":
        return hybrid_mod.init_hybrid_state(cfg, batch, dtype)
    if cfg.family == "audio":
        return encdec_mod.init_encdec_cache_shapes(cfg, batch, max_seq, dtype)
    return tf_mod.init_decoder_cache(cfg, batch, max_seq, dtype)


def decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    """One-token serve step: (logits (B,1,V), new_cache)."""
    if cfg.family == "ssm":
        return rwkv_mod.rwkv_decode_step(params, cache, tokens, pos, cfg)
    if cfg.family == "hybrid":
        return hybrid_mod.hybrid_decode_step(params, cache, tokens, pos, cfg)
    if cfg.family == "audio":
        return encdec_mod.encdec_decode_step(params, cache, tokens, pos, cfg)
    return tf_mod.decoder_decode_step(params, cache, tokens, pos, cfg)


def prefill(cfg: ModelConfig, params, batch, *, max_seq: int, backend="auto"):
    """Prefill returning (logits, cache/state) — every serving family."""
    if cfg.family in ("dense", "moe", "vlm"):
        return tf_mod.decoder_prefill(
            params, batch["tokens"], cfg, max_seq=max_seq, backend=backend
        )
    if cfg.family == "ssm":
        return rwkv_mod.rwkv_prefill(
            params, batch["tokens"], cfg, backend=backend
        )
    if cfg.family == "hybrid":
        return hybrid_mod.hybrid_prefill(
            params, batch["tokens"], cfg, backend=backend
        )
    if cfg.family == "audio":
        cache = encdec_mod.init_encdec_cache(
            params, batch["frames"], cfg, batch["tokens"].shape[0], max_seq
        )
        logits, _ = encdec_mod.encdec_forward(
            params, batch["tokens"], batch["frames"], cfg, backend=backend
        )
        return logits, cache
    raise ValueError(f"prefill not defined for family {cfg.family}")


# ---------------------------------------------------------------------------
# analytic parameter counts (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    if cfg.family == "cnn":
        widths = [cfg.cnn_width * (2**i) for i in range(len(cfg.cnn_stages))]
        total = 3 * 3 * cfg.image_channels * widths[0]
        cin = widths[0]
        for n, cout in zip(cfg.cnn_stages, widths):
            for b in range(n):
                total += 9 * cin * cout + 9 * cout * cout
                if cin != cout:
                    total += cin * cout
                cin = cout
        return total + cin * cfg.num_classes

    embed_head = 2 * V * D

    if cfg.family == "ssm":
        time = 5 * D * D + D * 5 * 32 + 5 * 32 * D + D * 64 + 64 * D + 2 * D
        chan = D * F + F * D + D * D
        return cfg.num_layers * (time + chan) + embed_head

    if cfg.family == "hybrid":
        W = cfg.lru_width
        rec = 2 * D * W + 2 * W * W + W * D + 4 * W
        attn = D * H * hd + 2 * D * K * hd + H * hd * D
        mlp_p = 3 * D * F
        n_rec = sum(1 for k in cfg.block_pattern if k == "rec")
        n_attn = cfg.num_layers - n_rec
        return n_rec * (rec + mlp_p) + n_attn * (attn + mlp_p) + embed_head

    if cfg.family == "audio":
        attn = D * H * hd + 2 * D * K * hd + H * hd * D
        mlp_p = 3 * D * F
        enc = cfg.encoder_layers * (attn + mlp_p)
        dec = cfg.num_layers * (2 * attn + mlp_p)
        return enc + dec + embed_head

    # dense / moe / vlm
    if cfg.use_mla:
        nope, rope_d, v_d = (
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
        )
        attn = (
            D * cfg.q_lora_rank
            + cfg.q_lora_rank * H * (nope + rope_d)
            + D * (cfg.kv_lora_rank + rope_d)
            + cfg.kv_lora_rank * H * (nope + v_d)
            + H * v_d * D
        )
    else:
        attn = D * H * hd + 2 * D * K * hd + H * hd * D

    if cfg.num_experts:
        Fm = cfg.moe_d_ff
        e_eff = (
            (cfg.num_experts_per_tok if active_only else cfg.num_experts)
            + cfg.num_shared_experts
        )
        ffn = D * cfg.num_experts + e_eff * 3 * D * Fm
    else:
        ffn = 3 * D * F
    return cfg.num_layers * (attn + ffn) + embed_head
