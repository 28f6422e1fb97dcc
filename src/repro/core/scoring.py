"""PFedDST scoring — the three peer-evaluation signals (paper §II-B).

* loss disparity  s_l (Eq. 6): loss of client i's model on peer j's probe
  data — high loss ⇒ peer j holds information i lacks (the decentralized
  surrogate for the selection skew ρ of Eq. 5).
* header distance s_d (Eq. 7): element-wise cosine similarity between header
  weight vectors — high similarity ⇒ similar tasks/label distributions.
* peer recency    s_p (Eq. 8): exponential-CDF of rounds since last
  selection — pushes engagement toward stale peers.

Population-mode entry points operate on client-stacked pytrees (leading M
axis) and return (M, M) matrices: row i = client i scoring peer j. For LLM
headers the cosine Gram matrix is the Pallas peer_score kernel's job
(kernels/peer_score.py); the pure-jnp path here is its oracle.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.selection import NEG
from repro.kernels.peer_score import HIGHEST, gram_to_cosine
from repro.models import model as model_mod
from repro.utils.pytree import tree_flatten_vector


# ---------------------------------------------------------------------------
# Eq. 6 — loss disparity
# ---------------------------------------------------------------------------

def _probe_row(cfg, probe_batches):
    """params → (M,) losses of one model on every client's probe batch."""
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), probe_batches
    )

    def row(params_r):
        logits, _ = model_mod.forward(cfg, params_r, flat)
        return model_mod.client_losses(cfg, logits, probe_batches)

    return row


def loss_disparity_rows(cfg, stacked_params_rows, probe_batches):
    """L[r, j] = eval-loss of row-client r's model on client j's probe.

    stacked_params_rows: pytree with leading R axis (any subset of the
    population — typically the round's sampled clients); probe_batches:
    dict of (M, B, ...) arrays. R·M evaluations — this is how the engine
    keeps Eq. 6 scoring at O(n_active·M) instead of O(M²): inactive rows
    keep their cached `loss_matrix` entries.

    A row's weights do not vary over M, so its forward runs once, on the
    M probes flattened to one plain batch of M·B; the logits are split
    back per client for the loss (`model.client_losses`). Vmapped over
    M instead, every activation carried an extra leading axis that each
    conv and group norm reshaped around: at full-width ResNet-18, M = 100
    and B = 32, the TPU compiler counted 306.7 GB accessed a row that
    way, against 64.5 GB for the flat batch, for the same FLOPs.

    Rows run one after another (lax.map): the peak holds one row's M·B
    forward pass, not R·M·B. The vmapped-rows form of the paper's
    default population (M = 100, 10 sampled rows, 32-image probes,
    ResNet-18) compiled to 17.9 GB on each of four 16 GB TPU v5e chips.
    """
    return jax.lax.map(_probe_row(cfg, probe_batches),
                       stacked_params_rows)  # (R, M)


def loss_disparity_matrix(cfg, stacked_params, probe_batches):
    """L[i, j] = eval-loss of client i's model on client j's probe batch.

    Full O(M²) form of `loss_disparity_rows` (all clients as rows).
    Production note: with clients on the mesh data axis this is an
    all-gather of probe batches + local eval (batches ≪ models).
    """
    return loss_disparity_rows(cfg, stacked_params, probe_batches)


def loss_disparity_row(cfg, params_i, probe_batches):
    """One client's row (decentralized deployment path)."""
    return _probe_row(cfg, probe_batches)(params_i)


# ---------------------------------------------------------------------------
# Eq. 7 — header cosine similarity
# ---------------------------------------------------------------------------

def flatten_headers(stacked_header):
    """Client-stacked header pytree → (M, P) float32 matrix."""
    return jax.vmap(tree_flatten_vector)(stacked_header)


def header_distance_matrix(headers_flat, *, use_kernel: bool = False):
    """S_d[i, j] = cos(h_i, h_j) ∈ [-1, 1]. headers_flat: (M, P).

    use_kernel routes through the Pallas blocked-Gram kernel (TPU path for
    d_model×vocab LLM headers; interpret-mode on CPU). Both paths share
    `gram_to_cosine` — Gram first, then diagonal-norm normalization with
    the zero-norm guard and [-1, 1] clip — so flipping `use_score_kernel`
    cannot perturb Eq. 9 scores past fp tolerance.
    """
    if use_kernel:
        from repro.kernels.ops import cosine_gram

        return cosine_gram(headers_flat)
    x = headers_flat.astype(jnp.float32)
    return gram_to_cosine(jnp.matmul(x, x.T, precision=HIGHEST))


def header_gram_tree(stacked_header):
    """Cosine Gram (Eq. 7) computed leaf-wise — no flattened (M, P) matrix.

    cos over the concatenation of leaves = Σ_leaf <h_i, h_j> normalized by
    the global norms, so the Gram accumulates per leaf and every partial
    product keeps the leaf's sharding (the multi-pod path: headers are
    TP/FSDP-sharded; flattening would force an all-gather of the full
    d_model × vocab header before the Gram).
    """
    leaves = jax.tree_util.tree_leaves(stacked_header)
    m = leaves[0].shape[0]
    raw = jnp.zeros((m, m), jnp.float32)
    for leaf in leaves:
        x = leaf.reshape(m, -1).astype(jnp.float32)
        raw = raw + jnp.einsum("ip,jp->ij", x, x, precision=HIGHEST)
    return gram_to_cosine(raw)


# ---------------------------------------------------------------------------
# fused Eq. 7–9 + top-k — the streaming selection entry point
# ---------------------------------------------------------------------------

def score_topk(headers_flat, last_selected, loss_matrix, round_t, *,
               alpha: float, lam: float, comm_cost, k: int,
               candidate_mask=None, impl: str = "auto"):
    """Fused Eq. 7–9 scoring + streaming per-row top-k selection.

    The masked/scored-Gram entry point: instead of materializing the
    (M, M) cosine, recency, and score matrices (header_distance_matrix →
    recency_scores → combined_scores → select_peers), the whole chain
    runs tile-resident in the kernels/select_score pipeline.

    headers_flat: (M, P); last_selected: (M, M) int32 context array t;
    loss_matrix: (M, M) Eq. 6 scores; round_t: scalar round;
    comm_cost: the Eq. 9 `c` — scalar or per-link (M, M) matrix.

    → (values (M, k), indices (M, k), s_d_stats (M, 2)), where
    s_d_stats[:, 0] = Σ_j s_d[i, j] and s_d_stats[:, 1] = s_d[i, i]
    (enough for the round's s_d metrics without the dense matrix).
    Convert to a selection mask with `selection.topk_to_mask`.
    """
    from repro.kernels.ops import select_topk

    m = headers_flat.shape[0]
    cost = jnp.asarray(comm_cost, jnp.float32)
    if cost.ndim not in (0, 2) or (cost.ndim == 2
                                   and cost.shape != (m, m)):
        raise ValueError(
            f"comm_cost must be a scalar or ({m}, {m}) matrix, "
            f"got shape {cost.shape}"
        )
    return select_topk(
        headers_flat, last_selected, loss_matrix,
        jnp.asarray(round_t, jnp.int32), cost, candidate_mask,
        k=k, alpha=float(alpha), lam=float(lam), impl=impl,
    )


def _gather_nbr_cols(arr, nbr_idx, m: int, what: str):
    """(M, M) dense → (M, D) neighbor columns; (M, D) passes through.

    The ambiguity at D == M resolves to "dense, gather" — packed fabrics
    always have D < M (no self-loops), so a square input is a matrix.
    """
    d = nbr_idx.shape[1]
    if arr.shape == (m, m):
        return jnp.take_along_axis(arr, nbr_idx, axis=1)
    if arr.shape == (m, d):
        return arr
    raise ValueError(
        f"{what} must be ({m}, {m}) dense or ({m}, {d}) neighbor "
        f"columns, got shape {arr.shape}"
    )


def score_topk_sparse(headers_flat, last_selected, loss_matrix, round_t, *,
                      nbr_idx, nbr_valid, alpha: float, lam: float,
                      comm_cost, k: int):
    """Eq. 7–9 scoring + top-k over PACKED neighbor lists — O(M·D·P).

    The sparse-fabric twin of `score_topk`: client i only ever scores its
    D ≤ degree-bound neighbors `nbr_idx[i]` (int32, ascending, padding
    arbitrary), with `nbr_valid[i]` marking the live slots this round
    (static topology ∧ round events — `SparseFabric.round_slots`). No
    (M, M) array is formed anywhere on this path.

    last_selected / loss_matrix / comm_cost accept either the dense
    (M, M) form (gathered here — the small-M parity configuration) or
    pre-gathered (M, D) neighbor columns (the at-scale path, e.g.
    `SparseFabric.slot_cost`); comm_cost may also be a scalar.

    → (values (M, k), indices (M, k) GLOBAL client ids, s_d_stats (M, 2)).
    Invalid slots score exactly NEG; when k exceeds D the tail is padded
    with (NEG, row-self) entries — `selection.topk_to_mask` drops both,
    so the resulting mask is identical to the dense pipeline's under the
    same candidates. Values are elementwise-identical arithmetic to
    `kernels.ref.select_score_ref` (same normalization, 1e-12 guard,
    [-1, 1] clip); only the cosine contraction order differs, so value
    parity vs dense is fp-tolerance, mask parity exact. Ascending
    neighbor order preserves lax.top_k's lowest-column tie-break.

    Telemetry caveat: s_d_stats[:, 0] sums cosine over the NEIGHBORHOOD
    (valid slots) plus the diagonal — the dense stats sum all M columns.
    s_d_stats[:, 1] (the diagonal) matches the dense Gram diagonal at
    ~1 ulp (row reduction vs matmul accumulation order).
    """
    m, _ = headers_flat.shape
    nbr_idx = jnp.asarray(nbr_idx, jnp.int32)
    d = nbr_idx.shape[1]
    xf = headers_flat.astype(jnp.float32)
    inv = 1.0 / (jnp.sqrt(jnp.sum(xf * xf, axis=1)) + 1e-12)
    raw = jnp.einsum("mp,mdp->md", xf, xf[nbr_idx], precision=HIGHEST)
    cos = jnp.clip(raw * inv[:, None] * inv[nbr_idx], -1.0, 1.0)
    last = _gather_nbr_cols(last_selected, nbr_idx, m, "last_selected")
    dt = jnp.maximum(round_t - last, 0).astype(jnp.float32)
    s_p = jnp.where(last < 0, 1.0, 1.0 - jnp.exp(-lam * dt))
    s_l = _gather_nbr_cols(loss_matrix, nbr_idx, m,
                           "loss_matrix").astype(jnp.float32)
    c = jnp.asarray(comm_cost, jnp.float32)
    if c.ndim == 0:
        c = jnp.broadcast_to(c, (m, d))
    else:
        c = _gather_nbr_cols(c, nbr_idx, m, "comm_cost")
    s = s_p * (alpha * s_l - cos + c)
    rows = jnp.arange(m, dtype=jnp.int32)[:, None]
    ok = jnp.asarray(nbr_valid, bool) & (nbr_idx != rows)
    s = jnp.where(ok, s, NEG)
    kk = min(k, d)
    vals, pos = jax.lax.top_k(s, kk)
    idx = jnp.take_along_axis(nbr_idx, pos, axis=1)
    # Floor-valued picks come from padded slots whose nbr_idx is an
    # arbitrary fill (0) — rewrite them to the row's own index so they
    # can never collide with a real selection in topk_to_mask's
    # duplicate-index scatter (the diagonal is always masked, so a
    # False landing there is harmless). The dense path never duplicates
    # (top_k over distinct columns), so only this path needs it.
    idx = jnp.where(vals > NEG / 2, idx,
                    jnp.broadcast_to(rows, vals.shape).astype(idx.dtype))
    if kk < k:
        pad = k - kk
        vals = jnp.concatenate(
            [vals, jnp.full((m, pad), NEG, vals.dtype)], axis=1)
        idx = jnp.concatenate(
            [idx, jnp.broadcast_to(rows, (m, pad)).astype(idx.dtype)],
            axis=1)
    diag = jnp.clip(jnp.sum(xf * xf, axis=1) * inv * inv, -1.0, 1.0)
    nbr_sum = jnp.sum(jnp.where(ok, cos, 0.0), axis=1) + diag
    stats = jnp.stack([nbr_sum, diag], axis=1)
    return vals, idx, stats


# ---------------------------------------------------------------------------
# Eq. 9 decomposition over selected pairs — the telemetry side-channel
# ---------------------------------------------------------------------------

def selected_components(headers_flat, last_selected, loss_matrix, round_t,
                        idx, *, alpha: float, lam: float, comm_cost):
    """Eq. 9 score decomposition restricted to the selected pairs.

    For each row i and its selected columns idx[i, :] (the (M, k) output
    of the fused pipeline, or top-k indices from a dense mask), returns
    the four components the combined score multiplies/sums — without
    materializing any (M, M) matrix: O(M·k·P) for the cosine gathers.

    → dict of (M, k) float32 arrays:
      s_l    Eq. 6 loss disparity        loss_matrix[i, j]
      s_d    Eq. 7 header cosine         cos(h_i, h_j)
      s_p    Eq. 8 recency CDF           1 − exp(−λ·Δt) (1 if never)
      cost   Eq. 9 link cost c           scalar broadcast or c[i, j]
      score  s_p · (α·s_l − s_d + cost)  — the recombined Eq. 9 value

    This is the channel `core.rounds.score_select` records per-round
    component summaries through (`sel_*_mean` metrics) and the opt-in
    dense probe in `repro.obs.selection_probe` parity-tests against the
    fused kernel. The normalization matches `kernels.ref.select_score_ref`
    (norm + 1e-12 guard, [-1, 1] clip), so recombined scores agree with
    both the dense and the fused pipeline at fp tolerance.
    """
    x = headers_flat.astype(jnp.float32)
    inv = 1.0 / (jnp.sqrt(jnp.sum(x * x, axis=1)) + 1e-12)
    xn = x * inv[:, None]
    s_d = jnp.clip(
        jnp.einsum("mp,mkp->mk", xn, xn[idx], precision=HIGHEST),
        -1.0, 1.0
    )
    last = jnp.take_along_axis(last_selected, idx, axis=1)
    dt = jnp.maximum(round_t - last, 0).astype(jnp.float32)
    s_p = jnp.where(last < 0, 1.0, 1.0 - jnp.exp(-lam * dt))
    s_l = jnp.take_along_axis(loss_matrix, idx, axis=1).astype(jnp.float32)
    c = jnp.asarray(comm_cost, jnp.float32)
    if c.ndim == 0:
        c = jnp.broadcast_to(c, idx.shape)
    else:
        c = jnp.take_along_axis(c, idx, axis=1)
    score = s_p * (alpha * s_l - s_d + c)
    return {"s_l": s_l, "s_d": s_d, "s_p": s_p, "cost": c, "score": score}


# ---------------------------------------------------------------------------
# Eq. 8 — peer recency
# ---------------------------------------------------------------------------

def recency_scores(last_selected, t, lam: float):
    """s_p[i, j] = 1 − exp(−λ·(t − t0[i,j])) — the exponential CDF.

    last_selected: (M, M) int32 round at which i last selected j (−1 ⇒
    never → maximal score). t: current round (scalar).
    """
    never = last_selected < 0
    dt = jnp.maximum(t - last_selected, 0).astype(jnp.float32)
    s = 1.0 - jnp.exp(-lam * dt)
    return jnp.where(never, 1.0, s)
