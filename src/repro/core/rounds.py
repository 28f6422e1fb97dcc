"""PFedDST Algorithm 1 — one full communication round over the population.

Round structure (per active client i, all vmapped/einsum'd over M):
  1. score every peer:      S_ij = s_p·(α·s_l − s_d + c)      (Eq. 6–9)
  2. select peers M_i       (top-k or threshold)
  3. aggregate extractors   e_i ← avg{e_j : j ∈ M_i ∪ {i}}
  4. phase-e training       K_e epochs, header frozen          (Eq. 3)
  5. broadcast e_i          (population mode: the state update itself)
  6. phase-h training       K_h epochs, extractor frozen       (Eq. 4)
  7. update context arrays  (loss array l, recency array t)

The round is expressed as repro.fl.engine stages (`make_pfeddst_stages`):
score_select → aggregate → phase-e → phase-h → update_context, so the
PFedDST spec in fl/strategies.py and the standalone `pfeddst_round`
entry point below execute the exact same code. Passing a
`repro.fl.hetero.HeteroRuntime` (the `pfeddst_async` spec) wraps the
same stages with a deadline gate, versioned-peer-store serving, and
staleness-weighted aggregation — see `make_pfeddst_stages`.

Client sampling (§III-A, ratio 0.1): inactive clients keep their state;
they remain selectable as peers (their parameters are still on the
network). The expensive Eq. 6 probe evaluations run ONLY for the
sampled rows — a static-size gather of the round's participants —
so scoring costs O(n_active·M) model evals instead of O(M²); inactive
rows keep their cached `loss_matrix` entries (which is also what the
paper's context array l stores between selections).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig, ModelConfig
from repro.core.aggregation import aggregate_extractors, selection_to_weights
from repro.core.client_state import PopulationState
from repro.core.partial_freeze import PhaseSteps
from repro.core.scoring import (
    flatten_headers,
    header_distance_matrix,
    loss_disparity_rows,
    recency_scores,
    score_topk,
    score_topk_sparse,
    selected_components,
)
from repro.core.selection import (
    NEG,
    as_cost_matrix,
    combined_scores,
    select_peers,
    topk_to_mask,
    update_recency,
)
from repro.data.pipeline import sample_client_batches
from repro.fl.engine import (
    ExchangePlan,
    RoundContext,
    gather_rows,
    named_stage,
    run_round,
    scan_train,
    scatter_rows,
    where_tree,
)
from repro.models.split import merge_params
from repro.obs.timers import part

# PRNG stream layout of one PFedDST round (order = seed-for-seed parity
# with the pre-engine implementation).
PFEDDST_STREAMS = ("probe", "act", "e", "h", "rand")


def make_pfeddst_stages(
    cfg: ModelConfig,
    fl: FLConfig,
    steps: PhaseSteps,
    *,
    steps_per_epoch: int = 1,
    probe_size: int = 64,
    use_score_kernel: bool = False,
    hetero=None,
):
    """Algorithm 1 as engine stages over a PopulationState.

    use_score_kernel: route Eq. 7–9 scoring + top-k selection through the
    fused streaming pipeline (core.scoring.score_topk →
    kernels/select_score): per-tile cosine + score combination with a
    running per-row top-k, so no (M, M) score matrix is materialized in
    HBM (O(M·k) selection output instead of O(M²)). Applies to the
    default "topk" selection mode — and to the hetero served-header path,
    which scores the versions peers actually publish — and changes scores
    only at fp tolerance vs the dense path. "threshold" selection is
    inherently dense ((M, M) mask output) and "random" never scores, so
    both keep the unfused path; for those modes the flag still routes the
    Eq. 7 Gram through the blocked Pallas kernel as before.

    hetero: optional `repro.fl.hetero.HeteroRuntime` — the semi-async
    variant (`pfeddst_async`). It prepends the deadline gate, scores and
    aggregates against the versioned peer store's *served* snapshots
    (Eq. 7 header distances use the version a peer actually publishes;
    the pull lag is discounted by `(1+lag)^(−α)` mixing weights), and
    appends a publish stage. The Eq. 6 loss-disparity rows are
    unaffected: they evaluate the (always fresh) row-client's own model
    on probe *data*, which does not version. With a uniform profile and
    an infinite deadline every hetero operation is a bitwise identity,
    so the stage tuple reproduces the synchronous trace exactly.
    """
    if hetero is not None:
        from repro.fl.hetero import (
            pull_staleness,
            stage_deadline_gate,
            store_publish,
            store_serve,
        )
        from repro.core.aggregation import staleness_weights

    # openworld defense: robust extractor aggregation over the selected
    # peer set (lazy import, like hetero — the honest path never loads it)
    defense = fl.threat.defense if fl.threat is not None else "none"
    if defense != "none":
        from repro.openworld.defense import robust_row_aggregate

    def score_select(state: PopulationState, ctx: RoundContext):
        # ---- 1. scoring — Eq. 6 restricted to the sampled rows ------------
        m = ctx.m
        with part("eq6_probe"):
            probe = sample_client_batches(ctx.keys["probe"], ctx.data,
                                          probe_size)
            params = jax.vmap(merge_params)(state.extractor, state.header)
            row_params = jax.tree_util.tree_map(
                lambda x: x[ctx.sampled_idx], params
            )
            # (n_act, M)
            s_l_rows = loss_disparity_rows(cfg, row_params, probe)
            s_l = state.loss_matrix.at[ctx.sampled_idx].set(s_l_rows)
        with part("eq79_select"):
            if hetero is not None:
                # serve each peer's published snapshot (channel lag picks an
                # older ring slot); Eq. 7 sees the header actually pulled.
                # ACTIVE clients' columns are their live state: a participant
                # exchanges in real time (and mixes its own diagonal from its
                # live params, never a stale self-snapshot) — only absent
                # peers are served from the store. Their value-staleness
                # (deadline misses since last publish) still discounts them
                # via store.lag below.
                ctx.store = state.store
                served, age = store_serve(state.store, state.round, ctx.stale)
                served = {
                    "e": where_tree(ctx.active, state.extractor, served["e"]),
                    "h": where_tree(ctx.active, state.header, served["h"]),
                }
                # a live-served column is current: age 0, like pull_staleness
                age = jnp.where(ctx.active, 0, age)
                lag = pull_staleness(state.store, ctx.stale, hetero.depth,
                                     active=ctx.active)
                ctx.aux.update(served=served, serve_age=age, pull_lag=lag)
                header_view = served["h"]
            else:
                header_view = state.header
            cost = fl.comm_cost if ctx.cost is None else ctx.cost
            flat = flatten_headers(header_view)
            if ctx.threat is not None and ctx.threat.score_game != "none":
                # score-integrity adversaries spoof the header/cost view
                # the scorer sees — repro.openworld.attacks.ThreatState
                # (both the fused and dense branches below read the
                # spoofed `flat`/`cost`)
                flat, cost = ctx.threat.game_scores(flat, cost, m)
            # degenerate populations (M < 2, k < 1) keep the dense path: its
            # select_peers returns the explicit empty mask for k = 0
            fused = (use_score_kernel and m > 1 and fl.peers_per_round > 0
                     and fl.selection not in ("threshold", "random"))
            # packed-neighbor scoring: the SparseFabric engine path (ctx.nbr
            # carries the round's padded neighbor lists + per-slot Eq. 9
            # cost). Pure-jnp O(M·D·P) — independent of use_score_kernel.
            # Score-integrity adversaries spoof dense cost matrices, so the
            # sparse branch requires an honest round; threat experiments on
            # a sparse fabric fall back to the dense branches (which the
            # engine only feeds at dense-oracle scale, M ≤ DENSE_ORACLE_MAX).
            sparse = (ctx.nbr is not None and m > 1 and fl.peers_per_round > 0
                      and fl.selection not in ("threshold", "random")
                      and ctx.threat is None)
            if sparse:
                # ---- 1b/2. packed Eq. 7–9 + top-k (no (M, M) scoring) ----
                vals, idx, sd_stats = score_topk_sparse(
                    flat, state.last_selected, s_l, state.round,
                    nbr_idx=ctx.nbr["idx"], nbr_valid=ctx.nbr["valid"],
                    alpha=fl.alpha, lam=fl.recency_lambda,
                    comm_cost=ctx.nbr["cost"],
                    k=min(fl.peers_per_round, m - 1),
                )
                mask = topk_to_mask(idx, vals, m)
                ctx.aux.update(s_l=s_l, s_l_rows=s_l_rows,
                               topk_vals=vals, topk_idx=idx,
                               sd_stats=sd_stats)
                fused = True   # downstream (metrics/context) reads the
                #                fused aux channel — identical keys
            elif fused:
                # ---- 1b/2. fused Eq. 7–9 + top-k (streaming pipeline) ----
                vals, idx, sd_stats = score_topk(
                    flat, state.last_selected, s_l,
                    state.round, alpha=fl.alpha, lam=fl.recency_lambda,
                    comm_cost=cost, k=min(fl.peers_per_round, m - 1),
                    candidate_mask=ctx.cand,
                )
                mask = topk_to_mask(idx, vals, m)
                ctx.aux.update(s_l=s_l, s_l_rows=s_l_rows,
                               topk_vals=vals, topk_idx=idx,
                               sd_stats=sd_stats)
            else:
                s_d = header_distance_matrix(
                    flat, use_kernel=use_score_kernel
                )                                                    # Eq. 7
                s_p = recency_scores(
                    state.last_selected, state.round, fl.recency_lambda
                )                                                    # Eq. 8
                scores = combined_scores(
                    s_l, s_d, s_p, alpha=fl.alpha, comm_cost=cost
                )                                                    # Eq. 9

                # ---- 2. selection ----------------------------------------
                if fl.selection == "threshold":
                    mask = select_peers(
                        scores, threshold=fl.score_threshold,
                        candidate_mask=ctx.cand,
                    )
                elif fl.selection == "random":
                    # ablation: identical round structure, random peers
                    rand = jnp.where(
                        jnp.eye(m, dtype=bool), -1.0,
                        jax.random.uniform(ctx.keys["rand"], (m, m)),
                    )
                    mask = select_peers(
                        rand, k=fl.peers_per_round, candidate_mask=ctx.cand
                    )
                else:
                    mask = select_peers(
                        scores, k=fl.peers_per_round, candidate_mask=ctx.cand
                    )
                ctx.aux.update(s_l=s_l, s_l_rows=s_l_rows, s_d=s_d,
                               scores=scores)
            mask = mask & ctx.active[:, None]

        # ---- Eq. 9 score decomposition over the selected edges ------------
        # (repro.obs telemetry, through the jit-safe ctx.record channel).
        # The dense path reduces matrices it already holds; the fused path
        # re-derives the components for the selected (M, k) pairs only —
        # O(M·k·P) gathers, never an (M, M) matrix.
        with part("telemetry"):
            n_sel = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
            if fused:
                comp = selected_components(
                    flat, state.last_selected, s_l,
                    state.round, ctx.aux["topk_idx"], alpha=fl.alpha,
                    lam=fl.recency_lambda, comm_cost=cost,
                )
                # scatter-valid ∧ active rows — the same entries `mask`
                # keeps, so the edge count matches the dense reduction
                # exactly
                valid = ((ctx.aux["topk_vals"] > NEG / 2)
                         & ctx.active[:, None])
                for comp_name in ("s_l", "s_d", "s_p", "cost"):
                    ctx.record(
                        f"sel_{comp_name}_mean",
                        jnp.sum(jnp.where(valid, comp[comp_name], 0.0))
                        / n_sel,
                    )
            else:
                for comp_name, mat in (("s_l", s_l), ("s_d", s_d),
                                       ("s_p", s_p),
                                       ("cost", as_cost_matrix(cost, m))):
                    ctx.record(
                        f"sel_{comp_name}_mean",
                        jnp.sum(jnp.where(mask, mat, 0.0)) / n_sel,
                    )

        if hetero is not None:
            lag = ctx.aux["pull_lag"]
            weights = staleness_weights(mask, lag, alpha=hetero.alpha)
            lagf = lag.astype(jnp.float32)
            n_edges = jnp.maximum(jnp.sum(mask), 1)
            ctx.metrics["eff_lag_mean"] = (
                jnp.sum(jnp.where(mask, lagf[None, :], 0.0)) / n_edges
            )
            ctx.metrics["eff_lag_max"] = jnp.max(
                jnp.where(mask, lag[None, :], 0)
            )
            ctx.metrics["serve_age_mean"] = (
                jnp.sum(jnp.where(mask,
                                  ctx.aux["serve_age"][None, :].astype(
                                      jnp.float32), 0.0)) / n_edges
            )
        else:
            weights = selection_to_weights(mask, include_self=True)
        ctx.plan = ExchangePlan(
            "p2p", active=ctx.active, edges=mask, weights=weights,
        )
        return state

    def aggregate(state: PopulationState, ctx: RoundContext):
        # ---- 3. aggregate extractors --------------------------------------
        src_e = ctx.aux["served"]["e"] if hetero is not None \
            else state.extractor
        if defense != "none":
            # robust aggregation over the selected peer set; norm_clip
            # keeps the plan weights (incl. staleness discounts), the
            # order-statistic defenses aggregate the set uniformly
            agg_e = robust_row_aggregate(
                src_e, ctx.plan.edges, ctx.plan.weights, ctx.m,
                defense=defense, trim=fl.threat.trim_fraction,
                clip=fl.threat.clip_factor,
            )
        else:
            agg_e = aggregate_extractors(src_e, ctx.plan.weights)
        ctx.aux["agg_e"] = where_tree(ctx.active, agg_e, state.extractor)
        return state

    def _active_mean(loss_row, active):
        return jnp.sum(loss_row * active) / jnp.maximum(jnp.sum(active), 1)

    def phase_e(state: PopulationState, ctx: RoundContext):
        # ---- 4. phase-e (header frozen) -----------------------------------
        # Train only the sampled rows (static-size gather → subset vmap →
        # scatter back). Bit-parity with the dense loop: batch keys stay
        # positional in the full population (scan_train rows/total), the
        # subset per-row compute is the same vmapped function, and the
        # loss metric scatters the subset losses back into an (M,) vector
        # before the SAME active-masked mean reduction.
        n_e = fl.epochs_extractor * steps_per_epoch
        idx = ctx.sampled_idx
        agg_sub, h_sub, oe_sub, e_sub = gather_rows(
            (ctx.aux["agg_e"], state.header, state.opt_e, state.extractor),
            idx,
        )
        data_sub = gather_rows(ctx.data, idx)

        def apply(carry, batch):
            e, o = carry
            e, o, met = jax.vmap(steps.phase_e)(e, h_sub, o, batch)
            return (e, o), met["loss"]

        (new_e, opt_e), loss_e = scan_train(
            apply, (agg_sub, oe_sub), data_sub,
            ctx.keys["e"], n_e, fl.batch_size, rows=idx, total=ctx.m,
        )
        act_sub = ctx.active[idx]
        new_e = scatter_rows(state.extractor, idx,
                             where_tree(act_sub, new_e, e_sub))
        opt_e = scatter_rows(state.opt_e, idx,
                             where_tree(act_sub, opt_e, oe_sub))
        loss_full = jnp.zeros((ctx.m,), loss_e.dtype).at[idx].set(loss_e[-1])
        ctx.metrics["train_loss_e"] = _active_mean(loss_full, ctx.active)
        return state._replace(extractor=new_e, opt_e=opt_e)

    def phase_h(state: PopulationState, ctx: RoundContext):
        # ---- 5/6. phase-h (extractor frozen) ------------------------------
        n_h = fl.epochs_header * steps_per_epoch
        idx = ctx.sampled_idx
        h_sub, e_sub, oh_sub = gather_rows(
            (state.header, state.extractor, state.opt_h), idx
        )
        data_sub = gather_rows(ctx.data, idx)

        def apply(carry, batch):
            h, o = carry
            h, o, met = jax.vmap(
                lambda h_, e_, o_, b: steps.phase_h(e_, h_, o_, b)
            )(h, e_sub, o, batch)
            return (h, o), met["loss"]

        (new_h, opt_h), loss_h = scan_train(
            apply, (h_sub, oh_sub), data_sub,
            ctx.keys["h"], n_h, fl.batch_size, rows=idx, total=ctx.m,
        )
        act_sub = ctx.active[idx]
        new_h = scatter_rows(state.header, idx,
                             where_tree(act_sub, new_h, h_sub))
        opt_h = scatter_rows(state.opt_h, idx,
                             where_tree(act_sub, opt_h, oh_sub))
        loss_full = jnp.zeros((ctx.m,), loss_h.dtype).at[idx].set(loss_h[-1])
        ctx.metrics["train_loss_h"] = _active_mean(loss_full, ctx.active)
        return state._replace(header=new_h, opt_h=opt_h)

    def update_context(state: PopulationState, ctx: RoundContext):
        # ---- 7. context arrays --------------------------------------------
        m = ctx.m
        mask = ctx.plan.edges
        loss_matrix = jnp.where(
            ctx.active[:, None], ctx.aux["s_l"], state.loss_matrix
        )
        with part("telemetry"):
            if "scores" in ctx.aux:
                scores, s_d = ctx.aux["scores"], ctx.aux["s_d"]
                sel_sum = jnp.sum(jnp.where(mask, scores, 0.0))
                sd_sum, sd_trace = jnp.sum(s_d), jnp.trace(s_d)
            else:
                # fused pipeline: the selected scores ARE the emitted top-k
                # values (mask = scatter of the valid indices ∧ active
                # rows), and the s_d stats come from the kernel's row
                # statistics. On the packed-neighbor branch the row sums
                # cover the NEIGHBORHOOD only (score_topk_sparse
                # docstring), so s_d_offdiag_mean reads lower there — same
                # normalizer, fewer summed pairs — and is not comparable
                # across fabrics.
                vals = ctx.aux["topk_vals"]
                sel = (vals > NEG / 2) & ctx.active[:, None]
                sel_sum = jnp.sum(jnp.where(sel, vals, 0.0))
                sd_sum = jnp.sum(ctx.aux["sd_stats"][:, 0])
                sd_trace = jnp.sum(ctx.aux["sd_stats"][:, 1])
            ctx.metrics.update(
                mean_selected_score=sel_sum / jnp.maximum(jnp.sum(mask), 1),
                # mean over the rows actually evaluated this round (the
                # sampled clients) — unsampled rows are served from cache
                s_l_mean=jnp.mean(ctx.aux["s_l_rows"]),
                s_d_offdiag_mean=(sd_sum - sd_trace) / (m * (m - 1)),
                select_mask=mask,
            )
        return state._replace(
            loss_matrix=loss_matrix,
            last_selected=update_recency(
                state.last_selected, mask, state.round
            ),
            round=state.round + 1,
        )

    # the stage scopes' names, which the device profiles read: written
    # out so that renaming a closure cannot change them
    stages = (named_stage(score_select, "score_select"),
              named_stage(aggregate, "aggregate"),
              named_stage(phase_e, "phase_e"),
              named_stage(phase_h, "phase_h"),
              named_stage(update_context, "update_context"))
    if hetero is None:
        return stages

    def publish(state: PopulationState, ctx: RoundContext):
        # ---- 6.5 publish — completers' snapshots enter the ring -----------
        store = store_publish(
            state.store,
            {"e": state.extractor, "h": state.header},
            ctx.active,
            ctx.aux["deadline_blocked"],
            state.round,
        )
        return state._replace(store=store)

    gate = stage_deadline_gate(hetero, get_round=lambda s: s.round)
    return (gate, *stages[:4], named_stage(publish, "publish"), stages[4])


def pfeddst_round(
    cfg: ModelConfig,
    fl: FLConfig,
    steps: PhaseSteps,
    state: PopulationState,
    train_data,
    key,
    *,
    steps_per_epoch: int = 1,
    probe_size: int = 64,
    use_score_kernel: bool = False,
    candidate_mask=None,
    comm_cost=None,
    available=None,
):
    """One communication round. train_data: dict of (M, N, ...) arrays.

    Standalone entry point over `make_pfeddst_stages` (the PFedDST spec in
    fl/strategies.py runs the same stages through repro.fl.engine).
    candidate_mask / comm_cost / available are the repro.comms hooks:
    reachable-peer mask, per-link (M, M) Eq. 9 `c` matrix (None → the
    scalar fl.comm_cost), and (M,) client-online mask composed with the
    protocol's client_sample_ratio. Returns (new_state, metrics dict).
    """
    # participation (client sampling × the `available` network mask, a
    # client trains iff sampled AND online) and the metrics contract are
    # the engine's run_round — identical to the spec path in
    # fl/strategies, which additionally derives the network hooks from a
    # CommsFabric.
    stages = make_pfeddst_stages(
        cfg, fl, steps, steps_per_epoch=steps_per_epoch,
        probe_size=probe_size, use_score_kernel=use_score_kernel,
    )
    return run_round(
        stages, state, train_data, key,
        m=state.loss_matrix.shape[0], ratio=fl.client_sample_ratio,
        key_streams=PFEDDST_STREAMS, candidate_mask=candidate_mask,
        comm_cost=comm_cost, available=available,
    )
