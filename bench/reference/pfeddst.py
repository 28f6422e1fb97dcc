"""PFedDST (Algorithm 1 of the paper), plain, for the reference.

One round, for the sampled clients R (round(M * ratio) of them):

1. Eq. 6: each row r in R evaluates its own model on every client's
   probe batch, L[r, j]; rows outside R keep last round's values.
2. Eq. 7-9: S_ij = s_p * (alpha * L_ij - cos(h_i, h_j) + c_ij), with
   s_p = 1 - exp(-lambda * (t - t_ij)) (1 if never chosen), the diagonal
   masked; each sampled row keeps its top-k peers.
3. Each sampled client averages its extractor with those of its peers.
4. Phase e: K_e epochs of SGD on the extractor, header frozen (Eq. 3).
5. Phase h: K_h epochs of SGD on the header, extractor frozen (Eq. 4).
6. The loss array keeps the sampled rows of L; t_ij = t where i chose j.

The sampled clients train side by side, as a population of clients on
one chip is trained; each client's result depends only on its own rows.
The header is the model's `HEADER`, its extractor the rest of the tree;
weights that every client shares, where the model has them, enter every
loss and are never trained.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import common as C

STREAMS = ("probe", "act", "e", "h", "rand")


def program_state(strat, key, weights, model):
    """The program's initial state around the benchmark's weights: its
    own init for everything else (optimizer state, context arrays); the
    shared weights, where there are any, in its field `shared`."""
    ext, hdr = C.split(weights["params"], model.HEADER)
    return C.hold_shared(strat.init(key)._replace(extractor=ext, header=hdr),
                         weights["shared"])


def snapshot_program(state, stacked, params0, model, shared0=None):
    """What the comparison reads of the program after its first chunk
    (`shared0`: the seed's shared weights, where there are any)."""
    return {
        "loss_e": C.host(stacked["train_loss_e"]),
        "loss_h": C.host(stacked["train_loss_h"]),
        "select": np.asarray(jax.device_get(stacked["select_mask"])),
        "s_l_mean": C.host(stacked["s_l_mean"]),
        "loss_matrix": C.host(state.loss_matrix),
        "change": C.host(C.change_norms(
            (state.extractor, state.header), C.split(params0, model.HEADER))),
        "momentum": C.host(C.norms((state.opt_e["mu"], state.opt_h["mu"]))),
        "round": int(state.round),
        **C.shared_reading(None if shared0 is None else state.shared,
                           shared0),
    }


def _round(state, data, key, r, shared, *, cfg, quant, model):
    m, k = cfg["num_clients"], min(cfg["peers_per_round"],
                                   cfg["num_clients"] - 1)
    spe = cfg["steps_per_epoch"]
    hp = {n: cfg[n] for n in ("lr", "momentum", "weight_decay",
                              "batch_size")}
    ks = C.streams(key, r, STREAMS)
    idx, active = C.sample_clients(ks["act"], m, cfg["client_sample_ratio"])
    e, h = state["e"], state["h"]

    # Eq. 6 on the sampled rows
    losses = C.bind_losses(model, cfg, quant, shared)
    rows = C.probe_losses(C.take_rows(C.merge(e, h), idx), data,
                          ks["probe"], m=m, probe=cfg["probe_size"],
                          losses=losses)
    s_l = state["loss_matrix"].at[idx].set(rows)

    # Eq. 7-9 and top-k
    flat = jnp.concatenate([x.reshape(m, -1).astype(C.F32)
                            for x in jax.tree_util.tree_leaves(h)], axis=1)
    inv = 1.0 / (jnp.sqrt(jnp.sum(flat * flat, axis=1)) + 1e-12)
    cos = jnp.clip(jnp.matmul(flat, flat.T,
                              precision=jax.lax.Precision.HIGHEST)
                   * inv[:, None] * inv[None, :], -1.0, 1.0)
    last, t = state["last"], state["round"]
    s_p = jnp.where(last < 0, 1.0,
                    1.0 - jnp.exp(-cfg["recency_lambda"]
                                  * jnp.maximum(t - last, 0).astype(C.F32)))
    cost = jnp.where(jnp.eye(m, dtype=bool), 0.0, cfg["comm_cost"])
    s = s_p * (cfg["alpha"] * s_l - cos + cost)
    s = jnp.where(jnp.eye(m, dtype=bool), C.NEG, s)
    vals, top = jax.lax.top_k(s, k)
    mask = jnp.zeros((m, m), bool).at[jnp.arange(m)[:, None], top].set(
        vals > C.NEG / 2) & active[:, None]

    # aggregation of the sampled rows' extractors
    w = C.row_weights(mask)
    agg = C.mix_rows(e, w[idx])

    # phase e, then phase h, the sampled clients side by side
    n_e = cfg["epochs_extractor"] * spe
    n_h = cfg["epochs_header"] * spe
    h_r = C.take_rows(h, idx)
    e_n, mue_n, le = C.train_rows(
        lambda p, fz, b, q: jnp.mean(losses(C.merge(p, fz), b, q)),
        agg, C.take_rows(state["mu_e"], idx), h_r, ks["e"], idx, data,
        m=m, steps=n_e, hp=hp, quant=quant)
    h_n, muh_n, lh = C.train_rows(
        lambda p, fz, b, q: jnp.mean(losses(C.merge(fz, p), b, q)),
        h_r, C.take_rows(state["mu_h"], idx), e_n, ks["h"], idx, data,
        m=m, steps=n_h, hp=hp, quant=quant)
    act = active[idx]
    n_act = jnp.maximum(act.sum(), 1)
    return {
        "e": C.put_rows(e, idx, e_n, act),
        "h": C.put_rows(h, idx, h_n, act),
        "mu_e": C.put_rows(state["mu_e"], idx, mue_n, act),
        "mu_h": C.put_rows(state["mu_h"], idx, muh_n, act),
        "loss_matrix": jnp.where(active[:, None], s_l, state["loss_matrix"]),
        "last": jnp.where(mask, t, last),
        "round": t + 1,
    }, {"loss_e": jnp.sum(le * act) / n_act,
        "loss_h": jnp.sum(lh * act) / n_act, "select": mask,
        "s_l_mean": jnp.mean(rows)}


def run_reference(cfg, data, init, key, rounds, model, quant=None):
    """`rounds` reference rounds from the benchmark's weights → the same
    readings as `snapshot_program`. `init()` makes the weights afresh
    (the rounds consume their buffers)."""
    m = cfg["num_clients"]
    w0 = init()
    shared = w0["shared"]
    e0, h0 = C.split(w0["params"], model.HEADER)
    zeros = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.zeros(x.shape, C.F32), t)
    state = {"e": e0, "h": h0, "mu_e": zeros(e0), "mu_h": zeros(h0),
             "loss_matrix": jnp.zeros((m, m), C.F32),
             "last": jnp.full((m, m), -1, jnp.int32),
             "round": jnp.zeros((), jnp.int32)}
    step = C.jitted(_round, cfg, quant, model)
    outs = []
    for r in range(rounds):
        state, out = step(state, data, key, jnp.int32(r), shared)
        outs.append(out)
    outs = jax.device_get(outs)
    e0, h0 = C.split(init()["params"], model.HEADER)
    return {
        "loss_e": np.array([o["loss_e"] for o in outs], np.float64),
        "loss_h": np.array([o["loss_h"] for o in outs], np.float64),
        "select": np.stack([o["select"] for o in outs]),
        "s_l_mean": np.array([o["s_l_mean"] for o in outs], np.float64),
        "loss_matrix": C.host(state["loss_matrix"]),
        "change": C.host(C.change_norms((state["e"], state["h"]), (e0, h0))),
        "momentum": C.host(C.norms((state["mu_e"], state["mu_h"]))),
        "round": rounds,
    }


def compare(prog, ref) -> dict:
    """Every number the comparison can hold against a limit; the cell's
    limits file names those it does."""
    sel_r = ref["select"].sum()
    lm_r = np.abs(ref["loss_matrix"])
    seen = lm_r > 0
    if not (np.isfinite(prog["loss_matrix"]).all()
            and np.isfinite(lm_r).all()):
        seen = np.zeros_like(seen)
    mom = C.leaf_gaps(prog["momentum"], ref["momentum"], ref["momentum"])
    chg = C.leaf_gaps(prog["change"], ref["change"], ref["momentum"])
    return {
        "loss1": max(C.rel_gap_series(prog["loss_e"][:1], ref["loss_e"][:1]),
                     C.rel_gap_series(prog["loss_h"][:1], ref["loss_h"][:1])),
        "loss": max(C.rel_gap_series(prog["loss_e"], ref["loss_e"]),
                    C.rel_gap_series(prog["loss_h"], ref["loss_h"])),
        "probe1": C.rel_gap_series(prog["s_l_mean"][:1], ref["s_l_mean"][:1]),
        "probe": float(np.max(np.abs(prog["loss_matrix"]
                                     - ref["loss_matrix"])[seen])
                       / max(lm_r[seen].mean(), 1e-30)) if seen.any()
        else float("inf"),
        "select1": float((prog["select"][0] != ref["select"][0]).sum()
                         / max(2 * ref["select"][0].sum(), 1)),
        "select": float((prog["select"] != ref["select"]).sum()
                        / max(2 * sel_r, 1)),
        "momentum": float(mom.max()), "momentum_median": float(np.median(mom)),
        "change": float(chg.max()), "change_median": float(np.median(chg)),
        "rounds": float(abs(prog["round"] - ref["round"])),
        **C.shared_number(prog),
    }
