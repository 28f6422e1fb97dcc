"""DisPFL (Dai et al. 2022), plain, for the reference.

One round:

1. Every client's parameters are multiplied by its personal mask.
2. Gossip plan: each sampled client draws k random neighbours among its
   candidates (a ring here), the plan is made symmetric within the
   candidates, and each sampled row averages uniformly over its
   neighbours and itself.
3. The sampled clients run K_e epochs of SGD on the whole model.
4. Sampled clients replace their extractor by the plan's average of the
   trained extractors; headers (the model's `HEADER`) stay personal.
5. Mask evolution, per leaf over the whole population: keep the
   `(1 - sparsity) * size` entries of largest magnitude, add the entries
   regrown at random with probability `regrow`, and multiply the
   parameters by the new mask.

Weights that every client shares, where the model has them, enter every
loss and are neither masked, trained nor mixed.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from reference import common as C

STREAMS = ("act", "train", "nbr", "grow")


def program_state(strat, key, weights, model):
    """The program's initial state around the benchmark's weights and
    masks: its own init for the optimizer state; the shared weights,
    where there are any, under its key `shared`."""
    return C.hold_shared({**strat.init(key), "params": weights["params"],
                          "mask": weights["masks"]}, weights["shared"])


def _packed(masks):
    return [np.asarray(jax.device_get(jnp.packbits(m.reshape(-1))))
            for m in jax.tree_util.tree_leaves(masks)]


def snapshot_program(state, stacked, params0, model, shared0=None):
    return {
        "loss": C.host(stacked["train_loss"]),
        "edges": np.asarray(jax.device_get(stacked["comm_edges"])),
        "mask": _packed(state["mask"]),
        "change": C.host(C.change_norms(state["params"], params0)),
        "momentum": C.host(C.norms(state["opt"]["mu"])),
        "round": int(state["round"]),
        **C.shared_reading(None if shared0 is None else state["shared"],
                           shared0),
    }


def candidates(cfg):
    """The ring: each client linked to its two neighbours."""
    m = cfg["num_clients"]
    i = np.arange(m)
    adj = np.zeros((m, m), bool)
    adj[i, (i + 1) % m] = adj[i, (i - 1) % m] = True
    adj[i, i] = False
    return adj


def evolve(leaf, key, *, sparsity, regrow):
    keep = max(int(leaf.size * (1 - sparsity)), 1)
    mag = jnp.abs(leaf.astype(C.F32))
    thr = jnp.sort(mag.reshape(-1))[leaf.size - keep]
    mask = (mag >= thr) | (jax.random.uniform(key, leaf.shape)
                           > 1.0 - regrow)
    return leaf * mask.astype(leaf.dtype), mask


def _round(state, data, key, r, shared, *, cfg, cand, quant, model):
    m = cfg["num_clients"]
    k = min(cfg["peers_per_round"], m - 1)
    hp = {n: cfg[n] for n in ("lr", "momentum", "weight_decay",
                              "batch_size")}
    ks = C.streams(key, r, STREAMS)
    idx, active = C.sample_clients(ks["act"], m, cfg["client_sample_ratio"])
    params = jax.tree_util.tree_map(lambda p, mk: p * mk.astype(p.dtype),
                                    state["params"], state["mask"])

    # gossip plan
    ok = cand & ~jnp.eye(m, dtype=bool)
    u = jnp.where(ok, jax.random.uniform(ks["nbr"], (m, m)), C.NEG)
    vals, top = jax.lax.top_k(u, k)
    pick = jnp.zeros((m, m), bool).at[jnp.arange(m)[:, None], top].set(
        vals > C.NEG / 2)
    edges = ((pick | pick.T) & ok) & active[:, None]
    w = C.row_weights(edges)

    # local training of the sampled clients, side by side
    n_steps = cfg["epochs_extractor"] * cfg["steps_per_epoch"]
    example_losses = C.bind_losses(model, cfg, quant, shared)
    p_n, mu_n, losses = C.train_rows(
        lambda p, fz, b, q: jnp.mean(example_losses(p, b, q)),
        C.take_rows(params, idx), C.take_rows(state["mu"], idx), None,
        ks["train"], idx, data, m=m, steps=n_steps, hp=hp, quant=quant)
    act = active[idx]
    params = C.put_rows(params, idx, p_n, act)
    mu = C.put_rows(state["mu"], idx, mu_n, act)

    # the sampled rows mix their extractors
    ext, hdr = C.split(params, model.HEADER)
    ext = C.put_rows(ext, idx, C.mix_rows(ext, w[idx]), act)
    params = C.merge(ext, hdr)

    # mask evolution, leaf by leaf in the order of the parameter tree
    leaves, treedef = jax.tree_util.tree_flatten(params)
    gkeys = jax.random.split(ks["grow"], len(leaves))
    ev = [evolve(l, g, sparsity=cfg["dispfl_sparsity"],
                 regrow=cfg["dispfl_regrow"]) for l, g in zip(leaves, gkeys)]
    params = jax.tree_util.tree_unflatten(treedef, [p for p, _ in ev])
    masks = jax.tree_util.tree_unflatten(treedef, [mk for _, mk in ev])
    return ({"params": params, "mask": masks, "mu": mu},
            {"loss": jnp.mean(losses), "edges": edges})


def run_reference(cfg, data, init, key, rounds, model, quant=None):
    w0 = init()
    zeros = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, C.F32),
                                   w0["params"])
    state = {"params": w0["params"], "mask": w0["masks"], "mu": zeros}
    step = C.jitted(_round, cfg, quant, model,
                    cand=jnp.asarray(candidates(cfg)))
    outs = []
    for r in range(rounds):
        state, out = step(state, data, key, jnp.int32(r), w0["shared"])
        outs.append(out)
    outs = jax.device_get(outs)
    params0 = init()["params"]
    return {
        "loss": np.array([o["loss"] for o in outs], np.float64),
        "edges": np.stack([o["edges"] for o in outs]),
        "mask": _packed(state["mask"]),
        "change": C.host(C.change_norms(state["params"], params0)),
        "momentum": C.host(C.norms(state["mu"])),
        "round": rounds,
    }


def compare(prog, ref) -> dict:
    """Every number the comparison can hold against a limit; the cell's
    limits file names those it does."""
    bits = sum(8 * r.size for r in ref["mask"])
    flips = sum(int(np.unpackbits(a ^ b).sum())
                for a, b in zip(prog["mask"], ref["mask"]))
    mom = C.leaf_gaps(prog["momentum"], ref["momentum"], ref["momentum"])
    chg = C.leaf_gaps(prog["change"], ref["change"], ref["momentum"])
    return {
        "loss1": C.rel_gap_series(prog["loss"][:1], ref["loss"][:1]),
        "loss": C.rel_gap_series(prog["loss"], ref["loss"]),
        "edges": float((prog["edges"] != ref["edges"]).sum()),
        "mask": flips / max(bits, 1),
        "momentum": float(mom.max()), "momentum_median": float(np.median(mom)),
        "change": float(chg.max()), "change_median": float(np.median(chg)),
        "rounds": float(abs(prog["round"] - ref["round"])),
        **C.shared_number(prog),
    }
